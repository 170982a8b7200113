"""The layer stack of a model whose layers are not all of one kind
(``ModelConfig.layers``): state-space mixers (Mamba-1, with or without
RMSNorms on dt, B and C), plain grouped- / multi-query attention,
differential attention with a window or without, gated memory units and
cross-attention onto another layer's cache, in one decoder (SambaY,
arXiv:2507.06607; Jamba, arXiv:2403.19887).

``Transformer`` owns the embedding, the final norm, the head and the
homogeneous scan; for a model with a per-layer spec it hands the layers
to :class:`HybridStack`, which walks **runs**: a run is a stretch of the
spec that repeats with a period (``(ssm, window attention) x 8``), stored
as one stacked dict a position of the period and run as one ``lax.scan``
over its repeats; a stretch that does not repeat is a run of one, run
inline. Two values cross layer boundaries inside a step: the memory ``m``
(the scan output of the nearest state-space layer below, which the gated
memory units gate) and, in a spec that has cross-attention layers, the
keys and values of its one ``paged`` attention layer (which every
cross-attention layer above it reads). A spec without them may hold any
number of ``paged`` layers: each keeps rows of its own, layer j of them
at index j of the ``paged`` arrays.

Every block is sequential: ``x + mixer(norm1(x))``, then ``x +
mlp(norm2(x))`` with the gated-SiLU MLP; no rotary embedding anywhere
(the state-space layers carry the order).

Differential attention (arXiv:2410.05258) runs through the attention ops
every other model uses, over stacked heads: query heads pair as (2j,
2j+1), key heads the same way, and a pair's two value heads are one value
of twice the width. Query head 2j is laid out ``[q | 0]`` and head 2j+1
``[0 | q]`` against the pair ``[k_2i | k_2i+1]`` (128 lanes at head size
64), so one grouped-query attention over paired rows gives both
softmaxes of every pair, each times the pair's value, and the difference
is taken on the outputs. Whole sequences attend that way; the paged
steps go one further and lay the query out against a token's whole
cached row (all pairs side by side, ``_wide_query``), so the pool is
read as it is stored.

Plain attention (mixer ``attention``) is the textbook form: no rotary,
no bias, scale ``head_dim ** -0.5``, query heads grouped over the key /
value heads. A cached row is a token's key (or value) heads side by
side, ``num_kv_heads * head_dim`` numbers, the same count as the paired
row above; with one key / value head the row is the key. In a paged
chunk program these layers read their cached rows in blocks up to the
context (``ops.attention.blockwise_paged_attention``), not the slot's
gathered window.
"""
from __future__ import annotations

import math
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dla_tpu.models.config import CacheArray, LayerSpec
from dla_tpu.ops.attention import blockwise_paged_attention, decode_attention
from dla_tpu.ops.norms import layer_norm, rms_norm
from dla_tpu.ops.selective_scan import (
    causal_conv_step,
    selective_scan_chunk,
    selective_scan_step,
)

Params = Dict[str, jnp.ndarray]
F32 = jnp.float32
#: a period longer than this is not looked for
MAX_PERIOD = 4
#: cached columns a block of the chunk programs' plain-attention walk
#: reads (``chunk_attention_block_pages``). Settled on a v5e at 20 heads
#: x 512 tokens: a block's float32 scores are then 40 MiB and XLA keeps
#: them in VMEM (0.05 ms a block a layer; at 2,048 one layer's of two
#: spill to HBM, 0.22; at 4,096 both, 0.70: PERF.md, PR 39)
CHUNK_ATTENTION_BLOCK_COLUMNS = 1024
#: the device scope of each mixer (utils/profiling.py DEVICE_SCOPES)
_ATTN_SCOPE = {"paged_window": "swa_attention", "paged": "full_attention",
               "shared": "cross_attention"}


class Run(NamedTuple):
    """Layers [start, start + period * reps): the ``period`` specs at
    ``start`` repeated ``reps`` times."""
    start: int
    period: int
    reps: int


def layer_runs(spec: Tuple[LayerSpec, ...]) -> Tuple[Run, ...]:
    """Cut a per-layer spec into runs, greedily: at each layer the
    (period, repeats) that covers the most layers, the shorter period on
    a tie. Layers compare by (mixer, cache): a window rides a scan as
    data."""
    keys = [(s.mixer, s.cache) for s in spec]
    runs: List[Run] = []
    i, n = 0, len(keys)
    while i < n:
        best = Run(i, 1, 1)
        for period in range(1, MAX_PERIOD + 1):
            reps = 1
            while keys[i + reps * period:i + (reps + 1) * period] \
                    == keys[i:i + period]:
                reps += 1
            if reps > 1 and period * reps > best.period * best.reps:
                best = Run(i, period, reps)
        runs.append(best)
        i += best.period * best.reps
    return tuple(runs)


def chunk_attention_block_pages(page_size: int, table_pages: int) -> int:
    """Pages a block of ``blockwise_paged_attention`` in a chunk program
    over pages of ``page_size`` and block tables of ``table_pages``
    entries: ``CHUNK_ATTENTION_BLOCK_COLUMNS`` columns in whole pages, the
    whole table where it is shorter. From the shapes alone, so that the
    engine's counters can work it out again
    (``HybridStack.chunk_attention_walk``)."""
    return max(1, min(CHUNK_ATTENTION_BLOCK_COLUMNS // page_size,
                      table_pages))


def lambda_init(layer_index) -> jnp.ndarray:
    """Differential attention's depth-dependent lambda offset."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer_index, F32))


class HybridStack:
    """The layers of a ``Transformer`` whose config has ``layers`` set."""

    def __init__(self, model):
        self.model = model
        cfg = self.cfg = model.cfg
        self.spec = cfg.layer_spec
        self.runs = layer_runs(self.spec)
        if cfg.pipeline_stages > 1 or cfg.pipeline_interleave > 1:
            raise ValueError(
                "a model with a per-layer spec has no pipeline layout: "
                "its runs are stacks of different kinds")
        # a cache array's leading axis counts the layers of its kind:
        # layer l0 + stride * rep of a run sits at base + per_period * rep
        seen: Dict[str, int] = {}
        base = []
        for s in self.spec:
            base.append(seen.get(s.cache, 0))
            seen[s.cache] = seen.get(s.cache, 0) + 1
        self._cache_layers = seen
        self._cache_index = {}
        for run in self.runs:
            kinds = [self.spec[run.start + j].cache
                     for j in range(run.period)]
            for j, kind in enumerate(kinds):
                self._cache_index[run.start + j] = (
                    base[run.start + j], kinds.count(kind))
        windows = {s.window for s in self.spec if s.cache == "paged_window"}
        if len(windows) > 1:
            raise ValueError(
                f"one window pool, one window: the spec has {windows}")
        self.window = next(iter(windows), None)
        # layers that read the one shared layer's rows: itself and the
        # cross layers (1 where every paged layer reads its own alone)
        shared = sum(s.cache == "shared" for s in self.spec)
        self.shared_readers = 1 + shared
        # plain-attention layers over rows of their own: what a chunk
        # program reads through the block walk (``paged``)
        self.walked_layers = sum(
            s.mixer == "attention" and s.cache == "paged" for s in self.spec)
        self._cache_spec = self._build_cache_spec()
        # cache kind -> positions of its arrays in cache_spec()
        self._slots: Dict[str, Tuple[int, ...]] = {}
        for i, entry in enumerate(self._cache_spec):
            self._slots[entry.kind] = self._slots.get(entry.kind, ()) + (i,)
        # the shared layer's gathered rows cross layer boundaries through
        # the carry's ``shared`` entry, outside every scan: it then has
        # to stand outside them too
        for run in self.runs:
            kinds = [self.spec[run.start + j].cache
                     for j in range(run.period)]
            if shared and run.reps > 1 and "paged" in kinds:
                raise ValueError(
                    "the paged attention layer whose cache the cross "
                    "layers read has to stand alone in the spec, not "
                    "inside a repeating stretch")

    def chunk_attention_walk(self, page_size: int,
                             table_pages: int) -> Tuple[int, int]:
        """(cached columns a block, layers that walk) of the block walk
        in this stack's paged chunk program over pages of ``page_size``
        and block tables of ``table_pages`` entries (``paged``); (0, 0)
        where no layer walks: the host's half of the counters
        ``serving/prefill/attn_read_tokens`` / ``attn_window_tokens``."""
        if not self.walked_layers:
            return 0, 0
        return (page_size * chunk_attention_block_pages(
            page_size, table_pages), self.walked_layers)

    # ------------------------------------------------------------- storage

    def run_key(self, run: Run, j: int) -> str:
        """``<first layer>s<stride>_<mixer>``: stack i of the leaves
        under this key is layer first + i * stride."""
        l = run.start + j
        return f"{l:02d}s{run.period}_{self.spec[l].mixer}"

    def _run_of(self, l: int) -> Tuple[Run, int, int]:
        """(run, position in the period, repeat) of layer ``l``."""
        for run in self.runs:
            off = l - run.start
            if 0 <= off < run.period * run.reps:
                return run, off % run.period, off // run.period
        raise IndexError(f"layer {l} of {len(self.spec)}")

    def layer_params(self, layers: Dict[str, Params], l: int) -> Params:
        """Layer ``l``'s weights out of the stored tree."""
        run, j, i = self._run_of(l)
        return {k: v[i] for k, v in layers[self.run_key(run, j)].items()}

    def _block_shapes(self, mixer: str) -> Dict[str, Tuple]:
        """name -> (shape, init) of one block's leaves. init: a float is
        the std of a normal, "ones" / "zeros", or a mixer's own rule."""
        cfg = self.cfg
        d, f, dh = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim_
        qdim, kvdim = cfg.num_heads * dh, cfg.num_kv_heads * dh
        di, n, r = cfg.ssm_inner_, cfg.ssm_state_size, cfg.ssm_dt_rank_
        std = 0.02
        out_std = std / (2 * cfg.num_layers) ** 0.5
        shapes: Dict[str, Tuple] = {"norm1": ((d,), "ones"),
                                    "norm2": ((d,), "ones")}
        if cfg.norm == "layer":
            shapes["norm1_bias"] = shapes["norm2_bias"] = ((d,), "zeros")
        shapes.update(w_gate=((d, f), std), w_up=((d, f), std),
                      w_down=((f, d), out_std))
        lambdas = {f"lambda_{n_}": ((dh,), 0.1)
                   for n_ in ("q1", "k1", "q2", "k2")}
        if mixer == "ssm":
            shapes.update(
                in_proj=((d, 2 * di), std),
                conv_w=((cfg.ssm_conv_width, di), "conv"),
                conv_b=((di,), "zeros"),
                x_proj=((di, r + 2 * n), std),
                dt_proj=((r, di), r ** -0.5),
                dt_bias=((di,), "dt"), a_log=((di, n), "a_log"),
                d_skip=((di,), "ones"), out_proj=((di, d), out_std))
            if cfg.ssm_inner_norms:
                shapes.update(dt_norm=((r,), "ones"), b_norm=((n,), "ones"),
                              c_norm=((n,), "ones"))
        elif mixer == "attention":
            shapes.update(wq=((d, qdim), std), wk=((d, kvdim), std),
                          wv=((d, kvdim), std), wo=((qdim, d), out_std))
        elif mixer == "diff_attention":
            shapes.update(
                wq=((d, qdim), std), wq_bias=((qdim,), "zeros"),
                wk=((d, kvdim), std), wk_bias=((kvdim,), "zeros"),
                wv=((d, kvdim), std), wv_bias=((kvdim,), "zeros"),
                wo=((qdim, d), out_std), wo_bias=((d,), "zeros"),
                subln=((2 * dh,), "ones"), **lambdas)
        elif mixer == "cross_diff_attention":
            shapes.update(
                wq=((d, qdim), std), wq_bias=((qdim,), "zeros"),
                wo=((qdim, d), out_std), wo_bias=((d,), "zeros"),
                subln=((2 * dh,), "ones"), **lambdas)
        elif mixer == "gmu":
            shapes.update(gmu_in=((d, di), std), gmu_out=((di, d), out_std))
        else:
            raise ValueError(mixer)
        return shapes

    def init(self, rng: jax.Array) -> Dict[str, Params]:
        pdtype = self.model.pdtype
        cfg = self.cfg
        layers: Dict[str, Params] = {}
        for run in self.runs:
            for j in range(run.period):
                key = self.run_key(run, j)
                block: Params = {}
                for i, (name, (shape, how)) in enumerate(sorted(
                        self._block_shapes(self.spec[run.start + j].mixer
                                           ).items())):
                    k = jax.random.fold_in(
                        jax.random.fold_in(rng, run.start + j), i)
                    full = (run.reps,) + shape
                    if how == "ones":
                        v = jnp.ones(full, F32)
                    elif how == "zeros":
                        v = jnp.zeros(full, F32)
                    elif how == "conv":      # uniform +-1/sqrt(width)
                        bound = shape[0] ** -0.5
                        v = jax.random.uniform(k, full, F32, -bound, bound)
                    elif how == "a_log":     # A = -(1..N), S4D-real
                        v = jnp.broadcast_to(jnp.log(jnp.arange(
                            1, cfg.ssm_state_size + 1, dtype=F32)), full)
                    elif how == "dt":
                        # softplus^-1 of a step size log-uniform in
                        # [1e-3, 1e-1] (the Mamba initialisation)
                        dt = jnp.exp(jax.random.uniform(k, full, F32)
                                     * (math.log(0.1) - math.log(1e-3))
                                     + math.log(1e-3))
                        v = dt + jnp.log(-jnp.expm1(-dt))
                    else:
                        v = jax.random.normal(k, full, F32) * how
                    block[name] = v.astype(pdtype)
                layers[key] = block
        return layers

    def partition_specs(self) -> Dict[str, Dict[str, P]]:
        """Matrices shard their input dim over ``fsdp`` and their output
        dim over ``model`` (down-projections the other way round);
        vectors and the small state-space leaves are replicated."""
        down = {"w_down", "wo", "out_proj", "gmu_out"}
        specs: Dict[str, Dict[str, P]] = {}
        for run in self.runs:
            for j in range(run.period):
                block = {}
                for name, (shape, _) in self._block_shapes(
                        self.spec[run.start + j].mixer).items():
                    if len(shape) == 2 and name not in (
                            "conv_w", "a_log", "x_proj", "dt_proj"):
                        block[name] = (P(None, "model", "fsdp")
                                       if name in down
                                       else P(None, "fsdp", "model"))
                    else:
                        block[name] = P(*([None] * (len(shape) + 1)))
                specs[self.run_key(run, j)] = block
        return specs

    # --------------------------------------------------------------- cache

    def cache_spec(self) -> Tuple[CacheArray, ...]:
        return self._cache_spec

    def _build_cache_spec(self) -> Tuple[CacheArray, ...]:
        """The arrays a cache manager holds for this model, in the order
        the paged steps take and return them: keys and values of the
        ``paged`` layers, keys and values of the window layers, then the
        state-space layers' state (float32) and convolution tail. A row
        is a token's key / value heads side by side, one vector of
        num_kv_heads x head_dim numbers, which is its differential pairs
        side by side, kv_heads / 2 x (2 * head_dim) (10 x 128 = 1,280),
        and one key of 128 under multi-query attention: the TPU
        tiles an array's two minor axes (16 x 128 for bfloat16), and a
        [page, 10, 128] page, with 10 in the second-minor place, gets a
        layout of the compiler's choosing at the program's boundary and
        a whole-pool copy to and from the one its scatter wants, every
        step; [page, 1280] tiles as it is."""
        cfg = self.cfg
        row = (cfg.num_kv_heads * cfg.head_dim_,)
        adtype = self.model.adtype
        out: List[CacheArray] = []
        for kind in ("paged", "paged_window"):
            if self._cache_layers.get(kind):
                out += [CacheArray(
                    kind, self._cache_layers[kind], row, adtype,
                    self.window if kind == "paged_window" else None)] * 2
        if self._cache_layers.get("state"):
            n = self._cache_layers["state"]
            out.append(CacheArray(
                "state", n, (cfg.ssm_state_size, cfg.ssm_inner_), F32, None))
            out.append(CacheArray(
                "state", n, (cfg.ssm_conv_width - 1, cfg.ssm_inner_),
                adtype, None))
        return tuple(out)

    # --------------------------------------------------------------- blocks

    def _norm(self, layer: Params, name: str, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        if cfg.norm == "layer":
            return layer_norm(x, layer[name], layer[name + "_bias"],
                              cfg.rms_norm_eps)
        return rms_norm(x, layer[name], cfg.rms_norm_eps)

    def _proj(self, layer: Params):
        model = self.model

        def proj(name, inp):
            out = model._dense(layer, name, inp)
            bias = layer.get(name + "_bias")
            return out if bias is None else out + bias.astype(out.dtype)
        return proj

    def _ssm(self, layer: Params, h: jnp.ndarray, state, tail, real,
             scan_kernel=None):
        """The Mamba-1 mixer over T tokens from (state, tail). ``real``
        [B, T] bool, a prefix of each row: the others move neither the
        state nor the tail. ``scan_kernel``: the module whose
        ``selective_scan_chunk_kernel`` runs a chunk's recurrence
        (``Transformer.scan_chunk_kernel``), or None for XLA's form.
        Returns (mixer output [B, T, D], memory [B, T, d_inner] = the
        scan's output before the gate, new state, new tail)."""
        cfg, model = self.cfg, self.model
        di, n, r = cfg.ssm_inner_, cfg.ssm_state_size, cfg.ssm_dt_rank_
        with jax.named_scope("ssm_mixer"):
            xz = model._dense(layer, "in_proj", h)
            x, z = xz[..., :di], xz[..., di:]
            conv, tail = causal_conv_step(
                x, tail, layer["conv_w"], layer["conv_b"],
                jnp.sum(real, axis=1, dtype=jnp.int32))
            xc = jax.nn.silu(conv)
            dbc = model._dense(layer, "x_proj", xc)

            def inner(name, v):
                # Jamba norms dt, B and C between x_proj and their use
                return (rms_norm(v, layer[name], cfg.rms_norm_eps)
                        if cfg.ssm_inner_norms else v)
            dt = jax.nn.softplus(
                model._dense(layer, "dt_proj", inner(
                    "dt_norm", dbc[..., :r])).astype(F32)
                + layer["dt_bias"].astype(F32))
            dt = jnp.where(real[..., None], dt, 0.0)
            a = -jnp.exp(layer["a_log"].astype(F32)).T          # [N, d]
            bm = inner("b_norm", dbc[..., r:r + n])
            cm = inner("c_norm", dbc[..., r + n:])
            # the recurrence alone: projections and the convolution stay
            # outside this scope
            with jax.named_scope("ssm_scan"):
                if h.shape[1] == 1:
                    y, state = selective_scan_step(
                        xc[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0],
                        layer["d_skip"], state)
                    y = y[:, None]
                else:
                    scan = (selective_scan_chunk if scan_kernel is None
                            else scan_kernel.selective_scan_chunk_kernel)
                    y, state = scan(
                        xc, dt, a, bm, cm, layer["d_skip"], state)
            y = y.astype(h.dtype)
            out = model._dense(layer, "out_proj", y * jax.nn.silu(z))
        return out, y, state, tail

    def _gmu(self, layer: Params, h: jnp.ndarray, memory: jnp.ndarray):
        with jax.named_scope("gmu"):
            gate = jax.nn.silu(self.model._dense(layer, "gmu_in", h))
            return self.model._dense(layer, "gmu_out", memory * gate)

    def _plain_qkv(self, layer: Params, h: jnp.ndarray):
        """Plain attention's query [B, T, H, dh] and this token's key and
        value heads [B, T, KH, dh]."""
        cfg = self.cfg
        b, t, _ = h.shape
        proj = self._proj(layer)
        kv = (b, t, cfg.num_kv_heads, cfg.head_dim_)
        return (proj("wq", h).reshape(b, t, cfg.num_heads, cfg.head_dim_),
                proj("wk", h).reshape(kv), proj("wv", h).reshape(kv))

    def _paired_query(self, layer: Params, h: jnp.ndarray) -> jnp.ndarray:
        """[B, T, H, 2 * dh]: head 2j as [q | 0], head 2j + 1 as [0 | q]."""
        cfg = self.cfg
        b, t, _ = h.shape
        dh = cfg.head_dim_
        q = self._proj(layer)("wq", h).reshape(
            b, t, cfg.num_heads // 2, 2, dh)
        zero = jnp.zeros_like(q[..., 0, :])
        first = jnp.concatenate([q[..., 0, :], zero], -1)
        second = jnp.concatenate([zero, q[..., 1, :]], -1)
        return jnp.stack([first, second], axis=3).reshape(
            b, t, cfg.num_heads, 2 * dh)

    def _paired_kv(self, layer: Params, h: jnp.ndarray):
        """Keys and values as rows of pairs, [B, T, KH / 2, 2 * dh]."""
        cfg = self.cfg
        b, t, _ = h.shape
        proj = self._proj(layer)
        shape = (b, t, cfg.num_kv_heads // 2, 2 * cfg.head_dim_)
        return proj("wk", h).reshape(shape), proj("wv", h).reshape(shape)

    def _wide_query(self, layer: Params, h: jnp.ndarray) -> jnp.ndarray:
        """The query laid out against a whole cached row, [B, T, H, row]:
        head (pair k, c) holds its head_dim numbers at the lanes of key
        head 2k + c and zeros elsewhere, so a cached row (a token's
        pairs side by side) is one key and one value for all heads, read
        as it is stored. Splitting a gathered row back into [pairs, 2 *
        head_dim] moves the whole gathered window to another tiling, two
        copies a reading layer a step (PERF.md, PR 33); the zeros cost
        multiplies the step does not wait for."""
        cfg = self.cfg
        b, t, _ = h.shape
        dh, kp = cfg.head_dim_, cfg.num_kv_heads // 2
        share = cfg.num_heads // cfg.num_kv_heads
        q = self._proj(layer)("wq", h).reshape(b, t, kp, share, 2, dh)
        wide = jnp.einsum("btkjcd,kl,ce->btkjcled", q,
                          jnp.eye(kp, dtype=q.dtype),
                          jnp.eye(2, dtype=q.dtype))
        return wide.reshape(b, t, cfg.num_heads, kp * 2 * dh)

    def _own_pair(self, att: jnp.ndarray) -> jnp.ndarray:
        """[B, T, H, row] (each head's softmax times whole rows) -> [B, T,
        H, 2 * dh]: the lanes of the head's own key/value pair."""
        cfg = self.cfg
        b, t = att.shape[:2]
        kp = cfg.num_kv_heads // 2
        x = att.reshape(b, t, kp, cfg.num_heads // kp, kp,
                        2 * cfg.head_dim_)
        own = jnp.einsum("btkglw,kl->btkgw", x, jnp.eye(kp, dtype=x.dtype))
        return own.reshape(b, t, cfg.num_heads, 2 * cfg.head_dim_)

    def _diff_output(self, layer: Params, att: jnp.ndarray) -> jnp.ndarray:
        """[B, T, H, 2 * dh] (each head's softmax times the pair's value)
        -> the mixer's output: (A1 - lambda A2) V per pair, the
        sub-layer RMSNorm over the pair's width times (1 - lambda_init),
        concatenated, through the output projection."""
        cfg = self.cfg
        b, t = att.shape[:2]
        lam0 = layer["lambda_init"].astype(F32)

        def dot(a, b_):
            return jnp.sum(layer[a].astype(F32) * layer[b_].astype(F32))
        lam = (jnp.exp(dot("lambda_q1", "lambda_k1"))
               - jnp.exp(dot("lambda_q2", "lambda_k2")) + lam0)
        att = att.astype(F32).reshape(
            b, t, cfg.num_heads // 2, 2, 2 * cfg.head_dim_)
        pair = att[..., 0, :] - lam * att[..., 1, :]
        pair = rms_norm(pair, layer["subln"], cfg.rms_norm_eps) * (1.0 - lam0)
        return self._proj(layer)("wo", pair.astype(self.model.adtype).reshape(
            b, t, cfg.num_heads * cfg.head_dim_))

    def _mlp(self, layer: Params, x: jnp.ndarray) -> jnp.ndarray:
        h = self._norm(layer, "norm2", x)
        out, _ = self.model._mlp(layer, h, self._proj(layer))
        return x + out

    def _with_depth(self, layers: Dict[str, Params], run: Run
                    ) -> List[Params]:
        """A run's stacked dicts, one a position of the period, each
        with what rides the scan beside the weights: the repeat index and
        differential attention's ``lambda_init`` of the layer's depth."""
        out = []
        for j in range(run.period):
            depth = run.start + j + run.period * jnp.arange(run.reps)
            out.append({**layers[self.run_key(run, j)],
                        "rep": jnp.arange(run.reps, dtype=jnp.int32),
                        "lambda_init": lambda_init(depth)})
        return out

    def _walk(self, layers: Dict[str, Params], x, carry: Dict, block,
              remat=None):
        """Run every run: ``block(l0, layer, x, carry) -> (x, carry)`` for
        each layer, ``l0`` the layer's index at repeat 0 (``layer["rep"]``
        counts the repeats); a run that repeats goes through one
        ``lax.scan``. ``carry`` is a dict; its ``shared`` entry (the paged
        attention layer's keys and values, read by every cross layer
        above) never rides a scan's carry: the body closes over it."""
        def no_shared(c):
            return {k: v for k, v in c.items() if k != "shared"}

        for run in self.runs:
            stacks = self._with_depth(layers, run)
            shared = carry.get("shared")

            def body(x_, carry_, xs, run=run, shared=shared):
                if shared is not None:
                    carry_ = {**carry_, "shared": shared}
                for j in range(run.period):
                    x_, carry_ = block(run.start + j, xs[j], x_, carry_)
                return x_, carry_

            if run.reps == 1:
                x, carry = body(x, carry, [{k: v[0] for k, v in s.items()}
                                           for s in stacks])
                continue

            def scanned(state, xs, body=body):
                x_, carry_ = body(*state, xs)
                return (x_, no_shared(carry_)), None

            (x, carry), _ = jax.lax.scan(
                remat(scanned) if remat else scanned,
                (x, no_shared(carry)), stacks)
            if shared is not None:
                carry = {**carry, "shared": shared}
        return x, carry

    # ------------------------------------------------------- full sequence

    def forward(self, layers: Dict[str, Params], x: jnp.ndarray,
                positions: jnp.ndarray, kv_mask: Optional[jnp.ndarray],
                real: jnp.ndarray) -> jnp.ndarray:
        """Whole sequences from an empty state: [B, T, D] -> [B, T, D]
        (before the final norm). ``real`` [B, T] bool marks the tokens
        that move the state-space layers' state (right padding does
        not)."""
        cfg, model = self.cfg, self.model
        b, t, _ = x.shape
        di = cfg.ssm_inner_
        carry = {"memory": jnp.zeros((b, t, di), x.dtype)}

        def attend(q, k, v, window):
            return model._attention(q, k, v, kv_mask, positions, positions,
                                    window=window)

        def block(l0, layer, x_, carry_):
            spec = self.spec[l0]
            h = self._norm(layer, "norm1", x_)
            if spec.mixer == "ssm":
                out, memory, _, _ = self._ssm(
                    layer, h,
                    jnp.zeros((b, cfg.ssm_state_size, di), F32),
                    jnp.zeros((b, cfg.ssm_conv_width - 1, di), x_.dtype),
                    real)
                carry_ = {**carry_, "memory": memory}
            elif spec.mixer == "gmu":
                out = self._gmu(layer, h, carry_["memory"])
            elif spec.mixer == "attention":
                with jax.named_scope(_ATTN_SCOPE[spec.cache]):
                    q, k, v = self._plain_qkv(layer, h)
                    out = self._proj(layer)("wo", attend(
                        q, k, v, None).reshape(b, t, -1))
            else:
                with jax.named_scope(_ATTN_SCOPE[spec.cache]):
                    q = self._paired_query(layer, h)
                    if spec.cache == "shared":
                        k, v = carry_["shared"]
                    else:
                        k, v = self._paired_kv(layer, h)
                        if spec.cache == "paged" and self.shared_readers > 1:
                            carry_ = {**carry_, "shared": (k, v)}
                    out = self._diff_output(
                        layer, attend(q, k, v, spec.window))
            return self._mlp(layer, x_ + out), carry_

        x, _ = self._walk(layers, x, carry, block, remat=model._maybe_remat)
        return x

    # ---------------------------------------------------------- paged steps

    def paged(self, layers: Dict[str, Params], view: Dict, x: jnp.ndarray,
              positions: jnp.ndarray, attention, scan_kernel=None):
        """The layers of one paged step (decode: T = 1 for every slot;
        prefill chunk: T tokens of one slot) over the arrays of
        ``cache_spec()``. Beside what ``Transformer._paged_layers`` reads,
        ``view`` holds:

          window_tables [B, ring]  the window pool's page ids: logical
                        page j of a row lives at entry j % ring
          state_rows    [B] int32, optional: the slot whose state each
                        row reads and writes (unset: row b is slot b)
          fresh         [B] bool, optional: rows that start from an empty
                        state whatever the slot holds (a request's first
                        chunk: this is how a slot's state is zeroed)
          real          [B, T] bool: rows that are real; the others write
                        the trash page and move no state

        ``scan_kernel``: what runs a chunk's selective scans (``_ssm``).

        Returns (hidden before the final norm, the arrays updated)."""
        cfg = self.cfg
        b, t, _ = x.shape
        slots = self._slots
        pools = view["pools"]
        real = view["real"]
        tables = view["block_tables"]
        window_cols = view["valid"].shape[1]
        q0 = positions[:, 0]
        carry: Dict = {"pools": tuple(pools)}
        if "state" in slots:
            carry["memory"] = jnp.zeros((b, t, cfg.ssm_inner_), x.dtype)
        rows = view.get("state_rows")
        fresh = view.get("fresh")

        win = None
        if "paged_window" in slots:
            ps = pools[slots["paged_window"][0]].shape[2]
            ring = view["window_tables"].shape[1]
            # the pages a window layer reads: those holding (q0 - window,
            # q0), ceil(window / page) + 1 of them from page j0 on
            gather = -(-self.window // ps) + 1
            j0 = jnp.maximum(q0 // ps - (gather - 1), 0)
            entries = (j0[:, None] + jnp.arange(gather)[None, :]) % ring
            win = {
                "pages": jnp.take_along_axis(
                    view["window_tables"], entries, axis=1),
                "pos": (j0 * ps)[:, None] + jnp.arange(gather * ps)[None, :],
                "write_pages": jnp.where(real, jnp.take_along_axis(
                    view["window_tables"], (positions // ps) % ring,
                    axis=1), 0),
                "write_offs": jnp.where(real, positions % ps, 0)}
            win["valid"] = win["pos"] < q0[:, None]

        def read_rows(pool, i, pages, cols):
            """Layer i's pages [B, G] as rows [B, G * page_size, 1, row]:
            one key (or value) all heads share (``_wide_query``)."""
            return pool[i, pages].reshape(b, cols, 1, -1)

        def fresh_rows(layer, h):
            k, v = self._paired_kv(layer, h)
            return k.reshape(b, t, 1, -1), v.reshape(b, t, 1, -1)

        def write_rows(pool, i, pages, offs, rows_):
            return pool.at[i, pages, offs].set(rows_[:, :, 0])

        def block(l0, layer, x_, carry_):
            spec = self.spec[l0]
            pools_ = list(carry_["pools"])
            # this layer's index into the arrays of its cache kind
            first, per_period = self._cache_index[l0]
            i = first + layer["rep"] * per_period
            h = self._norm(layer, "norm1", x_)
            if spec.mixer == "ssm":
                si, ti = slots["state"]
                state, tail = pools_[si][i], pools_[ti][i]
                if rows is not None:
                    state, tail = state[rows], tail[rows]
                if fresh is not None:
                    state = jnp.where(fresh[:, None, None], 0.0, state)
                    tail = jnp.where(fresh[:, None, None],
                                     jnp.zeros_like(tail), tail)
                out, memory, state, tail = self._ssm(
                    layer, h, state, tail, real, scan_kernel)
                at = (i,) if rows is None else (i, rows)
                pools_[si] = pools_[si].at[at].set(state)
                pools_[ti] = pools_[ti].at[at].set(tail)
                carry_ = {**carry_, "memory": memory}
            elif spec.mixer == "gmu":
                out = self._gmu(layer, h, carry_["memory"])
            elif spec.mixer == "attention":
                # plain attention over the layer's own rows, arrays i of
                # the paged kind. The one-token step of every slot
                # gathers each row's whole window (a row splits into its
                # key / value heads; one head: the row is the key); a
                # chunk walks the cached columns in blocks up to the
                # rows' context, ``q0`` (``blockwise_paged_attention``)
                with jax.named_scope(_ATTN_SCOPE[spec.cache]):
                    ki, vi = slots["paged"]
                    q, k, v = self._plain_qkv(layer, h)
                    if attention is decode_attention:
                        heads = (b, window_cols, cfg.num_kv_heads,
                                 cfg.head_dim_)
                        att = attention(
                            q, pools_[ki][i, tables].reshape(heads),
                            pools_[vi][i, tables].reshape(heads), k, v,
                            kv_valid=view["valid"],
                            kv_positions=view["pos"], window=None,
                            q_positions=positions,
                            softmax_scale=cfg.head_dim_ ** -0.5)
                    else:
                        att = blockwise_paged_attention(
                            q, pools_[ki], pools_[vi], i, tables, q0, k, v,
                            q_positions=positions,
                            block_pages=chunk_attention_block_pages(
                                pools_[ki].shape[2], tables.shape[1]),
                            softmax_scale=cfg.head_dim_ ** -0.5)
                    at = (i, view["write_pages"], view["write_offs"])
                    pools_[ki] = pools_[ki].at[at].set(k.reshape(b, t, -1))
                    pools_[vi] = pools_[vi].at[at].set(v.reshape(b, t, -1))
                    out = self._proj(layer)("wo", att.reshape(b, t, -1))
            else:
                with jax.named_scope(_ATTN_SCOPE[spec.cache]):
                    q = self._wide_query(layer, h)
                    kw = dict(q_positions=positions,
                              softmax_scale=cfg.head_dim_ ** -0.5)
                    if spec.cache == "paged_window":
                        ki, vi = slots["paged_window"]
                        k, v = fresh_rows(layer, h)
                        att = attention(
                            q, read_rows(pools_[ki], i, win["pages"],
                                         win["pos"].shape[1]),
                            read_rows(pools_[vi], i, win["pages"],
                                      win["pos"].shape[1]),
                            k, v, kv_valid=win["valid"],
                            kv_positions=win["pos"], window=spec.window,
                            **kw)
                        at = (i, win["write_pages"], win["write_offs"])
                        pools_[ki] = write_rows(pools_[ki], *at, k)
                        pools_[vi] = write_rows(pools_[vi], *at, v)
                    else:
                        if spec.cache == "paged":
                            ki, vi = slots["paged"]
                            k, v = fresh_rows(layer, h)
                            shared = (
                                read_rows(pools_[ki], i, tables,
                                          window_cols),
                                read_rows(pools_[vi], i, tables,
                                          window_cols), k, v)
                            at = (i, view["write_pages"],
                                  view["write_offs"])
                            pools_[ki] = write_rows(pools_[ki], *at, k)
                            pools_[vi] = write_rows(pools_[vi], *at, v)
                            carry_ = {**carry_, "shared": shared}
                        att = attention(
                            q, *carry_["shared"], kv_valid=view["valid"],
                            kv_positions=view["pos"], window=None, **kw)
                    out = self._diff_output(layer, self._own_pair(att))
            carry_ = {**carry_, "pools": tuple(pools_)}
            return self._mlp(layer, x_ + out), carry_

        x, carry = self._walk(layers, x, carry, block)
        return x, carry["pools"]
