"""The one module of the yardstick that touches the system under test
and JAX: building the model from a configuration file, making weights on
the device from the seed, handing the plain reference its weights, and
reading the device. From the program it takes only entry points, spans,
counters and kernel names."""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional


def model_config(cfg: Dict, **run_shape):
    """A configuration file (Hugging Face key names) -> the program's
    ``ModelConfig``. ``run_shape`` carries what belongs to the run and
    not to the architecture: dtypes, remat, attention backend, the
    sequence length served or trained."""
    from dla_tpu.models.config import ModelConfig
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("only the gated-SiLU block is mapped here")
    return ModelConfig(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        num_layers=int(cfg["num_hidden_layers"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=cfg.get("head_dim"),
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        sliding_window=cfg.get("sliding_window"),
        **run_shape)


def seed_key(seed: int):
    """A PRNG key from any whole number: the driver's seeds pass 2**31,
    more than the 32 signed bits a JAX seed takes."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def init_params(model, seed: int, shardings=None):
    """Every weight in one jitted call on the device, in the type the
    model stores them, placed as ``shardings`` says. (The program's
    ``load_causal_lm`` makes them leaf by leaf and then moves them.)"""
    import jax
    return jax.jit(model.init, out_shardings=shardings)(seed_key(seed))


def reference_weights(params, mesh=None):
    """What the plain reference takes, from the program's parameter
    tree: (embedding, layer(l) -> dict, final_norm, lm_head). A layer is
    sliced out of the stacked leaves in one jitted call; on a mesh it is
    gathered to every device, one layer at a time."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    whole = NamedSharding(mesh, PartitionSpec()) if mesh is not None else None
    take = jax.jit(
        lambda layers, l: {k: jax.lax.dynamic_index_in_dim(
            v, l, 0, keepdims=False) for k, v in layers.items()},
        out_shardings=whole)
    gather = jax.jit(lambda x: x, out_shardings=whole)
    names = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
             "w_gate", "w_up", "w_down")
    layers = {k: params["layers"][k] for k in names}

    def layer(l: int) -> Dict:
        return take(layers, jnp.asarray(l, jnp.int32))

    return (gather(params["embed"]["embedding"]), layer,
            gather(params["final_norm"]), gather(params["lm_head"]))


# ------------------------------------------------------------------ device

def require_devices(chips: int, rehearsal: bool) -> Dict:
    """The device as JAX reports it. Fails unless the first device is a
    TPU and exactly the cell's chips are there (a rehearsal takes the
    CPU and says so)."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if rehearsal:
        if len(devs) < chips:
            raise SystemExit(f"REHEARSAL needs {chips} devices, has "
                             f"{len(devs)}: pass --rehearsal-devices")
        return info
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"no TPU: jax.devices()[0].platform is {devs[0].platform!r}. "
            "This benchmark measures only on the chip.")
    if len(devs) != chips:
        raise SystemExit(f"the cell asks for {chips} chip(s), JAX sees "
                         f"{len(devs)}")
    return info


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest device, where the backend says."""
    import jax
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


@dataclasses.dataclass
class CompileWatch:
    """Counts the backend compiles JAX reports (a hit in the persistent
    cache counts as the compile it replaced). ``in_window`` is the count
    while the window was open: it has to stay 0."""
    total: int = 0
    in_window: int = 0
    open: bool = False
    names: List[str] = dataclasses.field(default_factory=list)

    def install(self) -> "CompileWatch":
        import jax

        def on_duration(event, seconds, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.total += 1
                if self.open:
                    self.in_window += 1
                    self.names.append(str(kw.get("fun_name", "?")))

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        return self
