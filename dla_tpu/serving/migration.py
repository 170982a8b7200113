"""KV page migration: move an in-flight request's committed state
between serving engines — the handoff primitive behind prefill/decode
disaggregation and rebalance-without-recompute.

A request mid-decode is fully described by host metadata the engine
already mirrors (rid, sampling params, streamed tokens and logprobs,
the committed length) plus the KV columns its pages hold for the
committed prefix. :class:`KVMigrator` serializes that into a
:class:`MigrationTicket`:

- **export** gathers the request's ordered page list out of the source
  pool in ONE fixed-shape jitted call (``ServingEngine._export_kv_fn``,
  compile counter pinned at 1 per engine build) — pad page ids route to
  the trash page, so every export of every request reuses one compile;
- **transfer** keeps the payload on device when source and target share
  a device (or ``jax.device_put`` reaches the target directly), with a
  host bounce as the fallback (``transport: host`` forces it; the
  bounced bytes are counted on ``serving/migration/host_bounce_bytes``);
- **install** (``ServingEngine.import_request``) allocates pages on the
  target, scatters the KV columns in ONE fixed-shape jitted call
  (``_import_kv_fn``, also pinned at 1), registers the committed full
  pages into the target's PrefixCache, and binds the request straight
  into a decode slot — it resumes mid-stream on the next engine step.

The continuation is bit-identical to never having moved: token k of a
request is sampled with ``fold_in(PRNGKey(seed), k)`` where the seed
depends only on (engine config seed, rid) or explicit SamplingParams —
never on slot, engine, or placement — and the import preserves rid,
sampling, and the generated-token index.

Failure semantics: export REJECTS requests that are not resumable in
place — queued, prefilling, evicted (their pages are gone: the
"eviction hole"), or with uncomputed committed columns — and import
rejects geometry mismatches and page-pool exhaustion, all as
:class:`MigrationError` with the source request untouched. The fleet's
handoff path moves the supervisor journal entry atomically with the
install, so a source-engine crash mid-handoff replays the request on
exactly one engine (docs/SERVING.md "Disaggregated prefill/decode").
"""
from __future__ import annotations

import dataclasses
import json
import struct
from typing import List, Optional

import jax
import numpy as np

TRANSPORTS = ("auto", "device", "host")

#: Wire format version for ``MigrationTicket.to_bytes``. Bump on any
#: header-field or payload-layout change; ``from_bytes`` rejects other
#: versions with :class:`MigrationError` rather than misparsing.
WIRE_VERSION = 2      # 2: one payload per pool of the cache (was k, v)

_WIRE_MAGIC = b"DLAT"
# magic(4) | version u16 | header-json length u32, little-endian
_WIRE_HEAD = struct.Struct("<4sHI")


def _wire_dtype(name: str) -> np.dtype:
    """Resolve a serialized dtype name, including the ml_dtypes families
    (bfloat16, float8_*) jax payloads use that numpy does not register
    under their string names."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        try:
            return np.dtype(getattr(ml_dtypes, name))
        except AttributeError:
            raise MigrationError(
                f"ticket payload dtype {name!r} is not resolvable on "
                f"this host") from None


class MigrationError(RuntimeError):
    """A migration step refused or failed; the source request (when one
    exists) is untouched and keeps running where it was."""


@dataclasses.dataclass(frozen=True)
class MigrationConfig:
    """KV handoff knobs (``latency.serving.migration`` in config).

    ``transport`` picks how the page payload travels: ``auto`` stays on
    device when the pools share one (device-to-device put otherwise,
    host bounce only when that fails), ``device`` requires a device
    path, ``host`` forces the bounce — the portability/debug arm, and
    what exercises ``serving/migration/host_bounce_bytes``."""

    transport: str = "auto"

    def __post_init__(self):
        if self.transport not in TRANSPORTS:
            raise ValueError(
                f"migration transport must be one of {TRANSPORTS}, "
                f"got {self.transport!r}")

    @classmethod
    def from_config(cls, cfg: Optional[dict]) -> "MigrationConfig":
        if not cfg:
            return cls()
        cfg = dict(cfg)
        cfg.pop("enabled", None)
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(cfg) - known
        if unknown:
            raise ValueError(
                f"unknown migration config keys: {sorted(unknown)}")
        return cls(**cfg)


@dataclasses.dataclass
class MigrationTicket:
    """A request's complete resumable state, engine-independent.

    ``payloads`` are the gathered page contents, one array per pool of
    the exporting engine (keys and values; or one pool of latent rows),
    each ``[L, pages_per_slot, page_size, heads, width]`` — fixed per
    engine geometry, with only the first ``n_pages`` rows real (the pad
    rows hold trash-page contents and are never scattered onto real
    pages).
    ``committed_len`` is the number of KV columns the payload covers:
    ``len(prompt) + len(generated) - 1`` — the last generated token is
    the next decode input and its column has not been written yet.
    """
    rid: int
    prompt_tokens: List[int]
    max_new_tokens: int
    generated: List[int]
    generated_logprobs: List[float]
    sampling: Optional[object]          # SamplingParams override or None
    arrival_time: float
    deadline: Optional[float]
    priority: int
    committed_len: int
    page_size: int
    n_pages: int                        # real payload rows (committed)
    payloads: tuple                     # per pool [L, P, page_size, h, w]
    transport: str = "device"           # how the payload currently lives
    src_slot: Optional[int] = None      # fleet slot of the exporter
    # source-engine clocks, carried so TTFT is not double-counted and
    # the cross-engine ITL gap (the handoff wait) is real
    admitted_time: Optional[float] = None
    first_token_time: Optional[float] = None
    last_token_time: Optional[float] = None
    # distributed-tracing context ({"trace", "span", "parent"} hex ids,
    # docs/OBSERVABILITY.md): optional meta key read via ``meta.get`` on
    # the old side, so carrying it needs no WIRE_VERSION bump
    trace_ctx: Optional[dict] = None
    # owning tenant (multi-tenant serving): the importer re-binds the
    # request's adapter and KV namespace from this; optional meta key
    # read via ``meta.get``, so no WIRE_VERSION bump either
    tenant: Optional[str] = None

    @property
    def payload_bytes(self) -> int:
        return sum(int(getattr(x, "nbytes", 0)) for x in self.payloads)

    # ------------------------------------------------------- wire format

    def to_bytes(self) -> bytes:
        """Serialize for a cross-host handoff: a versioned header
        (magic, :data:`WIRE_VERSION`, JSON metadata with payload
        dtype/shape) followed by the raw KV page bytes. The payload is
        host-bounced first (one D2H, same contract as ``transport:
        host``), and the round trip is bit-exact: ``from_bytes`` yields
        payload arrays whose bytes equal the originals, and float
        metadata (arrival clocks, logprobs) survives via JSON's
        shortest-roundtrip float repr."""
        # dla: disable=host-sync-in-hot-loop -- designed wire export: one D2H per shipped ticket, counted by the caller on serving/federation/handoff_bytes
        arrays = [np.ascontiguousarray(np.asarray(x))
                  for x in self.payloads]
        sampling = (None if self.sampling is None
                    else dataclasses.asdict(self.sampling))
        meta = {
            "rid": int(self.rid),
            "prompt_tokens": [int(t) for t in self.prompt_tokens],
            "max_new_tokens": int(self.max_new_tokens),
            "generated": [int(t) for t in self.generated],
            "generated_logprobs": [float(p)
                                   for p in self.generated_logprobs],
            "sampling": sampling,
            "arrival_time": float(self.arrival_time),
            "deadline": self.deadline,
            "priority": int(self.priority),
            "committed_len": int(self.committed_len),
            "page_size": int(self.page_size),
            "n_pages": int(self.n_pages),
            "src_slot": self.src_slot,
            "admitted_time": self.admitted_time,
            "first_token_time": self.first_token_time,
            "last_token_time": self.last_token_time,
            "trace_ctx": self.trace_ctx,
            "tenant": self.tenant,
            "payloads": [{"dtype": str(a.dtype), "shape": list(a.shape)}
                         for a in arrays],
        }
        header = json.dumps(meta, separators=(",", ":")).encode()
        return (_WIRE_HEAD.pack(_WIRE_MAGIC, WIRE_VERSION, len(header))
                + header + b"".join(a.tobytes() for a in arrays))

    @classmethod
    def from_bytes(cls, blob: bytes) -> "MigrationTicket":
        """Parse a :meth:`to_bytes` payload. Rejects a wrong magic,
        a version mismatch, and truncation at any layer (header or
        payload bytes) with :class:`MigrationError` — a half-received
        ticket must never install."""
        if len(blob) < _WIRE_HEAD.size:
            raise MigrationError(
                f"truncated ticket: {len(blob)} bytes is shorter than "
                f"the {_WIRE_HEAD.size}-byte wire header")
        magic, version, hlen = _WIRE_HEAD.unpack_from(blob)
        if magic != _WIRE_MAGIC:
            raise MigrationError(
                f"bad ticket magic {magic!r} (expected {_WIRE_MAGIC!r})")
        if version != WIRE_VERSION:
            raise MigrationError(
                f"ticket wire version {version} does not match this "
                f"host's {WIRE_VERSION}")
        if len(blob) < _WIRE_HEAD.size + hlen:
            raise MigrationError(
                f"truncated ticket header: need {hlen} bytes, have "
                f"{len(blob) - _WIRE_HEAD.size}")
        try:
            meta = json.loads(blob[_WIRE_HEAD.size:_WIRE_HEAD.size + hlen])
        except ValueError as exc:
            raise MigrationError(
                f"corrupt ticket header: {exc}") from exc
        specs = [(_wire_dtype(p["dtype"]),
                  tuple(int(d) for d in p["shape"]))
                 for p in meta["payloads"]]
        counts = [int(np.prod(shape, dtype=np.int64)) for _, shape in specs]
        declared = sum(n * dt.itemsize for n, (dt, _) in zip(counts, specs))
        off = _WIRE_HEAD.size + hlen
        if len(blob) != off + declared:
            raise MigrationError(
                f"truncated ticket payload: header declares "
                f"{declared} payload bytes, have {len(blob) - off}")
        arrays = []
        for n, (dt, shape) in zip(counts, specs):
            arrays.append(np.frombuffer(
                blob, dtype=dt, count=n, offset=off).reshape(shape).copy())
            off += n * dt.itemsize
        sampling = meta["sampling"]
        if sampling is not None:
            from dla_tpu.ops.sampling import SamplingParams
            sampling = SamplingParams(**sampling)
        return cls(
            rid=meta["rid"], prompt_tokens=meta["prompt_tokens"],
            max_new_tokens=meta["max_new_tokens"],
            generated=meta["generated"],
            generated_logprobs=meta["generated_logprobs"],
            sampling=sampling, arrival_time=meta["arrival_time"],
            deadline=meta["deadline"], priority=meta["priority"],
            committed_len=meta["committed_len"],
            page_size=meta["page_size"], n_pages=meta["n_pages"],
            payloads=tuple(arrays), transport="host",
            src_slot=meta["src_slot"],
            admitted_time=meta["admitted_time"],
            first_token_time=meta["first_token_time"],
            last_token_time=meta["last_token_time"],
            trace_ctx=meta.get("trace_ctx"),
            tenant=meta.get("tenant"))


class KVMigrator:
    """Orchestrates export -> transfer -> install between two engines.

    The migrator is stateless beyond its config; counters live on the
    ENGINES' ``_mig_stats`` (delta-mirrored into their registries each
    step, Supervisor-re-seeded across rebuilds — the speculative-counter
    idiom), so totals stay monotone however many migrators touch an
    engine. Export failures count on the source, import failures and
    successes on the target."""

    def __init__(self, cfg: Optional[MigrationConfig] = None):
        self.cfg = cfg or MigrationConfig()

    # ---------------------------------------------------------- pipeline

    def export_ticket(self, engine, rid: int,
                      src_slot: Optional[int] = None) -> MigrationTicket:
        """Serialize ``rid``'s committed state out of ``engine``. Raises
        :class:`MigrationError` (and counts a failed migration on the
        source) when the request is not resumable in place."""
        ticket = engine.export_request(rid)
        ticket.src_slot = src_slot
        return ticket

    def deliver(self, ticket: MigrationTicket, dst_engine) -> None:
        """Apply the transport policy: land the payload where the target
        engine's pool lives. Mutates the ticket in place."""
        mode = self.cfg.transport
        if mode == "host":
            self._bounce(ticket)
            return
        dst_dev = self._pool_device(dst_engine)
        src_dev = self._payload_device(ticket)
        if dst_dev is None or src_dev is None or src_dev == dst_dev:
            return                      # shared device: zero-copy handoff
        try:
            ticket.payloads = tuple(
                jax.device_put(x, dst_dev) for x in ticket.payloads)
        except Exception as exc:  # noqa: BLE001 — no D2D path: bounce
            if mode == "device":
                raise MigrationError(
                    f"device-to-device transfer failed and transport is "
                    f"pinned to 'device': {exc!r}") from exc
            self._bounce(ticket)

    def install(self, dst_engine, ticket: MigrationTicket):
        """Install the ticket into the target engine (see
        ``ServingEngine.import_request``); returns the live Request."""
        self.deliver(ticket, dst_engine)
        return dst_engine.import_request(ticket)

    def migrate(self, src_engine, rid: int, dst_engine):
        """Engine-level end-to-end move: export, transfer, install, then
        release the source copy. On an install failure the source
        request keeps running untouched. Fleet handoffs do NOT use this
        directly — they interleave the supervisor-journal move for the
        exactly-once crash contract (serving.fleet)."""
        ticket = self.export_ticket(src_engine, rid)
        req = self.install(dst_engine, ticket)
        src_engine.release_migrated(rid)
        return req

    # --------------------------------------------------------- internals

    @staticmethod
    def _pool_device(engine):
        devs = getattr(engine.cache.pools[0], "devices", None)
        if devs is None:
            return None
        try:
            return next(iter(devs()))
        except Exception:  # noqa: BLE001 — sharded/committed-less array
            return None

    @staticmethod
    def _payload_device(ticket: MigrationTicket):
        devs = getattr(ticket.payloads[0], "devices", None)
        if devs is None:
            return None                 # host-resident payload
        try:
            return next(iter(devs()))
        except Exception:  # noqa: BLE001
            return None

    @staticmethod
    def _bounce(ticket: MigrationTicket) -> None:
        if ticket.transport == "host":
            return
        # dla: disable=host-sync-in-hot-loop -- designed migration host bounce: one D2H per migrated request, counted on serving/migration/host_bounce_bytes
        ticket.payloads = tuple(np.asarray(x) for x in ticket.payloads)
        ticket.transport = "host"
