"""Worker process for the federation acceptance test
(test_federation.py): one gateway-fronted serving fleet on this host.

Builds the deterministic tiny Transformer (params from
``jax.random.key(7)`` — every worker and the in-process reference hold
bit-identical weights), fronts a 2-member ``FleetRouter`` with a
``ServingGateway`` on an ephemeral port, heartbeats into the shared
gossip directory, prints ``READY <name> <port>`` and serves until
killed. An optional per-step delay keeps streams open long enough for
the parent to kill this worker MID-STREAM (the zero-loss replay path)
or migrate a live request away.

Usage: python tests/_gateway_worker.py <gossip_dir> <name> [slow_ms]
[spool_dir] (launched on the virtual CPU platform; see
_cpuhost.cpu_child_env). A non-empty ``spool_dir`` installs an
enabled process tracer spooling into it — the distributed-tracing
acceptance test merges every worker's spool with tools/trace_merge.py.
"""
import sys
import time


def main() -> None:
    gossip_dir, name = sys.argv[1], sys.argv[2]
    slow_ms = float(sys.argv[3]) if len(sys.argv) > 3 else 0.0
    spool_dir = sys.argv[4] if len(sys.argv) > 4 else ""

    import jax

    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.config import get_model_config
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.serving import (
        FleetConfig,
        FleetRouter,
        GossipBeater,
        ServingConfig,
        ServingEngine,
        ServingGateway,
    )

    if spool_dir:
        from dla_tpu.telemetry.trace import Tracer, install_tracer
        install_tracer(Tracer.from_config(
            {"enabled": True, "spool_dir": spool_dir, "proc": name}))

    cfg = get_model_config("tiny")
    model = Transformer(cfg)
    params = model.init(jax.random.key(7))
    gen = GenerationConfig(max_new_tokens=16, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    kw = dict(page_size=4, num_pages=64, num_slots=2, max_model_len=32,
              prefill_chunk=4, prefix_cache=True,
              fault_plan="")

    def factory(slot):
        return ServingEngine(model, params, gen, ServingConfig(**kw))

    router = FleetRouter(factory, FleetConfig(engines=2))
    if slow_ms > 0:
        orig_step = router.step

        def slow_step():
            time.sleep(slow_ms / 1000.0)
            return orig_step()
        router.step = router.poll = slow_step

    gw = ServingGateway(router)
    beater = GossipBeater(gw, gossip_dir, name)
    print(f"READY {name} {gw.port}", flush=True)
    try:
        while True:           # serve until the parent kills us
            time.sleep(0.5)
    finally:
        beater.stop()
        gw.close()


if __name__ == "__main__":
    main()
