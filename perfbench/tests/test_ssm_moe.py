"""The Granite 4.0-H configuration's benchmark files: the plain reference on
hand-computed cases, ``costs_ssm_moe`` against hand counts and the
arithmetic of the configuration's ``cut``, the traffic file, the cell's
manifest entries (as sets: later PRs append to the shared lists), and the
new readers on a run that gives them nothing to read."""
import json
import math
import os

import jax.numpy as jnp
import numpy as np

from perfbench.lib import costs_ssm_moe, traffic
from perfbench.lib.manifest import Manifest
from perfbench.tests.conftest import ROOT

CELL = "serve_ssm_moe_decode_long"
CONFIG = "granite4_h_small_serve_p10_ep2"
DRIVER = "serve_closed_loop_ssm_moe"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"ssm2_scan_chunk_roofline", "ssm_moe_decode_roofline"}
JOINED = {"slot_occupancy_pct", "chunk_step_share_pct", "ttft_p50_ms.layer",
          "ttft_p90_ms", "page_occupancy_peak_pct", "preemptions",
          "engine_host_gap_ms", "prefill_chunk_device_ms",
          "device_idle_pct.serve", "peak_hbm_gib.serve",
          "reference_gap.serve", "request_queue_wait_ms",
          "prefill_service_ms", "schedule_host_ms", "engine_host_self_ms",
          "decode_args_host_ms", "kv_read_amplification",
          "sampled_step_share_pct", "moe_experts_device_ms",
          "moe_tokens_per_expert", "ssm_state_mib_per_slot",
          "ssm_mixer_device_ms", "ssm_scan_chunk_device_ms",
          "setup_trace_lower_s", "setup_compile_s", "setup_cache_misses",
          "setup_kernel_import_wait_s", "setup_build_s", "setup_jit_other_s",
          "setup_attributed_pct"}


def _ref():
    return Manifest(ROOT).reference("granite4h_block")


def test_one_mamba2_step_by_hand():
    """Two heads of one channel, N = 2: head 0 decays by exp(-ln 2) = 1/2
    and takes in ln 2 * x B; head 1 has step size 0 and keeps its state.
    y = S C + D x."""
    ref = _ref()
    state = jnp.asarray([[[1.0, 0.0]], [[0.0, 2.0]]])          # [H, P, N]
    ln2 = math.log(2.0)
    new, y = ref.mamba2_step(
        state, jnp.asarray([[1.0], [2.0]]), jnp.asarray([ln2, 0.0]),
        -jnp.ones((2,)), jnp.asarray([1.0, 2.0]), jnp.asarray([1.0, 1.0]),
        jnp.asarray([0.5, 0.5]))
    assert np.allclose(new, [[[0.5 + ln2, 2 * ln2]], [[0.0, 2.0]]],
                       atol=1e-6)
    assert np.allclose(y, [[0.5 + 3 * ln2 + 0.5], [2.0 + 1.0]], atol=1e-6)


def test_grouped_query_attention_scale_by_hand():
    """Two query heads over one key / value head, scale 1/2: token 1 head
    0 scores [ln 9, 0] * 1/2 = [ln 3, 0] -> [3/4, 1/4]."""
    ref = _ref()
    q = jnp.asarray([[[0.0], [0.0]], [[math.log(9.0)], [0.0]]])
    k = jnp.asarray([[[1.0]], [[0.0]]])
    v = jnp.asarray([[[2.0]], [[6.0]]])
    out = ref.attention(q, k, v, 0.5)
    assert np.allclose(out, [[2.0, 2.0], [3.0, 4.0]], atol=1e-6)


def test_router_softmaxes_the_chosen_logits_of_the_held_share():
    """One token, 4 experts scored [3, 1, 2, 0], top 2 = experts 0 and 2
    with weights softmax([3, 2]); a share holding experts 2..3 adds only
    expert 2's part, every expert the identity-like gate here."""
    ref = _ref()
    d = 2
    h = jnp.asarray([[1.0, 0.0]])
    w = {"router": jnp.asarray([[3.0, 1.0, 2.0, 0.0], [0.0] * 4]),
         "w_gate": jnp.full((2, d, 1), 10.0), "w_up": jnp.ones((2, d, 1)),
         "w_down": jnp.asarray([[[1.0, 0.0]], [[0.0, 1.0]]]),
         "ws_gate": jnp.zeros((d, 1)), "ws_up": jnp.zeros((d, 1)),
         "ws_down": jnp.zeros((1, d))}
    routed, shared = ref.expert_layer(h, w, {"num_experts_per_tok": 2},
                                      experts=(2, 2))
    p2 = 1.0 / (1.0 + math.e)              # softmax([3, 2])[1]
    silu10 = 10.0 / (1.0 + math.exp(-10.0))
    assert np.allclose(routed, [[p2 * silu10, 0.0]], atol=1e-5)
    assert np.allclose(shared, 0.0)


def test_costs_match_hand_counts_and_the_configurations_arithmetic():
    cfg = Manifest(ROOT).config(CONFIG)
    assert costs_ssm_moe.layer_counts(cfg) == {"mamba": 9, "attention": 1}
    assert costs_ssm_moe.mixer_params(cfg) == {
        "mamba": 102_286_976, "attention": 41_943_040}
    assert costs_ssm_moe.expert_params(cfg) == 9_437_184
    assert costs_ssm_moe.shared_params(cfg) == 18_874_368
    assert costs_ssm_moe.router_params(cfg) == 294_912
    ffn = 294_912 + 36 * 9_437_184 + 18_874_368
    assert costs_ssm_moe.ffn_params(cfg) == ffn
    total = costs_ssm_moe.total_params(cfg)
    assert total == (9 * (102_286_976 + ffn + 8192)
                     + 41_943_040 + ffn + 8192 + 4096 + 100_352 * 4096)
    assert total == 4_962_732_672 and round(2 * total / 2 ** 30, 2) == 9.24
    assert costs_ssm_moe.kv_row_bytes(cfg) == 4096
    state = costs_ssm_moe.state_bytes_per_slot(cfg)
    assert state == 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert round(32 * state / 2 ** 30, 2) == 1.14
    # the issue's decode step: 32 slots, 35.7 of 36 experts hit a layer
    step = costs_ssm_moe.decode_step_bytes(cfg, 100_000, 32, 357.0)
    fixed = total - 10 * 36 * 9_437_184
    assert step == (2 * (fixed + 357.0 * 9_437_184) + 100_000 * 4096
                    + 2 * 32 * state)
    # a chunk's SSD scans: blocks of 256, one group
    per_block = (2 * 256 * 256 * (128 + 128 * 64)
                 + 4 * 256 * 128 * 64 * 128)
    assert costs_ssm_moe.ssd_chunk_flops(cfg, 512) == 9 * 2 * per_block
    assert costs_ssm_moe.ssd_chunk_bytes(cfg, 512) == 9 * (
        512 * (2 * 8192 + 256 + 128) * 2 + 2 * 128 * 64 * 128 * 4)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    least = costs_ssm_moe.ssd_chunk_least_seconds(cfg, 512, peaks)
    assert set(least) == {"mxu", "hbm"} and min(least.values()) > 0


def test_traffic_is_decode_long_closed_under_this_driver():
    m = Manifest(ROOT)
    mine, theirs = (m.traffic("decode_long_closed_ssm_moe"),
                    m.traffic("decode_long_closed"))
    assert mine["driver"] == DRIVER
    for key in ("clients", "prompt", "output", "pairing", "warm_steps",
                "part_seconds"):
        assert mine[key] == theirs[key], key
    cfg = m.config(CONFIG)
    gen = traffic.ClosedLoopTraffic(mine, 3000000019, int(cfg["vocab_size"]))
    assert max(p + o for p, o in gen.grid) <= int(
        cfg["serving"]["max_model_len"])
    chunk = int(cfg["serving"]["prefill_chunk"])
    assert sum(-(-p // chunk) for p, _ in gen.grid) <= mine["warm_steps"]
    assert int(cfg["serving"]["num_slots"]) == mine["clients"]


def test_manifest_entries_of_the_cell():
    m = Manifest(ROOT)
    cell = m.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "decode_long_closed_ssm_moe", 1)
    entry = m.config_entry(CONFIG)
    cfg = m.config(CONFIG)
    assert set(entry["reduced"]) == {"num_hidden_layers", "layer_types",
                                     "num_local_experts"}
    assert set(cfg["reduced_from"]) == set(entry["reduced"])
    if os.path.exists(CATALOG):
        row = json.loads(next(line for line in open(CATALOG)
                              if '"granite-4.0-h-small"' in line))
        assert entry["source"] == row["source_url"] == cfg["source"]
        for key, value in row["config"].items():
            if key in entry["reduced"]:
                continue
            assert cfg[key] == value, key
        assert cfg["layer_types"] == row["config"]["layer_types"][:10]
    e2e = {x["name"] for x in m.metrics_for("end_to_end", CELL)}
    assert e2e == {"itl_p99_ms", "setup_s"}
    names = {x["name"] for x in m.metrics_for("per_layer", CELL)}
    assert NEW | JOINED == names
    for entry in m.data["per_layer"]:
        if entry["name"] not in NEW:
            continue
        reader = m.layer_metric(entry["name"])
        assert entry["workloads"] == [CELL]
        assert (reader.MOVES, reader.UNIT, reader.LAYER, reader.SOURCE) == (
            entry["moves"], entry["unit"], entry["layer"], entry["source"])
        assert reader.DRIVERS == (DRIVER,)
    driver = m.driver(DRIVER)
    assert driver.ANNOTATIONS and driver.PROGRAMS


def test_readers_return_nothing_without_what_they_read():
    """On a program without the scopes and the counters (the parent), or
    with no trace, each new reader leaves its metric out and raises
    nothing."""
    m = Manifest(ROOT)

    class Ctx:
        counters = {}
        samples = {}
        trace = None
        peaks = None
        config = {"mamba_n_heads": 128, "num_local_experts": 36}
        programs = {"decode": r"jit__decode_fn",
                    "prefill_chunk": r"jit__prefill_chunk_fn"}
        trace_window = None
        annotations = ()
    for name in NEW:
        assert m.layer_metric(name).read(Ctx) is None

    class Other(Ctx):
        """A trace and peaks, but a configuration without Mamba-2 heads
        (Jamba's, say): the mechanism is not there to price."""
        trace = object()
        peaks = {"hbm_bytes_per_s": 1.0, "bf16_flops_per_s": 1.0}
        config = {"mamba_d_state": 16, "num_experts": 16}
        samples = {"live_context_tokens": [1], "running_slots": [1]}
        counters = {"decode_steps": 1, "moe_experts_hit": 1}
    for name in NEW:
        assert m.layer_metric(name).read(Other) is None


def test_the_driver_refuses_a_program_without_mamba2(monkeypatch):
    """What the parent gives: an importer that makes no ``ssm2`` layer
    (the parent's imported this file as a Mixtral) exits 3 before any
    weight is made."""
    import pytest
    driver = Manifest(ROOT).driver(DRIVER)
    cfg = Manifest(ROOT).config(CONFIG)
    from dla_tpu.models import hf_import

    def mixtral(hf, **kw):
        return hf_import.ModelConfig(
            vocab_size=512, hidden_size=64, intermediate_size=16,
            num_layers=2, num_heads=4, num_kv_heads=2, num_experts=4)
    monkeypatch.setattr(hf_import, "hf_config_to_model_config", mixtral)
    with pytest.raises(SystemExit) as exc:
        driver._model_config(cfg, cfg["serving"])
    assert exc.value.code == 3
