"""The Jamba configuration's benchmark files: the plain reference on
hand-computed cases, ``costs_ssm_attn`` against the arithmetic of the
configuration's ``cut``, the traffic file's grid, and the new readers on a
run that gives them nothing to read."""
import json
import math
import os

import jax.numpy as jnp
import numpy as np

from perfbench.lib import costs_ssm_attn, traffic
from perfbench.lib.manifest import Manifest
from perfbench.tests.conftest import ROOT

CELL = "serve_ssm_attn_longdoc"
CONFIG = "jamba2_3b_serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["ssm_scan_chunk_device_ms", "ssm_scan_chunk_roofline",
       "ssm_attn_chunk_roofline", "ssm_attn_decode_roofline"]


def _ref():
    return Manifest(ROOT).reference("jamba_block")


def test_multi_query_attention_two_tokens_by_hand():
    """Two query heads of size 1 over ONE key / value head. Token 0 sees
    itself alone: both heads give v_0. Token 1, head 0: s q k = [ln 3, 0]
    -> [3/4, 1/4] -> 3/4 * 2 + 1/4 * 6 = 3; head 1: [ln 4, 0] -> [4/5,
    1/5] -> 8/5 + 6/5 = 14/5. A query of 0 weighs both tokens alike."""
    ref = _ref()
    q = jnp.asarray([[[0.5], [-1.0]], [[math.log(3.0)], [math.log(4.0)]]])
    k = jnp.asarray([[[1.0]], [[0.0]]])
    v = jnp.asarray([[[2.0]], [[6.0]]])
    assert np.allclose(ref.attention(q, k, v), [[2.0, 2.0], [3.0, 2.8]],
                       atol=1e-6)
    q = q.at[1, 1, 0].set(0.0)                 # head 1 now scores [0, 0]
    assert np.allclose(ref.attention(q, k, v)[1, 1], 4.0, atol=1e-6)


def test_one_selective_scan_step_by_hand():
    """d = 2 channels, N = 2: channel 0 decays by exp(-ln 2) = 1/2 and
    takes in ln 2 * x B; channel 1 has step size 0 and keeps its state."""
    ref = _ref()
    state = jnp.asarray([[1.0, 0.0], [0.0, 2.0]])
    new, y = ref.mamba_step(
        state, jnp.asarray([1.0, 2.0]), jnp.asarray([math.log(2.0), 0.0]),
        -jnp.ones((2, 2)), jnp.asarray([1.0, 2.0]), jnp.asarray([1.0, 1.0]),
        jnp.asarray([0.5, 0.5]))
    ln2 = math.log(2.0)
    assert np.allclose(new, [[0.5 + ln2, 2 * ln2], [0.0, 2.0]], atol=1e-6)
    assert np.allclose(y, [0.5 + 3 * ln2 + 0.5, 2.0 + 1.0], atol=1e-6)


def test_the_mixer_norms_dt_b_and_c():
    """One token, d_inner 2, N 2, dt_rank 2, every projection the identity
    or ones: scaling x_proj's dt, B or C columns by 10 changes nothing
    once each is RMS-normed (eps 0), and the norm's weight scales B."""
    ref = _ref()
    w = {"in_proj": jnp.asarray([[1.0, 0.0, 1.0, 1.0], [0.0, 1.0, 1.0, 1.0]]),
         "conv_w": jnp.asarray([[1.0, 1.0]]), "conv_b": jnp.zeros((2,)),
         "x_proj": jnp.asarray([[1.0, 0.5, 1.0, 2.0, 0.5, 1.0],
                                [0.5, 1.0, 2.0, 1.0, 1.0, 0.5]]),
         "dt_proj": jnp.eye(2), "dt_bias": jnp.zeros((2,)),
         "a_log": jnp.zeros((2, 2)), "d_skip": jnp.ones((2,)),
         "out_proj": jnp.eye(2), "dt_norm": jnp.ones((2,)),
         "b_norm": jnp.ones((2,)), "c_norm": jnp.ones((2,))}
    h = jnp.asarray([[1.0, 2.0]])
    base = ref.mamba(h, w, 0.0)
    scaled = dict(w, x_proj=w["x_proj"] * jnp.asarray(
        [10.0, 10.0, 3.0, 3.0, 7.0, 7.0]))
    assert np.allclose(ref.mamba(h, scaled, 0.0), base, atol=1e-5)
    doubled = ref.mamba(h, dict(w, b_norm=2 * jnp.ones((2,))), 0.0)
    skip = ref.mamba(h, dict(w, b_norm=jnp.zeros((2,))), 0.0)   # D x alone
    assert np.allclose(doubled - skip, 2 * (base - skip), atol=1e-5)


def test_layer_kinds_and_costs_match_the_configurations_arithmetic():
    cfg = Manifest(ROOT).config(CONFIG)
    kinds = _ref().layer_kinds(cfg)
    assert [l for l, k in enumerate(kinds) if k == "attention"] == [7, 21]
    assert kinds.count("ssm") == 26 and len(kinds) == 28
    assert costs_ssm_attn.layer_counts(cfg) == {"ssm": 26, "attention": 2}
    assert costs_ssm_attn.mixer_params(cfg) == {
        "ssm": 41_241_792, "attention": 13_762_560}
    assert costs_ssm_attn.mlp_params(cfg) == 62_914_560
    total = costs_ssm_attn.total_params(cfg)
    assert total == 3_029_337_472 and 6.05 < 2 * total / 1e9 < 6.07
    assert costs_ssm_attn.kv_row_bytes(cfg) == 512
    assert costs_ssm_attn.state_bytes_per_slot(cfg) == 26 * 358_400
    srv = cfg["serving"]
    assert int(srv["num_pages"]) * int(srv["page_size"]) == \
        int(srv["num_slots"]) * int(srv["max_model_len"])
    pages = 2 * int(srv["num_pages"]) * int(srv["page_size"]) * 512
    assert round(pages / 1e9, 2) == 0.55
    assert round(16 * 26 * 358_400 / 1e9, 2) == 0.15
    # the issue's decode step: 165k live rows, 16 slots
    step = costs_ssm_attn.decode_step_bytes(cfg, 165_000, 16)
    assert step == 2 * total + 165_000 * 1024 + 2 * 16 * 26 * 358_400
    # a full chunk at 8k of context is compute-bound on the MXU ...
    flops = costs_ssm_attn.chunk_flops(cfg, 512, 8192)
    matmul = 26 * (41_123_840 + 62_914_560) + 2 * (13_762_560 + 62_914_560)
    assert flops == (2 * 512 * matmul + 2 * 4 * 512 * 2560 * (8192 + 256.5)
                     + 2 * 2560 * 65536)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    assert flops / 197e12 > costs_ssm_attn.chunk_bytes(cfg, 8192) / 819e9
    # ... and its scans are bound by the vector unit, not by HBM
    least = costs_ssm_attn.scan_chunk_least_seconds(cfg, 512, peaks)
    assert least["vpu"] > least["hbm"]
    assert costs_ssm_attn.scan_chunk_bytes(cfg, 512) == 26 * (
        512 * (3 * 5120 + 32) * 2 + 2 * 5120 * 16 * 4)
    assert costs_ssm_attn.scan_chunk_ops(cfg, 512) == 26 * 512 * 5120 * 16 * 8


def test_traffic_grid_permutation_and_bounds():
    m = Manifest(ROOT)
    mix = m.traffic("longdoc_closed")
    assert mix["driver"] == "serve_closed_loop_ssm_attn"
    assert (mix["clients"], mix["warm_steps"], mix["part_seconds"]) == (
        16, 352, 5.0)
    assert mix["pairing"] == [(3 + 7 * i) % 16 for i in range(16)]
    assert mix["prompt"] == {"dist": "lognormal_grid", "median": 8192,
                             "sigma": 0.7, "min": 2048, "max": 32768}
    assert mix["output"] == {"dist": "lognormal_grid", "median": 384,
                             "sigma": 0.5, "min": 128, "max": 1024}
    cfg = m.config(CONFIG)
    gen = traffic.ClosedLoopTraffic(mix, 3000000019, int(cfg["vocab_size"]))
    prompts = [p for p, _ in gen.grid]
    outputs = [o for _, o in gen.grid]
    assert (min(prompts), max(prompts)) == (2224, 30177)
    assert (min(outputs), max(outputs)) == (151, 975)
    assert max(p + o for p, o in gen.grid) <= int(
        cfg["serving"]["max_model_len"])
    # the opening prompts: 327 chunks, one an engine step, inside the warm-up
    chunk = int(cfg["serving"]["prefill_chunk"])
    assert sum(-(-p // chunk) for p in prompts) == 327 <= mix["warm_steps"]
    assert int(cfg["serving"]["num_slots"]) == mix["clients"]


def test_manifest_entries_of_the_cell():
    m = Manifest(ROOT)
    cell = m.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longdoc_closed", 1)
    entry = m.config_entry(CONFIG)
    cfg = m.config(CONFIG)
    assert entry["reduced"] == [] and cfg["reduced_from"] == {}
    if os.path.exists(CATALOG):
        row = json.loads(next(line for line in open(CATALOG)
                              if '"AI21-Jamba2-3B"' in line))
        assert entry["source"] == row["source_url"] == cfg["source"]
        for key, value in row["config"].items():
            assert cfg[key] == value, key
    e2e = {x["name"] for x in m.metrics_for("end_to_end", CELL)}
    assert e2e == {"itl_p99_ms", "setup_s"}
    names = [x["name"] for x in m.metrics_for("per_layer", CELL)]
    assert names[-len(NEW):] == NEW
    assert not {"shared_kv_attn_device_ms", "swa_attn_device_ms",
                "window_pool_occupancy_pct", "hybrid_decode_roofline"} \
        & set(names)
    for entry in m.data["per_layer"][-len(NEW):]:
        reader = m.layer_metric(entry["name"])
        assert entry["workloads"] == [CELL]
        assert (reader.MOVES, reader.UNIT, reader.LAYER, reader.SOURCE) == (
            entry["moves"], entry["unit"], entry["layer"], entry["source"])
        assert reader.DRIVERS == ("serve_closed_loop_ssm_attn",)
    driver = m.driver("serve_closed_loop_ssm_attn")
    assert driver.ANNOTATIONS and driver.PROGRAMS


def test_readers_return_nothing_without_what_they_read():
    """On a program without the scope, the span argument and the counters
    (the parent), or with no trace, each new reader leaves its metric out
    and raises nothing."""
    m = Manifest(ROOT)

    class Ctx:
        counters = {}
        samples = {}
        trace = None
        peaks = None
        config = {"model_type": "mistral"}
        programs = {"decode": r"jit__decode_fn",
                    "prefill_chunk": r"jit__prefill_chunk_fn"}
        trace_window = None
        annotations = ()
    for name in NEW:
        assert m.layer_metric(name).read(Ctx) is None


def test_step_anatomy_counts_kinds_and_stalls():
    """Three kinds of step by hand: 6 that only decode (17 ms), 10 that
    carry a chunk (79 ms, one of them stalled at 190), 2 that end a prompt
    (86 ms): the medians of each kind, one stalled step, 111 ms beyond."""
    driver = Manifest(ROOT).driver("serve_closed_loop_ssm_attn")
    gaps = [17, 79, 79, 86, 17, 79, 190, 79, 17, 79, 79, 17, 86, 79, 17, 79,
            79, 17]
    ends = list(np.cumsum([100.0] + [g / 1e3 for g in gaps]))
    firsts = [ends[4], ends[13], 1.0]      # 1.0: a first token of warm-up
    out = driver.step_anatomy(ends, firsts)
    assert (out["steps_prompt_end"], out["steps_chunk"],
            out["steps_plain"]) == (2, 10, 6)
    assert math.isclose(out["step_ms_prompt_end"], 86.0, abs_tol=1e-6)
    assert math.isclose(out["step_ms_chunk"], 79.0, abs_tol=1e-6)
    assert math.isclose(out["step_ms_plain"], 17.0, abs_tol=1e-6)
    assert out["stalled_steps"] == 1
    assert math.isclose(out["stalled_excess_ms"], 111.0, abs_tol=1e-6)
