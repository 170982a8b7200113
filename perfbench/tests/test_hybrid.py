"""The SambaY configuration's benchmark files: the plain reference on
hand-computed cases, ``costs_hybrid`` against the arithmetic of the
configuration's ``cut``, and the traffic file's grid."""
import json
import math

import jax.numpy as jnp
import numpy as np

from perfbench.lib import costs_hybrid, traffic
from perfbench.lib.manifest import Manifest
from perfbench.tests.conftest import ROOT

CELL = "serve_hybrid_reasoning_long"


def _ref():
    return Manifest(ROOT).reference("phi4flash_block")


def test_differential_attention_two_tokens_one_pair():
    """Head size 1, one pair: Q1 / Q2 are heads 0 / 1, K1 / K2 the key
    heads, the pair's value [v0 | v1]. Token 0 sees itself alone, so both
    softmaxes are 1 and O = (1 - lambda) V_0. Token 1: s Q1 K1^T = [ln 3,
    0] -> A1 = [3/4, 1/4]; s Q2 K2^T = [0, ln 2] -> A2 = [1/3, 2/3]; with
    lambda 1/2, A1 - lambda A2 = [7/12, -1/12], O = 7/12 [1, 2] - 1/12
    [3, -1] = [1/3, 5/4]. Then RMSNorm over the pair's two numbers (eps
    0, weight 1) times 1 - lambda_init = 0.8."""
    ref = _ref()
    q = jnp.asarray([[[0.0], [0.0]], [[math.log(3.0)], [math.log(2.0)]]])
    k = jnp.asarray([[[1.0], [0.0]], [[0.0], [1.0]]])
    v = jnp.asarray([[[1.0], [2.0]], [[3.0], [-1.0]]])
    out = ref.differential_attention(
        q, k, v, 0.5, 0.2, jnp.ones((2,)), None, 0.0)
    want = []
    for pair in ([0.5, 1.0], [1.0 / 3.0, 1.25]):
        rms = math.sqrt((pair[0] ** 2 + pair[1] ** 2) / 2)
        want.append([0.8 * pair[0] / rms, 0.8 * pair[1] / rms])
    assert np.allclose(out, want, atol=1e-6)
    assert np.allclose(want, [[0.505964, 1.011929], [0.291512, 1.093170]],
                       atol=1e-6)
    # a window of 1 leaves token 1 itself alone: O = (1 - lambda) V_1
    alone = ref.differential_attention(
        q, k, v, 0.5, 0.2, jnp.ones((2,)), 1, 0.0)
    rms = math.sqrt((1.5 ** 2 + 0.5 ** 2) / 2)
    assert np.allclose(alone[1], [0.8 * 1.5 / rms, 0.8 * -0.5 / rms],
                       atol=1e-6)
    assert abs(ref.lambda_init(0) - 0.2) < 1e-12
    assert abs(ref.lambda_init(17) - (0.8 - 0.6 * math.exp(-5.1))) < 1e-12


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


def test_layer_kinds_follow_the_published_layout():
    ref = _ref()
    cfg = Manifest(ROOT).config("phi4_mini_flash_serve")
    kinds = ref.layer_kinds(cfg)
    assert [k for k, _ in kinds[:4]] == ["ssm", "attention"] * 2
    assert kinds[1] == ("attention", 512) and kinds[15] == ("attention", 512)
    assert kinds[16] == ("ssm", None) and kinds[17] == ("attention", None)
    assert kinds[18:20] == [("gmu", None), ("cross", None)]
    assert kinds[30:] == [("gmu", None), ("cross", None)]
    assert costs_hybrid.layer_counts(cfg) == {
        "ssm": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}


def test_costs_match_the_configurations_arithmetic():
    cfg = Manifest(ROOT).config("phi4_mini_flash_serve")
    mixers = costs_hybrid.mixer_params(cfg)
    assert [round(mixers[k] / 1e6, 1) for k in
            ("ssm", "full", "gmu", "cross")] == [41.2, 19.7, 26.2, 13.1]
    assert round(costs_hybrid.mlp_params(cfg) / 1e6, 1) == 78.6
    total = costs_hybrid.total_params(cfg)
    assert total == 3_852_562_944
    assert round(total / 1e9, 2) == 3.85 and 7.70 <= 2 * total / 1e9 < 7.71
    assert costs_hybrid.kv_row_bytes(cfg) == 5120
    assert costs_hybrid.state_bytes_per_slot(cfg) == 9 * 358_400
    assert costs_hybrid.shared_readers(cfg) == 8
    srv = cfg["serving"]
    # the cut's memory table
    full = int(srv["num_slots"]) * int(srv["max_model_len"]) * 5120
    assert round(full / 1e9, 2) == 1.17
    assert int(srv["num_pages"]) * int(srv["page_size"]) == \
        int(srv["num_slots"]) * int(srv["max_model_len"])
    ring = -(-(512 + int(srv["prefill_chunk"])) // int(srv["page_size"])) + 1
    assert ring == 49
    window = 8 * (64 * ring + 1) * 16 * 5120
    assert round(window / 1e9, 2) == 2.06
    # the issue's step: 70k live tokens, 480 rows a slot inside the window
    step = costs_hybrid.decode_step_bytes(cfg, 70_000, 64 * 480, 64)
    assert step == (2 * total + 70_000 * 5120 * 8 + 64 * 480 * 5120 * 8
                    + 2 * 64 * 9 * 358_400)
    assert 12.2e9 < step < 12.3e9 and 14.9 < step / 819e9 * 1e3 < 15.0


def test_traffic_grid_permutation_and_bounds():
    m = Manifest(ROOT)
    mix = m.traffic("reasoning_long_closed")
    assert mix["driver"] == "serve_closed_loop_hybrid"
    assert (mix["clients"], mix["warm_steps"], mix["part_seconds"]) == (
        64, 192, 5.0)
    assert mix["pairing"] == [(3 + 7 * i) % 64 for i in range(64)]
    assert sorted(mix["pairing"]) == list(range(64))
    assert mix["prompt"] == {"dist": "lognormal_grid", "median": 192,
                             "sigma": 0.6, "min": 64, "max": 512}
    assert mix["output"] == {"dist": "lognormal_grid", "median": 1280,
                             "sigma": 0.5, "min": 512, "max": 3072}
    cfg = m.config("phi4_mini_flash_serve")
    gen = traffic.ClosedLoopTraffic(mix, 11, int(cfg["vocab_size"]))
    prompts = [p for p, _ in gen.grid]
    outputs = [o for _, o in gen.grid]
    assert min(prompts) == 64 and max(prompts) == 512
    assert min(outputs) == 512 and max(outputs) == 3072
    assert max(p + o for p, o in gen.grid) <= int(
        cfg["serving"]["max_model_len"]) == 512 + 3072
    # the opening prompts are at most two chunks each, one chunk a step
    chunk = int(cfg["serving"]["prefill_chunk"])
    chunks = sum(-(-p // chunk) for p in prompts)
    assert max(-(-p // chunk) for p in prompts) == 2
    assert chunks <= mix["warm_steps"]
    ids, out_len = gen.request(0, 0)
    assert len(ids) in prompts and out_len in outputs
    assert max(ids) < int(cfg["vocab_size"]) - 1


def test_manifest_entries_of_the_cell():
    m = Manifest(ROOT)
    cell = m.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi4_mini_flash_serve", "reasoning_long_closed", 1)
    entry = m.config_entry("phi4_mini_flash_serve")
    assert entry["reduced"] == [] and m.config(
        "phi4_mini_flash_serve")["reduced_from"] == {}
    row = json.loads(next(
        line for line in open(
            "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"Phi-4-mini-flash-reasoning"' in line)) \
        if __import__("os").path.exists(
            "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if row is not None:
        cfg = m.config("phi4_mini_flash_serve")
        assert entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert cfg[key] == value, key
    e2e = {x["name"] for x in m.metrics_for("end_to_end", CELL)}
    assert e2e == {"itl_p99_ms", "setup_s"}
    names = [x["name"] for x in m.metrics_for("per_layer", CELL)]
    new = ["ssm_mixer_device_ms", "shared_kv_attn_device_ms",
           "swa_attn_device_ms", "ssm_state_mib_per_slot",
           "window_pool_occupancy_pct", "hybrid_decode_roofline"]
    assert names[-len(new):] == new
    for name in new:
        reader = m.layer_metric(name)
        assert reader.MOVES == "itl_p99_ms"
        assert reader.DRIVERS == ("serve_closed_loop_hybrid",)
    driver = m.driver("serve_closed_loop_hybrid")
    assert driver.ANNOTATIONS and driver.PROGRAMS
    assert (driver.TOL_LOGPROB_P75, driver.TOL_LOGPROB_RMS,
            driver.TOL_ARGMAX) == (0.10, 0.09, 0.4)


def test_readers_return_nothing_without_what_they_read():
    """On a program without the scopes and gauges (the parent), or with no
    trace, each new reader leaves its metric out and raises nothing."""
    m = Manifest(ROOT)

    class Ctx:
        counters = {}
        samples = {}
        trace = None
        peaks = None
        config = {"model_type": "mistral"}
        programs = {"decode": r"jit__decode_fn"}
        trace_window = None
        annotations = ()
    for name in ("ssm_mixer_device_ms", "shared_kv_attn_device_ms",
                 "swa_attn_device_ms", "ssm_state_mib_per_slot",
                 "window_pool_occupancy_pct", "hybrid_decode_roofline"):
        assert m.layer_metric(name).read(Ctx) is None
