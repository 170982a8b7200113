"""Percentile and sub-window arithmetic; operations and bytes against
numbers worked by hand for Mistral-7B's shapes."""
import json
from pathlib import Path

import numpy as np
import pytest

from perfbench.lib import costs, stats
from perfbench.lib.peaks import peaks_for

BENCH = Path(__file__).resolve().parents[1]


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("q", [0, 50, 90, 99, 100])
def test_percentile_is_numpys_linear_rule(q):
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0, 8.0]
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_refuses_nothing_and_nonsense():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


def test_subwindow_rates_drop_the_partial_part():
    # 12 s window from t0 = 100, parts of 5 s: two whole parts
    times = [100.5, 104.9, 105.0, 109.0, 111.0, 99.0]
    weights = [10, 10, 30, 20, 99, 99]
    assert stats.subwindow_rates(times, weights, 100.0, 12.0, 5.0) == [4.0, 10.0]
    assert stats.subwindow_rates([], [], 0.0, 4.0, 5.0) == []


def test_matmul_parameters_by_hand():
    cfg = config("mistral7b_sft_d2")
    # q and o: 4096 x 4096 each; k and v: 4096 x 1024 each; MLP 3 x 4096 x 14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808 == costs.layer_matmul_params(cfg)
    head = 4096 * 32000
    assert costs.matmul_params(cfg) == 2 * layer + head == 567_279_616
    # all parameters: + the embedding table + 2 norms a layer + the last
    assert costs.total_params(cfg) == 567_279_616 + head + 5 * 4096
    assert costs.matmul_params(config("mistral7b_serve_d16")) == 16 * layer + head
    assert costs.total_params(config("mistral7b_sft_d8_4chip")) == pytest.approx(
        2.01e9, rel=5e-3)


def test_attention_pairs_and_train_flops_by_hand():
    assert costs.attended_pairs(4, None) == 10          # 1 + 2 + 3 + 4
    assert costs.attended_pairs(4, 2) == 1 + 2 + 2 + 2
    assert costs.attended_pairs(5000, 4096) == 4096 * 4097 // 2 + 904 * 4096
    cfg = config("mistral7b_sft_d2")
    # one document of 2048 tokens: (2048 * 2049 / 2) / 2048 = 1024.5 keys a
    # token; 12 * 32 heads * 128 * 2 layers = 98304 operations a pair
    want = 6 * 567_279_616 + 98_304 * 1024.5
    assert costs.train_flops_per_token(cfg, [2048]) == pytest.approx(want)
    # short documents need less attention than one long one
    assert costs.train_flops_per_token(cfg, [512] * 4) < want


def test_decode_bytes_by_hand():
    cfg = config("mistral7b_serve_d16")
    # keys and values: 2 x 8 heads x 128 x 2 bytes x 16 layers = 64 KiB a token
    assert costs.kv_bytes_per_token(cfg) == 65_536
    weights = 2 * (16 * 218_103_808 + 4096 * 32000)
    assert costs.decode_step_bytes(cfg, 0) == weights == 7_241_465_856
    assert costs.decode_step_bytes(cfg, 14_000) == weights + 14_000 * 65_536
    # 16 full windows are the whole 2 GiB pool
    serving = cfg["serving"]
    assert (serving["num_slots"] * serving["max_model_len"]
            * costs.kv_bytes_per_token(cfg)) == 2 * 2 ** 30
    assert serving["num_pages"] * serving["page_size"] == (
        serving["num_slots"] * serving["max_model_len"])


def test_peaks_table_knows_the_v5e_and_nothing_else():
    v5e = peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("cpu")
    with pytest.raises(KeyError):
        peaks_for("TPU v9")
