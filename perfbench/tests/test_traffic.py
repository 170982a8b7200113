"""The seed shuffles, it does not draw: every seed yields the same
multiset of sizes for each traffic file, in another order."""
import json
from collections import Counter
from pathlib import Path

import pytest

from perfbench.lib import traffic

BENCH = Path(__file__).resolve().parents[1]
SEEDS = (0, 1, 7, 2 ** 31 + 11, 3_000_000_019)
SERVING = ("decode_heavy_closed", "prefill_heavy_closed")


def spec(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def test_grid_is_the_quantiles_and_is_clipped():
    grid = traffic.lognormal_grid(24, 256, 0.7, 64, 768)
    assert grid == sorted(grid) and grid[0] == 64 and grid[-1] == 768
    # the two middle points straddle the median
    assert grid[11] < 256 < grid[12]
    assert traffic.lognormal_grid(1, 100, 1.0, 1, 1000) == [100]
    with pytest.raises(ValueError):
        traffic.lognormal_grid(0, 100, 1.0, 1, 10)
    # a file may also list its sizes outright
    assert traffic.sizes({"dist": "list", "values": [9, 3, 5]}, 3) == [3, 5, 9]
    with pytest.raises(ValueError):
        traffic.sizes({"dist": "list", "values": [1, 2]}, 3)
    with pytest.raises(ValueError):
        traffic.sizes({"dist": "uniform"}, 3)


@pytest.mark.parametrize("name", SERVING)
def test_every_pass_is_the_whole_grid_under_any_seed(name):
    mix = spec(name)
    want = None
    orders = []
    for seed in SEEDS:
        gen = traffic.ClosedLoopTraffic(mix, seed, vocab=32000)
        for k in range(3):
            reqs = [gen.request(c, k) for c in range(gen.n)]
            got = Counter((len(p), o) for p, o in reqs)
            want = want or got
            assert got == want == Counter(gen.grid)
        orders.append(tuple(gen.element(c, 0) for c in range(gen.n)))
    assert len(set(orders)) == len(SEEDS), "seeds must differ in order"


@pytest.mark.parametrize("name", SERVING)
def test_requests_fit_the_window_and_tokens_follow_the_seed(name):
    mix = spec(name)
    a = traffic.ClosedLoopTraffic(mix, 5, vocab=32000)
    b = traffic.ClosedLoopTraffic(mix, 5, vocab=32000)
    c = traffic.ClosedLoopTraffic(mix, 6, vocab=32000)
    assert a.request(3, 2) == b.request(3, 2)
    assert a.request(3, 2)[0] != c.request(3, 2)[0]
    assert max(p + o for p, o in a.grid) <= 2048
    prompt, _ = a.request(0, 0)
    assert min(prompt) >= traffic.FIRST_TOKEN_ID and max(prompt) < 31999


def test_document_lengths_same_multiset_other_order():
    mix = spec("sft_packed")
    base = traffic.document_lengths(mix, SEEDS[0])
    assert len(base) == mix["documents"]
    assert base.min() >= 16 and base.max() <= 2048
    for seed in SEEDS[1:]:
        other = traffic.document_lengths(mix, seed)
        assert sorted(other) == sorted(base)
        assert list(other) != list(base)


def test_documents_speak_the_packer_protocol():
    docs = traffic.SyntheticDocuments(spec("sft_packed"), 9, vocab=32000)
    ex = docs[17]
    n = int(docs.lengths[17])
    assert ex["input_ids"].shape == ex["labels"].shape == (n,)
    assert (ex["labels"][:n // 4] == docs.IGNORE_INDEX).all()
    assert (ex["labels"][n // 4:] == ex["input_ids"][n // 4:]).all()
    assert (docs[17]["input_ids"] == ex["input_ids"]).all()


def test_packed_fill_is_the_same_for_every_seed():
    """The set of lengths is fixed, so first-fit ends on the same number
    of rows whatever the order: the fill, and so the share of a step's
    tokens that count, does not move with the seed."""
    from dla_tpu.data.packing import PackedInstructionDataset
    fills = set()
    for seed in SEEDS:
        docs = traffic.SyntheticDocuments(spec("sft_packed"), seed, 32000)
        fills.add(round(
            PackedInstructionDataset(docs, 2048).packing_efficiency(), 6))
    assert len(fills) == 1 and fills.pop() > 0.98
