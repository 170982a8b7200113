"""``sampled_step_share_pct`` on synthetic spans: the share of decode
phases with a sampling slot, and nothing where the program does not say
(the parent's ``serve_decode`` has no ``sampling_slots``)."""
import types

import pytest

from perfbench.lib import spans
from perfbench.lib.manifest import Manifest
from perfbench.tests.conftest import ROOT

NAME = "sampled_step_share_pct"


def decode(t, **args):
    return ("serve_decode", float(t), t + 0.04, dict(
        slots=2, live_tokens=100, read_tokens=400, **args))


def read(host, monkeypatch):
    trace = spans.SpanTrace(spans._sorted(host), [], [])
    monkeypatch.setattr(spans, "for_context", lambda ctx: trace)
    ctx = types.SimpleNamespace(trace=object(),
                                cell={"name": "serve_decode_heavy"})
    return Manifest(ROOT).layer_metric(NAME).read(ctx)


@pytest.mark.parametrize("slots,share", [
    ((0, 0, 0, 0), 0.0), ((0, 3, 0, 1), 50.0), ((2, 2, 2, 2), 100.0)])
def test_share_of_decode_phases_with_a_sampling_slot(monkeypatch, slots,
                                                     share):
    host = [decode(0.05 * i, sampling_slots=n) for i, n in enumerate(slots)]
    host.append(("serve", 0.0, 0.05, {"step_num": 0, "host_ns": 0}))
    assert read(host, monkeypatch) == pytest.approx(share)


def test_nothing_to_read_without_the_argument_or_the_trace(monkeypatch):
    assert read([decode(0.0), decode(0.05)], monkeypatch) is None
    assert read([], monkeypatch) is None
    monkeypatch.undo()
    bare = types.SimpleNamespace(trace=None,
                                 cell={"name": "serve_decode_heavy"})
    assert Manifest(ROOT).layer_metric(NAME).read(bare) is None


def test_entry_lists_both_serving_cells():
    (entry,) = [e for e in Manifest(ROOT).data["per_layer"]
                if e["name"] == NAME]
    assert entry["workloads"] == ["serve_decode_heavy", "serve_prefill_heavy"]
