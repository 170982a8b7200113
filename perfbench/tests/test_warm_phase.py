"""The window opens at the same point of the same schedule: the warm
phase is a fixed count of engine steps, long enough for every caller's
first prefill, whatever the seed."""
import json
import math
from pathlib import Path

import pytest

from perfbench.lib import rehearsal, traffic
from perfbench.lib.manifest import Manifest
from perfbench.tests.conftest import ROOT

BENCH = Path(ROOT) / "perfbench"
SERVING = ("decode_heavy_closed", "prefill_heavy_closed")


@pytest.mark.parametrize("name", SERVING)
def test_warm_steps_cover_the_opening_burst_of_prefills(name):
    """The prefill lane takes one chunk a step, first come first served,
    so the first round needs sum(ceil(prompt / chunk)) steps, and a
    request sent later queues behind it."""
    manifest = Manifest(ROOT)
    mix = manifest.traffic(name)
    chunk = manifest.config("mistral7b_serve_d16")["serving"]["prefill_chunk"]
    gen = traffic.ClosedLoopTraffic(mix, 0, 32000)
    chunks = sum(math.ceil(p / chunk) for p, _ in gen.grid)
    assert chunks <= mix["warm_steps"] <= chunks + 32
    # every caller fits a slot, so none waits for another to finish
    assert gen.n <= manifest.config("mistral7b_serve_d16")["serving"]["num_slots"]


@pytest.fixture(scope="module")
def tiny_engine_factory():
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.serving import ServingConfig, ServingEngine

    from perfbench.lib import sut
    manifest = Manifest(ROOT)
    cfg, _ = rehearsal.shrink(BENCH / "rehearsal.json",
                              manifest.config("mistral7b_serve_d16"), {})
    srv = cfg["serving"]
    model = Transformer(sut.model_config(
        cfg, dtype=srv["dtype"], param_dtype=srv["param_dtype"],
        attention="xla", max_seq_length=srv["max_model_len"]))
    params = sut.init_params(model, 0)

    def build():
        return ServingEngine(
            model, params,
            GenerationConfig(max_new_tokens=64, do_sample=False,
                             eos_token_id=-1),
            ServingConfig(page_size=srv["page_size"],
                          num_pages=srv["num_pages"],
                          num_slots=srv["num_slots"],
                          max_model_len=srv["max_model_len"],
                          prefill_chunk=srv["prefill_chunk"]))
    return manifest, build


@pytest.mark.parametrize("name", SERVING)
def test_warm_phase_ends_on_the_same_step_for_every_seed(
        name, tiny_engine_factory):
    """The real loop on a tiny engine, lengths divided as in the
    rehearsal: after the fixed warm steps every caller has its first
    token, the engine has run exactly that many steps, and the step at
    which the last first-prefill lands does not depend on the seed."""
    manifest, build = tiny_engine_factory
    driver = manifest.driver("serve_closed_loop")
    _, mix = rehearsal.shrink(BENCH / "rehearsal.json", {},
                              manifest.traffic(name))
    last_first = set()
    for seed in (1, 2 ** 31 + 7, 99):
        engine = build()
        try:
            loop = driver.ClosedLoop(
                engine, traffic.ClosedLoopTraffic(mix, seed, 512))
            loop.start()
            for _ in range(mix["warm_steps"]):
                loop.step()
            assert engine.engine_steps == len(loop.steps) == mix["warm_steps"]
            assert None not in loop.first_prefill_step
            last_first.add(max(loop.first_prefill_step))
            # closed loop: every caller has exactly one request open
            assert sorted(r.client for r in loop.open.values()) == list(
                range(loop.traffic.n))
        finally:
            engine.close()
    assert len(last_first) == 1
