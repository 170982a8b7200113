"""dla-lint framework tests (docs/ANALYSIS.md).

THE pins: (a) every rule fires on its bad fixture and stays silent on
the good twin — the firing fixtures double as executable documentation
of what each rule means; (b) the repo itself lints clean: zero
unsuppressed findings over dla_tpu/ + tools/ + bench.py + config/, in
under the 10 s acceptance bound, and every suppression carries a human
reason; (c) the JSON report is the shared strict ``dla-report/1``
schema — the same validator accepts dla-lint and metrics_diff output;
(d) baselines match by (rule, path, source-line) fingerprint and so
survive pure line-number drift; (e) CLI exit codes follow the 0/1/2
convention.
"""
import json
import os
import sys
import textwrap
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from dla_tpu.analysis import all_rules, run_lint  # noqa: E402
from dla_tpu.analysis.cli import main as lint_main  # noqa: E402
from dla_tpu.analysis.report import (  # noqa: E402
    SCHEMA_ID,
    apply_baseline,
    dump_baseline,
    dump_report,
    lint_json_report,
    load_baseline,
    validate_report,
)

ALL_RULE_NAMES = {
    "retrace-hazard", "trace-side-effect", "host-sync-in-hot-loop",
    "donation-misuse", "pallas-tiling", "config-schema-drift",
    "metric-name-drift", "unsynchronized-shared-state",
    "lock-order-inversion", "blocking-under-lock",
    "conditional-collective",
}


def lint_src(tmp_path, src, rules=None, name="mod.py"):
    """Write one fixture file and return the active rule names hit."""
    p = tmp_path / name
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    result = run_lint([p], rules=rules, root=tmp_path)
    return result


def fired(result):
    return {f.rule for f in result.active}


# --------------------------------------------------------------- registry

def test_rule_catalog_is_complete():
    rules = all_rules()
    assert set(rules) == ALL_RULE_NAMES
    for name, rule in rules.items():
        assert rule.name == name and rule.summary


# ---------------------------------------------------------- retrace-hazard

def test_retrace_hazard_fires_on_python_branch_on_traced_arg(tmp_path):
    r = lint_src(tmp_path, """
        import jax

        @jax.jit
        def f(x, n):
            if n > 0:
                return x + n
            return x
        """)
    assert "retrace-hazard" in fired(r)


def test_retrace_hazard_silent_with_static_argnums(tmp_path):
    r = lint_src(tmp_path, """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnums=(1,))
        def f(x, n):
            if n > 0:
                return x + n
            return x
        """)
    assert "retrace-hazard" not in fired(r)


def test_retrace_hazard_fires_on_traced_shape(tmp_path):
    r = lint_src(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(n):
            return jnp.zeros(n)
        """)
    assert "retrace-hazard" in fired(r)


def test_retrace_hazard_split_key_is_not_a_shape(tmp_path):
    # jax.random.split's first arg is the (traced) key — only its `num`
    # argument is shape-like. Regression test for the self-apply pass.
    ok = lint_src(tmp_path, """
        import jax

        @jax.jit
        def g(key):
            return jax.random.split(key, 4)
        """)
    assert "retrace-hazard" not in fired(ok)
    bad = lint_src(tmp_path, """
        import jax

        @jax.jit
        def g(key, n):
            return jax.random.split(key, n)
        """, name="bad_split.py")
    assert "retrace-hazard" in fired(bad)


# ------------------------------------------------------- trace-side-effect

def test_trace_side_effect_fires_inside_jit(tmp_path):
    r = lint_src(tmp_path, """
        import jax
        import time

        @jax.jit
        def f(x):
            t = time.time()
            print(x)
            return x
        """)
    assert "trace-side-effect" in fired(r)
    assert len([f for f in r.active if f.rule == "trace-side-effect"]) == 2


def test_trace_side_effect_silent_outside_jit(tmp_path):
    r = lint_src(tmp_path, """
        import time

        def f(x):
            t = time.time()
            print(x)
            return x
        """)
    assert "trace-side-effect" not in fired(r)


# --------------------------------------------------- host-sync-in-hot-loop

def test_host_sync_fires_via_pragma_root_and_call_chain(tmp_path):
    r = lint_src(tmp_path, """
        def hot(xs):  # dla: hot-loop-root
            for x in xs:
                helper(x)

        def helper(x):
            return x.item()
        """)
    hits = [f for f in r.active if f.rule == "host-sync-in-hot-loop"]
    assert hits and "hot -> helper" in hits[0].message


def test_host_sync_fires_from_trainer_fit_root(tmp_path):
    r = lint_src(tmp_path, """
        class Trainer:
            def fit(self, xs):
                for x in xs:
                    v = float(x)
        """)
    assert "host-sync-in-hot-loop" in fired(r)


def test_host_sync_silent_without_a_root(tmp_path):
    r = lint_src(tmp_path, """
        def cold(xs):
            return [x.item() for x in xs]
        """)
    assert "host-sync-in-hot-loop" not in fired(r)


# --------------------------------------------------------- donation-misuse

def test_donation_misuse_fires_on_use_after_donate(tmp_path):
    r = lint_src(tmp_path, """
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def train_step(state, batch):
            return state

        def loop(state, batches):
            for b in batches:
                new_state = train_step(state, b)
                log(state)
                state = new_state
            return state
        """)
    assert "donation-misuse" in fired(r)


def test_donation_misuse_silent_on_same_statement_rebind(tmp_path):
    r = lint_src(tmp_path, """
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def train_step(state, batch):
            return state

        def loop(state, batches):
            for b in batches:
                state = train_step(state, b)
            return state
        """)
    assert "donation-misuse" not in fired(r)


_POOL_ENGINE = """
    import jax

    class Engine:
        def __init__(self, spec):
            self._decode = jax.jit(self._decode_fn, donate_argnums=1)
            self._draft = (jax.jit(self._draft_fn, donate_argnums=1)
                           if spec else None)

        def _decode_fn(self, params, pools):
            return pools, 0

        def _draft_fn(self, params, pools):
            return pools, 1

        def step(self):
            c = self.cache
            {first}
            {second}
"""


@pytest.mark.parametrize("first,second,fires", [
    ("c.pools, out = self._decode(self.params, c.pools)",
     "log(c.pools)", False),
    ("fresh, out = self._decode(self.params, c.pools)",
     "log(c.pools)", True),
    ("self.cache.pools, out = self._draft(self.params, self.cache.pools)",
     "log(self.cache.pools)", False),
    ("fresh, out = self._draft(self.params, self.cache.pools)",
     "log(self.cache.pools)", True),
], ids=["rebind", "read-after", "rebind-conditional-jit",
        "read-after-conditional-jit"])
def test_donation_misuse_follows_bound_method_jits_and_attribute_chains(
        tmp_path, first, second, fires):
    """The serving engine's form: ``self._fn = jax.jit(self._fn_body,
    donate_argnums=..)`` (also behind ``.. if cond else None``) called
    with ``c.pools`` / ``self.cache.pools`` at the donated position."""
    r = lint_src(tmp_path, _POOL_ENGINE.format(first=first, second=second))
    assert ("donation-misuse" in fired(r)) is fires


# ----------------------------------------------------------- pallas-tiling

def test_pallas_tiling_fires_off_tile_and_missing_interpret(tmp_path):
    r = lint_src(tmp_path, """
        from jax.experimental import pallas as pl

        def launch(x, kernel):
            spec = pl.BlockSpec((8, 100), lambda i: (i, 0))
            return pl.pallas_call(kernel)(x)
        """)
    msgs = [f.message for f in r.active if f.rule == "pallas-tiling"]
    assert any("multiple of 128" in m for m in msgs)
    assert any("interpret" in m for m in msgs)


def test_pallas_tiling_silent_on_tile_aligned_with_fallback(tmp_path):
    r = lint_src(tmp_path, """
        from jax.experimental import pallas as pl

        def launch(x, kernel, interpret=False):
            spec = pl.BlockSpec((8, 128), lambda i: (i, 0))
            return pl.pallas_call(kernel, interpret=interpret)(x)
        """)
    assert "pallas-tiling" not in fired(r)


# ----------------------------------------------------- config-schema-drift

def test_config_schema_drift_fires_with_suggestion(tmp_path):
    p = tmp_path / "config" / "exp.yaml"
    p.parent.mkdir()
    p.write_text("experiment_name: t\nmodel:\n  max_seq_lenght: 128\n")
    r = run_lint([p], rules=["config-schema-drift"], root=tmp_path)
    hits = [f for f in r.active if f.rule == "config-schema-drift"]
    assert hits and "max_seq_length" in hits[0].message


def test_config_schema_drift_silent_on_declared_keys(tmp_path):
    p = tmp_path / "config" / "exp.yaml"
    p.parent.mkdir()
    p.write_text("experiment_name: t\nseed: 0\nmodel:\n"
                 "  max_seq_length: 128\n")
    r = run_lint([p], rules=["config-schema-drift"], root=tmp_path)
    assert "config-schema-drift" not in fired(r)


@pytest.mark.parametrize("block, key", [
    ("latency:\n  serving:\n", "max_prefill_batch"),
    ("latency:\n  serving:\n", "lookahead"),
    ("ppo:\n  rollout:\n    serving:\n", "max_prefill_batch")],
    ids=["latency.serving.max_prefill_batch", "latency.serving.lookahead",
         "ppo.rollout.serving.max_prefill_batch"])
def test_config_schema_drift_fires_on_a_removed_prefill_knob(
        tmp_path, block, key):
    """The two knobs of the removed bucketed prefill are no keys of the
    schema: a YAML that still sets one is reported, not ignored."""
    p = tmp_path / "config" / "exp.yaml"
    p.parent.mkdir()
    indent = " " * (2 * block.count("\n"))
    p.write_text(f"experiment_name: t\n{block}{indent}page_size: 16\n"
                 f"{indent}{key}: 2\n")
    r = run_lint([p], rules=["config-schema-drift"], root=tmp_path)
    hits = [f for f in r.active if f.rule == "config-schema-drift"]
    assert len(hits) == 1 and f"serving.{key}`" in hits[0].message


# ------------------------------------------------------- metric-name-drift

def test_metric_name_drift_fires_on_undeclared_name(tmp_path):
    r = lint_src(tmp_path,
                 'M = "train/not_a_real_metric_xyz"\n',
                 rules=["metric-name-drift"])
    hits = [f for f in r.active if f.rule == "metric-name-drift"]
    assert hits and hits[0].data["name"] == "train/not_a_real_metric_xyz"


def test_metric_name_drift_silent_on_catalog_name(tmp_path):
    r = lint_src(tmp_path, 'M = "train/loss"\n',
                 rules=["metric-name-drift"])
    assert "metric-name-drift" not in fired(r)


def test_check_metric_names_shim_delegates_to_rule(tmp_path, capsys):
    from tools.check_metric_names import run
    (tmp_path / "dla_tpu").mkdir()
    (tmp_path / "dla_tpu" / "x.py").write_text(
        'm = "train/ghost_metric"  '
        '# dla: disable=metric-name-drift -- fixture\n')
    (tmp_path / "bench.py").write_text("")
    # pragma honored through the shim: framework semantics for free
    assert run(tmp_path) == 0


# ------------------------------------------------------------ suppressions

def test_suppression_inline_and_reason_carried(tmp_path):
    r = lint_src(tmp_path, """
        import jax

        @jax.jit
        def f(x, n):
            if n > 0:  # dla: disable=retrace-hazard -- bounded by caller
                return x + n
            return x
        """)
    assert not r.active
    assert r.suppressed and r.suppressed[0].reason == "bounded by caller"


def test_suppression_standalone_comment_covers_next_line(tmp_path):
    r = lint_src(tmp_path, """
        import jax

        @jax.jit
        def f(x, n):
            # dla: disable=retrace-hazard -- fixture
            if n > 0:
                return x + n
            return x
        """)
    assert not r.active and r.suppressed


def test_suppression_file_level_and_all_wildcard(tmp_path):
    r = lint_src(tmp_path, """
        # dla: disable-file=all -- generated fixture
        import jax
        import time

        @jax.jit
        def f(x, n):
            t = time.time()
            if n > 0:
                return x + n
            return x
        """)
    assert not r.active and len(r.suppressed) >= 2


def test_wrong_rule_suppression_does_not_hide(tmp_path):
    r = lint_src(tmp_path, """
        import jax

        @jax.jit
        def f(x, n):
            if n > 0:  # dla: disable=pallas-tiling -- wrong rule
                return x + n
            return x
        """)
    assert "retrace-hazard" in fired(r)


# -------------------------------------------------------------- the report

def test_json_report_is_strict_and_round_trips(tmp_path):
    r = lint_src(tmp_path, """
        import jax

        @jax.jit
        def f(x, n):
            if n > 0:
                return x + n
            return x
        """)
    doc = json.loads(dump_report(lint_json_report(r)))
    validate_report(doc)
    assert doc["schema"] == SCHEMA_ID and doc["status"] == "findings"
    with pytest.raises(ValueError):
        validate_report({**doc, "extra": 1})


def test_metrics_diff_emits_the_same_schema(tmp_path, capsys):
    from tools.metrics_diff import main as mdiff_main
    base = tmp_path / "base.json"
    cand = tmp_path / "cand.json"
    base.write_text('{"serving": {"ttft_ms": 100.0}}')
    cand.write_text('{"serving": {"ttft_ms": 150.0}}')
    rc = mdiff_main([str(base), str(cand), "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    validate_report(doc)
    assert rc == 1 and doc["tool"] == "metrics-diff"
    assert doc["findings"][0]["rule"] == "metric-regression"
    rc = mdiff_main([str(base), str(base), "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    validate_report(doc)
    assert rc == 0 and doc["status"] == "ok"


# --------------------------------------------------------------- baselines

def test_baseline_fingerprints_survive_line_drift(tmp_path):
    src = """
        import jax

        @jax.jit
        def f(x, n):
            if n > 0:
                return x + n
            return x
        """
    r = lint_src(tmp_path, src)
    baseline = dump_baseline(r)
    # shift every line down: fingerprint is (rule, path, source line)
    (tmp_path / "mod.py").write_text(
        "# a new leading comment\n" + textwrap.dedent(src))
    r2 = run_lint([tmp_path / "mod.py"], root=tmp_path)
    assert r2.active
    matched = apply_baseline(r2, load_baseline(baseline))
    assert matched == 1 and not r2.active
    assert r2.suppressed[0].reason == "baseline"


def test_baseline_rejects_foreign_json():
    with pytest.raises(ValueError):
        load_baseline('{"something": "else"}')


# --------------------------------------------------------------------- CLI

def test_cli_exit_codes(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n\n@jax.jit\ndef f(x, n):\n"
                   "    if n > 0:\n        return x\n    return n\n")
    ok = tmp_path / "ok.py"
    ok.write_text("def g():\n    return 1\n")
    assert lint_main([str(ok), "--root", str(tmp_path)]) == 0
    assert lint_main([str(bad), "--root", str(tmp_path)]) == 1
    assert lint_main([str(tmp_path / "missing.py")]) == 2
    assert lint_main([str(ok), "--rules", "no-such-rule"]) == 2
    assert lint_main(["--list-rules"]) == 0
    capsys.readouterr()
    assert lint_main([str(bad), "--root", str(tmp_path),
                      "--format", "json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    validate_report(doc)
    assert doc["tool"] == "dla-lint" and doc["summary"]["findings"] == 1


def test_cli_write_then_apply_baseline(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n\n@jax.jit\ndef f(x, n):\n"
                   "    if n > 0:\n        return x\n    return n\n")
    base = tmp_path / "baseline.json"
    assert lint_main([str(bad), "--root", str(tmp_path),
                      "--write-baseline", str(base)]) == 0
    assert lint_main([str(bad), "--root", str(tmp_path),
                      "--baseline", str(base)]) == 0
    assert lint_main([str(bad), "--root", str(tmp_path),
                      "--baseline", str(tmp_path / "nope.json")]) == 2


# ------------------------------------------ unsynchronized-shared-state

def test_shared_state_fires_across_thread_roles(tmp_path):
    r = lint_src(tmp_path, """
        import threading

        class Pipe:
            def __init__(self):
                self._lock = threading.Lock()
                self._count = 0
                self._t = threading.Thread(
                    target=self._worker, name="dla-pipe-worker", daemon=True)
                self._t.start()

            def _worker(self):
                while True:
                    self._count += 1

            def read(self):
                return self._count
        """)
    assert "unsynchronized-shared-state" in fired(r)


def test_shared_state_silent_with_common_lock(tmp_path):
    r = lint_src(tmp_path, """
        import threading

        class Pipe:
            def __init__(self):
                self._lock = threading.Lock()
                self._count = 0
                self._t = threading.Thread(
                    target=self._worker, name="dla-pipe-worker", daemon=True)
                self._t.start()

            def _worker(self):
                while True:
                    with self._lock:
                        self._count += 1

            def read(self):
                with self._lock:
                    return self._count
        """)
    assert "unsynchronized-shared-state" not in fired(r)


def test_thread_roles_propagate_to_spawn_targets(tmp_path):
    from dla_tpu.analysis.core import collect_files
    from dla_tpu.analysis.threads import get_model
    p = tmp_path / "m.py"
    p.write_text(textwrap.dedent("""
        import threading

        class Pipe:
            def __init__(self):
                self._t = threading.Thread(
                    target=self._worker, name="dla-pipe-worker")

            def _worker(self):
                self._tick()

            def _tick(self):
                pass

            def read(self):
                return 1
        """))
    model = get_model(collect_files([p], root=tmp_path))
    assert model.roles_of("m.py::Pipe._worker") == {"dla-pipe-worker"}
    assert model.roles_of("m.py::Pipe._tick") == {"dla-pipe-worker"}
    assert "main" in model.roles_of("m.py::Pipe.read")


# ----------------------------------------------- lock-order-inversion

def test_lock_order_inversion_fires_on_cycle_via_call_chain(tmp_path):
    r = lint_src(tmp_path, """
        import threading

        class Pair:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def forward(self):
                with self._a:
                    with self._b:
                        pass

            def backward(self):
                with self._b:
                    self._locked_a()

            def _locked_a(self):
                with self._a:
                    pass
        """)
    assert "lock-order-inversion" in fired(r)


def test_lock_order_silent_with_consistent_order(tmp_path):
    r = lint_src(tmp_path, """
        import threading

        class Pair:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def forward(self):
                with self._a:
                    with self._b:
                        pass

            def backward(self):
                with self._a:
                    with self._b:
                        pass
        """)
    assert "lock-order-inversion" not in fired(r)


# ----------------------------------------------- blocking-under-lock

def test_blocking_under_lock_fires_on_sleep(tmp_path):
    r = lint_src(tmp_path, """
        import threading
        import time

        _lock = threading.Lock()

        def heartbeat():
            with _lock:
                time.sleep(0.5)
        """)
    assert "blocking-under-lock" in fired(r)


def test_blocking_under_lock_silent_outside_region(tmp_path):
    r = lint_src(tmp_path, """
        import threading
        import time

        _lock = threading.Lock()
        _beats = []

        def heartbeat():
            time.sleep(0.5)
            with _lock:
                _beats.append(1)
        """)
    assert "blocking-under-lock" not in fired(r)


# --------------------------------------------- conditional-collective

def test_conditional_collective_fires_on_rank_gated_barrier(tmp_path):
    r = lint_src(tmp_path, """
        import jax
        from jax.experimental import multihost_utils

        def publish(step):
            if jax.process_index() == 0:
                multihost_utils.sync_global_devices("publish")
        """)
    assert "conditional-collective" in fired(r)


def test_conditional_collective_silent_when_hoisted(tmp_path):
    r = lint_src(tmp_path, """
        import jax
        from jax.experimental import multihost_utils

        def publish(step, manifest):
            if jax.process_index() == 0:
                manifest.write_text("ok")
            multihost_utils.sync_global_devices("publish")
        """)
    assert "conditional-collective" not in fired(r)


# ---------------------------------------------------- thread naming policy

def test_every_repo_spawn_site_is_dla_named():
    """Every thread/timer/executor the repo spawns carries an explicit
    dla- prefixed name, so `py-spy`/`gdb` dumps and the lock witness
    attribute work to a subsystem by name alone."""
    from dla_tpu.analysis.core import collect_files
    from dla_tpu.analysis.threads import get_model
    model = get_model(collect_files(["dla_tpu", "tools"], root=REPO))
    spawns = [s for s in model.spawns
              if s.kind in ("thread", "timer", "executor")]
    assert len(spawns) >= 7, "expected the repo's known spawn sites"
    bad = sorted(f"{s.rel}:{s.line} name={s.name_source!r}"
                 for s in spawns
                 if not (s.name_source or "").startswith("dla-"))
    assert not bad, "spawn sites without a dla- thread name:\n" \
        + "\n".join(bad)


# ----------------------------------------------------- the repo lints clean

def test_repo_lints_clean_with_documented_suppressions():
    t0 = time.perf_counter()
    result = run_lint(["dla_tpu", "tools", "bench.py", "config"], root=REPO)
    elapsed = time.perf_counter() - t0
    assert not result.active, "unsuppressed findings:\n" + "\n".join(
        f"  {f.path}:{f.line}: [{f.rule}] {f.message}"
        for f in result.active)
    # every deliberate exception documents WHY it is allowed
    for f in result.suppressed:
        assert f.reason and f.reason.strip(), (
            f"{f.path}:{f.line}: suppression without a reason")
    assert elapsed < 10.0, f"lint took {elapsed:.1f}s (bound: 10s)"
