"""tools/tslint test suite (ISSUE 3).

Three layers:
  * per-rule fixtures — a positive (the bug class the rule exists for)
    and a negative (the disciplined version) per rule, plus inline
    suppression and baseline round-trip semantics;
  * CLI contract — exit 0 clean / 1 new findings / 2 usage error, the
    codes scripts/lint.sh keys off;
  * repo self-check — the committed baseline keeps the package clean,
    and the baseline stays near-empty (<= 5 grandfathered findings, the
    ISSUE 3 acceptance bound).

The engine is stdlib-only, so none of these tests need jax.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tools.tslint import analyze, load_baseline, match_baseline, write_baseline
from tools.tslint.config import DEFAULT_BASELINE
from tools.tslint.rules import RULES

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
PACKAGE = "textsummarization_on_flink_tpu"

#: fixture-friendly TS002 config: every function is hot
HOT_ALL = {"rules": {"TS002": {"hot_functions": [r".*"],
                               "exempt_functions": [r"_flush"]}}}


def run_snippet(tmp_path, code, config=None, select=None):
    f = tmp_path / "snippet.py"
    f.write_text(textwrap.dedent(code), encoding="utf-8")
    result = analyze([str(f)], root=str(tmp_path), config=config,
                     select=select)
    return result


def rules_of(result):
    return [f.rule for f in result.findings]


# --------------------------------------------------------------------------
# TS001 — jit purity
# --------------------------------------------------------------------------

def test_ts001_print_in_jitted_fn(tmp_path):
    r = run_snippet(tmp_path, """
        import jax

        def step(params, batch):
            print("loss", params)
            return params

        train = jax.jit(step)
    """)
    assert rules_of(r) == ["TS001"]


def test_ts001_factory_returned_step_is_traced(tmp_path):
    # the repo's make_train_step shape: jax.jit(make_step(hps)) traces
    # the factory's returned def
    r = run_snippet(tmp_path, """
        import time
        import jax

        def make_step(lr):
            def step(params, batch):
                t0 = time.time()
                return params - lr * batch, t0
            return step

        train = jax.jit(make_step(0.1))
    """)
    assert rules_of(r) == ["TS001"]


def test_ts001_lax_scan_body_and_self_mutation(tmp_path):
    r = run_snippet(tmp_path, """
        import jax

        class Model:
            def fit(self, xs):
                def body(c, x):
                    self.last = x
                    return c + x, c
                return jax.lax.scan(body, 0.0, xs)
    """)
    assert rules_of(r) == ["TS001"]
    assert "self.last" in r.findings[0].message


def test_ts001_metric_mutation_via_partial_decorator(tmp_path):
    r = run_snippet(tmp_path, """
        import functools
        import jax

        class T:
            @functools.partial(jax.jit, static_argnames=("k",))
            def step(self, x, k):
                self._c_steps.inc()
                return x * k
    """)
    assert rules_of(r) == ["TS001"]


def test_ts001_negative_pure_step_and_jax_debug(tmp_path):
    r = run_snippet(tmp_path, """
        import jax
        import jax.numpy as jnp

        def make_step(lr):
            def step(params, batch):
                jax.debug.print("loss {}", params)
                g = jax.grad(lambda p: jnp.sum(p * batch))(params)
                return params - lr * g
            return step

        train = jax.jit(make_step(0.1))

        def host_side():
            print("this print is NOT traced")
    """)
    assert rules_of(r) == []


# --------------------------------------------------------------------------
# TS002 — host sync in hot loop
# --------------------------------------------------------------------------

def test_ts002_syncs_in_hot_loop(tmp_path):
    r = run_snippet(tmp_path, """
        import jax
        import numpy as np

        class Loop:
            def run(self, steps, state):
                for _ in range(steps):
                    state, metrics = self.step(state)
                    loss = float(metrics.loss)
                    host = jax.device_get(metrics)
                    arr = np.asarray(state.step)
                    scalar = metrics.loss.item()
                return state
    """, config=HOT_ALL)
    assert rules_of(r) == ["TS002"] * 4


def test_ts002_flush_window_exempt_and_cold_code_ignored(tmp_path):
    r = run_snippet(tmp_path, """
        import jax

        class Loop:
            def _flush(self, pending):
                for m in pending:
                    yield float(m.loss)  # sanctioned sync window

        def cold_path(xs):
            for x in xs:
                jax.device_get(x)  # not a declared hot function? still .*
    """, config={"rules": {"TS002": {
        "hot_functions": [r"^Loop\."], "exempt_functions": [r"_flush"]}}})
    assert rules_of(r) == []


def test_ts002_nested_loop_reports_once(tmp_path):
    # a sync two loops deep is ONE finding, not one per enclosing loop
    # (duplicates would also inflate --write-baseline and the
    # suppressed count)
    r = run_snippet(tmp_path, """
        class Loop:
            def run(self, batches):
                while True:
                    for b in batches:
                        x = b.loss.item()
    """, config=HOT_ALL)
    assert rules_of(r) == ["TS002"]


def test_rule_config_bool_shorthand_disables(tmp_path):
    code = """
        def f():
            try:
                g()
            except Exception:
                pass
    """
    r = run_snippet(tmp_path, code, config={"rules": {"TS005": False}})
    assert rules_of(r) == []
    with pytest.raises(ValueError):
        run_snippet(tmp_path, code, config={"rules": {"TS005": "nope"}})


def test_ts002_default_config_names_repo_hot_loops():
    from tools.tslint.config import DEFAULT

    pats = DEFAULT["rules"]["TS002"]["hot_functions"]
    assert any("_train_steps" in p for p in pats)
    assert any("next_batch" in p for p in pats)


def test_ts002_default_config_flags_a_per_slot_read_in_the_batched_retire(
        tmp_path):
    # the harvest's batched retire reads every slot's output ONCE, after
    # its dispatch loop: a per-slot read back inside the loop is flagged
    # under the default config, the one read outside it is not
    r = run_snippet(tmp_path, """
        import jax
        import numpy as np

        class SlotDecodeEngine:
            def unpack_many(self, pairs):
                outs = []
                for idx, example in pairs:
                    out = self._dispatch(idx)
                    outs.append((np.asarray(out.tokens), int(out.length)))
                return outs
    """)
    assert rules_of(r) == ["TS002", "TS002"]
    r = run_snippet(tmp_path, """
        import jax

        class SlotDecodeEngine:
            def unpack_many(self, pairs):
                outs = [self._dispatch(idx) for idx, _ in pairs]
                for idx, _ in pairs:
                    self._release(idx)
                return jax.device_get(outs)
    """)
    assert rules_of(r) == []


# --------------------------------------------------------------------------
# TS003 — monotonic clock
# --------------------------------------------------------------------------

def test_ts003_direct_and_var_tracked_subtraction(tmp_path):
    r = run_snippet(tmp_path, """
        import time

        def direct(t0):
            return time.time() - t0

        def tracked():
            t0 = time.time()
            work()
            dur = now() - t0
            return dur
    """)
    assert rules_of(r) == ["TS003", "TS003"]


def test_ts003_regression_batcher_timeout_pattern(tmp_path):
    # the exact bug PR 2 fixed by hand in batcher._get_example: a poll
    # deadline budgeted from the wall clock stretches unboundedly when
    # the clock jumps — tslint now catches the class statically
    r = run_snippet(tmp_path, """
        import time

        def get_example(q, timeout):
            deadline = time.time() + timeout
            while True:
                remaining = deadline - time.time()
                if remaining <= 0:
                    return None
    """)
    assert rules_of(r) == ["TS003"]


def test_ts003_negative_monotonic_and_serialized_epoch(tmp_path):
    r = run_snippet(tmp_path, """
        import time

        def good():
            t0 = time.monotonic()
            dur = time.monotonic() - t0
            record = {"ts": time.time()}  # serialized epoch: legitimate
            return dur, record
    """)
    assert rules_of(r) == []


# --------------------------------------------------------------------------
# TS004 — lock discipline
# --------------------------------------------------------------------------

def test_ts004_unlocked_write_to_protected_attr(tmp_path):
    r = run_snippet(tmp_path, """
        import threading

        class Registry:
            def __init__(self):
                self._lock = threading.Lock()
                self._metrics = {}

            def register(self, name, m):
                with self._lock:
                    self._metrics[name] = m

            def sneak(self, name, m):
                self._metrics[name] = m
    """)
    assert rules_of(r) == ["TS004"]
    assert r.findings[0].scope == "Registry.sneak"


def test_ts004_lock_held_helper_fixpoint(tmp_path):
    # a private helper called ONLY under the lock (directly or through
    # another lock-held helper) is disciplined — the CircuitBreaker
    # _set_state shape must not be a finding
    r = run_snippet(tmp_path, """
        import threading

        class Breaker:
            def __init__(self):
                self._lock = threading.Lock()
                self._state = "closed"

            def _set_state(self, s):
                self._state = s

            def _maybe_open(self):
                self._set_state("open")

            def trip(self):
                with self._lock:
                    self._maybe_open()

            def reset(self):
                with self._lock:
                    self._set_state("closed")
    """)
    assert rules_of(r) == []


def test_ts004_unprotected_attrs_and_lockless_classes_ignored(tmp_path):
    r = run_snippet(tmp_path, """
        import threading

        class NoLock:
            def set(self, v):
                self.value = v

        class Flag:
            def __init__(self):
                self._lock = threading.Lock()
                self.done = False  # never touched under the lock

            def finish(self):
                self.done = True
    """)
    assert rules_of(r) == []


# --------------------------------------------------------------------------
# TS005 — broad except
# --------------------------------------------------------------------------

def test_ts005_swallowing_handler(tmp_path):
    r = run_snippet(tmp_path, """
        def load(path):
            try:
                return open(path).read()
            except Exception:
                return None
    """)
    assert rules_of(r) == ["TS005"]


def test_ts005_reraise_typed_mapping_and_counter_pass(tmp_path):
    r = run_snippet(tmp_path, """
        def a():
            try:
                work()
            except Exception:
                raise

        def b():
            try:
                work()
            except Exception as e:
                raise CheckpointCorruptError("bad") from e

        def c(reg):
            try:
                work()
            except Exception:
                reg.counter("errors_total").inc()

        def d():
            try:
                work()
            except (OSError, ValueError):
                pass  # narrow: not TS005's business
    """)
    assert rules_of(r) == []


def test_ts005_bare_except_flagged(tmp_path):
    r = run_snippet(tmp_path, """
        def f():
            try:
                work()
            except:
                pass
    """)
    assert rules_of(r) == ["TS005"]


# --------------------------------------------------------------------------
# TS006 — donation aliasing
# --------------------------------------------------------------------------

def test_ts006_donated_arg_read_after_call(tmp_path):
    r = run_snippet(tmp_path, """
        import jax

        def train(state, batch):
            step = jax.jit(update, donate_argnums=0)
            new_state = step(state, batch)
            return new_state, state.step
    """)
    assert rules_of(r) == ["TS006"]
    assert "'state'" in r.findings[0].message


def test_ts006_reassignment_clears_and_no_donation_ok(tmp_path):
    r = run_snippet(tmp_path, """
        import jax

        def loop(state, batches):
            step = jax.jit(update, donate_argnums=0)
            for b in batches:
                state = step(state, b)  # rebound every iteration
            return state

        def undonated(state, batch):
            step = jax.jit(update)
            new = step(state, batch)
            return new, state.step
    """)
    assert rules_of(r) == []


# --------------------------------------------------------------------------
# suppression + baseline + engine mechanics
# --------------------------------------------------------------------------

def test_inline_suppression_and_disable_all(tmp_path):
    r = run_snippet(tmp_path, """
        def a():
            try:
                work()
            except Exception:  # tslint: disable=TS005 — fixture: intentional
                pass

        def b():
            try:
                work()
            except Exception:  # tslint: disable=all
                pass

        def c():
            try:
                work()
            except Exception:  # tslint: disable=TS003 — wrong rule: no effect
                pass
    """)
    assert rules_of(r) == ["TS005"]
    assert r.suppressed == 2
    assert r.findings[0].scope == "c"


def test_suppression_shares_comment_with_pragma(tmp_path):
    r = run_snippet(tmp_path, """
        def f():
            try:
                work()
            except Exception:  # pragma: no cover - tslint: disable=TS005 — teardown
                pass
    """)
    assert rules_of(r) == []
    assert r.suppressed == 1


def test_baseline_round_trip(tmp_path):
    code = """
        import time

        def slow():
            t0 = time.time()
            return time.time() - t0
    """
    r = run_snippet(tmp_path, code)
    assert rules_of(r) == ["TS003"]
    bl_path = tmp_path / "baseline.json"
    write_baseline(r.findings, str(bl_path))
    baseline = load_baseline(str(bl_path))
    assert len(baseline["findings"]) == 1

    # same findings -> fully absorbed
    new, baselined, stale = match_baseline(r.findings, baseline)
    assert (len(new), baselined, stale) == (0, 1, [])

    # a NEW bug is not absorbed by the grandfathered one
    r2 = run_snippet(tmp_path, code + """
        def worse(t_start):
            return time.time() - t_start
    """)
    new, baselined, stale = match_baseline(r2.findings, baseline)
    assert baselined == 1
    assert [f.rule for f in new] == ["TS003"]
    assert new[0].scope == "worse"

    # the bug got fixed -> the baseline entry is reported stale
    new, baselined, stale = match_baseline([], baseline)
    assert (new, baselined) == ([], 0)
    assert len(stale) == 1


def test_baseline_fingerprint_survives_line_drift(tmp_path):
    base = """
        import time

        def slow():
            t0 = time.time()
            return time.time() - t0
    """
    r1 = run_snippet(tmp_path, base)
    # unrelated code added ABOVE the finding moves its line number
    r2 = run_snippet(tmp_path, "\nHEADER = 1\n\n" + textwrap.dedent(base))
    assert r1.findings[0].line != r2.findings[0].line
    assert r1.findings[0].fingerprint == r2.findings[0].fingerprint


def test_syntax_error_becomes_ts000_finding(tmp_path):
    r = run_snippet(tmp_path, "def broken(:\n    pass\n")
    assert rules_of(r) == ["TS000"]


def test_select_restricts_rules(tmp_path):
    code = """
        import time

        def f():
            t0 = time.time()
            try:
                return time.time() - t0
            except Exception:
                return None
    """
    r = run_snippet(tmp_path, code, select={"TS005"})
    assert rules_of(r) == ["TS005"]


# --------------------------------------------------------------------------
# CLI contract
# --------------------------------------------------------------------------

def _cli(args, cwd=REPO_ROOT):
    return subprocess.run(
        [sys.executable, "-m", "tools.tslint", *args],
        cwd=cwd, capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": REPO_ROOT})


def test_cli_exits_nonzero_on_fixture_bug(tmp_path):
    bug = tmp_path / "bug.py"
    bug.write_text(textwrap.dedent("""
        import time

        def f(t0):
            return time.time() - t0
    """), encoding="utf-8")
    proc = _cli(["--no-baseline", "--root", str(tmp_path), str(bug)])
    assert proc.returncode == 1
    assert "TS003" in proc.stdout


def test_cli_exits_zero_on_clean_file(tmp_path):
    ok = tmp_path / "ok.py"
    ok.write_text("import time\n\n\ndef f():\n    return time.monotonic()\n",
                  encoding="utf-8")
    proc = _cli(["--no-baseline", "--root", str(tmp_path), str(ok)])
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_write_baseline_then_clean(tmp_path):
    bug = tmp_path / "bug.py"
    bug.write_text(textwrap.dedent("""
        import time

        def f(t0):
            return time.time() - t0
    """), encoding="utf-8")
    bl = tmp_path / "bl.json"
    proc = _cli(["--root", str(tmp_path), "--baseline", str(bl),
                 "--write-baseline", str(bug)])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    proc = _cli(["--root", str(tmp_path), "--baseline", str(bl), str(bug)])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "1 baselined" in proc.stdout


def test_cli_json_format(tmp_path):
    bug = tmp_path / "bug.py"
    bug.write_text("def f():\n    try:\n        g()\n    except Exception:\n"
                   "        pass\n", encoding="utf-8")
    proc = _cli(["--no-baseline", "--format", "json", "--root",
                 str(tmp_path), str(bug)])
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["new"][0]["rule"] == "TS005"
    assert payload["new"][0]["fingerprint"]


def test_cli_missing_path_is_usage_error(tmp_path):
    proc = _cli(["--no-baseline", "--root", str(tmp_path), "nope.py"])
    assert proc.returncode == 2


def test_cli_missing_explicit_baseline_is_usage_error(tmp_path):
    ok = tmp_path / "ok.py"
    ok.write_text("X = 1\n", encoding="utf-8")
    proc = _cli(["--root", str(tmp_path), "--baseline",
                 str(tmp_path / "gone.json"), str(ok)])
    assert proc.returncode == 2
    assert "baseline not found" in proc.stderr


def test_cli_list_rules():
    proc = _cli(["--list-rules"])
    assert proc.returncode == 0
    for rule in RULES:
        assert rule.id in proc.stdout


# --------------------------------------------------------------------------
# repo self-check (the lint.sh gate, in-process)
# --------------------------------------------------------------------------

def test_repo_is_clean_against_committed_baseline():
    result = analyze([PACKAGE], root=REPO_ROOT)
    baseline = load_baseline(os.path.join(REPO_ROOT, DEFAULT_BASELINE))
    new, baselined, stale = match_baseline(result.findings, baseline)
    assert new == [], "\n".join(f.format_text() for f in new)
    assert stale == [], (
        "baseline entries no longer match any finding — regenerate with "
        "python -m tools.tslint --write-baseline: "
        + json.dumps(stale, indent=2))


def test_committed_baseline_stays_near_empty():
    baseline = load_baseline(os.path.join(REPO_ROOT, DEFAULT_BASELINE))
    assert len(baseline["findings"]) <= 5  # ISSUE 3 acceptance bound


def test_every_rule_is_exercised_by_this_suite():
    # the per-file rules live here; the interprocedural concurrency
    # rules (TS007–TS010) are covered by tests/test_tslint_concurrency.py
    ids = {r.id for r in RULES}
    assert ids == {"TS001", "TS002", "TS003", "TS004", "TS005", "TS006"}
    from tools.tslint import ALL_RULES

    assert {r.id for r in ALL_RULES} == ids | {"TS007", "TS008", "TS009",
                                               "TS010"}


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
