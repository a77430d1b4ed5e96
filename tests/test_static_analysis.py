"""Tier-1 gate + unit tests for the static-analysis plane
(horovod_tpu/analysis/ + tools/check.py + the runtime lock-order
witness). ISSUE 14.

Layout:
* fixture tests — every pass must flag its seeded-bad fixture under
  tests/data/analysis_fixtures/ and pass the annotated twin;
* baseline round-trip — --update-baseline then a clean run;
* the REPO GATE — all passes over this repo exit 0 with zero
  unsuppressed findings (the acceptance bar: every future PR runs the
  same review passes the costliest historical bugs needed);
* witness tests — a deliberately-inverted two-lock toy must trip the
  cycle check; a single global order must stay green; Condition
  integration must keep cond.wait() inside the bookkeeping.
"""
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from horovod_tpu import analysis
from horovod_tpu.analysis import (collective, core, knobs, locks,
                                  metrics_drift, resilience_lint,
                                  trace_registry, witness)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "data", "analysis_fixtures")
CHECK = os.path.join(REPO, "tools", "check.py")
BASELINE = os.path.join(REPO, "tools", "analysis_baseline.json")


def _run_pass(p, root=FIXTURES):
    findings, _ = core.run_passes(root, [p])
    return findings


def _codes(findings, path_part):
    return sorted(f.code for f in findings if path_part in f.path)


# --------------------------------------------------------------------------
# per-pass fixtures: seeded-bad flagged, annotated twin green
# --------------------------------------------------------------------------

class TestFixtures:
    def test_collective_bad_flagged(self):
        f = _run_pass(collective)
        assert _codes(f, "bad_collective") == ["divergent-collective"] * 3
        lines = sorted(x.line for x in f if "bad_collective" in x.path)
        # fs probe, env one-hop taint, wall clock
        assert len(lines) == 3

    def test_collective_good_green(self):
        assert _codes(_run_pass(collective), "good_collective") == []

    def test_lock_bad_flagged(self):
        f = _run_pass(locks)
        codes = _codes(f, "bad_locks")
        assert codes.count("blocking-under-lock") == 2
        assert codes.count("lock-cycle") == 1

    def test_lock_good_green(self):
        assert _codes(_run_pass(locks), "good_locks") == []

    def test_knob_bad_flagged(self):
        f = _run_pass(knobs)
        assert _codes(f, "bad_knobs") == ["bypass-config",
                                          "undeclared-knob"]
        cfg = _codes(f, "core/config")
        assert "lenient-parse" in cfg
        assert "undocumented-knob" in cfg      # declared, no docs row
        assert "stale-doc-row" in cfg          # docs row, no config read

    def test_knob_good_green(self):
        assert _codes(_run_pass(knobs), "good_knobs") == []

    def test_metric_bad_flagged(self):
        f = _run_pass(metrics_drift)
        assert _codes(f, "bad_metrics") == ["duplicate-help",
                                            "undocumented-metric"]

    def test_metric_good_green(self):
        assert _codes(_run_pass(metrics_drift), "good_metrics") == []

    def test_resilience_bad_flagged(self):
        f = _run_pass(resilience_lint)
        assert _codes(f, "bad_resilience") == \
            ["unclassified-socket-handler"]

    def test_resilience_good_green(self):
        assert _codes(_run_pass(resilience_lint), "good_resilience") == []

    def test_trace_bad_flagged(self):
        f = _run_pass(trace_registry)
        # the wire-clock call, the thread span, the local record
        assert _codes(f, "bad_trace") == ["undeclared-span"] * 3
        reg = _codes(f, "trace/spans")
        # declaration <-> docs drift, both directions, plus the
        # unregistered leg label
        for code in ("unknown-leg", "undocumented-span",
                     "stale-doc-span", "undocumented-leg",
                     "stale-doc-leg"):
            assert code in reg, (code, reg)

    def test_trace_good_green(self):
        assert _codes(_run_pass(trace_registry), "good_trace") == []


# --------------------------------------------------------------------------
# framework: annotations, finding keys, baseline
# --------------------------------------------------------------------------

class TestFramework:
    def test_annotation_requires_reason(self, tmp_path):
        d = tmp_path / "horovod_tpu"
        d.mkdir()
        (d / "m.py").write_text(
            "import time, threading\n"
            "class C:\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            # lock-order:\n"
            "            time.sleep(1)\n")
        findings, _ = core.run_passes(str(tmp_path), [locks])
        assert [f.code for f in findings] == ["blocking-under-lock"]

    def test_annotation_comment_block_above(self, tmp_path):
        d = tmp_path / "horovod_tpu"
        d.mkdir()
        (d / "m.py").write_text(
            "import time\n"
            "class C:\n"
            "    def f(self):\n"
            "        with self._lock:\n"
            "            # lock-order: exempt (reasoned twice —\n"
            "            # over two comment lines)\n"
            "            time.sleep(1)\n")
        findings, _ = core.run_passes(str(tmp_path), [locks])
        assert findings == []

    def test_finding_key_stable_across_line_drift(self):
        k1 = core.finding_key("p", "a/b.py", "c", "  x = recv()  ")
        k2 = core.finding_key("p", "a/b.py", "c", "x = recv()")
        assert k1 == k2                     # keyed on stripped text
        k3 = core.finding_key("p", "a/b.py", "c", "y = recv()")
        assert k3 != k1

    def test_syntax_error_is_a_finding(self, tmp_path):
        d = tmp_path / "horovod_tpu"
        d.mkdir()
        (d / "broken.py").write_text("def f(:\n")
        findings, _ = core.run_passes(str(tmp_path), [locks])
        assert [f.code for f in findings] == ["syntax-error"]

    def test_baseline_round_trip(self, tmp_path):
        """--update-baseline grandfathers the fixture findings; the
        next run is clean; deleting the baseline re-surfaces them."""
        bl = str(tmp_path / "bl.json")
        env = dict(os.environ)
        r1 = subprocess.run(
            [sys.executable, CHECK, "--root", FIXTURES,
             "--baseline", bl, "--update-baseline"],
            capture_output=True, text=True, env=env)
        assert r1.returncode == 0, r1.stderr
        data = json.load(open(bl))
        assert data["version"] == 1 and len(data["entries"]) >= 10
        r2 = subprocess.run(
            [sys.executable, CHECK, "--root", FIXTURES,
             "--baseline", bl],
            capture_output=True, text=True, env=env)
        assert r2.returncode == 0, r2.stdout + r2.stderr
        r3 = subprocess.run(
            [sys.executable, CHECK, "--root", FIXTURES,
             "--baseline", str(tmp_path / "none.json")],
            capture_output=True, text=True, env=env)
        assert r3.returncode == 1
        assert "divergent-collective" in r3.stdout

    def test_aggregate_doc_findings_get_distinct_keys(self, tmp_path):
        """Two undocumented knobs both anchor at config.py:1 — their
        baseline keys must differ, or baselining one grandfathers
        every future sibling."""
        pkg = tmp_path / "horovod_tpu" / "core"
        pkg.mkdir(parents=True)
        (pkg / "config.py").write_text(
            "import os\n"
            "def from_env():\n"
            "    a = os.environ.get('HOROVOD_FIX_A')\n"
            "    b = os.environ.get('HOROVOD_FIX_B')\n"
            "    return a, b\n")
        (tmp_path / "docs").mkdir()
        (tmp_path / "docs" / "knobs.md").write_text("# empty table\n")
        findings, _ = core.run_passes(str(tmp_path), [knobs])
        undoc = [f for f in findings if f.code == "undocumented-knob"]
        assert len(undoc) == 2
        assert undoc[0].key != undoc[1].key

    def test_missing_metrics_table_is_a_finding(self, tmp_path):
        pkg = tmp_path / "horovod_tpu"
        pkg.mkdir()
        (pkg / "m.py").write_text(
            "def setup(R):\n"
            "    return R.counter('orphan_total', 'help')\n")
        findings, _ = core.run_passes(str(tmp_path), [metrics_drift])
        assert [f.code for f in findings] == ["missing-doc-table"]

    def test_partial_update_keeps_other_passes_entries(self, tmp_path):
        """--update-baseline --pass X must not discard grandfathered
        entries belonging to passes that did not run."""
        bl = str(tmp_path / "bl.json")
        subprocess.run(
            [sys.executable, CHECK, "--root", FIXTURES,
             "--baseline", bl, "--update-baseline"],
            capture_output=True, text=True, check=True)
        before = {e["key"] for e in json.load(open(bl))["entries"]}
        assert any(k.startswith("knob-registry|") for k in before)
        r = subprocess.run(
            [sys.executable, CHECK, "--root", FIXTURES,
             "--baseline", bl, "--pass", "lock-order",
             "--update-baseline"],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        after = {e["key"] for e in json.load(open(bl))["entries"]}
        assert after == before          # nothing lost, nothing new
        r2 = subprocess.run(
            [sys.executable, CHECK, "--root", FIXTURES,
             "--baseline", bl],
            capture_output=True, text=True)
        assert r2.returncode == 0, r2.stdout

    def test_witness_knob_accepts_bool_spellings(self, monkeypatch):
        """HOROVOD_ANALYSIS_WITNESS is declared bool — every _env_bool
        truthy spelling must arm the witness, not just '1'."""
        was = witness.installed()
        try:
            for v in ("true", "YES", "on", "1"):
                witness.uninstall()
                monkeypatch.setenv("HOROVOD_ANALYSIS_WITNESS", v)
                assert witness.maybe_install() is True, v
            witness.uninstall()
            monkeypatch.setenv("HOROVOD_ANALYSIS_WITNESS", "0")
            assert witness.maybe_install() is False
        finally:
            if was:
                witness.install()
            else:
                witness.uninstall()

    def test_cli_pass_selection_and_list(self):
        r = subprocess.run(
            [sys.executable, CHECK, "--root", FIXTURES,
             "--pass", "metric-help", "--baseline", ""],
            capture_output=True, text=True)
        assert r.returncode == 1
        assert "duplicate-help" in r.stdout
        assert "divergent-collective" not in r.stdout
        r = subprocess.run([sys.executable, CHECK, "--pass", "nope"],
                           capture_output=True, text=True)
        assert r.returncode == 2
        r = subprocess.run([sys.executable, CHECK, "--list"],
                           capture_output=True, text=True)
        assert r.returncode == 0
        for p in analysis.ALL_PASSES:
            assert p.PASS_ID in r.stdout


# --------------------------------------------------------------------------
# THE repo gate
# --------------------------------------------------------------------------

class TestRepoGate:
    def test_repo_clean_under_all_passes(self):
        """The acceptance bar: every pass over horovod_tpu/ with the
        committed baseline — zero unsuppressed findings, < 30 s."""
        t0 = time.time()
        baseline = core.load_baseline(BASELINE)
        findings, _ = core.run_passes(REPO, list(analysis.ALL_PASSES),
                                      baseline=baseline)
        dt = time.time() - t0
        assert not findings, "\n".join(f.render() for f in findings)
        assert dt < 30, f"analysis took {dt:.1f}s (budget 30s)"

    def test_cli_runs_jax_free(self):
        """tools/check.py must work on a box with no jax: run it with
        an import hook that fails on jax."""
        env = dict(os.environ)
        code = ("import runpy, sys\n"
                "class B:\n"
                "    def find_spec(self, name, path=None, target=None):\n"
                "        assert not name.startswith('jax'), name\n"
                "        return None\n"
                "sys.meta_path.insert(0, B())\n"
                "sys.argv = ['check.py', '-q']\n"
                "try:\n"
                f"    runpy.run_path({CHECK!r}, run_name='__main__')\n"
                "except SystemExit as e:\n"
                "    raise SystemExit(e.code or 0)\n")
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, env=env,
                           cwd=REPO)
        assert r.returncode == 0, r.stdout + r.stderr


# --------------------------------------------------------------------------
# runtime lock-order witness
# --------------------------------------------------------------------------

def _tracked_locks(src, fname="/x/horovod_tpu/_witness_fixture/toy.py"):
    """exec() lock-creating code under a horovod_tpu-looking filename
    so the witness factory instruments it."""
    g = {}
    exec(compile(src, fname, "exec"), g)
    return g


@pytest.fixture()
def armed_witness():
    """Arm the witness with a CLEAN graph, then RESTORE whatever the
    session had witnessed before — in an env-armed full-suite run,
    reset() alone would erase a cycle an earlier suite recorded and
    turn the conftest session-teardown check green."""
    was_installed = witness.installed()
    with witness._state_lock:
        saved = (dict(witness._edges),
                 {k: set(v) for k, v in witness._graph.items()},
                 list(witness._violations),
                 set(witness._seen_cycles))
    witness.install()
    witness.reset()
    yield witness
    witness.reset()
    with witness._state_lock:
        witness._edges.update(saved[0])
        for k, v in saved[1].items():
            witness._graph.setdefault(k, set()).update(v)
        witness._violations.extend(saved[2])
        witness._seen_cycles.update(saved[3])
    if not was_installed:       # leave an env-armed session witness on
        witness.uninstall()


class TestWitness:
    def test_inverted_two_lock_toy_trips_the_cycle_check(
            self, armed_witness):
        g = _tracked_locks(
            "import threading\n"
            "a = threading.Lock()\n"
            "b = threading.Lock()\n")
        a, b = g["a"], g["b"]
        with a:
            with b:
                pass
        assert armed_witness.violations() == []

        def inverted():
            with b:
                with a:
                    pass
        t = threading.Thread(target=inverted)
        t.start()
        t.join()
        with pytest.raises(witness.WitnessCycleError) as ei:
            armed_witness.check()
        assert "cycle" in str(ei.value)
        snap = armed_witness.snapshot()
        assert any(snap.values())

    def test_single_global_order_stays_green(self, armed_witness):
        g = _tracked_locks(
            "import threading\n"
            "a = threading.Lock()\n"
            "b = threading.Lock()\n"
            "c = threading.RLock()\n")
        a, b, c = g["a"], g["b"], g["c"]
        for _ in range(3):
            with a:
                with b:
                    with c:
                        pass
        with b:
            with c:
                pass
        armed_witness.check()      # no cycle
        # reentrant RLock re-acquire adds no self-edges
        with c:
            with c:
                pass
        armed_witness.check()

    def test_same_site_pairs_are_not_edges(self, armed_witness):
        g = _tracked_locks(
            "import threading\n"
            "def mk():\n"
            "    return threading.Lock()\n")
        l1, l2 = g["mk"](), g["mk"]()
        with l1:
            with l2:
                pass
        with l2:
            with l1:
                pass
        armed_witness.check()      # instance inversion at ONE site: ok

    def test_condition_wait_stays_tracked(self, armed_witness):
        g = _tracked_locks(
            "import threading\n"
            "cv = threading.Condition(threading.RLock())\n")
        cv = g["cv"]
        hits = []

        def waiter():
            with cv:
                cv.wait(timeout=2.0)
                hits.append(1)
        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        with cv:
            cv.notify_all()
        t.join()
        assert hits == [1]
        armed_witness.check()

    def test_outside_locks_untracked(self, armed_witness):
        lk = threading.Lock()      # created from tests/ — not tracked
        assert type(lk).__name__ != "_Tracked"

    def test_uninstall_restores_factories(self):
        was = witness.installed()
        witness.install()
        if not was:
            witness.uninstall()
            assert threading.Lock is witness._REAL_LOCK
            assert threading.RLock is witness._REAL_RLOCK
