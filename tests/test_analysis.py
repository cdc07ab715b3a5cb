"""Tests for the ``repro.analysis`` gate — AST lint, trace lint,
lockdep, suppressions, dead-modules, CLI formats."""
import json
import os
import threading

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import ALL_RULES, locks
from repro.analysis.cli import main as cli_main
from repro.analysis.findings import Baseline, Finding, noqa_rules_for_line
from repro.analysis.lint import run_lint

pytestmark = [pytest.mark.analysis, pytest.mark.tier1]

HERE = os.path.dirname(__file__)
REPO = os.path.dirname(HERE)
FIX = os.path.join(HERE, "fixtures", "lint")
BASELINE = os.path.join(REPO, "analysis-baseline.json")


# --- layer 1: AST lint fixtures ---------------------------------------------

FIXTURE_RULES = [
    ("key_reuse.py", "PRNG-REUSE"),
    ("wallclock.py", "WALL-CLOCK"),
    ("host_sync.py", "HOST-SYNC"),
    ("donation.py", "DONATED-USE"),
    ("traced_branch.py", "TRACED-BRANCH"),
]


@pytest.mark.parametrize("fixture,rule", FIXTURE_RULES)
def test_fixture_violation_is_flagged(fixture, rule):
    findings = run_lint([os.path.join(FIX, fixture)])
    assert any(f.rule == rule for f in findings), (fixture, findings)


@pytest.mark.parametrize("fixture,rule", FIXTURE_RULES)
def test_cli_exits_nonzero_on_fixture(fixture, rule):
    assert cli_main([os.path.join(FIX, fixture), "--no-trace"]) == 1


def test_clean_fixture_passes():
    """Legit twins of every flagged pattern (fold_in loops, perf_counter,
    provenance timestamps, noqa'd replay, early-return arms) lint clean."""
    assert run_lint([os.path.join(FIX, "clean.py")]) == []


def test_shipped_tree_is_clean_with_committed_baseline():
    """The clean-pass gate: exactly what CI runs (minus trace checks,
    which have their own tests below)."""
    rc = cli_main([os.path.join(REPO, "src"), os.path.join(REPO, "tests"),
                   os.path.join(REPO, "benchmarks"),
                   "--no-trace", "--baseline", BASELINE])
    assert rc == 0


# --- suppressions -----------------------------------------------------------


def test_noqa_parsing():
    assert noqa_rules_for_line("x = 1") is None
    assert noqa_rules_for_line("x = f(k)  # repro: noqa") == set()
    assert noqa_rules_for_line(
        "x = f(k)  # repro: noqa[PRNG-REUSE, wall-clock]"
    ) == {"PRNG-REUSE", "WALL-CLOCK"}


def test_baseline_roundtrip_and_multiset_filter(tmp_path):
    f1 = Finding("PRNG-REUSE", "a.py", 10, "key reused")
    f2 = Finding("WALL-CLOCK", "b.py", 3, "duration math")
    path = tmp_path / "bl.json"
    Baseline.from_findings([f1, f2]).save(str(path))
    bl = Baseline.load(str(path))
    # line drift does not resurrect a baselined finding
    moved = Finding("PRNG-REUSE", "a.py", 99, "key reused")
    assert bl.filter([moved, f2]) == []
    # but a SECOND instance of the same pattern still surfaces
    dupe = Finding("PRNG-REUSE", "a.py", 120, "key reused")
    assert bl.filter([moved, dupe, f2]) == [dupe]


# --- layer 2: trace lint ----------------------------------------------------


def test_dispatch_budget_matches_committed_bench():
    from repro.analysis.jaxpr_lint import check_dispatch_budget

    assert check_dispatch_budget(os.path.join(
        REPO, "BENCH_sampling.json")) == []


def test_dispatch_budget_fails_when_budget_exceeded(tmp_path):
    """Shrink the committed budget below reality: the rule must fire —
    this is the acceptance path for a future fusion regression."""
    from repro.analysis.jaxpr_lint import check_dispatch_budget

    with open(os.path.join(REPO, "BENCH_sampling.json")) as f:
        bench = json.load(f)
    for row in bench["rows"]:
        if row[0] == "fr-fused/n10000":
            row[2] = row[2].replace(
                "dispatches=" + dict(
                    kv.split("=") for kv in row[2].split())["dispatches"],
                "dispatches=1")
    tight = tmp_path / "bench.json"
    tight.write_text(json.dumps(bench))
    findings = check_dispatch_budget(str(tight))
    assert [f.rule for f in findings] == ["DISPATCH-BUDGET"]
    assert "over the committed budget of 1" in findings[0].message


def test_slab_prefetch_path_has_one_trace():
    from repro.analysis.jaxpr_lint import check_recompile

    assert check_recompile() == []


def test_trace_cache_counter_sees_signature_churn():
    from repro.analysis.jaxpr_lint import trace_cache_entries

    f = jax.jit(lambda x: x + 1)
    calls = [(jnp.zeros((2,), jnp.float32),),
             (jnp.zeros((3,), jnp.float32),)]  # shape change -> retrace
    assert trace_cache_entries(f, calls) == 2
    jax.clear_caches()


def test_dtype_promotion_clean_on_registry_samplers():
    from repro.analysis.jaxpr_lint import check_dtype_promotion

    assert check_dtype_promotion() == []


def test_dtype_scan_flags_wide_and_weak():
    from repro.analysis.jaxpr_lint import _weak_outputs, scan_jaxpr_dtypes

    with jax.enable_x64(True):
        closed = jax.make_jaxpr(
            lambda x: jnp.cumsum(x * 2.0))(jnp.arange(4, dtype=jnp.float64))
    wide = scan_jaxpr_dtypes(closed.jaxpr, "x64-fixture")
    assert any(f.rule == "DTYPE-WIDE" and "float64" in f.message
               for f in wide)

    weak_closed = jax.make_jaxpr(lambda x: x + 1.0)(1.0)
    weak = _weak_outputs(weak_closed, "weak-fixture")
    assert any("weak-typed" in f.message for f in weak)


# --- layer 3: lockdep -------------------------------------------------------


def _inversion(rec):
    a, b = locks.make_lock("A"), locks.make_lock("B")
    with a:
        with b:
            pass
    with b:
        with a:
            pass


def test_seeded_lock_inversion_reports_cycle():
    rec = locks.enable()
    try:
        _inversion(rec)
        cycles = rec.cycles()
        assert cycles == [["A", "B"]]
        findings = locks.cycle_findings(cycles)
        assert findings[0].rule == "LOCK-ORDER"
        assert "A -> B -> A" in findings[0].message
    finally:
        locks.disable()


def test_ordered_acquisition_is_acyclic():
    rec = locks.enable()
    try:
        a, b = locks.make_lock("A"), locks.make_lock("B")
        for _ in range(3):
            with a:
                with b:
                    pass
        assert rec.edges() == {("A", "B")}
        assert rec.cycles() == []
    finally:
        locks.disable()


def test_lockdep_offline_log_roundtrip(tmp_path):
    log = tmp_path / "locks.jsonl"
    rec = locks.enable(str(log))
    try:
        _inversion(rec)
        rec.flush()
    finally:
        locks.disable()
    findings = locks.check_log(str(log))
    assert [f.rule for f in findings] == ["LOCK-ORDER"]


def test_cli_lock_log_fixture_exits_nonzero(capsys):
    rc = cli_main(["--lock-log",
                   os.path.join(FIX, "lock_inversion.jsonl")])
    assert rc == 1
    assert "LOCK-ORDER" in capsys.readouterr().out


def test_tracked_queue_and_condition_record_edges():
    rec = locks.enable()
    try:
        q = locks.tracked_queue("q", 2)
        cv = locks.make_condition("cv")
        with cv:
            q.put(1)  # q's mutex acquired while cv held
        q.get()
        q.task_done()
        q.join()
        assert ("cv", "q") in rec.edges()
        assert rec.cycles() == []
    finally:
        locks.disable()


def test_async_service_acquisition_graph_is_acyclic():
    """Lockdep over a real (tiny, churny) async service run: the
    instrumented queues, registry lock, exporter lock and pause gate
    must form an acyclic acquisition order."""
    from repro.rl.dqn import DQNConfig
    from repro.runtime.service import ReplayService

    cfg = DQNConfig(sampler="amper-fr", n_step=1, num_envs=2,
                    replay_size=32, batch=16, learn_start=4,
                    eps_decay_steps=100, target_sync=10, v_max=8.0)
    rec = locks.enable()
    try:
        svc = ReplayService(cfg, num_actors=2, chunk_len=2, slab=2,
                            queue_size=2)
        res = svc.run(jax.random.key(0), 8)
        assert res.metrics["learner_steps"] == 8
        counts = rec.counts()
        # The instrumented primitives all fired...
        assert any(n.startswith("runtime.") for n in counts), counts
        assert "obs.registry" in counts, counts
        # ...and the runtime's acquisition order is deadlock-free.  (A
        # sparse edge set is the DESIGN: the fabric rarely nests locks.)
        cycles = rec.cycles()
        assert cycles == [], f"lock-order cycle in the runtime: {cycles}"
    finally:
        locks.disable()


# --- dead modules -----------------------------------------------------------


def test_dead_modules_report(monkeypatch):
    from repro.analysis.deadcode import dead_module_report, render_report

    monkeypatch.chdir(REPO)
    report = dead_module_report("src")
    # the seed config zoo is the known candidate set
    assert any(m.startswith("repro.configs.")
               for m in report["unreferenced"])
    # the fabric itself is alive
    for mod in ("repro.runtime.service", "repro.core.amper",
                "repro.analysis.lint"):
        assert mod not in report["unreferenced"]
        assert mod not in report["outside_fabric"]
    text = render_report(report)
    assert "report only" in text


# --- output formats ---------------------------------------------------------


def test_prom_format_counts_and_stable_series():
    from repro.analysis.cli import _emit_prom
    from repro.obs.exporters import parse_prometheus

    findings = [Finding("PRNG-REUSE", "a.py", 1, "m1"),
                Finding("PRNG-REUSE", "a.py", 2, "m2"),
                Finding("LOCK-ORDER", "<lockdep>", 0, "m3")]
    metrics = parse_prometheus(_emit_prom(findings))
    assert metrics["repro_analysis_findings_prng_reuse_total"] == 2.0
    assert metrics["repro_analysis_findings_lock_order_total"] == 1.0
    # clean runs still emit every rule's series (at 0)
    clean = parse_prometheus(_emit_prom([]))
    for rule in ALL_RULES:
        name = "repro_analysis_findings_" + rule.lower().replace("-", "_")
        assert clean[name + "_total"] == 0.0


def test_cli_findings_json_artifact(tmp_path):
    out = tmp_path / "findings.json"
    rc = cli_main([os.path.join(FIX, "key_reuse.py"), "--no-trace",
                   "--out", str(out), "--format", "json"])
    assert rc == 1
    payload = json.loads(out.read_text())
    assert payload["total"] == 1
    assert payload["counts"] == {"PRNG-REUSE": 1}
    assert payload["findings"][0]["path"].endswith("key_reuse.py")


def test_cli_write_baseline_then_clean(tmp_path):
    bl = tmp_path / "bl.json"
    fixture = os.path.join(FIX, "key_reuse.py")
    assert cli_main([fixture, "--no-trace",
                     "--write-baseline", str(bl)]) == 0
    assert cli_main([fixture, "--no-trace", "--baseline", str(bl)]) == 0
