"""Every cell through the harness at a tiny size on the CPU: the result
line's keys, the checks, the control failing a limit, and the refusal to
measure without a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.tests._tiny import run_tiny

ROOT = Path(__file__).resolve().parents[2]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _assert_line(out, e2e):
    assert list(out)[:5] == KEYS[:5] and list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == set(e2e)
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["kind"] and out["device"]["count"] >= 1
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("cell,e2e", [
    ("amper-1m.learn", {"updates_per_s", "setup_s"}),
    ("per-1m.learn", {"updates_per_s", "setup_s"}),
    ("amper-1m.draw", {"draw_p95_ms", "setup_s"}),
])
def test_cell_rehearsal_and_control(cell, e2e):
    out, ctrl = run_tiny(cell)
    _assert_line(out, e2e)
    # The bfloat16 reference in the program's place fails a limit.
    assert any(c["value"] > c["limit"] for c in ctrl.values()), ctrl


def test_traced_run_reports_per_layer_metrics_and_breakdown():
    out, _ = run_tiny("amper-1m.draw", trace=True, control=False)
    assert out["correct"] is True
    dev = out["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(out["breakdown"]["device_ops"]) <= 10


def test_main_refuses_without_a_tpu(capsys):
    from bench import run

    rc = run.main(["--workload", "amper-1m.learn", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert rc == 2 and captured.out == ""
    assert "TPU" in captured.err


def test_no_result_outside_a_checkout(tmp_path):
    # Only BENCHMARK.json and bench/: the program is missing.
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "amper-1m.learn",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""
