"""CPU rehearsal of ``chip_smoke.py``: every phase at a tiny size.

The phases run here on the CPU backend with the Pallas kernels in
interpret mode, so a wrong path, argument or check fails in CI instead
of on the chip.  ``main()`` itself must refuse to run off a TPU.
"""
import importlib.util
import json
from pathlib import Path

import jax
import pytest

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_kernel_phase_tiny(smoke, capsys):
    out = smoke.kernel_phase(n=4096, n_keys=2, batch=32)
    assert out["idx_mismatch"] == 0 and out["weight_mismatch"] == 0
    assert out["non_member_draws"] == 0 and min(out["csp_count"]) > 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "kernel" and line["n"] == 4096


@pytest.mark.parametrize("sampler", ["amper-fr", "per-sumtree"])
def test_trainer_phase_tiny(smoke, sampler):
    out = smoke.trainer_phase(sampler, replay_size=2048, learner_steps=4,
                              slab=2, chunk=8)
    assert out["learner_steps"] >= 4 and out["feedback_rows"] > 0


def test_sharded_phase_tiny(smoke):
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices (conftest forces 8 host devices)")
    out = smoke.sharded_phase(n=8192, batch=64, shards=4, learner_steps=2,
                              slab=2)
    assert out["membership_mismatch"] == 0 and out["idx_mismatch"] == 0
    assert out["trainer"]["feedback_rows"] > 0
