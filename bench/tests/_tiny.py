"""Tiny sizes at which the tests drive the harness on the CPU."""
import contextlib
import io
import json

import jax


TINY = {"config": {"dqn": {"replay_size": 4096, "batch": 8}},
        "traffic": {"prefill": {"chunk_steps": 64},
                    "calibrate_slabs": 4, "calibrate_s": 0.2,
                    "warmup_draws": 8, "insert_pool_steps": 16,
                    "td_pool_rows": 32}}


@contextlib.contextmanager
def restored_jax_config():
    """run() turns the persistent compile cache on; give the worker back
    the configuration it had."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        yield
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)


def run_tiny(cell: str, seed: int = 2**31 + 11, seconds: float = 0.5,
             trace: bool = False, control: bool = True):
    """-> (result, control checks or None)."""
    from bench import run

    log = io.StringIO()
    with restored_jax_config():
        out = run.run(cell, seed, seconds, trace, control=control,
                      platform="cpu", overrides=TINY, log=log)
    ctrl = None
    for line in log.getvalue().splitlines():
        if line.startswith("control "):
            ctrl = json.loads(line[len("control "):])
    return out, ctrl
