"""Benchmark entry: one run of one cell on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` with ``--trace 1``) and, last, ``checks``: each number
compared with its limit, also printed as the last lines of standard
error.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics from a profiled run.  Without a TPU, or with fewer
chips than the cell asks for, it exits 2 and prints no result.

``--control 1`` also computes every compared number with the bfloat16
reference in the program's place and prints those readings to standard
error (the check that the limits can fail; the benchmark's own runs do
not use it).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


def _devices(chips: int, platform: str):
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"needs a {platform.upper()}, JAX found "
                     f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def _key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


@contextlib.contextmanager
def _profiling(on: bool, out: Path):
    if not on:
        yield
        return
    import jax

    shutil.rmtree(out, ignore_errors=True)
    # Host ranges (TraceAnnotation) and device activity only: the Python
    # function tracer would slow every host thread of the service.
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class Ctx:
    """What a per-layer reader gets; ``reference(name)`` loads a module of
    ``bench/reference``."""

    def __init__(self, cell, result, tr, window, device_kind):
        self.config, self.reference = cell.config, cell.reference
        self.chips, self.result = cell.chips, result
        self.trace, self.window = tr, window
        self.device_kind = device_kind


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        control: bool = False, platform: str = "tpu",
        overrides: dict | None = None, log=sys.stderr) -> dict:
    """One run; returns the result object (raises NoChip)."""
    from bench import spec

    cell = spec.load_cell(workload, seed, overrides)
    # The configuration names both references; a missing name is a KeyError.
    ref_sampler, ref_learner = (
        cell.reference(cell.config["law"][k])
        for k in ("reference_sampler", "reference_learner"))
    devs = _devices(cell.chips, platform)
    import jax

    from bench import check, drive
    from bench import trace as trace_mod
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out_dir = ROOT / ".bench_trace" / workload
    profile = lambda: _profiling(trace, out_dir)  # noqa: E731
    loop = cell.traffic["loop"]
    result, rec = drive.LOOPS[loop](cell, _key(seed), seconds, trace,
                                    profile)
    used = devs[:max(cell.chips, 1)]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    metrics, breakdown = {}, None
    if trace:
        files = sorted(out_dir.rglob("*.xplane.pb"))
        tr = trace_mod.load(str(files[-1]))
        shutil.rmtree(out_dir, ignore_errors=True)
        window = trace_mod.window_of(tr)
        ctx = Ctx(cell, result, tr, window, devs[0].device_kind)
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        ids = [d.id for d in used if d.id in tr.devices]
        device["busy_s"] = sum(trace_mod.length(trace_mod.busy(
            tr.devices[i], window)) for i in ids) / max(len(ids), 1) * 1e-9
        device["window_s"] = (window[1] - window[0]) * 1e-9
        d0 = tr.devices[ids[0]]
        breakdown = {"device_ops": trace_mod.top_ops(d0, window),
                     "idle_gaps": trace_mod.idle_gaps(
                         d0, tr.spans, window, cell.traffic["gap_spans"])}
    else:
        values = {"setup_s": result["t_setup"] - T_PROCESS, **result}
        for m in cell.end_to_end:
            if m["name"] in values:  # a traffic's loop says what it times
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    # The references run after the window and the memory reading.
    law = check.Cell(cell.config, ref_sampler, ref_learner, rec["shards"])
    numbers = check.NUMBERS[loop](law, cell.config, rec, False)
    limits = cell.config["limits"][cell.traffic_name]
    correct, checks = check.judge(numbers, limits)
    if control:
        ctrl = check.NUMBERS[loop](law, cell.config, rec, True)
        _, ctrl_checks = check.judge(ctrl, limits)
        print("control " + json.dumps(ctrl_checks), file=log)
        print("readings " + json.dumps({"program": numbers,
                                        "control": ctrl}), file=log)
    for k, e in checks.items():
        print(f"check {k} {e['value']!r} limit {e['limit']!r}", file=log)
    out = {"correct": bool(correct), "attempted": int(result["attempted"]),
           "failed": 0, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  control=bool(args.control))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
