"""Benchmark harness: one module per paper table/figure.

``python -m benchmarks.run [--quick]`` runs every benchmark, prints
``name,us_per_call,derived`` CSV rows (plus human-readable logs), and
persists each section's rows as machine-readable ``BENCH_<section>.json``
(see :func:`benchmarks.common.write_bench_json`) so the perf trajectory
is recorded across commits.  Roofline tables come from the dry-run
artifacts: see benchmarks/roofline.py and EXPERIMENTS.md.

Everything runs in this one process: an accelerator belongs to the
process that first touches JAX, so a JAX child started later would fail
or hang.  The ``sharded`` section needs several devices; on a CPU host
:func:`main` forces 8 host devices via XLA_FLAGS before JAX is first
imported (the flag does not affect an accelerator backend), and the
section sweeps the shard counts the visible devices allow.
"""
from __future__ import annotations

import argparse
import contextlib
import os
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller sizes / fewer seeds")
    ap.add_argument("--only", default=None,
                    help="comma list: fig4,fig7,fig9,table1,samplers,"
                         "sampling,venv,sharded,runtime,replay,storage")
    ap.add_argument("--out", default=".",
                    help="directory for the BENCH_*.json artifacts")
    ap.add_argument("--profile", action="store_true",
                    help="wrap each benched section in jax.profiler.trace; "
                         "traces land under <out>/profile/<section>")
    ap.add_argument("--metrics-out", default=None,
                    help="telemetry JSONL path: enables the repro.obs "
                         "registry for the whole run and writes one "
                         "snapshot per section (spans, counters) there")
    args = ap.parse_args()
    from benchmarks import bench_sharded

    bench_sharded.force_host_devices()  # before the first JAX import
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None
    failures = []
    written = []

    from benchmarks import common

    exporter = registry = None
    if args.metrics_out:
        from repro import obs

        registry = obs.Registry(enabled=True)
        obs.set_registry(registry)
        exporter = obs.JsonlExporter(args.metrics_out)

    def section(name, fn):
        if only and name not in only:
            return
        print(f"\n=== {name} ===", flush=True)
        try:
            if args.profile:
                import jax

                trace_dir = os.path.join(args.out, "profile", name)
                print(f"profiler trace -> {trace_dir}", flush=True)
                ctx = jax.profiler.trace(trace_dir)
            else:
                ctx = contextlib.nullcontext()
            with ctx:
                rows = fn()
        except Exception:
            failures.append(name)
            traceback.print_exc()
            if exporter:
                exporter.write_event("section_failed", section=name)
            return
        if rows:
            written.append(common.write_bench_json(name, rows,
                                                   out_dir=args.out))
        if exporter:
            exporter.write_snapshot(registry.snapshot(),
                                    extra={"section": name})

    from benchmarks import (bench_replay, bench_runtime, bench_samplers,
                            bench_storage, bench_vector_env, fig4_latency,
                            fig7_sampling_error, fig9_hw_latency,
                            table1_learning)

    section("fig4", lambda: fig4_latency.run(
        sizes=(1000, 10_000) if args.quick else (1000, 10_000, 100_000)))
    section("fig7", lambda: fig7_sampling_error.run(
        n=5000 if args.quick else 10_000,
        m_values=(2, 8) if args.quick else (2, 4, 8, 12)))
    if not args.quick:
        section("fig7d", fig7_sampling_error.run_sizes)
    section("fig9", fig9_hw_latency.main)
    section("table1", lambda: table1_learning.run(
        steps=4000 if args.quick else 6000,
        seeds=(0,) if args.quick else (0, 1)))
    section("samplers", lambda: bench_samplers.run(
        sizes=(10_000, 100_000) if args.quick else
        (10_000, 100_000, 1_000_000)))
    section("sampling", lambda: bench_samplers.run_sampling(
        sizes=(10_000,) if args.quick else (10_000, 100_000)))
    section("venv", lambda: bench_vector_env.run(
        widths=(1, 16) if args.quick else (1, 4, 16, 64),
        steps=1000 if args.quick else 2000))
    section("runtime", lambda: bench_runtime.run(
        steps=200 if args.quick else 400,
        trials=2 if args.quick else 3))
    # replay keeps the full 120-step service runs even in quick mode: a
    # 60-step base is ~50ms of wall, short enough that overhead_frac is
    # mostly measurement noise and checkpoint cadence artifacts.
    section("replay", lambda: bench_replay.run(
        sizes=(10_000,) if args.quick else (10_000, 100_000),
        steps=120))
    section("storage", lambda: bench_storage.run(
        sizes=(10_000,) if args.quick else (10_000, 100_000)))
    section("sharded", lambda: bench_sharded.run(
        n=1 << 13 if args.quick else 1 << 16))

    if exporter:
        exporter.close()
        print(f"\ntelemetry JSONL: {args.metrics_out}")
    if written:
        print(f"\nBENCH artifacts: {written}")
    if failures:
        print(f"\nFAILED sections: {failures}")
        sys.exit(1)
    print("\nall benchmark sections completed")


if __name__ == "__main__":
    main()
