"""Sharded replay latency: sample+update wall-time vs shard count.

The mesh-level claim under test (ISSUE 2 / the paper's Sec. 3 argument
lifted to SPMD): AMPER-fr's per-batch communication is O(shards + batch)
scalars (one all-gather of shard match counts + one psum of the picked
indices), while hierarchical-cumsum PER must realise the global prefix
structure every draw.  Neither law needs the table on one host, so both
scale to tables that do not fit a device — this benchmark records how
their sample and priority-update latencies move as the same table is
split over 1/2/4/8 shards.

On CPU the forced host devices share the machine, so absolute numbers
are a software-overhead proxy (collective count, not bandwidth); the
shape of the curve — AMPER flat-ish, PER paying the global cumsum — is
the recorded signal.

Run standalone (forces 8 host devices on a CPU host before JAX starts):

    python -m benchmarks.bench_sharded --json BENCH_sharded.json

``benchmarks/run.py`` runs the same :func:`run` in its own process,
after forcing the host devices itself.
"""
from __future__ import annotations

import argparse
import os
import sys

DEVICE_COUNT = 8


def force_host_devices(n: int = DEVICE_COUNT) -> None:
    """Give the CPU backend ``n`` devices; a no-op once JAX has started."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def _ensure_repro_importable() -> None:
    """Standalone-friendly: put <repo>/src on sys.path if needed."""
    try:
        import repro  # noqa: F401
    except ModuleNotFoundError:
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "src"))


def run(shard_counts=(1, 2, 4, 8), n: int = 1 << 16, batch: int = 256,
        verbose: bool = True):
    """Times sample() and update() for both sharded samplers per shard
    count; shard counts above the visible device count are skipped."""
    import jax
    import jax.numpy as jnp

    from benchmarks.common import time_fn
    from repro.core.samplers import make_sampler
    from repro.launch.mesh import make_replay_mesh

    prio = jax.random.uniform(jax.random.key(0), (n,)) + 0.01
    upd_idx = jnp.arange(batch, dtype=jnp.int32) * (n // batch)
    key = jax.random.key(1)
    rows = []
    for shards in shard_counts:
        if shards > jax.device_count():
            if verbose:
                print(f"skip shards={shards}: only {jax.device_count()} devices")
            continue
        mesh = make_replay_mesh(shards)
        for kind in ("amper-fr-sharded", "per-sharded"):
            s = make_sampler(kind, n, v_max=1.0, mesh=mesh,
                             csp_capacity=max(int(n * 0.15), batch))
            st = s.update(s.init(), jnp.arange(n), prio)
            t_sample = time_fn(jax.jit(lambda st_, k, s_=s: s_.sample(st_, k, batch)),
                               st, key)
            t_update = time_fn(jax.jit(lambda st_, i, p, s_=s: s_.update(st_, i, p)),
                               st, upd_idx, prio[:batch])
            rows.append({"kind": kind, "shards": shards, "n": n,
                         "batch": batch, "sample_us": t_sample,
                         "update_us": t_update})
            if verbose:
                print(f"sharded {kind:18s} shards={shards} n={n} "
                      f"sample={t_sample:8.0f}us update={t_update:8.0f}us",
                      flush=True)
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", default=None,
                    help="write the machine-readable BENCH json to exactly "
                         "this path")
    ap.add_argument("--shards", default="1,2,4,8")
    args = ap.parse_args(argv)

    force_host_devices()
    _ensure_repro_importable()
    shard_counts = tuple(int(s) for s in args.shards.split(","))
    n = 1 << 13 if args.quick else 1 << 16
    rows = run(shard_counts=shard_counts, n=n)

    from benchmarks.common import csv_row, write_bench_json
    for r in rows:
        print(csv_row(f"sharded/{r['kind']}/s{r['shards']}/n{r['n']}",
                      r["sample_us"], f"update_us={r['update_us']:.1f}"))
    if args.json:
        out_dir = os.path.dirname(args.json) or "."
        path = write_bench_json("sharded", rows, out_dir=out_dir,
                                n=n, shard_counts=list(shard_counts))
        if os.path.abspath(path) != os.path.abspath(args.json):
            os.replace(path, args.json)
            path = args.json
        print(f"wrote {path}")


if __name__ == "__main__":
    # must run before any jax import in this process
    force_host_devices()
    main()
