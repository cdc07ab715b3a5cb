"""Shared table-layout helpers for the Pallas kernels.

Every priority-table kernel views the flat int32 table as (rows, 128) so
the last dim matches the VPU lane width, pads the row count to a multiple
of the block size, and decides interpret-vs-Mosaic from the backend.
Those three decisions used to be duplicated between ``kernels.ops`` and
``kernels.tcam_match``; this module is now the single owner, used by the
TCAM kernels and the fused ``amper_sample`` kernel alike.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_BLOCK_ROWS = 64  # (64, 128) int32 tile = 32 KiB VMEM per operand

# Tri-state interpret override: None = auto (backend != "tpu").  Used by
# the dispatch-count instrumentation, which traces kernels with
# interpret=False so the jaxpr shows one ``pallas_call`` per kernel launch
# instead of the interpreter's unrolled emulation ops.
_INTERPRET_OVERRIDE: bool | None = None


def interpret_default() -> bool:
    """Should kernels run in interpret mode?  (True off-TPU, unless
    overridden by :func:`force_interpret`.)"""
    if _INTERPRET_OVERRIDE is not None:
        return _INTERPRET_OVERRIDE
    return jax.default_backend() != "tpu"


@contextlib.contextmanager
def force_interpret(value: bool | None):
    """Temporarily pin the interpret-mode default (None restores auto).

    Tracing (``jax.make_jaxpr``) under ``force_interpret(False)`` never
    executes the kernel, so it is safe on any backend — that is how the
    benchmark counts real XLA dispatches on CPU CI.

    Caveat: this override is NOT part of jax's trace-cache key (which is
    function identity + avals + jax config state), so a jaxpr traced
    under the override can be replayed by a later call to the same
    function object outside it.  Callers that trace under an override
    they don't want to execute must ``jax.clear_caches()`` afterwards
    (``benchmarks.bench_samplers.dispatch_count`` does).
    """
    global _INTERPRET_OVERRIDE
    prev = _INTERPRET_OVERRIDE
    _INTERPRET_OVERRIDE = value
    try:
        yield
    finally:
        _INTERPRET_OVERRIDE = prev


def smem_spec() -> pl.BlockSpec:
    """A whole small array (range bounds, scalars, counters) resident in
    SMEM for every grid step: Mosaic stores and reads scalars there, and
    refuses scalar stores to VMEM."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def auto_block_rows(n: int) -> int:
    """Largest sensible row-block for an n-element table.

    Small tables (e.g. one shard of a sharded replay ring) would otherwise
    pad to the full 64x128 default tile; capping the block at the table's
    own row count keeps the padding (and the interpret-mode cost on CPU)
    proportional to the input.  Rounded up to a multiple of 8 rows so the
    (block_rows, 128) int32 block always satisfies Mosaic's (8, 128)
    sublane tiling when the kernel really compiles on TPU.
    """
    rows = -(-n // LANES)
    return min(DEFAULT_BLOCK_ROWS, max(8, 8 * (-(-rows // 8))))


def pad_table(pq: jax.Array, valid: jax.Array, block_rows: int):
    """Pad a flat int32 table to (R, 128) with R % block_rows == 0.

    Padding rows carry pq = -1 (matches no non-negative range) and
    valid = False, so they are invisible to every membership law.
    Returns (pq2d, valid2d, n) with n the original flat length.
    """
    n = pq.shape[0]
    tile = block_rows * LANES
    n_pad = -n % tile
    pq = jnp.pad(pq, (0, n_pad), constant_values=-1)
    valid = jnp.pad(valid, (0, n_pad), constant_values=False)
    rows = (n + n_pad) // LANES
    return pq.reshape(rows, LANES), valid.reshape(rows, LANES), n
