"""The random numbers a draw consumes, from its key (JAX's threefry).

A law is defined over the key the caller passes, so the references take
their uniforms and bits from the same public ``jax.random`` functions the
law names; what they compute from them is their own.
"""
from __future__ import annotations

import jax
import numpy as np


def split(key, n: int = 2):
    return list(jax.random.split(key, n))


def uniform(key, shape) -> np.ndarray:
    return np.asarray(jax.random.uniform(key, shape), np.float32)


def bits(key, shape) -> np.ndarray:
    return np.asarray(jax.random.bits(key, shape, np.uint32), np.uint32)


def randint(key, shape, lo: int, hi: int) -> np.ndarray:
    return np.asarray(jax.random.randint(key, shape, lo, hi), np.int64)

