"""Inputs drawn from a run's seed: images, labels, query pools, arrival
schedules.

Everything here is numpy only at import time: the load generator
(`bench/loadgen.py`) runs in a process that never imports JAX and makes
its images with the same functions.  The device-side generators import
JAX inside the function.

The same seed always gives the same inputs.  Seeds are any non-negative
integer, often past 32 bits, so the JAX key is built
from both halves.
"""

from __future__ import annotations

import numpy as np

# separate streams of one seed, so that e.g. the query pool does not
# change when the training set grows
STREAM_TRAIN = 1
STREAM_POOL = 2
STREAM_STORE = 3
STREAM_SCHEDULE = 4
STREAM_PLANT = 5


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), int(stream)]))


def images_np(seed: int, stream: int, n: int, h: int) -> np.ndarray:
    """(n, h) float32 integer intensities 0..255, uniform."""
    return rng(seed, stream).integers(0, 256, size=(n, h)).astype(np.float32)


def jax_key(seed: int, stream: int):
    import jax

    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def device_dataset(seed: int, n: int, h: int, c: int):
    """(n, h) float32 intensities 0..255 and (n,) int32 labels 0..c-1,
    made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        kx, ky = jax.random.split(key)
        x = jax.random.randint(kx, (n, h), 0, 256, jnp.int32).astype(jnp.float32)
        y = jax.random.randint(ky, (n,), 0, c, jnp.int32)
        return x, y

    return make(jax_key(seed, STREAM_TRAIN))


def device_store(seed: int, rows: int, words: int):
    """(rows, words) uint32 uniform random packed rows, on the device."""
    import jax
    import jax.numpy as jnp

    make = jax.jit(lambda key: jax.random.bits(key, (rows, words), jnp.uint32))
    return make(jax_key(seed, STREAM_STORE))


def poisson_schedule(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of round(rate * seconds)
    requests with exponential gaps, rescaled so the last is due at
    `seconds`: every seed offers the same number of requests over the
    same window, in a different order of gaps."""
    n = max(1, int(round(rate * seconds)))
    gaps = rng(seed, STREAM_SCHEDULE).exponential(size=n)
    return np.cumsum(gaps) * (seconds / gaps.sum())


def schedule(traffic: dict, seed: int, seconds: float) -> np.ndarray:
    kind = traffic["arrival"]
    if kind == "poisson":
        return poisson_schedule(seed, traffic["rate_per_s"], seconds)
    raise ValueError(f"unknown arrival process {kind!r}")
