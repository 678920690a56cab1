"""The benchmark's traffic generator: a pool of training batches made on
the device from ``--seed`` in one jitted call, during set-up.

A traffic file's ``data`` block names its batch kind, ``bench/batches/
<kind>.py``, which makes the pool from the block and the sizes the
configuration gives (its ``data_shape``).  Every batch of the pool
differs; the window cycles through the pool.
"""
from __future__ import annotations

import jax

import manifest


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, all of its bits used (a plain
    ``PRNGKey`` keeps only the low 32)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_pool(data: dict, key: jax.Array) -> tuple:
    """The pool of batches the ``data`` block describes, on the device."""
    return manifest.batches_module(data["kind"]).make_pool(data, key)
