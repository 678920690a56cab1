"""Batch kind of the toy stacked cell: Gaussian vectors labelled by a
fixed random linear teacher; a batch is ``(x, labels)``."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n", "batch", "width",
                                             "classes"))
def _vectors(key, n, batch, width, classes):
    kt, kx = jax.random.split(key)
    teacher = jax.random.normal(kt, (width, classes))
    x = jax.random.normal(kx, (n, batch, width))
    y = jnp.argmax(x @ teacher, axis=-1).astype(jnp.int32)
    return tuple((x[i], y[i]) for i in range(n))


def make_pool(data: dict, key):
    return _vectors(key, n=int(data["pool"]), batch=int(data["batch"]),
                    width=int(data["width"]), classes=int(data["classes"]))


def half(batch):
    return tuple(x[: x.shape[0] // 2] for x in batch)


def alter(batch, data: dict):
    x, y = batch
    return x, y.at[0].set((y[0] + 1) % int(data["classes"]))
