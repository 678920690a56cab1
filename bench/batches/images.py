"""Batch kind ``images``: Gaussian images labelled by a fixed random
linear teacher plus noise (a copy of the idea of
``repro.data.ImageStream``).

A traffic file's ``data`` block ``{"kind": "images", "batch", "pool",
"margin"}``, with ``img``, ``channels`` and ``classes`` from the
configuration (``data_shape``), describes the pool; a batch is
``(x, labels)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n", "batch", "img", "channels",
                                             "classes", "margin"))
def _images(key, n, batch, img, channels, classes, margin):
    kt, kx, kn = jax.random.split(key, 3)
    dim = img * img * channels
    teacher = jax.random.normal(kt, (dim, classes)) / jnp.sqrt(dim)
    x = jax.random.normal(kx, (n, batch, img, img, channels))
    logits = x.reshape(n, batch, dim) @ teacher
    noise = jax.random.normal(kn, logits.shape) / margin
    y = jnp.argmax(logits + noise, axis=-1).astype(jnp.int32)
    return tuple((x[i], y[i]) for i in range(n))


def make_pool(data: dict, key: jax.Array) -> tuple:
    """The pool of ``data["pool"]`` batches, on the device."""
    return _images(key, n=int(data["pool"]), batch=int(data["batch"]),
                   img=int(data["img"]), channels=int(data["channels"]),
                   classes=int(data["classes"]),
                   margin=float(data["margin"]))


def half(batch):
    """The first half of the images and of the labels."""
    return tuple(x[: x.shape[0] // 2] for x in batch)


def alter(batch, data: dict):
    """The first image's label moved to the next class."""
    x, y = batch
    return x, y.at[0].set((y[0] + 1) % int(data["classes"]))
