"""The benchmark's one traffic generator: a pool of training batches made
on the device from ``--seed`` in one jitted call, during set-up.

It reads a traffic file's ``data`` block ``{"kind": "images", "batch",
"pool", "img", "channels", "classes", "margin"}``: Gaussian images
labelled by a fixed random linear teacher plus noise (a copy of the idea
of ``repro.data.ImageStream``).

Every batch of the pool differs; the window cycles through the pool.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, all of its bits used (a plain
    ``PRNGKey`` keeps only the low 32)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


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
    """The pool of batches the ``data`` block describes, on the device."""
    if data["kind"] == "images":
        return _images(key, n=int(data["pool"]), batch=int(data["batch"]),
                       img=int(data["img"]), channels=int(data["channels"]),
                       classes=int(data["classes"]),
                       margin=float(data["margin"]))
    raise ValueError(f"unknown traffic kind {data['kind']!r}")
