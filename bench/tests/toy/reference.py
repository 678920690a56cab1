"""Plain reference of the toy stacked cell (``config.py`` beside it),
with nothing of the program imported: the residual MLP's blocks as a
Python loop over the layers of the stacked weights."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_PATHS = {"up": "blocks/up", "down": "blocks/down", "head": "head/w"}


def layers(sizes: dict):
    """(name, d_in, d_out, stack) of every tapped matmul."""
    d, h, n = sizes["width"], sizes["hidden"], sizes["layers"]
    return [("up", d, h, (n,)), ("down", h, d, (n,)),
            ("head", d, sizes["classes"], ())]


def weight_path(name: str) -> str:
    return _PATHS[name]


def init(sizes: dict, key, dtype=jnp.float32):
    d, h = sizes["width"], sizes["hidden"]
    n, c = sizes["layers"], sizes["classes"]
    ku, kd, kh = jax.random.split(key, 3)
    params = {"blocks": {"up": jax.random.normal(ku, (n, d, h))
                         / jnp.sqrt(d),
                         "down": jax.random.normal(kd, (n, h, d))
                         / jnp.sqrt(h)},
              "head": {"w": jax.random.normal(kh, (d, c)) / jnp.sqrt(d),
                       "b": jnp.zeros((c,))}}
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), params)


def _tapped(x, W, probe, n_stat):
    """(x W with ``probe`` added to its first rows, those rows of x)."""
    n = min(n_stat, x.shape[0])
    y = (x @ W).at[:n].add(probe[:n].astype(x.dtype))
    return y, jnp.pad(x[:n], ((0, n_stat - n), (0, 0)))


def loss_and_stats(params, probes, batch, sizes: dict, n_stat: int):
    """(loss, acts) with acts[name] the (*stack, n_stat, d_in) rows."""
    x, labels = batch
    h = x.astype(params["head"]["w"].dtype)
    rows = {"up": [], "down": []}
    for i in range(sizes["layers"]):
        u, a = _tapped(h, params["blocks"]["up"][i], probes["up"][i], n_stat)
        rows["up"].append(a)
        v, a = _tapped(jnp.tanh(u), params["blocks"]["down"][i],
                       probes["down"][i], n_stat)
        rows["down"].append(a)
        h = h + v
    acts = {k: jnp.stack(v) for k, v in rows.items()}
    logits, acts["head"] = _tapped(h, params["head"]["w"], probes["head"],
                                   n_stat)
    logp = jax.nn.log_softmax(logits + params["head"]["b"])
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    return loss, acts
