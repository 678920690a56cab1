"""Plain reference of ``vgg16bn-mod``: the paper's modified VGG16_bn
(arXiv:2210.08494 §6) written out in ``jax.numpy``, with nothing of the
program imported.

Convolutions are 3×3 "same" patches (channel-major, then kernel row and
column, the order of ``lax.conv_general_dilated_patches``) times a
(9·c_in, c_out) matrix, then bias, batch norm over the batch and the two
spatial axes, ReLU; each stage ends in a 2×1 max pool.  The flattened
(H, W, C) features go through FC0, ReLU and FC1; the loss is the mean
cross-entropy.  Weights are N(0, 1/d_in) from the seed, biases and
shifts 0, scales 1, drawn in the order the program's ``make_vgg`` draws
them, so one seed gives both the same weights.

For the K-FAC statistics every tapped matmul y = x W reports its first
``n_stat`` input rows (zero rows where the layer has fewer) and the
gradient of the loss with respect to the same output rows.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp


def layers(sizes: dict) -> List[Tuple[str, int, int, Tuple[int, ...]]]:
    """(name, d_in, d_out, stack) of every tapped matmul, in forward
    order; none is stacked over layers, so every stack is ()."""
    out, c_in = [], sizes["channels"]
    for s, c in enumerate(sizes["stages"]):
        for j in range(sizes["convs_per_stage"]):
            out.append((f"conv{s}_{j}", 9 * c_in, c, ()))
            c_in = c
    h = sizes["img"] // sizes["pool"][0] ** len(sizes["stages"])
    w = sizes["img"] // sizes["pool"][1] ** len(sizes["stages"])
    out.append(("fc0", h * w * sizes["stages"][-1], sizes["fc_hidden"], ()))
    out.append(("fc1", sizes["fc_hidden"], sizes["n_classes"], ()))
    return out


def weight_path(name: str) -> str:
    return f"{name}/w"


def init(sizes: dict, key, dtype=jnp.float32) -> Dict[str, dict]:
    taps = layers(sizes)
    convs = [t for t in taps if t[0].startswith("conv")]
    keys = jax.random.split(key, len(convs) + 2)
    params = {}
    for k, (name, d_in, d_out, _) in zip(keys[:len(convs)], convs):
        params[name] = {
            "w": jax.random.normal(k, (d_in, d_out)) / jnp.sqrt(d_in),
            "b": jnp.zeros((d_out,)), "bn_s": jnp.ones((d_out,)),
            "bn_b": jnp.zeros((d_out,))}
    for k, (name, d_in, d_out, _) in zip(keys[-2:], taps[-2:]):
        params[name] = {"w": jax.random.normal(k, (d_in, d_out))
                        / jnp.sqrt(d_in), "b": jnp.zeros((d_out,))}
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), params)


def _patches(x):
    """(B, H, W, C) → (B, H, W, 9·C), feature c·9 + 3·i + j."""
    B, H, W, C = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    cols = [xp[:, i:i + H, j:j + W, :] for i in range(3) for j in range(3)]
    return jnp.stack(cols, axis=-1).reshape(B, H, W, 9 * C)


def _rows(x2d, n_stat):
    n = min(n_stat, x2d.shape[0])
    return jnp.pad(x2d[:n], ((0, n_stat - n), (0, 0)))


def _tapped(x2d, W, probe, n_stat, acts, name):
    """y = x W, recording the stats rows; ``probe`` (zeros) is added to
    the first rows so its gradient is ∂L/∂y there."""
    acts[name] = _rows(x2d, n_stat)
    y = x2d @ W
    n = min(n_stat, x2d.shape[0])
    return y.at[:n].add(probe[:n].astype(y.dtype))


def loss_and_stats(params, probes, batch, sizes: dict, n_stat: int):
    """(loss, acts) with acts[name] the (n_stat, d_in) input rows."""
    x, labels = batch
    dtype = params["fc0"]["w"].dtype
    h = x.astype(dtype)
    acts: Dict[str, jax.Array] = {}
    eps = sizes["bn_eps"]
    for s in range(len(sizes["stages"])):
        for j in range(sizes["convs_per_stage"]):
            name = f"conv{s}_{j}"
            p = params[name]
            pt = _patches(h)
            B, H, W, D = pt.shape
            y = _tapped(pt.reshape(B * H * W, D), p["w"], probes[name],
                        n_stat, acts, name)
            y = y.reshape(B, H, W, -1) + p["b"]
            mu = jnp.mean(y, axis=(0, 1, 2), keepdims=True)
            var = jnp.mean(jnp.square(y - mu), axis=(0, 1, 2), keepdims=True)
            y = (y - mu) / jnp.sqrt(var + eps) * p["bn_s"] + p["bn_b"]
            h = jnp.maximum(y, 0)
        ph, pw = sizes["pool"]
        B, H, W, C = h.shape
        h = h.reshape(B, H // ph, ph, W // pw, pw, C).max(axis=(2, 4))
    h = h.reshape(h.shape[0], -1)
    h = _tapped(h, params["fc0"]["w"], probes["fc0"], n_stat, acts, "fc0")
    h = jnp.maximum(h + params["fc0"]["b"], 0)
    logits = _tapped(h, params["fc1"]["w"], probes["fc1"], n_stat, acts,
                     "fc1") + params["fc1"]["b"]
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    return loss, acts
