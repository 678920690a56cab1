"""Program side of the toy stacked cell, which only the benchmark's
tests run (it is in no ``BENCHMARK.json``): a residual MLP whose blocks
are one ``lax.scan`` over stacked weights, each block's two matmuls
tapped with ``TapInfo(stack=(layers,))``, and an unstacked head.  At its
sizes (width 16, hidden 10, r=4, n_stat=8) the 16-wide factors take the
Brand update and the 10-wide ones the rank-r factor from M (RSVD),
stacked and unstacked alike, so both share a bucket across taps."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.kfac import TapInfo
from repro.models import layers


def build_model(sizes: dict, n_stat: int):
    """(init, loss_fn, taps) of the program at these sizes."""
    d, h = int(sizes["width"]), int(sizes["hidden"])
    n_layers, c = int(sizes["layers"]), int(sizes["classes"])
    taps = {"up": TapInfo("blocks/up", d, h, stack=(n_layers,),
                          n_stat=n_stat),
            "down": TapInfo("blocks/down", h, d, stack=(n_layers,),
                            n_stat=n_stat),
            "head": TapInfo("head/w", d, c, n_stat=n_stat)}

    def init(key):
        ku, kd, kh = jax.random.split(key, 3)
        return {"blocks": {
                    "up": jax.random.normal(ku, (n_layers, d, h))
                    / jnp.sqrt(d),
                    "down": jax.random.normal(kd, (n_layers, h, d))
                    / jnp.sqrt(h)},
                "head": {"w": jax.random.normal(kh, (d, c)) / jnp.sqrt(d),
                         "b": jnp.zeros((c,))}}

    def loss_fn(params, probes, batch):
        x, labels = batch

        def block(hh, xs):
            w_up, w_down, p_up, p_down = xs
            u, a_up = layers.tapped_matmul(w_up, hh, p_up, n_stat)
            v, a_down = layers.tapped_matmul(w_down, jnp.tanh(u), p_down,
                                             n_stat)
            return hh + v, {"up": a_up, "down": a_down}

        blocks = params["blocks"]
        hh, acts = jax.lax.scan(block, x, (blocks["up"], blocks["down"],
                                           probes["up"], probes["down"]))
        logits, acts["head"] = layers.tapped_matmul(
            params["head"]["w"], hh, probes["head"], n_stat)
        logp = jax.nn.log_softmax(logits + params["head"]["b"])
        loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
        return loss, acts

    return init, loss_fn, taps


def data_shape(sizes: dict) -> dict:
    return {"width": sizes["width"], "classes": sizes["classes"]}


def n_tokens(data: dict) -> int:
    return int(data["batch"])


def model_flops(sizes: dict, taps: dict, data: dict) -> float:
    """Every tapped matmul of every layer, forward and backward."""
    rows = int(data["batch"])
    return 3.0 * sum(2.0 * rows * t.d_in * t.d_out * math.prod(t.stack)
                     for t in taps.values())
