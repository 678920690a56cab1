"""Pallas TPU kernel: EA K-factor SYRK update  M ← keep·M + coef·X Xᵀ.

This is the per-stats-step hot spot of every K-FAC variant that materializes
the dense EA factor (EVD / RSVD / B-R / B-C modes).  On TPU the natural
mapping is an MXU-tiled SYRK with the EA decay fused into the epilogue so M
is read and written exactly once (one HBM round-trip instead of three for
the naive  ρ·M  then  + (1-ρ)·X Xᵀ  sequence).

All operands carry a leading stack axis B (scanned layers / MoE experts /
plain B=1) so a whole stack of factors updates in one launch instead of a
vmap of per-layer launches.

Grid: (B, d/bm, d/bn, n/bk).  The k axis accumulates partial X Xᵀ products
in a float32 VMEM accumulator; on the last k step the decayed M tile is
added and the tile is written out.  Block dims are 128-aligned for the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


Array = jax.Array


def _ea_syrk_kernel(keep_ref, coef_ref, m_ref, xi_ref, xj_ref, o_ref,
                    acc_ref, *, n_k: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        xi_ref[0], xj_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        keep = keep_ref[0]
        coef = coef_ref[0]
        out = keep * m_ref[0].astype(jnp.float32) + coef * acc_ref[...]
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def ea_syrk_batched_pallas(M: Array, X: Array, keep: Array, coef: Array,
                           bm: int = 256, bn: int = 256, bk: int = 256,
                           interpret: bool = False) -> Array:
    """M: (B, d, d), X: (B, d, n); requires d % bm == d % bn == 0 and
    n % bk == 0 after the ops.py block pick (it pads / falls back
    otherwise).  ``keep``/``coef`` are shared across the stack (the EA
    schedule is global)."""
    B, d, n = X.shape
    bm, bn, bk = min(bm, d), min(bn, d), min(bk, n)
    grid = (B, d // bm, d // bn, n // bk)
    keep = jnp.reshape(keep, (1,)).astype(jnp.float32)
    coef = jnp.reshape(coef, (1,)).astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(_ea_syrk_kernel, n_k=grid[3]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm, bn),
                             lambda b, i, j, k, *_: (b, i, j)),  # M tile
                pl.BlockSpec((1, bm, bk),
                             lambda b, i, j, k, *_: (b, i, k)),  # X rows
                pl.BlockSpec((1, bn, bk),
                             lambda b, i, j, k, *_: (b, j, k)),  # X cols
            ],
            out_specs=pl.BlockSpec((1, bm, bn),
                                   lambda b, i, j, k, *_: (b, i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, d, d), M.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(keep, coef, M, X, X)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def ea_syrk_pallas(M: Array, X: Array, keep: Array, coef: Array,
                   bm: int = 256, bn: int = 256, bk: int = 256,
                   interpret: bool = False) -> Array:
    """Single-factor entry point: M (d, d), X (d, n)."""
    return ea_syrk_batched_pallas(M[None], X[None], keep, coef,
                                  bm=bm, bn=bn, bk=bk, interpret=interpret)[0]
