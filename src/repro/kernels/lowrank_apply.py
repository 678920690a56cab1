"""Pallas TPU kernel: fused low-rank inverse application.

    Y = (X U) diag(s) Uᵀ + X/λ

with X (p, d), U (d, w), s (w,) = (D+λ)⁻¹ − 1/λ.  This is the inner loop of
K-FAC preconditioning with a low-rank K-factor representation (paper Alg 1
lines 15-17 and both factors of Alg 8).  Fusing the two tall-skinny matmuls
with the 1/λ residual path reads X once and never materializes the (p, w)
intermediate in HBM when w is small.

All operands carry a leading stack axis B (scanned layers / MoE experts /
plain B=1); the per-element s and 1/λ ride along indexed by the stack
coordinate, so a whole stack of applications is one batched launch.

Stage A (``_xu``): T = (X U)·diag(s), grid (B, p/bm, d/bk) accumulating over
d.  Stage B (``_tut``): Y = T Uᵀ + X/λ, grid (B, p/bm, d/bn) — row blocks of
T ride along; s applied in stage A so stage B is a plain matmul + epilogue.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


Array = jax.Array


def _xu_kernel(x_ref, u_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[0], u_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[0] = (acc_ref[...] * s_ref[0].astype(jnp.float32)
                    ).astype(o_ref.dtype)


def _tut_kernel(ilam_ref, t_ref, u_ref, x_ref, o_ref):
    b = pl.program_id(0)
    acc = jax.lax.dot_general(
        t_ref[0], u_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ilam = ilam_ref[b]
    o_ref[0] = (acc + ilam * x_ref[0].astype(jnp.float32)
                ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def lowrank_apply_batched_pallas(X: Array, U: Array, s: Array, ilam: Array,
                                 bm: int = 256, bn: int = 512, bk: int = 512,
                                 interpret: bool = False) -> Array:
    """Y = (X U) diag(s) Uᵀ + X·ilam, batched over the leading stack axis.

    X: (B, p, d), U: (B, d, w), s: (B, w), ilam: (B,) (= 1/λ per element).
    s enters the kernel as (B, 1, w) rows: a (1, w) block of a (B, w)
    array is refused by Mosaic for B > 1 (second-minor block of 1).
    """
    B, p, d = X.shape
    w = U.shape[-1]
    bm, bn, bk = min(bm, p), min(bn, d), min(bk, d)
    ilam = jnp.reshape(ilam, (B,)).astype(jnp.float32)
    s = jnp.reshape(s, (B, 1, w))

    # Stage A: T = (X U) * s  — contraction over d (no scalars needed).
    grid_a = (B, p // bm, d // bk)
    T = pl.pallas_call(
        functools.partial(_xu_kernel, n_k=grid_a[2]),
        grid=grid_a,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda b, i, k: (b, i, k)),
            pl.BlockSpec((1, bk, w), lambda b, i, k: (b, k, 0)),
            pl.BlockSpec((1, 1, w), lambda b, i, k: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, w), lambda b, i, k: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, p, w), X.dtype),
        scratch_shapes=[pltpu.VMEM((bm, w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(X, U, s)

    # Stage B: Y = T Uᵀ + X·ilam.
    grid_b = (B, p // bm, d // bn)
    return pl.pallas_call(
        _tut_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid_b,
            in_specs=[
                pl.BlockSpec((1, bm, w), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, bn, w), lambda b, i, j, *_: (b, j, 0)),
                pl.BlockSpec((1, bm, bn), lambda b, i, j, *_: (b, i, j)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn),
                                   lambda b, i, j, *_: (b, i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, p, d), X.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(ilam, T, U, X)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def lowrank_apply_pallas(X: Array, U: Array, s: Array, lam: Array,
                         bm: int = 256, bn: int = 512, bk: int = 512,
                         interpret: bool = False) -> Array:
    """Single-factor entry point: Y = (X U) diag(s) Uᵀ + X/λ."""
    ilam = 1.0 / jnp.reshape(lam, (1,)).astype(jnp.float32)
    return lowrank_apply_batched_pallas(X[None], U[None], s[None], ilam,
                                        bm=bm, bn=bn, bk=bk,
                                        interpret=interpret)[0]
