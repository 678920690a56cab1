"""Pallas TPU kernel: batched GEMM with a fused scale-and-add epilogue,
``out = alpha·C + beta·A B`` — the building block of the Newton–Schulz
inverse-refinement heavy path (Mode.NS).

One NS/Hotelling step  X ← X (2I − M̂ X) = 2X − X (M̂ X)  is two launches
of this kernel:

    T = M̂ X                  (alpha = 0, beta = 1; C rides along unused)
    X' = 2·X − X T            (alpha = 2, beta = −1, C = X)

Both are pure MXU matmuls — no eigh/qr/svd anywhere in the heavy firing,
which is the whole point of the NS variant.  The tiling is the ``ea_syrk``
pattern verbatim: grid (B, d/bm, d/bn, d/bk), float32 VMEM accumulator
over the k axis, epilogue fused into the last k step so C and the output
tile make exactly one HBM round-trip.  All operands carry a leading stack
axis B so a whole factor bucket refines in one launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


Array = jax.Array


def _gemm_update_kernel(alpha_ref, beta_ref, c_ref, a_ref, b_ref, o_ref,
                        acc_ref, *, n_k: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[0], b_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        alpha = alpha_ref[0]
        beta = beta_ref[0]
        out = alpha * c_ref[0].astype(jnp.float32) + beta * acc_ref[...]
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def gemm_update_batched_pallas(C: Array, A: Array, B: Array,
                               alpha, beta,
                               bm: int = 256, bn: int = 256, bk: int = 256,
                               interpret: bool = False) -> Array:
    """out = alpha·C + beta·A B.  C: (B, m, n), A: (B, m, k), B: (B, k, n);
    requires m % bm == n % bn == k % bk == 0 after the ops.py block pick
    (it pads / falls back otherwise).  ``alpha``/``beta`` are shared
    across the stack (the NS schedule is global)."""
    nb, m, kk = A.shape
    n = B.shape[-1]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, kk)
    grid = (nb, m // bm, n // bn, kk // bk)
    alpha = jnp.reshape(jnp.asarray(alpha), (1,)).astype(jnp.float32)
    beta = jnp.reshape(jnp.asarray(beta), (1,)).astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(_gemm_update_kernel, n_k=grid[3]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm, bn),
                             lambda b, i, j, k, *_: (b, i, j)),  # C tile
                pl.BlockSpec((1, bm, bk),
                             lambda b, i, j, k, *_: (b, i, k)),  # A rows
                pl.BlockSpec((1, bk, bn),
                             lambda b, i, j, k, *_: (b, k, j)),  # B cols
            ],
            out_specs=pl.BlockSpec((1, bm, bn),
                                   lambda b, i, j, k, *_: (b, i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((nb, m, n), C.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(alpha, beta, C, A, B)
