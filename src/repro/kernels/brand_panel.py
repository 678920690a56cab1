"""Pallas TPU kernels: the O(d·r·n) panel of Brand's symmetric update.

The linear-in-d cost of the B-update (paper Alg 3 lines 3-4) is exactly two
tall-skinny operations over the layer dimension d:

    C  = Uᵀ A            (r, n)   — contraction over d
    A⊥ = A − U C         (d, n)   — row-parallel over d

Everything else in the B-update is O((r+n)-sized) and stays in XLA.

All operands carry a leading stack axis B (scanned layers / MoE experts /
plain B=1) so a whole stack of panels is one batched launch.

Kernel 1 (``_ut_a``): grid (B, d/bk), accumulating the (r, n) product in a
float32 VMEM accumulator (r·n ≤ ~768·512 → ≤ 1.5 MB, fits VMEM comfortably).

Kernel 2 (``_a_perp``): grid (B, d/bm); each row block reads its U and A
tiles once and writes A⊥ — U's full width r rides along in VMEM.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


Array = jax.Array


def _ut_a_kernel(u_ref, a_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        u_ref[0], a_ref[0], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _a_perp_kernel(a_ref, u_ref, c_ref, o_ref):
    uc = jnp.dot(u_ref[0], c_ref[0],
                 preferred_element_type=jnp.float32)
    o_ref[0] = (a_ref[0].astype(jnp.float32) - uc).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def ut_a_batched_pallas(U: Array, A: Array, bk: int = 512,
                        interpret: bool = False) -> Array:
    """C = Uᵀ A.  U: (B, d, r), A: (B, d, n); d % bk == 0."""
    B, d, r = U.shape
    n = A.shape[-1]
    bk = min(bk, d)
    grid = (B, d // bk)
    return pl.pallas_call(
        functools.partial(_ut_a_kernel, n_k=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bk, r), lambda b, k: (b, k, 0)),
            pl.BlockSpec((1, bk, n), lambda b, k: (b, k, 0)),
        ],
        out_specs=pl.BlockSpec((1, r, n), lambda b, k: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, r, n), U.dtype),
        scratch_shapes=[pltpu.VMEM((r, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(U, A)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def a_perp_batched_pallas(A: Array, U: Array, C: Array, bm: int = 512,
                          interpret: bool = False) -> Array:
    """A⊥ = A − U C.  A: (B, d, n), U: (B, d, r), C: (B, r, n); d % bm == 0."""
    B, d, n = A.shape
    r = U.shape[-1]
    bm = min(bm, d)
    grid = (B, d // bm)
    return pl.pallas_call(
        _a_perp_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bm, r), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, r, n), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, n), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, d, n), A.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(A, U, C)


def brand_panel_batched_pallas(U: Array, A: Array, bk: int = 512,
                               interpret: bool = False
                               ) -> Tuple[Array, Array]:
    """(C, A⊥) = (UᵀA, A − U(UᵀA)) for a whole stack in one batched launch."""
    C = ut_a_batched_pallas(U, A, bk=bk, interpret=interpret)
    return C, a_perp_batched_pallas(A, U, C, bm=bk, interpret=interpret)


def ut_a_pallas(U: Array, A: Array, bk: int = 512,
                interpret: bool = False) -> Array:
    """Single-factor entry point: C = Uᵀ A."""
    return ut_a_batched_pallas(U[None], A[None], bk=bk,
                               interpret=interpret)[0]


def a_perp_pallas(A: Array, U: Array, C: Array, bm: int = 512,
                  interpret: bool = False) -> Array:
    """Single-factor entry point: A⊥ = A − U C."""
    return a_perp_batched_pallas(A[None], U[None], C[None], bm=bm,
                                 interpret=interpret)[0]


def brand_panel_pallas(U: Array, A: Array, bk: int = 512,
                       interpret: bool = False):
    """(C, A⊥) = (UᵀA, A − U(UᵀA)) — the full Brand panel."""
    C, P = brand_panel_batched_pallas(U[None], A[None], bk=bk,
                                      interpret=interpret)
    return C[0], P[0]
