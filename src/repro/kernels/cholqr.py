"""Pallas TPU kernels: CholeskyQR2 tall-skinny QR (paper Alg 3 line 3).

The last O(d) op of the Brand update still in XLA was the QR of the
(d, n) orthogonal-complement panel A⊥.  Householder QR is sequential in n
and maps poorly onto the MXU; the CholeskyQR2 iteration reformulates it
as two passes of

    G = AᵀA                 (n, n)   — batched SYRK, contraction over d
    R, B = clamped √G, √G⁻¹ (n, n)   — tiny spectral root, stays in XLA
    Q = A B                 (d, n)   — row-parallel apply

(Yamamoto et al.'s CholeskyQR² data flow; the second pass repairs the
first pass's loss of orthogonality).  The small factorization is a
*clamped spectral root* rather than a raw Cholesky: Gram eigenvalues
below the fp32 resolvability floor were already destroyed by rounding
when AᵀA was formed, and a Cholesky — shifted or not — either goes
negative there or renormalizes that noise into unit-norm garbage basis
vectors.  The clamp maps them to an exactly-null subspace instead, so
for *any* fp32 panel (A⊥ is near rank-deficient whenever incoming
directions already lie in span(U)) QᵀQ is a rank-k projector to machine
precision and Q R reconstructs the retained spectral content of A.

Both O(d·n²) passes are Pallas kernels with a leading stack axis B so a
whole bucket of panels runs as one batched launch; the (n, n) eigh-based
root is O(n³) on tiny operands and stays in XLA *between* the launches
(``ref.gram_inv_sqrt`` — shared verbatim with the oracle).

Kernel 1 (``_syrk_tn``): grid (B, d/bk); accumulates AᵀA in an (n, n)
float32 VMEM accumulator (n ≤ 1024 → ≤ 4 MB).

Kernel 2 (``_rinv_apply``): grid (B, d/bm); each row block reads its A
tile once, multiplies by the resident (n, n) R⁻¹ and writes Q.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

Array = jax.Array


def _syrk_tn_kernel(a_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[0]
    acc_ref[...] += jax.lax.dot_general(
        a, a, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _rinv_apply_kernel(a_ref, r_ref, o_ref):
    o_ref[0] = jnp.dot(a_ref[0], r_ref[0],
                       preferred_element_type=jnp.float32
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def syrk_tn_batched_pallas(A: Array, bk: int = 512,
                           interpret: bool = False) -> Array:
    """G = AᵀA in float32.  A: (B, d, n); d % bk == 0."""
    B, d, n = A.shape
    bk = min(bk, d)
    assert d % bk == 0, f"d={d} not divisible by bk={bk} (rows would drop)"
    grid = (B, d // bk)
    return pl.pallas_call(
        functools.partial(_syrk_tn_kernel, n_k=grid[1]),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bk, n), lambda b, k: (b, k, 0))],
        out_specs=pl.BlockSpec((1, n, n), lambda b, k: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(A)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def rinv_apply_batched_pallas(A: Array, Rinv: Array, bm: int = 512,
                              interpret: bool = False) -> Array:
    """Q = A @ R⁻¹.  A: (B, d, n), Rinv: (B, n, n); d % bm == 0."""
    B, d, n = A.shape
    bm = min(bm, d)
    assert d % bm == 0, f"d={d} not divisible by bm={bm} (rows would drop)"
    grid = (B, d // bm)
    return pl.pallas_call(
        _rinv_apply_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, n, n), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, n), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, d, n), A.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(A, Rinv)


def cholqr2_batched_pallas(A: Array, n_true: int | None = None,
                           bk: int = 512, interpret: bool = False
                           ) -> Tuple[Array, Array]:
    """(Q, R) = CholeskyQR2-style tall-skinny QR for a whole stack in one
    batched launch sequence — the same two-round schedule as the
    ``ref.cholqr2`` oracle (Gram SYRK → clamped spectral inverse root →
    apply, twice).  A: (B, d, n) float32.  ``n_true`` is accepted for
    call-site symmetry with the dispatch layer; the spectral floors are
    trace-/max-relative and therefore padding-invariant on their own.
    """
    del n_true
    G1 = syrk_tn_batched_pallas(A, bk=bk, interpret=interpret)
    R1, B1 = ref.gram_inv_sqrt(G1, ref.CHOLQR_FLOOR_RESOLVE, "tr")
    Q0 = rinv_apply_batched_pallas(A, B1, bm=bk, interpret=interpret)
    G2 = syrk_tn_batched_pallas(Q0, bk=bk, interpret=interpret)
    R2, B2 = ref.gram_inv_sqrt(G2, ref.CHOLQR_FLOOR_REFINE, "max")
    Q = rinv_apply_batched_pallas(Q0, B2, bm=bk, interpret=interpret)
    return Q, R2 @ R1


def cholqr2_pallas(A: Array, bk: int = 512, interpret: bool = False
                   ) -> Tuple[Array, Array]:
    """Single-panel entry point: (Q, R) = CholeskyQR2(A), A (d, n)."""
    Q, R = cholqr2_batched_pallas(A[None], bk=bk, interpret=interpret)
    return Q[0], R[0]
