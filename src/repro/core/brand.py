"""Brand's (2006) fast low-rank SVD/EVD modification — the paper's §2.3.

Implements:
  * ``brand_update``            — general (non-symmetric) Algorithm 2.
  * ``sym_brand_update``        — symmetric Algorithm 3 (the one K-FAC uses).
  * ``truncate``                — optimal rank-r truncation of a held (U, D).
  * ``ea_brand_step``           — one B-KFAC K-factor step (Alg 4 lines 2-7):
                                  truncate to r, then Brand-update with the
                                  incoming EA term  ρ·M + (1-ρ)·X Xᵀ.

Conventions
-----------
Eigenvalues are kept sorted *descending*.  A Brand state is a pair
``(U, D)`` with ``U ∈ R[d, m]`` column-orthonormal and ``D ∈ R[m]`` so that
the represented matrix is ``U @ diag(D) @ U.T``.  All functions are pure and
jit/vmap friendly (static shapes; rank changes are expressed by zero modes).

Stacked-native: the symmetric path (``sym_brand_update`` / ``ea_brand_step``
/ ``init_from_factor``) accepts arbitrary leading stack axes, so a whole
bucket of K-factors (scanned layers, MoE experts, cross-layer shape
classes) updates in one batched call.

The two O(d)-sized ops of the symmetric update — the projection panel
(C, A⊥) and the tall-skinny QR of A⊥ — go through ``kernels/ops.py``
(``brand_panel`` + ``cholqr2``: Pallas on TPU, the ``ref.py`` oracles
elsewhere); the remaining O((r+n)²) eigenproblem stays in XLA.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels.ref import mt as _mt
from repro.obs import trace as obs_trace

Array = jax.Array


def _desc_eigh(M: Array) -> Tuple[Array, Array]:
    """eigh with eigenvalues sorted descending. Returns (vals, vecs)."""
    vals, vecs = jnp.linalg.eigh(M)
    return vals[..., ::-1], vecs[..., :, ::-1]


def _batched_diag(D: Array) -> Array:
    """(..., r) → (..., r, r) diagonal matrices."""
    return jnp.eye(D.shape[-1], dtype=D.dtype) * D[..., None, :]


def truncate(U: Array, D: Array, r: int) -> Tuple[Array, Array]:
    """Optimal rank-r truncation: keep the r strongest modes.

    ``D`` is descending, so this is a slice. Shapes shrink — use only at
    trace time with static ``r``.
    """
    return U[..., :, :r], D[..., :r]


def brand_update(U: Array, D: Array, V: Array, A: Array, B: Array
                 ) -> Tuple[Array, Array, Array]:
    """General Brand update (paper Alg 2):  X̂ = U diag(D) Vᵀ + A Bᵀ.

    U: (m, r), V: (d, r), D: (r,), A: (m, n), B: (d, n).
    Returns (U', D', V') of ranks r+n (exact thin SVD of X̂).
    """
    # Project the update onto the current subspaces and their complements.
    UtA = _mt(U) @ A                                 # (r, n)
    VtB = _mt(V) @ B                                 # (r, n)
    A_perp = A - U @ UtA
    B_perp = B - V @ VtB
    Qa, Ra = jnp.linalg.qr(A_perp)                   # (m, n), (n, n)
    Qb, Rb = jnp.linalg.qr(B_perp)                   # (d, n), (n, n)
    # M_S = [[I, UtA],[0, Ra]] @ diag(D, I) @ [[I, VtB],[0, Rb]]ᵀ  (eq. 7)
    top = jnp.concatenate([_batched_diag(D) + UtA @ _mt(VtB),
                           UtA @ _mt(Rb)], axis=-1)
    bot = jnp.concatenate([Ra @ _mt(VtB), Ra @ _mt(Rb)], axis=-1)
    Ms = jnp.concatenate([top, bot], axis=-2)        # (r+n, r+n)
    Um, Dm, Vmt = jnp.linalg.svd(Ms)
    U_new = jnp.concatenate([U, Qa], axis=-1) @ Um
    V_new = jnp.concatenate([V, Qb], axis=-1) @ _mt(Vmt)
    return U_new, Dm, V_new


def sym_brand_update(U: Array, D: Array, A: Array) -> Tuple[Array, Array]:
    """Symmetric Brand update (paper Alg 3):  X̂ = U diag(D) Uᵀ + A Aᵀ.

    U: (*stack, d, r) column-orthonormal, D: (*stack, r) descending,
    A: (*stack, d, n).  Returns (U', D') with U' (…, d, r+n), D' (…, r+n)
    descending — the exact EVD of X̂ (X̂ is symmetric psd when D ≥ 0).

    Derivation: with C = UᵀA and A⊥ = A − UC = Q R,
        X̂ = [U Q] [[diag(D)+CCᵀ, CRᵀ],[RCᵀ, RRᵀ]] [U Q]ᵀ
    and the middle (r+n)² matrix is symmetric — one small eigh finishes it.

    The O(d·r·n) panel and the O(d·n²) tall-skinny QR run as batched
    launches (``brand_panel`` + CholeskyQR2), so the whole light update is
    linear in d with no XLA QR left.
    """
    with obs_trace.span("brand_panel"):
        C, A_perp = kops.brand_panel(U, A)           # (…, r, n), (…, d, n)
    with obs_trace.span("brand_qr"):
        Q, R = kops.cholqr2(A_perp)                  # (…, d, n), (…, n, n)
    with obs_trace.span("brand_core"):
        top = jnp.concatenate([_batched_diag(D) + C @ _mt(C), C @ _mt(R)],
                              axis=-1)
        bot = jnp.concatenate([R @ _mt(C), R @ _mt(R)], axis=-1)
        Ms = jnp.concatenate([top, bot], axis=-2)    # (…, r+n, r+n)
        Dm, Wm = _desc_eigh(Ms)
    with obs_trace.span("brand_rotate"):
        U_new = jnp.concatenate([U, Q], axis=-1) @ Wm    # (…, d, r+n)
    return U_new, Dm


def ea_brand_step(U: Array, D: Array, X: Array, rho: float, r: int
                  ) -> Tuple[Array, Array]:
    """One B-KFAC K-factor inverse-representation step (paper Alg 4).

    Held state (U, D) has rank r+n (from the previous step).  We truncate to
    the r strongest modes (paper §3.1 "Controlling the size"), then apply the
    symmetric Brand update with the incoming EA term:

        M ← ρ · trunc_r(U diag(D) Uᵀ) + (1-ρ) · X Xᵀ

    X: (*stack, d, n) — the incoming K-factor square root (activations or
    output-gradients, already transposed to column-sample layout).
    Returns (U', D') of rank r+n.
    """
    Ut, Dt = truncate(U, D, r)
    return sym_brand_update(Ut, rho * Dt, jnp.sqrt(1.0 - rho) * X)


def init_from_factor(X: Array, m: int) -> Tuple[Array, Array]:
    """Initialize a Brand state from the first factor M₀ = X Xᵀ without ever
    forming the d×d product (the low-memory property of §3.5).

    X: (*stack, d, n).  Returns (U, D) padded with zero modes to width ``m``
    so the state shape is static across steps.
    """
    d, n = X.shape[-2:]
    # Thin SVD of X gives the EVD of X Xᵀ: eigvecs = left singular vectors,
    # eigvals = singular values squared.
    Ux, s, _ = jnp.linalg.svd(X, full_matrices=False)  # (…, d, n), (…, n)
    D = s * s
    if n >= m:
        return Ux[..., :, :m], D[..., :m]
    stack = X.shape[:-2]
    pad_u = jnp.zeros(stack + (d, m - n), dtype=X.dtype)
    pad_d = jnp.zeros(stack + (m - n,), dtype=X.dtype)
    return (jnp.concatenate([Ux, pad_u], axis=-1),
            jnp.concatenate([D, pad_d], axis=-1))
