"""Inverse application of low-rank K-factor representations to gradients.

Paper Alg 1 (lines 14-18) — quadratic application:
    M = J V_A [(D_A+λI)⁻¹ − (1/λ)I] V_Aᵀ + (1/λ) J
    S = V_Γ [(D_Γ+λI)⁻¹ − (1/λ)I] V_Γᵀ M + (1/λ) M
i.e. (U diag(D) Uᵀ + λI)⁻¹ applied exactly on the span and as (1/λ)I off it.

Paper Alg 8 (§5, left as future work there — implemented here) — linear
application for layers where the per-step sample count n_M < d: precondition
the gradient *factors* (A, G with Mat(g)=G Aᵀ) and only then multiply.

Paper §3.5 spectrum continuation: before inverting, shift the retained
spectrum down by its smallest retained eigenvalue and fold that amount into
λ — overestimating the missing tail gives more conservative steps.

Every function here is stacked-native: operands may carry arbitrary leading
stack axes (scanned layers / MoE experts) with per-element λ, so stacked
taps run as single batched kernel launches instead of vmapped 2D fallbacks.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels.ref import mt as _mt

Array = jax.Array


def spectrum_continuation(D: Array, lam: Array) -> Tuple[Array, Array]:
    """λ ← λ + min D, D ← D − (min D)  (paper §3.5).

    min is over the *retained* (positive) modes so zero-padded static-width
    states (RSVD pad_to) get the same treatment as fully-populated Brand
    states — otherwise the continuation would act on B-variants only and
    bias the inverse comparison.  D: (..., w), lam: scalar or (...,).
    """
    pos = D > 0
    dmin = jnp.min(jnp.where(pos, D, jnp.inf), axis=-1)
    dmin = jnp.where(jnp.isfinite(dmin), dmin, 0.0)
    return jnp.maximum(D - dmin[..., None], 0.0), lam + dmin


def damping_from_spectrum(D: Array, phi: Array) -> Array:
    """Paper §6: λ = φ_λ · λ_max where λ_max is the largest (approximate)
    eigenvalue of the represented K-factor.  D: (..., w) → λ: (...,)."""
    return phi * jnp.maximum(jnp.max(D, axis=-1), 1e-12)


#: floor for λ in the inverse-diagonal split.  The decomposition
#: (D+λ)⁻¹ − 1/λ (+ J/λ off-span) divides by λ itself, so an undamped
#: config (φ = 0) or a fully-clamped spectrum (max D = 0 → λ = 0) would
#: emit inf/NaN that propagates silently through the whole application.
#: Flooring λ keeps the limit exact where it is finite: on the span the
#: diagonal tends to D⁻¹ − 1/λ_eps which recombines with the 1/λ_eps
#: off-span term to plain D⁻¹, and rank-deficient directions get the
#: (huge but finite) 1/λ_eps instead of inf.
_LAM_EPS = 1e-12


def lowrank_inv_diag(D: Array, lam: Array) -> Array:
    """The diagonal (D+λ)⁻¹ − 1/λ used on the span (negative values —
    it *removes* the over-counted 1/λ there).  lam broadcasts over the
    trailing mode axis.  λ is floored at ``_LAM_EPS`` (see above); D+λ is
    floored too so a clamped-to-zero mode cannot divide by zero."""
    lam = jnp.maximum(jnp.asarray(lam), _LAM_EPS)[..., None]
    return 1.0 / jnp.maximum(D + lam, _LAM_EPS) - 1.0 / lam


def _lam_safe(lam: Array) -> Array:
    """The same λ floor for the off-span J/λ term — every caller pairing
    ``lowrank_inv_diag`` with a 1/λ residual must divide by the *same*
    floored λ or the split stops telescoping."""
    return jnp.maximum(jnp.asarray(lam), _LAM_EPS)


def apply_inv_right(J: Array, U: Array, D: Array, lam: Array) -> Array:
    """J @ (U diag(D) Uᵀ + λI)⁻¹  — right application (A-side).

    J: (..., p, d), U: (..., d, w).  O(p·d·w): two tall-skinny matmuls +
    rank-1 work, fused in ``kops.lowrank_apply``.
    """
    lam = _lam_safe(lam)
    return kops.lowrank_apply(J, U, lowrank_inv_diag(D, lam), lam)


def apply_inv_left(J: Array, U: Array, D: Array, lam: Array) -> Array:
    """(U diag(D) Uᵀ + λI)⁻¹ @ J — left application (Γ-side).
    J: (..., d, p)."""
    return _mt(apply_inv_right(_mt(J), U, D, lam))


def kfac_precondition(J: Array,
                      U_g: Array, D_g: Array, lam_g: Array,
                      U_a: Array, D_a: Array, lam_a: Array,
                      dense_g: bool = False, dense_a: bool = False) -> Array:
    """Full quadratic application (Alg 1): S = Γ̄⁻¹ J Ā⁻¹.

    J is the layer gradient in matrix form (d_out, d_in) = Mat(g);
    Γ̄ is (d_out, d_out), Ā is (d_in, d_in).

    The two-sided application dispatches to the fused path (one launch
    sequence, J resident, no transposes, no HBM intermediate) instead of
    two ``lowrank_apply`` round-trips.

    ``dense_g``/``dense_a`` mark NS-mode factors: U on that side *is* the
    dense damped inverse (U ≈ (M + λ̂I)⁻¹, symmetric), so the application
    is a plain GEMM and the (D, λ) arguments on that side are ignored —
    λ̂ was baked in at the NS refresh.
    """
    if dense_g or dense_a:
        M = J @ U_a if dense_a else apply_inv_right(J, U_a, D_a, lam_a)
        return U_g @ M if dense_g else apply_inv_left(M, U_g, D_g, lam_g)
    lam_g, lam_a = _lam_safe(lam_g), _lam_safe(lam_a)
    return kops.precond_fused(J,
                              U_g, lowrank_inv_diag(D_g, lam_g), lam_g,
                              U_a, lowrank_inv_diag(D_a, lam_a), lam_a)


def kfac_precondition_linear(G: Array, A: Array,
                             U_g: Array, D_g: Array, lam_g: Array,
                             U_a: Array, D_a: Array, lam_a: Array,
                             dense_g: bool = False, dense_a: bool = False
                             ) -> Array:
    """Alg 8 — linear-in-d application from gradient factors.

    The layer gradient is Mat(g) = G Aᵀ with G (d_out, n), A (d_in, n)
    (n = per-step samples).  Precondition each factor then contract:

        S = (Γ̄⁻¹ G) (Aᵀ Ā⁻¹)        — O(r·d·n) instead of O(r·d²).

    Only beneficial (and only used) when n < d (paper's applicability
    condition; holds for FC layers with n = batch).  ``dense_g``/
    ``dense_a`` as in ``kfac_precondition`` (NS sides apply by GEMM).
    """
    Gp = (U_g @ G if dense_g
          else apply_inv_left(G, U_g, D_g, lam_g))
    Ap = (_mt(A) @ U_a if dense_a
          else apply_inv_right(_mt(A), U_a, D_a, lam_a))
    return Gp @ Ap


def _damped(D: Array, phi: Array, continuation: bool
            ) -> Tuple[Array, Array]:
    """Per-element λ from the spectrum, plus the §3.5 continuation shift."""
    lam = damping_from_spectrum(D, phi)
    if continuation:
        D, lam = spectrum_continuation(D, lam)
    return D, lam


def precondition_with_damping(J: Array,
                              U_g: Array, D_g: Array,
                              U_a: Array, D_a: Array,
                              phi: Array, *,
                              continuation: bool = True,
                              dense_g: bool = False,
                              dense_a: bool = False) -> Array:
    """Damping + spectrum continuation + full quadratic application for a
    whole (possibly stacked) tap in one call.

    J: (*stack, d_out, d_in); U/D stacked alike; per-element λ is derived
    from each element's spectrum.  This is the entry point the optimizer
    uses — stacked taps become one batched fused kernel launch.

    A ``dense_*`` (NS-mode) side skips damping/continuation entirely: its
    U is already the inverse of the damped factor (λ̂ = ns_phi·λ_max baked
    in at the heavy refresh, D carries metadata rather than a spectrum),
    so deriving λ from D here would be meaningless.
    """
    lam_a = lam_g = jnp.asarray(1.0)
    if not dense_a:
        D_a, lam_a = _damped(D_a, phi, continuation)
    if not dense_g:
        D_g, lam_g = _damped(D_g, phi, continuation)
    return kfac_precondition(J, U_g, D_g, lam_g, U_a, D_a, lam_a,
                             dense_g=dense_g, dense_a=dense_a)


def precondition_linear_with_damping(G: Array, A: Array,
                                     U_g: Array, D_g: Array,
                                     U_a: Array, D_a: Array,
                                     phi: Array, *,
                                     continuation: bool = True,
                                     dense_g: bool = False,
                                     dense_a: bool = False) -> Array:
    """Damping + continuation + Alg-8 linear application (from gradient
    factors) — the linear-apply counterpart of precondition_with_damping.
    ``dense_*`` sides (NS) skip damping, as in the quadratic entry point."""
    lam_a = lam_g = jnp.asarray(1.0)
    if not dense_a:
        D_a, lam_a = _damped(D_a, phi, continuation)
    if not dense_g:
        D_g, lam_g = _damped(D_g, phi, continuation)
    return kfac_precondition_linear(G, A, U_g, D_g, lam_g,
                                    U_a, D_a, lam_a,
                                    dense_g=dense_g, dense_a=dense_a)


def dense_inv_apply(J: Array, M_g: Array, lam_g: Array,
                    M_a: Array, lam_a: Array) -> Array:
    """O(d³) dense-solve application (K-FAC reference path, tests/bench)."""
    d_out, d_in = J.shape
    A = M_a + lam_a * jnp.eye(d_in, dtype=J.dtype)
    Gm = M_g + lam_g * jnp.eye(d_out, dtype=J.dtype)
    return jnp.linalg.solve(Gm, jnp.linalg.solve(A, J.T).T)
