"""EA K-factor state and its update modes — the heart of the paper.

A K-factor is the exponential average  M_k = ρ M_{k-1} + (1-ρ) X_k X_kᵀ
(paper eq. 5/8).  Every optimizer variant in the paper is a choice of how the
*inverse representation* of M is maintained:

  mode        holds M?   update of (U, D)                         paper
  ----------  ---------  ---------------------------------------  -------
  EVD         yes        dense eigh of M every T_inv              K-FAC
  RSVD        yes        rsvd_psd(M) every T_inv                  R-KFAC
  BRAND       no         ea_brand_step every T_brand              B-KFAC
  BRAND_RSVD  yes        Brand every T_brand + RSVD overwrite     B-R-KFAC
                         every T_rsvd
  BRAND_CORR  yes        Brand every T_brand + light correction   B-KFAC-C
                         (Alg 6) every T_corct
  NS          yes        Newton–Schulz refinement of the held     NS-KFAC
                         dense inverse every T_inv (matmul-only)  (§iter.)

The state is a pytree with static shapes so it can live inside a jitted,
sharded train step and be vmapped across scan-stacked layers / experts.
``width`` (the number of held modes) is r + n_stat for Brand-family modes,
d for NS (U holds the dense refined inverse) and r for RSVD/EVD modes —
always static.
"""
from __future__ import annotations

import dataclasses
import enum
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import brand, rsvd
from repro.obs import trace as obs_trace

Array = jax.Array


class Mode(enum.Enum):
    EVD = "evd"                # K-FAC baseline
    RSVD = "rsvd"              # R-KFAC (RS-KFAC of [3])
    BRAND = "brand"            # B-KFAC  (pure; low-memory, M never formed)
    BRAND_RSVD = "brand_rsvd"  # B-R-KFAC
    BRAND_CORR = "brand_corr"  # B-KFAC-C
    NS = "ns"                  # NS-KFAC (Newton–Schulz inverse refinement)


# Modes that must materialize the dense d×d EA factor.
_NEEDS_M = {Mode.EVD, Mode.RSVD, Mode.BRAND_RSVD, Mode.BRAND_CORR, Mode.NS}
# Modes that run the Brand online update.
_HAS_BRAND = {Mode.BRAND, Mode.BRAND_RSVD, Mode.BRAND_CORR}


#: Channels of :attr:`KFactorState.aux` — per-slot heavy-op diagnostics.
#: Purely observational: nothing in the optimizer math ever reads them
#: (NS bakes λ̂ into U; the low-rank apply derives λ from D), so zeroing
#: aux changes no update.  They exist so telemetry (repro.obs) and tests
#: can watch inverse health without smuggling scalars through D.
AUX_LAM = 0     # NS: λ̂ = ns_phi·λ_max(M) used at the last refresh
AUX_RES = 1     # NS: final Frobenius residual ‖I − M̂X‖_F (≥ _NS_RES_MAX
                # flags that the dense-solve fallback fired)
AUX_TRUNC = 2   # EVD/RSVD overwrites: truncated spectral-mass fraction
                # max(0, tr M − Σ retained D) / tr M
AUX_WIDTH = 3


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KFactorState:
    """Inverse representation of one EA K-factor.

    U: (d, width) column-orthonormal basis; D: (width,) descending eigvals
    (NS: U is the dense damped inverse and D is all-zero).
    M: (d, d) dense EA factor or a (1, 1) placeholder for pure-Brand.
    aux: (AUX_WIDTH,) heavy-op diagnostics (see the AUX_* channels above);
    never read by the update math.
    """
    U: Array
    D: Array
    M: Array
    aux: Array


def make_state(d: int, width: int, needs_m: bool, dtype=jnp.float32
               ) -> KFactorState:
    m_shape = (d, d) if needs_m else (1, 1)
    return KFactorState(
        U=jnp.zeros((d, width), dtype),
        D=jnp.zeros((width,), dtype),
        M=jnp.zeros(m_shape, dtype),
        aux=jnp.zeros((AUX_WIDTH,), dtype),
    )


@dataclasses.dataclass(frozen=True)
class KFactorSpec:
    """Static description of one K-factor's update policy."""
    d: int                      # side of the factor
    r: int                      # truncation / target rank
    n_stat: int                 # incoming factor columns per stats step
    mode: Mode
    rho: float = 0.95
    r_o: int = 10               # RSVD oversampling
    n_pwr_iter: int = 2
    n_crc: int = 0              # correction subspace size (BRAND_CORR)
    ns_iters: int = 8           # Newton–Schulz steps per heavy firing (NS)
    ns_phi: float = 0.1         # NS damping ratio λ̂ = ns_phi·λ_max(M)
    ns_guard: float = 0.9       # warm-start guard: ‖I − M̂X₀‖₂ must sit below

    @property
    def width(self) -> int:
        if self.mode is Mode.NS:
            return self.d       # U holds the dense refined inverse
        if self.mode in _HAS_BRAND:
            return min(self.r + self.n_stat, self.d)
        return min(self.r, self.d)

    @property
    def needs_m(self) -> bool:
        return self.mode in _NEEDS_M

    def init(self, dtype=jnp.float32) -> KFactorState:
        return make_state(self.d, self.width, self.needs_m, dtype)


# ---------------------------------------------------------------------------
# individual update operations (all pure; X is (d, n_stat))
# ---------------------------------------------------------------------------

def ea_update_m(M: Array, X: Array, rho: float, first: Array) -> Array:
    """M ← ρ M + (1-ρ) X Xᵀ  (κ(0)=1 on the first-ever update, eq. 5).
    Stacked-native: M (*stack, d, d), X (*stack, d, n)."""
    upd = X @ jnp.swapaxes(X, -1, -2)
    coef = jnp.where(first, 1.0, 1.0 - rho)
    keep = jnp.where(first, 0.0, rho)
    return keep * M + coef * upd


def ea_update_m_kernel(M: Array, X: Array, rho: float, first: Array) -> Array:
    """Same as ea_update_m but routed through the Pallas EA-SYRK kernel when
    shapes are tile-friendly (ops.py pads/falls back otherwise).  Stacked
    inputs run as one batched launch over the flattened stack."""
    from repro.kernels import ops as kops
    return kops.ea_syrk(M, X, rho, first)


def ea_update_m_rows(M_rows: Array, X: Array, r0, rb: int, rho: float,
                     first: Array) -> Array:
    """Row block [r0, r0+rb) of the EA absorb — *exact*, not approximate:
    every element of X Xᵀ is an independent full-length dot product (no
    reduction is split), so the row slice of :func:`ea_update_m` equals the
    update of the row slice.  This is what lets the 2D-mesh curvature
    engine keep the dense M row-sharded through stats steps and only
    gather it transiently when a heavy op needs the full matrix.

    M_rows: (*stack, rb, d) local row block; X: (*stack, d, n) — full,
    every row-shard holds the whole incoming panel (it is O(d·n), the
    cheap side); ``r0`` may be traced (e.g. ``axis_index * rb``), ``rb``
    is static.  Coefficients mirror ``kernels.ref.ea_syrk`` exactly."""
    X_rows = jax.lax.dynamic_slice_in_dim(X, r0, rb, axis=X.ndim - 2)
    rho = jnp.asarray(rho, M_rows.dtype)
    firstf = jnp.asarray(first, M_rows.dtype)
    keep = rho * (1.0 - firstf)
    coef = 1.0 - keep
    upd = (X_rows @ jnp.swapaxes(X, -1, -2)).astype(M_rows.dtype)
    return keep * M_rows + coef * upd


def brand_step(spec: KFactorSpec, st: KFactorState, X: Array, first: Array
               ) -> KFactorState:
    """B-update (Alg 4): truncate to r then symmetric Brand with the EA term.

    Stacked-native: st/X may carry leading stack axes (``first`` is the
    global scalar flag) — a whole bucket of Brand factors steps as one
    batched panel + CholeskyQR2 + eigh (see ``brand.sym_brand_update``).

    On the first-ever stats batch the state is empty — initialize from the
    factor directly (exact, low-memory)."""
    def _init(_):
        U0, D0 = brand.init_from_factor(X, spec.width)
        return KFactorState(U=U0, D=D0, M=st.M, aux=st.aux)

    def _update(_):
        U, D = brand.ea_brand_step(st.U, st.D, X, spec.rho, spec.r)
        if U.shape[-1] > spec.width:  # r + n_stat exceeded d: re-truncate
            U, D = U[..., :, :spec.width], D[..., :spec.width]
        return KFactorState(U=U, D=D, M=st.M, aux=st.aux)

    return jax.lax.cond(first, _init, _update, operand=None)


def _trunc_mass_aux(aux: Array, M: Array, D: Array) -> Array:
    """AUX_TRUNC ← truncated spectral-mass fraction of an overwrite:
    max(0, tr M − Σ retained D) / tr M — the paper's accuracy knob (rank
    truncation) made observable.  Diagnostic only; never read back."""
    tr = jnp.trace(M, axis1=-2, axis2=-1)
    kept = jnp.sum(D, axis=-1)
    frac = jnp.maximum(tr - kept, 0.0) / jnp.maximum(tr, 1e-30)
    return aux.at[..., AUX_TRUNC].set(frac.astype(aux.dtype))


def rsvd_overwrite(spec: KFactorSpec, st: KFactorState, key: Array
                   ) -> KFactorState:
    """RSVD of the dense EA factor → overwrite the low-rank state
    (R-KFAC inverse update / B-R-KFAC overwrite)."""
    U, D = rsvd.rsvd_psd(st.M, spec.r, spec.r_o, key, spec.n_pwr_iter,
                         pad_to=spec.width)
    return KFactorState(U=U, D=D, M=st.M,
                        aux=_trunc_mass_aux(st.aux, st.M, D))


def evd_overwrite(spec: KFactorSpec, st: KFactorState) -> KFactorState:
    """Dense EVD of the EA factor (K-FAC baseline inverse update)."""
    U, D = rsvd.exact_evd(st.M, r=spec.width, pad_to=spec.width)
    return KFactorState(U=U, D=D, M=st.M,
                        aux=_trunc_mass_aux(st.aux, st.M, D))


def light_correction(spec: KFactorSpec, st: KFactorState, key: Array
                     ) -> KFactorState:
    """Alg 6: re-solve the eigenproblem of M in a random n_crc-column
    subspace of U and patch those columns/eigenvalues in place.

    Correction reads the *dense* M (needs_m mode).  Columns are chosen among
    the first r (the post-truncation basis), uniformly without replacement —
    the paper argues random beats top-modes (§3.4).
    """
    n_crc = spec.n_crc
    idx = jax.random.choice(key, spec.r, shape=(n_crc,), replace=False)
    Usub = st.U[:, idx]                               # (d, n_crc)
    Ms = Usub.T @ (st.M @ Usub)                       # (n_crc, n_crc)
    Ms = 0.5 * (Ms + Ms.T)
    vals, vecs = jnp.linalg.eigh(Ms)
    vals, vecs = vals[::-1], vecs[:, ::-1]
    U_new = st.U.at[:, idx].set(Usub @ vecs)
    D_new = st.D.at[idx].set(vals)
    return KFactorState(U=U_new, D=D_new, M=st.M, aux=st.aux)


_NS_PWR_ITERS = 12   # power-iteration steps for the λ_max(M) prescale
_NS_RES_MAX = 0.5    # Frobenius residual past which a slot falls back


def _ns_sym(x: Array) -> Array:
    return 0.5 * (x + jnp.swapaxes(x, -1, -2))


def _ns_lmax(M: Array) -> Array:
    """λ_max estimate of a symmetric psd M (*stack, d, d) → (*stack,) by
    deterministic power iteration (matmul-only; Rayleigh quotient).  The
    deterministic all-ones start keeps the heavy firing key-free and
    reproducible across replicated/sharded runs; an adversarial M exactly
    orthogonal to it would underestimate, which the residual fallback in
    ``ns_overwrite`` catches."""
    d = M.shape[-1]
    v0 = jnp.full(M.shape[:-1] + (1,), 1.0 / jnp.sqrt(d), M.dtype)

    def body(_, v):
        w = M @ v
        nrm = jnp.sqrt(jnp.sum(w * w, axis=(-2, -1), keepdims=True))
        return w / jnp.maximum(nrm, 1e-30)

    # rolled loop (not unrolled python): the iteration body is traced
    # once, keeping the heavy firing's XLA graph — and compile time —
    # independent of the iteration count
    v = jax.lax.fori_loop(0, _NS_PWR_ITERS, body, v0)
    return jnp.sum(v * (M @ v), axis=(-2, -1))


def _ns_resnorm(R: Array, iters: int = 8) -> Array:
    """Spectral-norm estimate ‖R‖₂ of (*stack, d, d) → (*stack,) by power
    iteration on RᵀR (matmul-only)."""
    d = R.shape[-1]
    Rt = jnp.swapaxes(R, -1, -2)
    v0 = jnp.full(R.shape[:-1] + (1,), 1.0 / jnp.sqrt(d), R.dtype)

    def body(_, v):
        w = Rt @ (R @ v)
        nrm = jnp.sqrt(jnp.sum(w * w, axis=(-2, -1), keepdims=True))
        return w / jnp.maximum(nrm, 1e-30)

    v = jax.lax.fori_loop(0, iters, body, v0)
    w = R @ v
    return jnp.sqrt(jnp.sum(w * w, axis=(-2, -1)))


def ns_overwrite(spec: KFactorSpec, st: KFactorState) -> KFactorState:
    """Newton–Schulz heavy refresh (Mode.NS): refine X ≈ M̂⁻¹ = (M + λ̂I)⁻¹
    with ``spec.ns_iters`` Hotelling steps X ← X(2I − M̂X) — pure GEMMs via
    ``kops.ns_step``, no eigh/qr/svd anywhere in the firing.

    Prescale and warm start (the convergence safeguard, part 1):
      * λ̂ = ns_phi · λ_max(M) from a matmul-only power iteration, so
        κ(M̂) ≤ (1 + ns_phi)/ns_phi regardless of M's conditioning;
      * warm start from the stale inverse held in U when its estimated
        residual ‖I − M̂ U‖₂ clears ``ns_guard``; otherwise cold-start from
        α·I with α = 2/(λ_max + 2λ̂), which puts the eigenvalues of αM̂ in
        (0, 2) and the initial residual at ≈ (κ−1)/(κ+1) < 1.  Either way
        the quadratic contraction r ← r² converges well within K = 8 at
        the default ns_phi = 0.1.

    Divergence fallback (part 2): if any slot's final Frobenius residual
    ‖I − M̂X‖_F fails to clear ``_NS_RES_MAX`` (NaN/Inf included — the
    comparison is written to catch them), a dense LU solve replaces that
    slot (``jnp.linalg.inv`` — still factorization-of-last-resort only, and
    still eigh/qr/svd-free).  The solve sits under ``lax.cond`` so healthy
    steps never pay for it, and a per-slot ``where`` inside keeps converged
    slots' NS results bit-identical whether or not a sibling slot diverged
    (preserving replicated ≡ sharded parity).

    Stacked-native over arbitrary leading axes; deterministic (key-free).
    The damping λ̂ is baked into the refreshed inverse — U is the inverse
    of the *damped* factor, refreshed with the spec's own ns_phi — so D
    is left all-zero (no spectrum to report) and the diagnostics go to
    their first-class channels: aux[..., AUX_LAM] = λ̂ and
    aux[..., AUX_RES] = the final Frobenius residual (≥ _NS_RES_MAX
    flags that the fallback fired).
    """
    from repro.kernels import ops as kops

    d = spec.d
    M = _ns_sym(st.M)
    lmax = jnp.maximum(_ns_lmax(M), 1e-12)
    lam = spec.ns_phi * lmax                               # (*stack,)
    eye = jnp.eye(d, dtype=M.dtype)
    Mhat = M + lam[..., None, None] * eye
    alpha = 2.0 / (lmax + 2.0 * lam)
    X_cold = alpha[..., None, None] * eye
    X_warm = _ns_sym(st.U)
    r_warm = _ns_resnorm(eye - Mhat @ X_warm)
    use_warm = r_warm < spec.ns_guard                      # NaN-safe: False
    X = jnp.where(use_warm[..., None, None], X_warm, X_cold)
    X = jax.lax.fori_loop(0, spec.ns_iters,
                          lambda _, x: kops.ns_step(Mhat, x), X)
    R = eye - Mhat @ X
    res = jnp.sqrt(jnp.sum(R * R, axis=(-2, -1)))
    bad = ~(res < _NS_RES_MAX)                             # NaN/Inf → True

    def _fallback(x):
        dense = jnp.linalg.inv(Mhat)                       # LU, no eigh/qr/svd
        return jnp.where(bad[..., None, None], dense, x)

    X = jax.lax.cond(jnp.any(bad), _fallback, lambda x: x, X)
    aux = st.aux.at[..., AUX_LAM].set(lam.astype(st.aux.dtype))
    aux = aux.at[..., AUX_RES].set(res.astype(st.aux.dtype))
    return KFactorState(U=X.astype(st.U.dtype),
                        D=jnp.zeros(st.D.shape, st.D.dtype), M=st.M,
                        aux=aux)


# ---------------------------------------------------------------------------
# fused per-step transition: stats step + (scheduled) inverse-rep step
# ---------------------------------------------------------------------------

def has_heavy_op(spec: KFactorSpec) -> bool:
    """True iff the mode has a periodic heavy op (EVD / RSVD overwrite /
    correction / NS refinement) — pure BRAND maintains its inverse rep with
    light work only, so the scheduler never assigns it a heavy phase."""
    return spec.mode in (Mode.EVD, Mode.RSVD, Mode.BRAND_RSVD,
                         Mode.BRAND_CORR, Mode.NS)


def has_work(spec: KFactorSpec, do_stats: bool, do_light: bool,
             do_heavy: bool) -> bool:
    """True iff this step's static flags actually touch the factor state.

    Lets the bucketed optimizer skip whole no-op buckets (e.g. a pure-Brand
    bucket on a stats-only step) instead of gathering, running identity
    branches, and scattering — the per-tap unrolled graph gets the same
    elision from XLA dead-code elimination, so skipping preserves parity.
    """
    if do_stats and spec.needs_m:
        return True
    if (do_light or do_heavy) and spec.mode in _HAS_BRAND:
        return True
    if do_heavy and has_heavy_op(spec):
        return True
    return False


def stats_step(spec: KFactorSpec, st: KFactorState, X: Array, first: Array
               ) -> KFactorState:
    """Absorb one incoming stats factor X into the EA (dense M if held).

    Stacked-native: st/X may carry leading stack axes — the EA absorb for a
    whole stack of factors is one batched kernel launch."""
    if spec.needs_m:
        M = ea_update_m_kernel(st.M, X, spec.rho, first)
        return KFactorState(U=st.U, D=st.D, M=M, aux=st.aux)
    return st


def inverse_rep_step(spec: KFactorSpec, st: KFactorState, X: Array,
                     key: Array, first: Array, heavy: Array) -> KFactorState:
    """Scheduled inverse-representation update (one 2-D factor).

    ``heavy`` selects the periodic heavy op for the mode (RSVD overwrite /
    EVD / correction); the light op is the Brand update (Brand modes) or a
    no-op (EVD/RSVD modes, matching the paper's T_inv > T_updt regime).
    """
    if spec.mode is Mode.EVD:
        return jax.lax.cond(heavy, lambda s: evd_overwrite(spec, s),
                            lambda s: s, st)
    if spec.mode is Mode.RSVD:
        return jax.lax.cond(heavy, lambda s: rsvd_overwrite(spec, s, key),
                            lambda s: s, st)
    if spec.mode is Mode.NS:
        return jax.lax.cond(heavy, lambda s: ns_overwrite(spec, s),
                            lambda s: s, st)
    if spec.mode is Mode.BRAND:
        return brand_step(spec, st, X, first)
    if spec.mode is Mode.BRAND_RSVD:
        st = brand_step(spec, st, X, first)
        return jax.lax.cond(heavy, lambda s: rsvd_overwrite(spec, s, key),
                            lambda s: s, st)
    if spec.mode is Mode.BRAND_CORR:
        st = brand_step(spec, st, X, first)
        return jax.lax.cond(heavy, lambda s: light_correction(spec, s, key),
                            lambda s: s, st)
    raise ValueError(spec.mode)


def heavy_overwrite_batched(spec: KFactorSpec, st: KFactorState,
                            keys: Array) -> KFactorState:
    """Unconditional heavy op over one flat batch axis (B, …): dense EVD /
    RSVD overwrite / Alg-6 correction, vmapped so the whole (sub-)bucket
    is one launch group.  The caller decides *whether* (and on *which
    slots*) this fires — scheduling is static, so no ``lax.cond`` wrapper
    ever enters the graph on steps (or slots) that skip heavy work."""
    if spec.mode is Mode.EVD:
        return jax.vmap(lambda s: evd_overwrite(spec, s))(st)
    if spec.mode is Mode.NS:
        # stacked-native (and its batched GEMMs must stay one launch, not a
        # vmap of launches); the divergence fallback is bucket-level cond +
        # per-slot where, which a vmap would defeat
        return ns_overwrite(spec, st)
    if spec.mode in (Mode.RSVD, Mode.BRAND_RSVD):
        return jax.vmap(lambda s, k: rsvd_overwrite(spec, s, k))(st, keys)
    if spec.mode is Mode.BRAND_CORR:
        return jax.vmap(lambda s, k: light_correction(spec, s, k))(st, keys)
    return st


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class InflightState:
    """Double buffer for one bucket's async heavy pipeline.

    At a *launch* step the live factor state of the firing slots is
    snapshotted here (post-stats, post-Brand — exactly what the inline
    heavy op would have read); at the *land* step, ``lag`` steps later,
    the heavy overwrite computed from the snapshot is swapped into the
    live state with the interim Brand panels replayed on top.  All
    leaves are slot-major (leading bucket batch axis) so the distributed
    curvature engine shards them with the same per-slot round-robin plan
    as the live state.

    U/D/M/keys: (B, d, w) / (B, w) / (B, d, d) / (B, 2) snapshots.
    panels: (B, n_replay, d, n_stat) ring of the last ``n_replay`` light
    panels (oldest first); ``n_replay = lag // T_brand`` is static and
    zero for non-Brand modes or ``lag < T_brand``.
    live: (B,) per-slot validity — set at launch, cleared at land.  A
    landing only swaps slots whose snapshot is live, so a launch that
    was dropped (straggler back-off) or never happened (fresh resume at
    an off-cycle phase) makes its scheduled landing a per-slot no-op
    instead of swapping in a zero or one-cycle-stale snapshot: the
    pipeline event simply defers to the next cycle.
    """
    U: Array
    D: Array
    M: Array
    keys: Array
    panels: Array
    live: Array


def make_inflight(spec: KFactorSpec, total: int, n_replay: int,
                  dtype=jnp.float32) -> InflightState:
    """Zero-initialized in-flight buffer for a bucket of ``total`` slots."""
    return InflightState(
        U=jnp.zeros((total, spec.d, spec.width), dtype),
        D=jnp.zeros((total, spec.width), dtype),
        M=jnp.zeros((total,) + ((spec.d, spec.d) if spec.needs_m
                                else (1, 1)), dtype),
        keys=jnp.zeros((total, 2), jnp.uint32),
        panels=jnp.zeros((total, n_replay, spec.d, spec.n_stat), dtype),
        live=jnp.zeros((total,), jnp.bool_),
    )


def record_panel(buf: InflightState, X: Array) -> InflightState:
    """Shift the light-panel ring left and append this step's panel."""
    if buf.panels.shape[1] == 0:
        return buf
    panels = jnp.concatenate([buf.panels[:, 1:], X[:, None]], axis=1)
    return dataclasses.replace(buf, panels=panels)


def launch_snapshot(buf: InflightState, st: KFactorState, keys: Array,
                    lo: int, hi: int) -> InflightState:
    """Snapshot the live state (and this step's per-slot keys) of slots
    [lo, hi) into the buffer — the operands of the future heavy op."""
    return InflightState(
        U=buf.U.at[lo:hi].set(st.U[lo:hi]),
        D=buf.D.at[lo:hi].set(st.D[lo:hi]),
        M=buf.M.at[lo:hi].set(st.M[lo:hi]),
        keys=buf.keys.at[lo:hi].set(keys[lo:hi]),
        panels=buf.panels,
        live=buf.live.at[lo:hi].set(True),
    )


def heavy_from_snapshot(spec: KFactorSpec, buf: InflightState,
                        lo: int, hi: int) -> Tuple[Array, Array, Array]:
    """The heavy overwrite, computed from the snapshot of slots [lo, hi)
    — a pure function of the buffer, so it can equally run in-graph at
    the land step or as a separately-dispatched program launched right
    after the snapshot (train.loop.AsyncInverseRunner).  Returns the
    landed (U, D, aux) triple; the snapshot's aux is synthesized as
    zeros — no heavy op reads it (it is write-only diagnostics), so the
    in-flight buffer does not carry an aux leaf."""
    snap = KFactorState(U=buf.U[lo:hi], D=buf.D[lo:hi], M=buf.M[lo:hi],
                        aux=jnp.zeros((hi - lo, AUX_WIDTH), buf.D.dtype))
    out = heavy_overwrite_batched(spec, snap, buf.keys[lo:hi])
    return out.U, out.D, out.aux


def replay_panels(spec: KFactorSpec, U: Array, D: Array, panels: Array
                  ) -> Tuple[Array, Array]:
    """Replay the interim light panels (oldest first) onto an incoming
    inverse rep — the landed state then carries every Brand absorb the
    live state received while the heavy op was in flight."""
    for j in range(panels.shape[1]):
        U, D = brand.ea_brand_step(U, D, panels[:, j], spec.rho, spec.r)
        if U.shape[-1] > spec.width:
            U, D = U[..., :, :spec.width], D[..., :spec.width]
    return U, D


def land_swap(spec: KFactorSpec, st: KFactorState, buf: InflightState,
              lo: int, hi: int, landed=None) -> Tuple[KFactorState, InflightState]:
    """Swap the landed inverse rep of slots [lo, hi) into the live state
    atomically.  ``landed`` is an optionally pre-computed (U, D, aux)
    triple from an overlapped dispatch; when absent the heavy op runs
    in-graph from the snapshot (same function, same operands, same
    result).

    Only slots whose snapshot is ``live`` swap (and the flag is consumed
    here): a dropped or never-fired launch turns its landing into a
    per-slot no-op rather than installing a zero / stale snapshot."""
    if landed is None:
        U, D, aux = heavy_from_snapshot(spec, buf, lo, hi)
    else:
        U, D, aux = landed
    if spec.mode in _HAS_BRAND:
        U, D = replay_panels(spec, U, D, buf.panels[lo:hi])
    ok = buf.live[lo:hi]
    U = jnp.where(ok[:, None, None], U, st.U[lo:hi])
    D = jnp.where(ok[:, None], D, st.D[lo:hi])
    aux = jnp.where(ok[:, None], aux, st.aux[lo:hi])
    st = KFactorState(U=st.U.at[lo:hi].set(U),
                      D=st.D.at[lo:hi].set(D), M=st.M,
                      aux=st.aux.at[lo:hi].set(aux))
    buf = dataclasses.replace(buf, live=buf.live.at[lo:hi].set(False))
    return st, buf


def bucket_factor_step(spec: KFactorSpec, st: KFactorState, X: Array,
                       keys: Array, first: Array, stats: bool, light: bool,
                       heavy_ranges) -> KFactorState:
    """One scheduled step for a whole shape-class bucket: st/X carry one
    flat batch axis (B, …); ``keys`` is (B, 2).  This is THE per-bucket
    program — the replicated bucketed optimizer, the per-tap comparison
    path (B = one tap's stack) and the sharded curvature engine (B = the
    device-local slot shard) all run it, so flag plumbing exists once.

    ``heavy_ranges`` is a static tuple of slot ranges (lo, hi) whose heavy
    overwrite fires this step (the work scheduler's staggering unit); the
    Brand light update runs bucket-wide whenever the step is light OR any
    heavy fires (heavy steps re-absorb the incoming panel — the seed's
    coupling, preserved; the scheduler snaps Brand-family phases to
    multiples of T_brand so staggering never adds extra Brand firings).
    """
    if stats:
        with obs_trace.span("stats"):
            st = stats_step(spec, st, X, first)
    heavy_ranges = tuple(heavy_ranges)
    if (light or heavy_ranges) and spec.mode in _HAS_BRAND:
        with obs_trace.span("light_brand"):
            st = brand_step(spec, st, X, first)
    for lo, hi in heavy_ranges:
        with obs_trace.span(f"heavy_{lo}_{hi}"):
            sub = jax.tree_util.tree_map(lambda x: x[lo:hi], st)
            sub = heavy_overwrite_batched(spec, sub, keys[lo:hi])
            st = jax.tree_util.tree_map(
                lambda full, part: full.at[lo:hi].set(part), st, sub)
    return st


def bucket_factor_step_async(spec: KFactorSpec, st: KFactorState, X: Array,
                             keys: Array, first: Array, stats: bool,
                             light: bool, heavy_ranges, launch_ranges,
                             land_ranges, buf: Optional[InflightState],
                             landed=None
                             ) -> Tuple[KFactorState,
                                        Optional[InflightState]]:
    """One scheduled step of the async double-buffered pipeline for a
    whole bucket: the synchronous program (stats / Brand / any inline
    heavy — e.g. the step-0 warmup) runs first, then this step's pipeline
    phases, in an order that makes ``lag=0`` bit-for-bit the synchronous
    path:

      1. record this step's light panel into the replay ring,
      2. *launch*: snapshot the post-stats/post-Brand state of the
         firing slots (plus their per-slot keys) into the buffer,
      3. *land*: swap the heavy result computed from the (possibly
         ``lag``-steps-old) snapshot into the live state, interim panels
         replayed on top.  With ``lag=0`` step 3 reads the snapshot step
         2 just wrote — the same operands the inline heavy op consumes.

    ``landed`` optionally supplies pre-computed (U, D) pairs, one per
    land range, from an overlapped dispatch (AsyncInverseRunner).
    """
    st = bucket_factor_step(spec, st, X, keys, first, stats, light,
                            heavy_ranges)
    if buf is None:
        return st, None
    if light:
        buf = record_panel(buf, X)
    for lo, hi in tuple(launch_ranges):
        with obs_trace.span(f"launch_{lo}_{hi}"):
            buf = launch_snapshot(buf, st, keys, lo, hi)
    for i, (lo, hi) in enumerate(tuple(land_ranges)):
        with obs_trace.span(f"land_{lo}_{hi}"):
            st, buf = land_swap(spec, st, buf, lo, hi,
                                landed=None if landed is None
                                else landed[i])
    return st, buf


# ---------------------------------------------------------------------------
# reconstruction helpers (testing / error metrics)
# ---------------------------------------------------------------------------

def reconstruct(st: KFactorState) -> Array:
    """Dense matrix represented by the low-rank state (tests only)."""
    return (st.U * st.D) @ st.U.T


def exact_ea(Xs, rho: float) -> Array:
    """Ground-truth EA K-factor from a list of stats factors (tests only)."""
    M = Xs[0] @ Xs[0].T
    for X in Xs[1:]:
        M = rho * M + (1 - rho) * (X @ X.T)
    return M
