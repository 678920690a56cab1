"""CholeskyQR2-style tall-skinny QR: algebraic properties of the oracle
and interpret-mode parity of the Pallas kernel pair (SYRK + root-apply)
against it, plus the Brand-update wiring (`sym_brand_update`).

Property tolerances are driven by the algorithm: two passes of the
clamped spectral root give ‖QᵀQ − I‖ ≈ machine-eps on full-rank panels,
QᵀQ is a rank-k projector to machine precision for *any* fp32 panel
(sub-noise-floor directions become an exactly-null subspace, never
unit-norm garbage), and Q R reconstructs the retained spectral content.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import brand
from repro.kernels import ref, ops
from repro.kernels.cholqr import cholqr2_batched_pallas


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-3, rtol=2e-3)


def _close(got, want, dtype):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.fixture
def interpret_mode(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "interpret")


def _no_fallback(monkeypatch, *names):
    def boom(*a, **k):
        raise AssertionError("ops dispatch fell back to the ref oracle")
    for name in names:
        monkeypatch.setattr(ops.ref, name, boom)


# ---------------------------------------------------------------------------
# oracle properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stack,d,n,dtype", [
    ((), 256, 128, jnp.float32),
    pytest.param((), 300, 72, jnp.float32,
                 marks=pytest.mark.slow),   # misaligned dims
    pytest.param((2,), 256, 128, jnp.float32,
                 marks=pytest.mark.slow),   # stacked
    ((), 256, 128, jnp.bfloat16),
])
def test_cholqr2_orthonormal_and_reconstructs(stack, d, n, dtype):
    A = jax.random.normal(jax.random.PRNGKey(d + n), stack + (d, n),
                          dtype=dtype)
    Q, R = ref.cholqr2(A)
    assert Q.shape == stack + (d, n) and R.shape == stack + (n, n)
    assert Q.dtype == A.dtype and R.dtype == jnp.float32
    eye = jnp.eye(n)
    QtQ = jnp.swapaxes(Q, -1, -2).astype(jnp.float32) @ Q.astype(jnp.float32)
    orth_tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(QtQ),
                               np.broadcast_to(eye, QtQ.shape),
                               atol=orth_tol)
    _close(Q.astype(jnp.float32) @ R, A, dtype)
    # R symmetric psd (the clamped spectral root, not a triangular factor)
    np.testing.assert_allclose(np.asarray(R),
                               np.asarray(jnp.swapaxes(R, -1, -2)),
                               atol=1e-5)


def test_cholqr2_rank_deficient_panel_is_finite():
    """Zero columns (A already in span of the held basis) must not NaN the
    factorization — the clamp keeps Q finite and Q R exact."""
    A = jax.random.normal(jax.random.PRNGKey(0), (192, 64))
    A = A.at[:, 32:].set(0.0)
    Q, R = ref.cholqr2(A)
    assert bool(jnp.isfinite(Q).all()) and bool(jnp.isfinite(R).all())
    np.testing.assert_allclose(np.asarray(Q @ R), np.asarray(A), atol=1e-4)


@pytest.mark.parametrize("cond", [
    1e2, pytest.param(1e4, marks=pytest.mark.slow),
    pytest.param(1e6, marks=pytest.mark.slow), 1e8])
def test_cholqr2_ill_conditioned_panel_stays_projector(cond):
    """For any fp32 conditioning, QᵀQ must be a rank-k projector to
    machine precision (sub-noise-floor directions become an exactly-null
    subspace — a raw/shifted Cholesky renormalizes them into unit-norm
    garbage instead) and Q R must reconstruct the retained content."""
    d, n = 512, 32
    k1, k2 = jax.random.split(jax.random.PRNGKey(int(np.log10(cond))))
    Qo, _ = jnp.linalg.qr(jax.random.normal(k1, (d, n)))
    V, _ = jnp.linalg.qr(jax.random.normal(k2, (n, n)))
    s = jnp.logspace(0, -float(np.log10(cond)), n)
    A = (Qo * s) @ V.T
    Q, R = ref.cholqr2(A)
    assert bool(jnp.isfinite(Q).all()) and bool(jnp.isfinite(R).all())
    P = Q.T @ Q
    np.testing.assert_allclose(np.asarray(P @ P), np.asarray(P), atol=1e-4)
    # retained content reconstructed: error bounded by the clamp floor
    rel = float(jnp.abs(Q @ R - A).max() / jnp.abs(A).max())
    assert rel < 3e-2, rel


def test_cholqr2_matches_householder_reconstruction():
    """Same factorization as jnp.linalg.qr up to column signs — compare
    via the sign-invariant products Q Qᵀ (span projector) and Q R."""
    A = jax.random.normal(jax.random.PRNGKey(1), (200, 48))
    Q, R = ref.cholqr2(A)
    Qh, Rh = jnp.linalg.qr(A)
    np.testing.assert_allclose(np.asarray(Q @ Q.T), np.asarray(Qh @ Qh.T),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(Q @ R), np.asarray(Qh @ Rh),
                               atol=1e-4)


# ---------------------------------------------------------------------------
# kernel parity (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stack,d,n,dtype", [
    ((), 256, 128, jnp.float32),      # aligned
    ((2,), 256, 128, jnp.float32),    # stacked
    ((2,), 200, 72, jnp.float32),     # pad path (d and n)
    ((), 256, 128, jnp.bfloat16),
])
def test_ops_cholqr2_matches_oracle(interpret_mode, monkeypatch, stack, d,
                                    n, dtype):
    A = jax.random.normal(jax.random.PRNGKey(d + n), stack + (d, n),
                          dtype=dtype)
    Q_want, R_want = ref.cholqr2(A)
    _no_fallback(monkeypatch, "cholqr2")
    Q_got, R_got = ops.cholqr2(A)
    assert Q_got.shape == stack + (d, n)
    assert R_got.shape == stack + (n, n)
    _close(Q_got, Q_want, dtype)
    _close(R_got, R_want, dtype)


def test_cholqr2_kernel_direct():
    """Raw batched kernel pair (no dispatch) against the oracle."""
    A = jax.random.normal(jax.random.PRNGKey(3), (2, 256, 128))
    Q, R = cholqr2_batched_pallas(A, bk=128, interpret=True)
    Q_want, R_want = ref.cholqr2(A)
    _close(Q, Q_want, jnp.float32)
    _close(R, R_want, jnp.float32)


def test_ops_orthonormalize(interpret_mode, monkeypatch):
    Y = jax.random.normal(jax.random.PRNGKey(4), (256, 128))
    _no_fallback(monkeypatch, "cholqr2")
    Q = ops.orthonormalize(Y)
    np.testing.assert_allclose(np.asarray(Q.T @ Q), np.eye(128), atol=1e-4)


def test_tiny_panel_falls_back_to_oracle(interpret_mode):
    """n = 8 → 128 is way past the pad growth cap: oracle semantics, same
    CholeskyQR2 numerics (the PowerSGD rank-8 compressor hits this)."""
    Y = jax.random.normal(jax.random.PRNGKey(5), (300, 8))
    Q = ops.orthonormalize(Y)
    np.testing.assert_allclose(np.asarray(Q.T @ Q), np.eye(8), atol=1e-4)


# ---------------------------------------------------------------------------
# Brand-update wiring
# ---------------------------------------------------------------------------

def _householder_sym_brand(U, D, A):
    """Paper Alg 3 with Householder QR of A⊥ — the reference the
    CholeskyQR2 light update is checked against."""
    C = jnp.swapaxes(U, -1, -2) @ A
    Q, R = jnp.linalg.qr(A - U @ C)
    mt = lambda x: jnp.swapaxes(x, -1, -2)
    top = jnp.concatenate([D[..., None, :] * jnp.eye(D.shape[-1])
                           + C @ mt(C), C @ mt(R)], axis=-1)
    bot = jnp.concatenate([R @ mt(C), R @ mt(R)], axis=-1)
    Dm, Wm = jnp.linalg.eigh(jnp.concatenate([top, bot], axis=-2))
    return (jnp.concatenate([U, Q], axis=-1) @ Wm[..., :, ::-1],
            Dm[..., ::-1])


@pytest.mark.slow
@pytest.mark.parametrize("stack", [(), (3,)])  # CI kernel-parity runs both
def test_sym_brand_update_kernel_path_matches_jnp(interpret_mode, stack):
    """The kernel path (Pallas panel + CholeskyQR2) and a Householder-QR
    Brand update represent the same matrix and spectrum."""
    d, r, n = 256, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    U = jnp.linalg.qr(jax.random.normal(ks[0], stack + (d, r)))[0]
    D = jnp.sort(jax.random.uniform(ks[1], stack + (r,), minval=0.1,
                                    maxval=2.0), axis=-1)[..., ::-1]
    A = jax.random.normal(ks[2], stack + (d, n))
    U1, D1 = _householder_sym_brand(U, D, A)
    U2, D2 = brand.sym_brand_update(U, D, A)
    np.testing.assert_allclose(np.asarray(D1), np.asarray(D2),
                               rtol=1e-3, atol=1e-3)
    rec1 = (U1 * D1[..., None, :]) @ jnp.swapaxes(U1, -1, -2)
    rec2 = (U2 * D2[..., None, :]) @ jnp.swapaxes(U2, -1, -2)
    np.testing.assert_allclose(np.asarray(rec1), np.asarray(rec2),
                               rtol=2e-3, atol=2e-3)
