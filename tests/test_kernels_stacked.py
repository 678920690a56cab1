"""Interpret-mode parity of the stack-batched kernels and the fused
preconditioner against the ``ref.py`` oracles.

Sweeps aligned shapes (direct kernel path), misaligned shapes (pad-to-tile
path), one- and two-level stacks, and fp32/bf16.  The dispatch tests pin
``REPRO_PALLAS=interpret`` and poison the oracle so a silent fallback fails
loudly instead of vacuously passing.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import precond
from repro.kernels import ref, ops
from repro.kernels.precond_fused import precond_fused_pallas


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
    """Poison oracle entry points used by ops dispatch so a fallback to ref
    inside ops.* raises instead of silently passing the parity check.
    Call AFTER computing the expected value (ref is shared)."""
    def boom(*a, **k):
        raise AssertionError("ops dispatch fell back to the ref oracle")
    for name in names:
        monkeypatch.setattr(ops.ref, name, boom)


def _orth(key, shape):
    q, _ = jnp.linalg.qr(jax.random.normal(key, shape))
    return q


# ---------------------------------------------------------------------------
# stacked kernels, aligned + pad path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stack,d,n,dtype", [
    ((2, 2), 128, 128, jnp.float32),  # aligned, 2-level stack
    ((2,), 136, 72, jnp.bfloat16),    # pad path
])
def test_ea_syrk_stacked(interpret_mode, monkeypatch, stack, d, n, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(d + n))
    M = jax.random.normal(k1, stack + (d, d), dtype=jnp.float32)
    M = ((M + jnp.swapaxes(M, -1, -2)) / 2).astype(dtype)
    X = jax.random.normal(k2, stack + (d, n), dtype=dtype)
    want = ref.ea_syrk(M, X, 0.95, False)
    _no_fallback(monkeypatch, "ea_syrk")
    got = ops.ea_syrk(M, X, 0.95, False)
    assert got.shape == want.shape == stack + (d, d)
    _close(got, want, dtype)


@pytest.mark.parametrize("stack,d,r,n,dtype", [
    ((2, 2), 128, 8, 128, jnp.float32),   # aligned, 2-level stack
    ((2,), 136, 12, 72, jnp.bfloat16),    # pad path
])
def test_brand_panel_stacked(interpret_mode, monkeypatch, stack, d, r, n,
                             dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(d + r + n))
    U = _orth(k1, stack + (d, r)).astype(dtype)
    A = jax.random.normal(k2, stack + (d, n), dtype=dtype)
    C_want, P_want = ref.brand_panel(U, A)
    _no_fallback(monkeypatch, "brand_panel")
    C_got, P_got = ops.brand_panel(U, A)
    assert C_got.shape == stack + (r, n) and P_got.shape == stack + (d, n)
    _close(C_got, C_want, dtype)
    _close(P_got, P_want, dtype)


@pytest.mark.parametrize("stack,p,d,w,dtype", [
    ((2, 2), 128, 128, 8, jnp.float32),   # aligned, 2-level stack
    ((2,), 120, 136, 12, jnp.bfloat16),   # pad path
])
def test_lowrank_apply_stacked(interpret_mode, monkeypatch, stack, p, d, w,
                               dtype):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(p + d + w), 4)
    X = jax.random.normal(k1, stack + (p, d), dtype=dtype)
    U = _orth(k2, stack + (d, w)).astype(dtype)
    s = -jax.random.uniform(k3, stack + (w,), minval=0.1,
                            maxval=1.0).astype(dtype)
    lam = jax.random.uniform(k4, stack, minval=0.3, maxval=2.0)  # per-element
    want = ref.lowrank_apply(X, U, s, lam)
    _no_fallback(monkeypatch, "lowrank_apply")
    got = ops.lowrank_apply(X, U, s, lam)
    _close(got, want, dtype)


# ---------------------------------------------------------------------------
# fused preconditioner
# ---------------------------------------------------------------------------

def _fused_operands(stack, p, d, w_g, w_a, dtype):
    ks = jax.random.split(jax.random.PRNGKey(p + d + w_g + w_a), 7)
    J = jax.random.normal(ks[0], stack + (p, d), dtype=dtype)
    U_g = _orth(ks[1], stack + (p, w_g)).astype(dtype)
    U_a = _orth(ks[2], stack + (d, w_a)).astype(dtype)
    s_g = -jax.random.uniform(ks[3], stack + (w_g,), minval=0.1,
                              maxval=1.0).astype(dtype)
    s_a = -jax.random.uniform(ks[4], stack + (w_a,), minval=0.1,
                              maxval=1.0).astype(dtype)
    lam_g = jax.random.uniform(ks[5], stack, minval=0.3, maxval=2.0)
    lam_a = jax.random.uniform(ks[6], stack, minval=0.3, maxval=2.0)
    return J, U_g, s_g, lam_g, U_a, s_a, lam_a


@pytest.mark.parametrize("stack,p,d,w_g,w_a,dtype", [
    ((2,), 128, 256, 16, 24, jnp.float32),   # aligned, stacked
    ((2,), 128, 256, 16, 24, jnp.bfloat16),
    pytest.param((), 256, 128, 8, 8, jnp.float32,
                 marks=pytest.mark.slow),    # unstacked
    pytest.param((2,), 120, 136, 13, 10, jnp.float32,
                 marks=pytest.mark.slow),    # pad path (bf16 twin stays fast)
    ((2,), 120, 136, 13, 10, jnp.bfloat16),
    pytest.param((2, 2), 128, 128, 8, 16, jnp.float32,
                 marks=pytest.mark.slow),    # 2-level stack
])
def test_precond_fused_vs_ref(interpret_mode, monkeypatch, stack, p, d,
                              w_g, w_a, dtype):
    args = _fused_operands(stack, p, d, w_g, w_a, dtype)
    want = ref.precond_fused(*args)
    _no_fallback(monkeypatch, "precond_fused")
    got = ops.precond_fused(*args)
    assert got.shape == stack + (p, d)
    _close(got, want, dtype)


def test_precond_fused_kernel_direct():
    """Raw batched kernel (no dispatch) against the oracle."""
    args = _fused_operands((2,), 128, 128, 16, 8, jnp.float32)
    J, U_g, s_g, lam_g, U_a, s_a, lam_a = args
    got = precond_fused_pallas(J, U_g, s_g, 1.0 / lam_g, U_a, s_a,
                               1.0 / lam_a, interpret=True)
    want = ref.precond_fused(*args)
    _close(got, want, jnp.float32)


def test_precond_fused_matches_two_sided_composition(interpret_mode):
    """Fused path ≡ apply_inv_right then apply_inv_left (Alg 1)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    p, d, w = 128, 256, 16
    J = jax.random.normal(ks[0], (p, d))
    U_a = _orth(ks[1], (d, w))
    U_g = _orth(ks[2], (p, w))
    D_a = jnp.sort(jax.random.uniform(ks[3], (w,), minval=0.05,
                                      maxval=3.0))[::-1]
    D_g = jnp.sort(jax.random.uniform(ks[4], (w,), minval=0.05,
                                      maxval=3.0))[::-1]
    lam_a, lam_g = jnp.asarray(0.4), jnp.asarray(0.7)
    got = precond.kfac_precondition(J, U_g, D_g, lam_g, U_a, D_a, lam_a)
    want = precond.apply_inv_left(
        precond.apply_inv_right(J, U_a, D_a, lam_a), U_g, D_g, lam_g)
    _close(got, want, jnp.float32)


# ---------------------------------------------------------------------------
# dispatch policy
# ---------------------------------------------------------------------------

def test_shared_operand_broadcasts_across_stack(interpret_mode, monkeypatch):
    """One U/s shared by every stacked element (matmul-style broadcasting)
    must batch correctly, not mis-index a size-1 axis."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    X = jax.random.normal(k1, (3, 128, 128))
    U = _orth(k2, (128, 16))                 # unstacked, shared
    s = -jax.random.uniform(k3, (16,), minval=0.1, maxval=1.0)
    want = ref.lowrank_apply(X, U, s, 0.5)
    _no_fallback(monkeypatch, "lowrank_apply")
    got = ops.lowrank_apply(X, U, s, 0.5)
    assert got.shape == (3, 128, 128)
    _close(got, want, jnp.float32)


def test_fused_vmem_guard_falls_back_unfused(interpret_mode, monkeypatch):
    """A d too large for the J-resident stripes must dispatch to the
    unfused kernel path (two lowrank_apply round-trips), not the oracle."""
    monkeypatch.setattr(ops, "_FUSED_VMEM_BUDGET", 16 * 1024)  # force it
    args = _fused_operands((2,), 128, 256, 16, 8, jnp.float32)
    want = ref.precond_fused(*args)
    _no_fallback(monkeypatch, "precond_fused")
    got = ops.precond_fused(*args)
    _close(got, want, jnp.float32)


def test_tiny_shapes_fall_back_to_ref(interpret_mode):
    """Dims whose padding would exceed the growth cap use the oracle."""
    M = jnp.eye(100)
    X = jnp.ones((100, 7))          # n: 7 → 128 is way past _PAD_MAX
    got = ops.ea_syrk(M, X, 0.9, False)
    want = ref.ea_syrk(M, X, 0.9, False)
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.slow
def test_stacked_optimizer_update_kernels_match_jnp(monkeypatch):
    """End to end: a stacked tap steps identically through the Pallas
    kernels (interpret) and through the jnp oracles."""
    from repro.core import kfac as kfac_lib
    from repro.core import policy
    from repro.optim import base as optbase

    L, D, N = 2, 128, 32
    taps = {"blk": kfac_lib.TapInfo("blk/w", D, D, stack=(L,), n_stat=N)}
    pol = policy.PolicyConfig(variant="bkfac", r=16, max_dense_dim=512)
    key = jax.random.PRNGKey(0)
    params = {"blk": {"w": jax.random.normal(key, (L, D, D)) * 0.05}}
    grads = {"blk": {"w": jax.random.normal(jax.random.fold_in(key, 1),
                                            (L, D, D))}}
    acts = {"blk": jax.random.normal(jax.random.fold_in(key, 2), (L, N, D))}
    pgs = {"blk": jax.random.normal(jax.random.fold_in(key, 3),
                                    (L, N, D)) * 1e-3}

    def run(pallas):
        monkeypatch.setenv("REPRO_PALLAS", pallas)
        cfg = kfac_lib.KfacConfig(policy=pol, lr=optbase.constant(0.05),
                                  T_updt=1, T_brand=1)
        opt = kfac_lib.Kfac(cfg, taps)
        st = opt.init(params)
        for step in range(1):
            upd, st = opt.update(grads, st, params, acts=acts,
                                 probe_grads=pgs, n_tokens=N,
                                 rng=jax.random.fold_in(key, 10 + step),
                                 work=opt.uniform_work(True, True, False))
        return upd["blk"]["w"]

    a, b = run("off"), run("interpret")
    assert np.isfinite(np.asarray(a)).all()
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)
