"""Cross-layer bucketing (core/buckets.py + the bucketed Kfac hot path):
shape-class grouping rules, gather/scatter round-trips, and bucketed
vs per-tap parity of full optimizer steps on a mixed-shape model
(FC + scanned stack + two-level MoE stack + linear-apply tap).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import buckets, kfac as kfac_lib, kfactor, policy
from repro.optim import base as optbase


def _mixed_taps(N=16):
    """FC + unrolled twin + scanned stack + MoE stack: the 48-wide
    specs share a class; the 32-wide G sides share another."""
    return {
        "fc":   kfac_lib.TapInfo("fc/w", 48, 32, n_stat=N),
        "fc2":  kfac_lib.TapInfo("fc2/w", 48, 32, n_stat=N),
        "scan": kfac_lib.TapInfo("scan/w", 48, 48, stack=(3,), n_stat=N),
        "moe":  kfac_lib.TapInfo("moe/w", 48, 32, stack=(2, 2), n_stat=N),
    }


def _data(taps, key=None):
    if key is None:
        key = jax.random.PRNGKey(0)
    params, grads, acts, pgs = {}, {}, {}, {}
    for i, (n, t) in enumerate(taps.items()):
        shp = t.stack + (t.d_in, t.d_out)
        params[n] = {"w": jax.random.normal(jax.random.fold_in(key, i),
                                            shp) * 0.05}
        grads[n] = {"w": jax.random.normal(jax.random.fold_in(key, 10 + i),
                                           shp)}
        acts[n] = jax.random.normal(jax.random.fold_in(key, 20 + i),
                                    t.stack + (t.n_stat, t.d_in))
        pgs[n] = jax.random.normal(jax.random.fold_in(key, 30 + i),
                                   t.stack + (t.n_stat, t.d_out)) * 1e-3
    return params, grads, acts, pgs


def _run(taps, variant, bucketed, steps=2, heavy_every=2, r=8,
         max_dense_dim=8192, momentum=0.9):
    pol = policy.PolicyConfig(variant=variant, r=r,
                              max_dense_dim=max_dense_dim)
    cfg = kfac_lib.KfacConfig(policy=pol, lr=optbase.constant(0.05),
                              momentum=momentum, T_updt=1, T_brand=1,
                              bucketed=bucketed)
    opt = kfac_lib.Kfac(cfg, taps)
    params, grads, acts, pgs = _data(taps)
    st = opt.init(params)
    key = jax.random.PRNGKey(7)
    outs = []
    for s in range(steps):
        upd, st = opt.update(grads, st, params, acts=acts, probe_grads=pgs,
                             n_tokens=list(taps.values())[0].n_stat,
                             rng=jax.random.fold_in(key, s),
                             work=opt.uniform_work(
                                 True, True, s % heavy_every == 0))
        outs.append(upd)
    return opt, outs


# ---------------------------------------------------------------------------
# bucket construction rules
# ---------------------------------------------------------------------------

def test_factor_buckets_group_by_spec():
    taps = _mixed_taps()
    pol = policy.PolicyConfig(variant="bkfac", r=8, max_dense_dim=8192)
    opt = kfac_lib.Kfac(kfac_lib.KfacConfig(policy=pol), taps)
    fb = opt.factor_buckets
    # d=48 A-sides of fc/fc2/moe + both sides of scan share one spec;
    # d=32 G-sides of fc/fc2/moe share another.
    assert len(fb) == 2
    by_d = {b.spec.d: b for b in fb}
    assert by_d[32].total == 1 + 1 + 4            # fc, fc2, moe G-sides
    assert by_d[48].total == 1 + 1 + 3 + 3 + 4    # A-sides + scan both sides
    # deterministic entry layout: offsets tile the batch exactly
    for b in fb:
        assert b.entries[0].offset == 0
        for e0, e1 in zip(b.entries, b.entries[1:]):
            assert e1.offset == e0.offset + e0.count
        assert b.entries[-1].offset + b.entries[-1].count == b.total


def test_precond_buckets_group_by_spec_pair_and_apply_mode():
    taps = _mixed_taps()
    taps = dict(taps, lin=kfac_lib.TapInfo("lin/w", 48, 32, n_stat=16,
                                           linear_apply=True))
    pol = policy.PolicyConfig(variant="bkfac", r=8, max_dense_dim=8192)
    opt = kfac_lib.Kfac(kfac_lib.KfacConfig(policy=pol), taps)
    pb = opt.precond_buckets
    # (48→32) quadratic {fc, fc2, moe}, (48→48) {scan}, (48→32) linear {lin}
    assert len(pb) == 3
    sizes = sorted((b.total, b.linear_apply) for b in pb)
    assert sizes == [(1, True), (3, False), (6, False)]


def test_odd_shape_falls_out_into_singleton_bucket():
    taps = _mixed_taps()
    taps = dict(taps, odd=kfac_lib.TapInfo("odd/w", 80, 48, n_stat=16))
    pol = policy.PolicyConfig(variant="bkfac", r=8, max_dense_dim=8192)
    opt = kfac_lib.Kfac(kfac_lib.KfacConfig(policy=pol), taps)
    d80 = [b for b in opt.factor_buckets if b.spec.d == 80]
    assert len(d80) == 1 and d80[0].total == 1


# ---------------------------------------------------------------------------
# gather / scatter round-trips
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_gather_scatter_roundtrip():
    entries = (buckets.Entry("a", "A", (), 0, 1),
               buckets.Entry("b", "A", (2, 3), 1, 6),
               buckets.Entry("c", "G", (4,), 7, 4))
    key = jax.random.PRNGKey(1)
    leaves = {("a", "A"): jax.random.normal(key, (5, 7)),
              ("b", "A"): jax.random.normal(key, (2, 3, 5, 7)),
              ("c", "G"): jax.random.normal(key, (4, 5, 7))}
    batched = buckets.gather(entries, leaves)
    assert batched.shape == (11, 5, 7)
    back = buckets.scatter(entries, batched)
    for k, v in leaves.items():
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(v))


def test_gather_scatter_states_roundtrip():
    entries = (buckets.Entry("a", "A", (), 0, 1),
               buckets.Entry("b", "G", (2,), 1, 2))
    spec = kfactor.KFactorSpec(d=16, r=4, n_stat=4, mode=kfactor.Mode.BRAND)
    sts = {("a", "A"): spec.init(),
           ("b", "G"): jax.tree_util.tree_map(
               lambda x: jnp.broadcast_to(x, (2,) + x.shape) + 1.0,
               spec.init())}
    big = buckets.gather_states(entries, sts)
    assert big.U.shape == (3,) + sts[("a", "A")].U.shape
    back = buckets.scatter_states(entries, big)
    for k in sts:
        for a, b in zip(jax.tree_util.tree_leaves(back[k]),
                        jax.tree_util.tree_leaves(sts[k])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# bucketed vs per-tap optimizer parity
# ---------------------------------------------------------------------------

def _assert_updates_close(a, b, taps, atol):
    for n in taps:
        x, y = np.asarray(a[n]["w"]), np.asarray(b[n]["w"])
        assert np.isfinite(x).all() and np.isfinite(y).all()
        np.testing.assert_allclose(x, y, atol=atol, rtol=1e-4)


@pytest.mark.slow
def test_bucketed_matches_per_tap_brand_modes():
    """Pure-Brand (deterministic) path: bucketed ≡ per-tap bitwise-ish.

    (slow tier locally; CI's bucketed-parity job runs this file in full —
    the fast tier keeps `test_bucketed_kernel_path_matches_jnp` as the
    end-to-end gate.)"""
    taps = _mixed_taps()
    _, a = _run(taps, "bkfac", bucketed=True)
    _, b = _run(taps, "bkfac", bucketed=False)
    for ua, ub in zip(a, b):
        _assert_updates_close(ua, ub, taps, atol=1e-6)


@pytest.mark.slow
def test_bucketed_matches_per_tap_evd_mode():
    """K-FAC baseline (EVD heavy, deterministic): parity incl. heavy."""
    taps = _mixed_taps()
    _, a = _run(taps, "kfac", bucketed=True)
    _, b = _run(taps, "kfac", bucketed=False)
    for ua, ub in zip(a, b):
        _assert_updates_close(ua, ub, taps, atol=1e-4)


@pytest.mark.slow
def test_bucketed_linear_apply_matches_per_tap():
    taps = {"lin": kfac_lib.TapInfo("lin/w", 48, 32, n_stat=16,
                                    linear_apply=True),
            "lin2": kfac_lib.TapInfo("lin2/w", 48, 32, n_stat=16,
                                     linear_apply=True),
            "fc": kfac_lib.TapInfo("fc/w", 48, 32, n_stat=16)}
    _, a = _run(taps, "bkfac", bucketed=True)
    _, b = _run(taps, "bkfac", bucketed=False)
    for ua, ub in zip(a, b):
        _assert_updates_close(ua, ub, taps, atol=1e-5)


@pytest.mark.slow
def test_bucketed_randomized_heavy_modes_run():
    """brkfac heavy overwrites draw different keys in the two paths, so
    only statistical agreement holds — assert finiteness + magnitudes."""
    taps = _mixed_taps()
    _, a = _run(taps, "brkfac", bucketed=True, r=8)
    _, b = _run(taps, "brkfac", bucketed=False, r=8)
    for ua, ub in zip(a, b):
        for n in taps:
            x, y = np.asarray(ua[n]["w"]), np.asarray(ub[n]["w"])
            assert np.isfinite(x).all() and np.isfinite(y).all()
            assert abs(np.linalg.norm(x) - np.linalg.norm(y)) \
                <= 0.5 * (np.linalg.norm(x) + np.linalg.norm(y))


@pytest.mark.slow
def test_bucketed_kernel_path_matches_jnp(monkeypatch):
    """Bucketed through the Pallas kernels (interpret) ≡ bucketed through
    the jnp oracles, end to end on the mixed model."""
    taps = _mixed_taps()
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    _, a = _run(taps, "bkfac", bucketed=True, steps=2)
    monkeypatch.setenv("REPRO_PALLAS", "off")
    _, b = _run(taps, "bkfac", bucketed=True, steps=2)
    for ua, ub in zip(a, b):
        _assert_updates_close(ua, ub, taps, atol=2e-3)
