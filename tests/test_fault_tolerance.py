"""Checkpoint/restart, elastic remesh, straggler detection, and gradient
compression tests."""
import os
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.train import checkpoint as ckpt
from repro.train import elastic, straggler
from repro.distributed import compress


class TestCheckpoint:
    def _tree(self, seed=0):
        k = jax.random.PRNGKey(seed)
        return {"params": {"w": jax.random.normal(k, (8, 4)),
                           "b": jnp.zeros((4,))},
                "opt": {"mu": jnp.ones((8, 4)) * 0.5},
                "step": jnp.asarray(7)}

    def test_save_restore_roundtrip(self, tmp_path):
        tree = self._tree()
        ckpt.save(str(tmp_path), 7, tree)
        got, manifest = ckpt.restore(str(tmp_path), tree)
        assert manifest["step"] == 7
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                       np.asarray(b)),
            tree, got)

    def test_latest_pointer_and_prune(self, tmp_path):
        tree = self._tree()
        for s in (1, 2, 3, 4):
            ckpt.save(str(tmp_path), s, tree)
        assert ckpt.latest_step(str(tmp_path)) == 4
        ckpt.prune(str(tmp_path), keep=2)
        dirs = sorted(d for d in os.listdir(tmp_path)
                      if d.startswith("step_"))
        assert len(dirs) == 2
        assert ckpt.latest_step(str(tmp_path)) == 4

    def test_async_checkpointer(self, tmp_path):
        c = ckpt.AsyncCheckpointer(str(tmp_path), keep=2)
        tree = self._tree()
        for s in (0, 5, 10):
            c.submit(s, tree)
        c.close()
        assert ckpt.latest_step(str(tmp_path)) == 10

    def test_shape_mismatch_rejected(self, tmp_path):
        ckpt.save(str(tmp_path), 0, self._tree())
        bad = {"params": {"w": jnp.zeros((9, 4)), "b": jnp.zeros((4,))},
               "opt": {"mu": jnp.zeros((8, 4))}, "step": jnp.asarray(0)}
        with pytest.raises(ValueError):
            ckpt.restore(str(tmp_path), bad)

    def test_manifest_carries_schema_version(self, tmp_path):
        import json
        path = ckpt.save(str(tmp_path), 0, self._tree())
        with open(os.path.join(path, "manifest.json")) as f:
            assert json.load(f)["schema"] == ckpt.SCHEMA_VERSION

    def test_old_pytree_fails_with_actionable_schema_error(self, tmp_path):
        """A checkpoint missing leaves the template has (the pre-PR-3 /
        pre-async trap) must fail naming both schema versions, not with
        an opaque KeyError."""
        import json
        path = ckpt.save(str(tmp_path), 0, self._tree())
        # simulate an old writer: pre-schema manifest (v1 implied)
        man = os.path.join(path, "manifest.json")
        with open(man) as f:
            m = json.load(f)
        del m["schema"]
        with open(man, "w") as f:
            json.dump(m, f)
        newer = dict(self._tree(), inflight={"0": jnp.zeros((2, 3))})
        with pytest.raises(ckpt.SchemaMismatchError) as ei:
            ckpt.restore(str(tmp_path), newer)
        msg = str(ei.value)
        assert "schema v1" in msg
        assert f"schema v{ckpt.SCHEMA_VERSION}" in msg
        assert "migrate" in msg

    def test_leaf_compatible_old_checkpoint_still_restores(self, tmp_path):
        """Schema is for explaining failures, not rejecting compatible
        trees: a v-old checkpoint whose leaves match restores fine (the
        async-off case — inflight={} adds no leaves)."""
        tree = self._tree()
        ckpt.save(str(tmp_path), 3, tree)
        template = dict(tree, inflight={})     # new field, no leaves
        got, _ = ckpt.restore(str(tmp_path), template)
        assert got["inflight"] == {}


class TestAsyncCheckpointRoundTrip:
    """The async pipeline's in-flight buffers are part of the optimizer
    pytree: a checkpoint taken mid-lag (heavy launched, not yet landed)
    must restore so the landing still fires on schedule and the run
    matches an uninterrupted one."""

    def _setup(self):
        from repro.core import kfac as kfac_lib
        from repro.core import policy
        from repro.models import layers
        from repro.optim import base as optbase

        taps = {"fc": kfac_lib.TapInfo("fc/w", 24, 8, n_stat=8)}
        cfg = kfac_lib.KfacConfig(
            policy=policy.PolicyConfig(variant="kfac", r=4),
            lr=optbase.constant(0.05), T_updt=1, T_inv=4, stagger=True,
            stagger_splits=2, async_heavy=True, heavy_lag=2)
        key = jax.random.PRNGKey(0)
        params = {"fc": {"w": jax.random.normal(key, (24, 8)) * 0.1}}

        def loss_fn(p, probes, batch):
            x, y = batch
            h, act = layers.tapped_matmul(p["fc"]["w"], x,
                                          probes.get("fc"), 8)
            return jnp.mean((h - y) ** 2), {"fc": act}

        batches = [(jax.random.normal(jax.random.fold_in(key, i),
                                      (8, 24)),
                    jax.random.normal(jax.random.fold_in(key, 50 + i),
                                      (8, 8)))
                   for i in range(8)]
        return kfac_lib, cfg, taps, params, loss_fn, batches

    @pytest.mark.slow
    def test_mid_lag_save_restore_matches_uninterrupted(self, tmp_path):
        from repro.train import loop
        kfac_lib, cfg, taps, params, loss_fn, batches = self._setup()

        # uninterrupted 8-step reference
        opt_a = kfac_lib.Kfac(cfg, taps)
        ref_state, ref_losses = loop.run_kfac_training(
            loss_fn, opt_a, params, batches, n_tokens=8)

        # split run: stop at step 3 — the launch at step 2 (phase-2
        # unit) is in flight, landing due at step 4
        opt_b = kfac_lib.Kfac(cfg, taps)
        sched = opt_b.scheduler()
        assert any(sched.work(2).launch), "test premise: launch at k=2"
        assert any(sched.work(4).land), "test premise: landing at k=4"
        mid, head = loop.run_kfac_training(loss_fn, opt_b, params,
                                           batches[:3], n_tokens=8)
        assert any(x.size and float(jnp.abs(x).max()) > 0
                   for x in jax.tree_util.tree_leaves(mid.opt.inflight)), \
            "test premise: snapshot actually in flight at the save"
        ckpt.save(str(tmp_path), 3, mid)

        # restore into a fresh template and finish the run
        opt_c = kfac_lib.Kfac(cfg, taps)
        template = loop.TrainState(params=params,
                                   opt=opt_c.init(params),
                                   rng=mid.rng)
        restored, manifest = ckpt.restore(str(tmp_path), template)
        assert manifest["schema"] == ckpt.SCHEMA_VERSION
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                       np.asarray(b)),
            mid.opt.inflight, restored.opt.inflight)
        end_state, tail_losses = loop.run_kfac_training(
            loss_fn, opt_c, None, batches[3:], n_tokens=8,
            state=restored)

        np.testing.assert_allclose(head + tail_losses, ref_losses,
                                   rtol=1e-6)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(np.asarray(a),
                                                    np.asarray(b),
                                                    rtol=1e-6, atol=1e-7),
            end_state.params, ref_state.params)

    @pytest.mark.slow
    def test_mid_lag_restore_with_overlap_runner(self, tmp_path):
        """Resuming with the overlapped runner: the landing whose launch
        predates the restore has no pending future and falls back to
        in-graph compute from the restored snapshot — same numbers."""
        from repro.train import loop
        kfac_lib, cfg, taps, params, loss_fn, batches = self._setup()
        opt_a = kfac_lib.Kfac(cfg, taps)
        _, ref_losses = loop.run_kfac_training(loss_fn, opt_a, params,
                                               batches, n_tokens=8)
        opt_b = kfac_lib.Kfac(cfg, taps)
        mid, head = loop.run_kfac_training(loss_fn, opt_b, params,
                                           batches[:3], n_tokens=8)
        ckpt.save(str(tmp_path), 3, mid)
        opt_c = kfac_lib.Kfac(cfg, taps)
        template = loop.TrainState(params=params, opt=opt_c.init(params),
                                   rng=mid.rng)
        restored, _ = ckpt.restore(str(tmp_path), template)
        _, tail = loop.run_kfac_training(loss_fn, opt_c, None,
                                         batches[3:], n_tokens=8,
                                         state=restored, overlap=True)
        np.testing.assert_allclose(head + tail, ref_losses, rtol=1e-6)


@pytest.mark.slow
class TestElastic:
    def test_failure_restart_resumes_from_checkpoint(self, tmp_path):
        """Inject a failure mid-run; the runner must resume from the last
        checkpoint on the fallback mesh and reach the same final state as
        an uninterrupted run (deterministic data)."""
        def make_state(mesh):
            return {"x": jnp.zeros((4,)), "step": jnp.asarray(0)}

        def make_step(mesh):
            def step(state, k):
                return {"x": state["x"] + (k + 1),
                        "step": jnp.asarray(k)}
            return step

        meshes = (((1,), ("data",)), ((1,), ("data",)))
        inj = elastic.FailureInjector(fail_at=[7])
        runner = elastic.ElasticRunner(
            ckpt_dir=str(tmp_path), make_state=make_state,
            make_step=make_step, ckpt_every=2, meshes=meshes, injector=inj)
        state, info = runner.run(10)
        assert info["restarts"] == 1
        assert inj.failed == [7]
        # uninterrupted reference
        ref = make_state(None)
        for k in range(10):
            ref = make_step(None)(ref, k)
        np.testing.assert_allclose(np.asarray(state["x"]),
                                   np.asarray(ref["x"]))

    def test_double_failure_walks_mesh_ladder(self, tmp_path):
        def make_state(mesh):
            return {"x": jnp.zeros(())}

        calls = []

        def make_step(mesh):
            calls.append(tuple(mesh.devices.shape))
            def step(state, k):
                return {"x": state["x"] + 1}
            return step

        meshes = (((1, 1), ("data", "model")), ((1,), ("data",)),
                  ((1,), ("data",)))
        inj = elastic.FailureInjector(fail_at=[2, 5])
        runner = elastic.ElasticRunner(
            ckpt_dir=str(tmp_path), make_state=make_state,
            make_step=make_step, ckpt_every=1, meshes=meshes, injector=inj)
        state, info = runner.run(8)
        assert info["restarts"] == 2
        assert len(calls) == 3


class TestStraggler:
    def _fleet(self, slow_host=None, slow_from=10, n=30, hosts=8):
        det = straggler.StragglerDetector(patience=3, rebalance_after=6)
        per_host_actions = {f"h{i}": [] for i in range(hosts)}
        for k in range(n):
            times = {f"h{i}": 1.0 + 0.02 * (i % 3) for i in range(hosts)}
            if slow_host is not None and k >= slow_from:
                times[slow_host] = 2.5
            acts = det.observe_step(k, times)
            for h, a in acts.items():
                per_host_actions[h].append(a)
        return det, per_host_actions

    def test_detects_persistent_straggler(self):
        det, acts = self._fleet(slow_host="h3")
        assert straggler.Action.DROP_STATS in acts["h3"]
        assert straggler.Action.REBALANCE in acts["h3"]
        for h in acts:
            if h != "h3":
                assert straggler.Action.DROP_STATS not in acts[h]

    def test_tolerates_single_blip(self):
        det = straggler.StragglerDetector(patience=3)
        flagged = []
        for k in range(25):
            times = {f"h{i}": 1.0 for i in range(6)}
            if k == 12:
                times["h2"] = 5.0
            acts = det.observe_step(k, times)
            flagged += [a for a in acts.values() if a != straggler.Action.NONE]
        assert not flagged

    def test_fleet_slowdown_flags_nobody(self):
        """Whole-fleet degradation is not a straggler."""
        det = straggler.StragglerDetector(patience=2)
        for k in range(20):
            scale = 1.0 if k < 10 else 3.0
            acts = det.observe_step(k, {f"h{i}": scale for i in range(4)})
            assert all(a == straggler.Action.NONE for a in acts.values())

    def test_drop_stats_flag_rewrite(self):
        flags = dict(do_stats=True, do_light=True, do_heavy=False)
        out = straggler.apply_to_flags(straggler.Action.DROP_STATS, flags)
        assert out == dict(do_stats=False, do_light=False, do_heavy=False)
        same = straggler.apply_to_flags(straggler.Action.NONE, flags)
        assert same == flags


class TestCompression:
    def test_lossless_for_lowrank(self):
        k = jax.random.PRNGKey(0)
        G = (jax.random.normal(k, (64, 4)) @
             jax.random.normal(jax.random.PRNGKey(1), (4, 32)))
        err = jnp.zeros_like(G)
        cfg = compress.CompressConfig(rank=4)
        P, Q, new_err = compress.compress(G, err, None, cfg)
        got = compress.decompress(P, Q, G.shape)
        np.testing.assert_allclose(np.asarray(got), np.asarray(G),
                                   atol=1e-3)
        assert float(jnp.linalg.norm(new_err)) < 1e-3

    @pytest.mark.parametrize("carried", [0.0, 0.5])
    def test_error_feedback_preserves_signal(self, carried):
        """Transmitted + new residual == gradient + carried residual each
        round: nothing the previous rounds held back is dropped."""
        k = jax.random.PRNGKey(2)
        G = jax.random.normal(k, (48, 48))
        err = carried * jax.random.normal(jax.random.fold_in(k, 1), G.shape)
        cfg = compress.CompressConfig(rank=4)
        P, Q, new_err = compress.compress(G, err, None, cfg)
        approx = compress.decompress(P, Q, G.shape)
        np.testing.assert_allclose(np.asarray(approx + new_err),
                                   np.asarray(G + err), atol=1e-4)

    @pytest.mark.slow
    def test_sgd_with_compression_converges(self):
        """Least squares with rank-2 EF compression still converges."""
        key = jax.random.PRNGKey(3)
        X = jax.random.normal(key, (128, 16))
        Wt = jax.random.normal(jax.random.PRNGKey(4), (16, 8))
        Y = X @ Wt
        W = jnp.zeros((16, 8))
        cfg = compress.CompressConfig(rank=2, min_size=1)
        cstate = compress.init_state({"w": W}, cfg)
        for _ in range(300):
            G = X.T @ (X @ W - Y) / 128
            approx, cstate = compress.compress_tree({"w": G}, cstate, cfg)
            W = W - 0.05 * approx["w"]
        final = float(jnp.linalg.norm(X @ W - Y) / jnp.linalg.norm(Y))
        assert final < 0.1, final
