"""Production trainer entry point.

    PYTHONPATH=src python -m repro.launch.train --arch gemma3_4b \
        --steps 100 [--variant bkfac] [--mesh 16x16|2x16x16|none] \
        [--ckpt-dir /path] [--compress] [--reduced]

On real hardware the mesh comes from the actual devices; ``--reduced``
trains the CPU-scale config of the same family (CI / this container).
Composes: model zoo + K-FAC optimizer + deterministic data + async
checkpointing + straggler detector + (optional) gradient compression.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ARCH_NAMES, SHAPES, get_arch
from repro.core import kfac as kfac_lib
from repro.core import policy as policy_lib
from repro.data.synthetic import TokenStream
from repro.distributed import compress as compress_lib
from repro.distributed import sharding as shd
from repro.launch import compile_cache
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_production_mesh, make_mesh
from repro.models import layers
from repro.models.lm import LM
from repro.obs import events as obs_events
from repro.obs import trace as obs_trace
from repro.optim import base as optbase
from repro.train import checkpoint as ckpt
from repro.train import health as health_lib
from repro.train import loop as loop_lib
from repro.train import straggler as strag_lib
from repro import specs as specs_lib


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3_4b", choices=ARCH_NAMES)
    ap.add_argument("--variant", default="bkfac",
                    choices=list(policy_lib.VARIANTS))
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--mesh", default="none",
                    help="none | 16x16 | 2x16x16 | AxB (custom)")
    ap.add_argument("--mesh-axes", default="",
                    help="comma-separated axis names for a custom --mesh "
                         "AxB, e.g. 'data,curv' for the 2D "
                         "data × curvature mesh (default: data,model)")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-scale config of the same family")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--compress", action="store_true",
                    help="PowerSGD-style DP gradient compression (error "
                         "feedback + warm-started power iteration)")
    ap.add_argument("--curvature-compress", type=int, default=0,
                    help="rank-q compression of the curvature engine's "
                         "(U, λ) cross-axis gathers (0 = raw gathers); "
                         "lossy — trades a little factor accuracy for "
                         "O(d·q) instead of O(d·r) bytes on the wire")
    ap.add_argument("--stagger", dest="stagger", action="store_true",
                    default=True,
                    help="phase heavy factor work across the T_inv window "
                         "(constant per-step cost instead of a spike)")
    ap.add_argument("--no-stagger", dest="stagger", action="store_false")
    ap.add_argument("--stagger-splits", type=int, default=4,
                    help="max entry-aligned chunks per factor bucket")
    ap.add_argument("--async-heavy", dest="async_heavy",
                    action="store_true",
                    help="two-phase launch/land heavy pipeline: heavy "
                         "overwrites compute against a snapshot and swap "
                         "in --heavy-lag steps later (overlapped with "
                         "training on a spare device when replicated)")
    ap.add_argument("--heavy-lag", type=int, default=2,
                    help="steps between a heavy launch (snapshot) and "
                         "its landing (swap-in); 0 = same-step (exactly "
                         "the synchronous numerics)")
    ap.add_argument("--curvature", default="auto",
                    choices=("auto", "none"),
                    help="auto: shard factor work across the mesh's first "
                         "data axis (distributed curvature engine)")
    ap.add_argument("--health", action="store_true",
                    help="enable the in-graph health guards + staged "
                         "remediation ladder (skip / damping escalation "
                         "/ forced refresh / checkpoint rollback — the "
                         "last needs --ckpt-dir).  Bit-inert on healthy "
                         "runs (train/health.py)")
    ap.add_argument("--telemetry-dir", default="",
                    help="write the structured JSONL event log to "
                         "<dir>/events.jsonl (repro.obs; feed it to "
                         "`python -m repro.obs.summary`)")
    ap.add_argument("--metrics-every", type=int, default=10,
                    help="in-graph curvature-metric flush cadence in "
                         "steps (needs --telemetry-dir; 0 disables)")
    ap.add_argument("--profile-dir", default="",
                    help="capture a jax.profiler trace of a short step "
                         "window into this directory")
    ap.add_argument("--profile-steps", type=int, default=3,
                    help="steps in the --profile-dir trace window")
    args = ap.parse_args()
    compile_cache.enable_compile_cache()

    jsonl = (os.path.join(args.telemetry_dir, "events.jsonl")
             if args.telemetry_dir else None)
    if jsonl is not None:
        os.makedirs(args.telemetry_dir, exist_ok=True)
    writer = obs_events.TelemetryWriter(path=jsonl, console=True)
    writer.emit("run_start", config={
        "arch": args.arch, "variant": args.variant, "steps": args.steps,
        "batch": args.batch, "seq": args.seq, "mesh": args.mesh,
        "reduced": args.reduced, "stagger": args.stagger,
        "async_heavy": args.async_heavy, "heavy_lag": args.heavy_lag,
        "metrics_every": args.metrics_every})

    arch = get_arch(args.arch)
    if args.reduced:
        arch = arch.reduced()
    mesh = None
    if args.mesh == "16x16":
        mesh = make_production_mesh()
    elif args.mesh == "2x16x16":
        mesh = make_production_mesh(multi_pod=True)
    elif args.mesh not in ("none", ""):
        dims = tuple(int(x) for x in args.mesh.split("x"))
        if args.mesh_axes:
            names = tuple(a.strip() for a in args.mesh_axes.split(","))
            if len(names) != len(dims):
                raise SystemExit(f"--mesh-axes {names} does not match "
                                 f"--mesh {args.mesh}")
        else:
            names = ("data", "model")[: len(dims)]
        mesh = make_mesh(dims, names)

    sp = steps_lib.shard_policy_for(mesh)
    lm = LM(arch, sp, remat=not args.reduced)
    kcfg = steps_lib.default_kfac_config(arch, args.variant)
    if args.reduced:
        kcfg = kfac_lib.KfacConfig(
            policy=policy_lib.PolicyConfig(variant=args.variant, r=32,
                                           max_dense_dim=1024),
            lr=optbase.constant(0.02), damping_phi=optbase.constant(0.1),
            weight_decay=1e-4, clip=0.5, T_updt=2, T_inv=10, T_brand=2,
            T_rsvd=10, T_corct=10, fallback_lr=optbase.constant(3e-3))
    kcfg = dataclasses.replace(kcfg, stagger=args.stagger,
                               stagger_splits=args.stagger_splits,
                               async_heavy=args.async_heavy,
                               heavy_lag=args.heavy_lag if args.async_heavy
                               else 0)
    opt = kfac_lib.Kfac(kcfg, lm.taps)
    curv_axis = None
    row_axis = None
    if args.curvature == "auto" and mesh is not None:
        dp = [a for a in mesh.axis_names if a != "model"]
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if "curv" in sizes and sizes["curv"] > 1:
            # 2D data × curvature mesh: factor *slots* shard over the
            # dedicated curv axis; the dense M *rows* (and the heavy
            # FLOPs on them) shard over the remaining data axis.
            curv_axis = "curv"
            rows = [a for a in dp if a != "curv" and sizes[a] > 1]
            row_axis = rows[0] if rows else None
        elif dp and sizes[dp[0]] > 1:
            curv_axis = dp[0]
    eng = specs_lib.DistSpec(
        mesh=mesh, curvature_axis=curv_axis, row_axis=row_axis,
        curvature_compress=args.curvature_compress or None).attach(opt)
    if eng is not None:
        rep, dev = eng.job_counts()
        writer.log(f"curvature sharded on '{curv_axis}': "
                   f"{rep} factor slots replicated -> {dev}/device "
                   f"({eng.describe()})")
        m_rep, m_dev = eng.m_bytes()
        cb = eng.collective_bytes()
        writer.log(f"dense-M memory: {m_rep / 1e6:.2f} MB replicated -> "
                   f"{m_dev / 1e6:.2f} MB/device; (U, lambda) gather "
                   f"bytes/round: {cb['uncompressed'] / 1e6:.3f} MB raw, "
                   f"{cb['on_wire'] / 1e6:.3f} MB on wire")
    sched = opt.scheduler()
    if args.stagger or args.async_heavy:
        writer.emit("sched",
                    detail=f"heavy-work scheduler: {sched.describe()}")
    runner = (loop_lib.AsyncInverseRunner.for_opt(opt, writer=writer)
              if args.async_heavy else None)
    if runner is not None:
        writer.log(f"async heavy pipeline: lag={kcfg.heavy_lag} offload="
                   f"{'spare device' if runner.device else 'in-thread'}")

    n_tokens = args.batch * args.seq
    stream = TokenStream(vocab=arch.vocab, batch=args.batch,
                         seq_len=args.seq, seed=0)
    params = lm.init(jax.random.PRNGKey(0))
    state = loop_lib.TrainState(params=params, opt=opt.init(params),
                                rng=jax.random.PRNGKey(1))
    if mesh is not None:
        p_sh = shd.params_sharding(params, mesh)
        o_sh = shd.kfac_state_sharding(state.opt, mesh,
                                       curvature_axis=curv_axis,
                                       row_axis=row_axis)
        state = loop_lib.TrainState(
            params=jax.device_put(params, p_sh),
            opt=jax.device_put(state.opt, o_sh), rng=state.rng)

    # DP gradient compression rides as a grad_transform inside the jitted
    # step; its CompressState (error feedback + warm-start Q) is a
    # separate carry, deliberately *outside* TrainState so the checkpoint
    # schema is untouched (a restore simply cold-starts the compressor).
    grad_transform = None
    cstate = None
    if args.compress:
        ccfg = compress_lib.CompressConfig(rank=8)
        cstate = compress_lib.init_state(params, ccfg)
        grad_transform = lambda gp, cs: compress_lib.compress_tree(
            gp, cs, ccfg)
        if args.health:
            writer.log("--compress ignored with --health: the resilient "
                       "step has no gradient-transform hook")
            grad_transform = cstate = None

    meter = None
    if jsonl is not None:
        meter = specs_lib.ObsSpec(
            writer=writer,
            metrics_every=args.metrics_every).make_meter(opt)
    policy = None
    if args.health:
        policy = health_lib.RemediationPolicy(writer=writer)
        step_fn = jax.jit(health_lib.make_resilient_kfac_step(
            lm.loss_fn, opt, n_tokens, meter=meter),
            static_argnames=("work",))
        writer.log("health guards on: staged remediation ladder armed"
                   + ("" if args.ckpt_dir
                      else " (no --ckpt-dir: rollback stage disabled)"))
    else:
        step_fn = jax.jit(loop_lib.make_scheduled_kfac_step(
            lm.loss_fn, opt, n_tokens, meter=meter,
            grad_transform=grad_transform),
            static_argnames=("work",))

    checkpointer = (ckpt.AsyncCheckpointer(args.ckpt_dir, keep=3)
                    if args.ckpt_dir else None)
    start = ckpt.latest_step(args.ckpt_dir) if args.ckpt_dir else None
    if start is not None:
        state, _ = ckpt.restore(args.ckpt_dir, state)
        writer.emit("ckpt_restore", step=start, path=args.ckpt_dir)
    k0 = 0 if start is None else start + 1

    mesh_txt = ("×".join(f"{a}={s}" for a, s in
                              zip(mesh.axis_names, mesh.devices.shape))
                if mesh is not None else "")
    det = strag_lib.StragglerDetector(writer=writer, mesh_desc=mesh_txt)
    profiler = obs_trace.StepProfiler(args.profile_dir or None,
                                      first=k0 + 1,
                                      steps=args.profile_steps)
    t_start = time.time()
    losses = []
    # the model's internal with_sharding_constraint calls need the mesh
    # context when PartitionSpecs are in play
    ctx = mesh if mesh is not None else contextlib.nullcontext()
    with ctx:
        run_steps(args, sched, det, stream, step_fn, state,
                  checkpointer, k0, t_start, losses, runner=runner,
                  writer=writer, meter=meter, profiler=profiler,
                  policy=policy, opt=opt, cstate=cstate)
    profiler.close()
    if runner is not None:
        runner.close()
    if checkpointer is not None:
        checkpointer.close()
    writer.emit("run_end", steps=len(losses), loss_first=losses[0],
                loss_last=float(np.mean(losses[-3:])),
                s_per_step=(time.time() - t_start) / max(len(losses), 1))
    writer.close()


def run_steps(args, sched, det, stream, step_fn, state, checkpointer,
              k0, t_start, losses, runner=None, writer=None, meter=None,
              profiler=None, policy=None, opt=None, cstate=None):
    mbuf = meter.init() if meter is not None else None
    last_k = k0
    k_off = 0          # rollback re-anchor: schedule runs at k_off + k
    for k in range(k0, args.steps):
        last_k = k
        if profiler is not None:
            profiler.tick(k)
        batch = stream.batch_at(k)
        t0 = time.time()
        kk = k_off + k
        with obs_trace.host_span(obs_trace.SCHEDULE):
            work = sched.work(kk)
            if policy is not None and policy.take_refresh():
                # remediation stage 2: abandon the (possibly poisoned)
                # pipeline, re-establish the inverse rep from the live M
                work = opt.remedial_work()
                state = state._replace(opt=opt.clear_inflight(state.opt))
                if runner is not None:
                    runner.drop_pending(reason="dropped")
            actions = det.observe_step(k, {"host0": time.time() - t0
                                           + 1e-6})
            work = strag_lib.apply_to_work(
                actions.get("host0", strag_lib.Action.NONE), work)
            landing = (runner.landing(work, step=kk)
                       if runner is not None else None)
        report = None
        scale = jnp.float32(policy.damping_scale) \
            if policy is not None else None
        with obs_trace.host_span(obs_trace.DISPATCH):
            if policy is not None:
                if meter is None:
                    state, loss, report = step_fn(state, batch, work,
                                                  landing, None, scale)
                else:
                    state, loss, report, mbuf = step_fn(
                        state, batch, work, landing, mbuf, scale)
            elif cstate is not None:
                # compressed-DP step: the CompressState carry trails the
                # outputs (after mbuf when a meter is on)
                if meter is None:
                    state, loss, cstate = step_fn(state, batch, work,
                                                  landing, None, cstate)
                else:
                    state, loss, mbuf, cstate = step_fn(
                        state, batch, work, landing, mbuf, cstate)
            elif meter is None:
                state, loss = step_fn(state, batch, work, landing)
            else:
                state, loss, mbuf = step_fn(state, batch, work, landing,
                                            mbuf)
        if runner is not None:
            runner.launch(state.opt, work, step=kk)
        with obs_trace.host_span(obs_trace.LOSS_SYNC):
            losses.append(float(loss))
        # the step's bookkeeping is this loop's per-step hook
        with obs_trace.host_span(obs_trace.CALLBACK):
            faulty = False
            if policy is not None:
                rep = {n: float(v) for n, v in
                       jax.device_get(report).items()}
                faulty = policy.observe(kk, losses[-1], rep)
                if policy.take_rollback() and args.ckpt_dir:
                    # remediation stage 3: restore the newest snapshot
                    # that verifies and re-anchor the staggered cadence
                    if runner is not None:
                        runner.drop_pending(reason="dropped")
                    if checkpointer is not None:
                        checkpointer.wait()
                    state, man = ckpt.restore_latest_healthy(
                        args.ckpt_dir, state)
                    k_off = int(jax.device_get(state.opt.phase)) - (k + 1)
                    policy.notify_rollback(kk, man["step"], args.ckpt_dir)
                    if writer is not None:
                        writer.emit("ckpt_restore", step=int(man["step"]),
                                    path=args.ckpt_dir)
                    faulty = False
            if (checkpointer is not None and not faulty
                    and k % args.ckpt_every == 0):
                checkpointer.submit(k, state)
                if writer is not None:
                    writer.emit("ckpt_save", step=k, path=args.ckpt_dir)
            if writer is not None:
                writer.emit("step", step=kk, loss=float(loss),
                            dt_s=time.time() - t0, phase=work.label)
    if meter is not None:
        meter.drain(mbuf, last_k)
    return state


if __name__ == "__main__":
    main()
