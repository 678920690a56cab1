"""Training-step factory: ties a tapped model, a loss, and an optimizer
(K-FAC family or baseline) into jit-able step functions.

The K-FAC step computes grads w.r.t. (params, probes) in one backward pass;
probe-grads and tapped activations feed the curvature machinery.

:class:`AsyncInverseRunner` is the loop-level half of the async heavy
pipeline (``KfacConfig.async_heavy``): right after a launch step writes a
factor snapshot into ``KfacState.inflight``, the runner dispatches the
heavy overwrite for those slots as a *separate* jitted program from a
worker thread — pinned to a spare device when one exists — and hands the
finished (U, D) back to the land step ``lag`` steps later.  The land step
then only swaps arrays and replays interim Brand panels; the EVD/RSVD
cost overlaps the lag window's training steps instead of sitting in any
step's critical path.  Without a runner the land step computes the same
function in-graph (same snapshot, same keys → same result), which is the
semantics tests and the sharded engine use.
"""
from __future__ import annotations

import functools
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import specs as specs_lib
from repro.core import kfac as kfac_lib
from repro.core import kfactor
from repro.models import layers
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.optim import base as optbase

Array = jax.Array


class TrainState(NamedTuple):
    params: Any
    opt: Any
    rng: Array


def kfac_grads(loss_fn, params, probes, batch, rng=None):
    """(loss, acts), grads w.r.t. params AND probes, one backward pass."""
    args = (params, probes, batch) + ((rng,) if rng is not None else ())
    (loss, acts), (gp, gprobe) = jax.value_and_grad(
        _model(loss_fn), argnums=(0, 1), has_aux=True)(*args)
    return loss, acts, gp, gprobe


def _model(loss_fn):
    """``loss_fn`` under the ``model`` scope, for differentiating: its
    forward ops then read ``jvp(model)/...`` and its backward ops
    ``transpose(jvp(model))/...`` in a profile."""

    def model(*args):
        with obs_trace.span("model"):
            return loss_fn(*args)

    return model


def make_kfac_step(loss_fn: Callable, opt: kfac_lib.Kfac,
                   n_tokens: int, probe_dtype=jnp.float32):
    """DEPRECATED legacy three-bool step factory.  The scheduler's
    :class:`~repro.core.schedule.StepWork` masks subsumed these flags in
    PR 3; this wrapper converts them via ``opt.uniform_work`` and
    delegates to :func:`make_scheduled_kfac_step`.  Jit the result with
    ``static_argnames=("do_stats", "do_light", "do_heavy")`` as before —
    identical numerics (the uniform mask compiles to the same HLO)."""
    specs_lib.warn_once(
        "make_kfac_step",
        "make_kfac_step is deprecated; use make_scheduled_kfac_step with "
        "a StepWork mask (opt.uniform_work / opt.scheduler().work)")
    scheduled = make_scheduled_kfac_step(loss_fn, opt, n_tokens,
                                         probe_dtype=probe_dtype)

    def step(state: TrainState, batch, do_stats: bool, do_light: bool,
             do_heavy: bool):
        work = opt.uniform_work(bool(do_stats), bool(do_light),
                                bool(do_heavy))
        return scheduled(state, batch, work)

    return step


def make_scheduled_kfac_step(loss_fn: Callable, opt: kfac_lib.Kfac,
                             n_tokens: int, probe_dtype=jnp.float32,
                             meter: Optional[obs_metrics.Meter] = None,
                             grad_transform: Optional[Callable] = None,
                             obs: Optional[specs_lib.ObsSpec] = None):
    """Returns step(state, batch, work, landing=None) with ``work`` a
    static :class:`repro.core.schedule.StepWork` mask — jit with
    ``static_argnames=("work",)``.  The mask is hashable, so each distinct
    mask (at most #scheduler-units + O(1) over a schedule cycle) compiles
    once to a lean HLO, exactly like the legacy bool variants.

    ``landing`` carries pre-computed heavy results for this step's land
    ranges (see :class:`AsyncInverseRunner`); ``None`` lands in-graph.

    With a ``meter`` (repro.obs in-graph metrics) the step becomes
    ``step(state, batch, work, landing=None, mbuf=None) -> (state, loss,
    mbuf)``: the optimizer runs under the meter's collector, the metric
    buffer is merged/flushed in-graph, and the params/loss outputs are
    bit-identical to the meter-less step (asserted in
    tests/test_obs.py).

    ``grad_transform`` — ``(grads, carry) -> (grads, carry)`` — rewrites
    the parameter gradients before the optimizer sees them (the DP
    gradient-compression path: ``compress_tree`` with its
    :class:`~repro.distributed.compress.CompressState` carry); the step
    then takes/returns that carry as a trailing argument/output.

    ``obs`` (a :class:`repro.specs.ObsSpec`) is the spec-level spelling of
    ``meter``: when given and no explicit meter is passed, the meter is
    built from it (``obs.make_meter(opt)``)."""
    if obs is not None and meter is None:
        meter = obs.make_meter(opt)

    def step(state: TrainState, batch, work, landing=None, mbuf=None,
             cstate=None):
        rng, sub = jax.random.split(state.rng)
        probes = layers.make_probes(opt.taps, probe_dtype)
        loss, acts, gp, gprobe = kfac_grads(loss_fn, state.params, probes,
                                            batch)
        if grad_transform is not None:
            gp, cstate = grad_transform(gp, cstate)
        with obs_trace.span("update"):
            if meter is None:
                updates, opt_state = opt.update(
                    gp, state.opt, state.params, acts=acts,
                    probe_grads=gprobe, n_tokens=n_tokens, rng=sub,
                    work=work, landing=landing)
            else:
                with meter.collecting() as col:
                    updates, opt_state = opt.update(
                        gp, state.opt, state.params, acts=acts,
                        probe_grads=gprobe, n_tokens=n_tokens, rng=sub,
                        work=work, landing=landing)
                mbuf = meter.maybe_flush(meter.merge(mbuf, col),
                                         opt_state.step)
            params = optbase.apply_updates(state.params, updates)
        out = TrainState(params=params, opt=opt_state, rng=rng)
        outs = (out, loss)
        if meter is not None:
            outs += (mbuf,)
        if grad_transform is not None:
            outs += (cstate,)
        return outs if len(outs) > 2 else (out, loss)

    return step


class AsyncInverseRunner:
    """Overlapped dispatch for the async heavy pipeline (replicated path).

    ``launch(opt_state, work)`` — call right AFTER the step that executed
    ``work`` (its launch mask wrote the snapshots being read here): slices
    each launched range out of the in-flight buffer and submits the heavy
    overwrite to a worker thread as its own jitted program.  With a spare
    ``device`` the operands are committed there, so the program runs
    concurrently with the main device's training steps (CPU host devices
    and TPU cores both give real overlap); without one it still runs off
    the critical path of the dispatching thread.

    ``landing(work)`` — call right BEFORE the step that executes ``work``:
    blocks on (usually long-finished) futures for this step's land ranges
    and returns the ``landing`` operand for ``Kfac.update``.  A range
    with no pending future (fresh resume mid-lag) maps to ``None`` and
    lands in-graph from the restored snapshot — the graceful
    re-snapshot-free resume path.

    Landings are **bounded**: ``landing`` waits at most the deadline —
    ``deadline_s`` when set, else ``deadline_factor`` × the median
    observed heavy time (floored at ``min_deadline_s``) — then treats
    the range as missed, cancels the future, **respawns the worker
    pool**, and lands in-graph from the snapshot.  Because
    ``heavy_from_snapshot`` is pure and the in-graph fallback reads the
    same snapshot with the same keys, a miss (timeout, worker crash, or
    dropped/resumed pipeline) is a perf event, never a numerics event.

    ``health`` counts launched / landed / missed ranges and pool
    respawns over the runner's lifetime, with ``miss_reasons`` split by
    cause (``timeout`` / ``crash`` / ``dropped`` / ``resume``).  A
    :class:`repro.obs.TelemetryWriter` passed as ``writer`` additionally
    gets per-range ``async_launch`` / ``async_land`` / ``async_miss``
    events (misses carry their ``reason``).
    """

    def __init__(self, opt: kfac_lib.Kfac, device=None, home=None,
                 writer=None, deadline_s: Optional[float] = None,
                 deadline_factor: float = 4.0, min_deadline_s: float = 5.0):
        self.opt = opt
        self.device = device
        self.home = home if home is not None else jax.devices()[0]
        self.writer = writer
        self.deadline_s = deadline_s
        self.deadline_factor = deadline_factor
        self.min_deadline_s = min_deadline_s
        self.health = {"launched": 0, "landed": 0, "missed": 0,
                       "respawns": 0, "miss_reasons": {}}
        self._pool = ThreadPoolExecutor(max_workers=2)
        self._fns: Dict = {}
        self._pending: Dict = {}
        self._dropped: Dict = {}        # range -> miss reason tombstone
        self._durations: List[float] = []

    @classmethod
    def for_opt(cls, opt: kfac_lib.Kfac,
                writer=None) -> Optional["AsyncInverseRunner"]:
        """A runner on the first spare device, or None when the optimizer
        does not pipeline (sync config, or a curvature engine attached —
        the engine lands in-graph, sharded)."""
        if not opt._async_buckets or opt.curvature is not None:
            return None
        devs = jax.devices()
        return cls(opt, device=devs[1] if len(devs) > 1 else None,
                   writer=writer)

    def _fn(self, bi: int, count: int):
        key = (bi, count)
        if key not in self._fns:
            spec = self.opt.factor_buckets[bi].spec
            self._fns[key] = jax.jit(functools.partial(
                kfactor.heavy_from_snapshot, spec, lo=0, hi=count))
        return self._fns[key]

    def _run(self, bi: int, count: int, buf_slice):
        with obs_trace.host_span(f"async/heavy/b{bi}"):
            t0 = time.perf_counter()
            if self.device is not None:
                buf_slice = jax.device_put(buf_slice, self.device)
            out = jax.device_put(self._fn(bi, count)(buf_slice), self.home)
            jax.block_until_ready(out)
            self._durations.append(time.perf_counter() - t0)
            return out

    def _deadline(self) -> float:
        if self.deadline_s is not None:
            return self.deadline_s
        if self._durations:
            med = sorted(self._durations)[len(self._durations) // 2]
            return max(self.min_deadline_s, self.deadline_factor * med)
        # No completed heavy yet (first landing may include compile):
        # a generous fixed cap still beats the old unbounded block.
        return max(self.min_deadline_s, 60.0)

    def _respawn(self) -> None:
        """Replace a hung/crashed worker pool.  Already-running tasks
        keep their (orphaned) threads; their futures stay pending and
        will land normally if they eventually complete."""
        self._pool.shutdown(wait=False, cancel_futures=True)
        self._pool = ThreadPoolExecutor(max_workers=2)
        self.health["respawns"] += 1

    def _submit(self, bi: int, count: int, buf_slice):
        try:
            return self._pool.submit(self._run, bi, count, buf_slice)
        except RuntimeError:            # pool died between steps
            self._respawn()
            return self._pool.submit(self._run, bi, count, buf_slice)

    def drop_pending(self, reason: str = "dropped") -> None:
        """Abandon every pending future (remediation refresh, elastic
        restart): the scheduled landings will miss with ``reason`` and
        fall back in-graph."""
        for key, fut in list(self._pending.items()):
            fut.cancel()
            self._dropped[key] = reason
        self._pending.clear()

    def _miss(self, key, reason: str, step) -> None:
        self.health["missed"] += 1
        reasons = self.health["miss_reasons"]
        reasons[reason] = reasons.get(reason, 0) + 1
        if self.writer is not None:
            bi, lo, hi = key
            self.writer.emit("async_miss", step=int(step or 0),
                             bucket=bi, lo=lo, hi=hi, reason=reason)

    def launch(self, opt_state, work, step: Optional[int] = None) -> None:
        for bi, ranges in enumerate(work.launch):
            if not ranges:
                continue
            buf = opt_state.inflight[str(bi)]
            for lo, hi in ranges:
                buf_slice = jax.tree_util.tree_map(lambda x: x[lo:hi], buf)
                self._pending[(bi, lo, hi)] = self._submit(
                    bi, hi - lo, buf_slice)
                self.health["launched"] += 1
                if self.writer is not None:
                    self.writer.emit("async_launch", step=int(step or 0),
                                     bucket=bi, lo=lo, hi=hi)

    def landing(self, work, step: Optional[int] = None):
        out = {}
        for bi, ranges in enumerate(work.land):
            if not ranges:
                continue
            results = []
            for lo, hi in ranges:
                key = (bi, lo, hi)
                fut = self._pending.pop(key, None)
                if fut is None:
                    # Fresh resume mid-lag, or a deliberately dropped
                    # pipeline: land in-graph from the snapshot.
                    results.append(None)
                    self._miss(key, self._dropped.pop(key, "resume"),
                               step)
                    continue
                overlapped = fut.done()
                try:
                    res = fut.result(timeout=self._deadline())
                except FuturesTimeout:
                    fut.cancel()
                    results.append(None)
                    self._miss(key, "timeout", step)
                    self._respawn()
                    continue
                except BaseException:
                    results.append(None)
                    self._miss(key, "crash", step)
                    self._respawn()
                    continue
                results.append(res)
                self.health["landed"] += 1
                if self.writer is not None:
                    self.writer.emit("async_land", step=int(step or 0),
                                     bucket=bi, lo=lo, hi=hi,
                                     overlapped=bool(overlapped))
            out[str(bi)] = tuple(results)
        return out or None

    def close(self):
        self._pool.shutdown(wait=False)


def make_baseline_step(loss_fn: Callable, opt: optbase.Optimizer):
    """Step for probe-free optimizers (SGD/AdamW/SENG uses its own maker)."""

    def step(state: TrainState, batch):
        rng, _ = jax.random.split(state.rng)
        probes = {}
        (loss, _), grads = jax.value_and_grad(_model(loss_fn), has_aux=True)(
            state.params, probes, batch)
        with obs_trace.span("update"):
            updates, opt_state = opt.update(grads, state.opt, state.params)
            params = optbase.apply_updates(state.params, updates)
        return TrainState(params=params, opt=opt_state, rng=rng), loss

    return step


def _donating_jit(step_fn):
    """``jax.jit(step_fn, static_argnames=("work",))``, called the same
    way, with the state's ``opt`` and ``rng`` donated and its ``params``
    kept: XLA writes the new optimizer state into the old one's buffers
    instead of the runtime allocating a buffer per leaf each step."""

    def step(params, owned, batch, work, *rest):
        opt_state, rng = owned
        return step_fn(TrainState(params=params, opt=opt_state, rng=rng),
                       batch, work, *rest)

    jitted = jax.jit(step, static_argnames=("work",), donate_argnums=(1,))

    def call(state: TrainState, batch, work, *rest):
        return jitted(state.params, (state.opt, state.rng), batch, work,
                      *rest)

    return call


def _donation_event(state: TrainState, reason: Optional[str]) -> dict:
    """The ``loop_donation`` event's fields: what each step call donates
    (``opt`` and ``rng``, or nothing and why) and keeps."""
    owned = jax.tree_util.tree_leaves((state.opt, state.rng))
    n_all = len(jax.tree_util.tree_leaves(state))
    if reason is not None:
        return dict(donated_leaves=0, donated_bytes=0, kept_leaves=n_all,
                    reason=reason)
    return dict(donated_leaves=len(owned),
                donated_bytes=int(sum(x.nbytes for x in owned)),
                kept_leaves=n_all - len(owned))


def run_kfac_training(loss_fn, opt: kfac_lib.Kfac, params, batches,
                      n_tokens: int, seed: int = 0, jit: bool = True,
                      callback=None,
                      state: Optional[TrainState] = None,
                      overlap: bool = False,
                      dist: Optional[specs_lib.DistSpec] = None,
                      obs: Optional[specs_lib.ObsSpec] = None,
                      ckpt: Optional[specs_lib.CkptSpec] = None,
                      resilience: Optional[specs_lib.ResilienceSpec] = None,
                      **legacy):
    """Python-level driver: dispatches the statically-masked step variants
    per the paper's T_* schedules (work scheduler; ``cfg.stagger`` phases
    heavy work; ``cfg.async_heavy``/``heavy_lag`` pipeline it).
    Subsystems are configured by the four ``repro.specs`` dataclasses:

    ``dist`` (:class:`~repro.specs.DistSpec`) — mesh + curvature_axis
    attach the distributed curvature engine so factor work shards across
    that mesh axis; row_axis adds the 2D path (dense M row-sharded over
    it, heavy FLOPs split across both axes) and curvature_compress
    routes the engine's U gathers through rank-q PowerSGD factors
    (lossy, opt-in).  ``overlap=True`` additionally dispatches launched
    heavy work through an :class:`AsyncInverseRunner` (replicated async
    configs only); otherwise landings compute in-graph — same result
    either way.

    Passing a restored ``state`` resumes: the schedule position is
    re-derived from ``state.opt.phase`` (step mod schedule cycle — kept
    inside the optimizer state exactly so an elastic restart that lost
    the global step counter continues the staggered heavy cadence
    instead of re-spiking every bucket at once).  An async config
    additionally restores the in-flight snapshots from
    ``state.opt.inflight``, so a landing scheduled before the save still
    fires on time after the restore.

    ``obs`` (:class:`~repro.specs.ObsSpec`) — its writer receives
    per-step ``step`` events and the async pipeline's launch/land/miss
    events; metrics_every > 0 additionally attaches an in-graph
    :class:`repro.obs.Meter` flushing the curvature-health metric buffer
    to the writer every that many steps.  Both are numerically inert.

    ``resilience`` (:class:`~repro.specs.ResilienceSpec`) — health
    (truthy, or a :class:`repro.train.health.HealthConfig`) swaps in the
    guarded resilient step and drives the staged remediation ladder:
    skip → damping escalation → forced heavy refresh → rollback (the
    last needs a ``ckpt`` spec).  A caller-built
    :class:`~repro.train.health.RemediationPolicy` can ride as policy
    for inspection; otherwise one is created internally.  A healthy run
    with health on is bit-for-bit identical to one with it off
    (tests/test_chaos.py).  chaos (a
    :class:`repro.train.chaos.ChaosMonkey`) injects its fault plan into
    the loop's hooks.

    ``ckpt`` (:class:`~repro.specs.CkptSpec`) — checkpoints every
    ``ckpt.every`` healthy steps into ``ckpt.dir`` (pruned to
    ``ckpt.keep``) and is where rollbacks restore from, walking past
    corrupted snapshots.

    The pre-spec flat kwargs (``mesh=``, ``writer=``, ``ckpt_dir=``, …)
    still work for one deprecation cycle — each warns once and folds
    into its spec (see :func:`repro.specs.consolidate_training_kwargs`).

    ``callback(k, state, loss)`` runs after each step.  Its
    ``state.params`` stay valid for as long as the caller keeps them;
    its ``state.opt`` and ``state.rng`` only until the next step, which
    (jitted, with no async runner) takes over their buffers.  A supplied
    ``state`` is copied once, not consumed.  With a writer, one
    ``loop_donation`` event says what each step donates.
    Returns (final TrainState, losses)."""
    dist, obs, ckpt, resilience = specs_lib.consolidate_training_kwargs(
        legacy, dist=dist, obs=obs, ckpt=ckpt, resilience=resilience,
        caller="run_kfac_training")
    dist.attach(opt)
    writer = obs.writer
    health, policy, chaos = (resilience.health, resilience.policy,
                             resilience.chaos)
    ckpt_dir, ckpt_every, ckpt_keep = ckpt.dir, ckpt.every, ckpt.keep
    from repro.train import checkpoint as ckpt_lib
    from repro.train import health as health_lib
    sched = opt.scheduler()
    runner = AsyncInverseRunner.for_opt(opt, writer=writer) \
        if overlap else None
    # The step donates the optimizer state and the key, which the loop
    # owns; not the params, which callbacks may keep, nor the batches.
    # The runner's worker reads ``state.opt`` after the step returns.
    no_donation = ("nojit" if not jit else
                   "runner" if runner is not None else None)
    k_off = 0
    if state is None:
        state = TrainState(params=params, opt=opt.init(params),
                           rng=jax.random.PRNGKey(seed))
    else:
        k_off = int(jax.device_get(state.opt.phase))
        if no_donation is None:
            state = state._replace(
                opt=jax.tree_util.tree_map(jnp.copy, state.opt),
                rng=jnp.copy(state.rng))
    if writer is not None:
        writer.emit("loop_donation", **_donation_event(state, no_donation))
    meter = obs.make_meter(opt)
    if health or policy is not None:
        hcfg = health if isinstance(health, health_lib.HealthConfig) \
            else None
        if policy is None:
            policy = health_lib.RemediationPolicy(hcfg, writer=writer)
        step_fn = health_lib.make_resilient_kfac_step(
            loss_fn, opt, n_tokens, health=policy.cfg, meter=meter)
    else:
        step_fn = make_scheduled_kfac_step(loss_fn, opt, n_tokens,
                                           meter=meter)
    if no_donation is None:
        step_fn = _donating_jit(step_fn)
    elif jit:
        step_fn = jax.jit(step_fn, static_argnames=("work",))
    mbuf = meter.init() if meter is not None else None
    losses = []
    for k, batch in enumerate(batches):
        kk = k_off + k
        # Chaos faults are keyed on the wall-clock loop iteration ``k``,
        # not the schedule step ``kk`` — a rollback re-anchors kk into
        # the past, and external faults must not replay with it.
        if chaos is not None:
            chaos.check(k)                        # host_loss raises here
            batch = chaos.corrupt_batch(k, batch)
            state = chaos.corrupt_state(k, state)
        with obs_trace.host_span(obs_trace.SCHEDULE):
            work = sched.work(kk)
            if policy is not None and policy.take_refresh():
                # Stage 2: abandon the (possibly poisoned) pipeline and
                # re-establish the inverse rep from the live M this step.
                work = opt.remedial_work()
                state = state._replace(opt=opt.clear_inflight(state.opt))
                if runner is not None:
                    runner.drop_pending(reason="dropped")
            if runner is not None and chaos is not None:
                chaos.harass_runner(k, runner)
            landing = runner.landing(work, step=kk) \
                if runner is not None else None
        t0 = time.perf_counter()
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
            elif meter is None:
                state, loss = step_fn(state, batch, work, landing)
            else:
                state, loss, mbuf = step_fn(state, batch, work, landing,
                                            mbuf)
        if runner is not None:
            runner.launch(state.opt, work, step=kk)
        with obs_trace.host_span(obs_trace.LOSS_SYNC):
            losses.append(float(loss))
        if writer is not None:
            writer.emit("step", step=kk, loss=float(loss),
                        dt_s=time.perf_counter() - t0, phase=work.label)
        faulty = False
        if policy is not None:
            rep = {name: float(v) for name, v in
                   jax.device_get(report).items()}
            faulty = policy.observe(kk, losses[-1], rep)
            if policy.take_rollback() and ckpt_dir is not None:
                # Stage 3: restore the newest snapshot that verifies,
                # walking past corrupt ones; re-anchor the schedule on
                # the restored phase so the staggered cadence resumes
                # without a heavy spike.
                if runner is not None:
                    runner.drop_pending(reason="dropped")
                state, man = ckpt_lib.restore_latest_healthy(ckpt_dir,
                                                             state)
                k_off = int(jax.device_get(state.opt.phase)) - (k + 1)
                policy.notify_rollback(kk, man["step"], ckpt_dir)
                if writer is not None:
                    writer.emit("ckpt_restore", step=int(man["step"]),
                                path=ckpt_dir)
                faulty = False          # restored state is healthy
        if (ckpt_dir is not None and ckpt_every > 0 and not faulty
                and kk % ckpt_every == 0):
            path = ckpt_lib.save(ckpt_dir, kk, state)
            ckpt_lib.prune(ckpt_dir, keep=ckpt_keep)
            if writer is not None:
                writer.emit("ckpt_save", step=kk, path=path)
            if chaos is not None:
                chaos.corrupt_ckpt(k, ckpt_dir)
        if callback is not None:
            with obs_trace.host_span(obs_trace.CALLBACK):
                callback(k, state, loss)
    if meter is not None:
        meter.drain(mbuf, int(jax.device_get(state.opt.step)))
    if runner is not None:
        runner.close()
    return state, losses
