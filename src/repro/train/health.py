"""In-graph numerical-health guards + the staged remediation ladder.

The paper's graceful-degradation contract (Props 4.1/4.2: a stale or
B-only inverse strictly beats *no* update) means the safe response to
almost any numerical fault is "do less curvature work, never apply a
poisoned update" — which is exactly what this module enacts, in four
escalating stages:

  stage 0  **skip**      — the in-graph guard: a step whose grads,
                           preconditioned updates, or post-step factor
                           states contain nonfinite values (or explode
                           past a threshold) applies *no* update at all;
                           params and optimizer state revert via a
                           bitwise ``where`` select, so the poisoned
                           step simply never happened.
  stage 1  **escalate**  — persistent faults or loss divergence scale
                           the damping ratio φ up (``damping_scale``,
                           a traced scalar into ``Kfac.update``), the
                           classic trust-region response.  De-escalates
                           after ``recovery_steps`` healthy steps.
  stage 2  **refresh**   — a *forced out-of-cadence heavy refresh*
                           (:meth:`Kfac.remedial_work`): the inverse rep
                           is re-established from the live M this step
                           and every in-flight async snapshot is
                           discarded (``Kfac.clear_inflight``) — the
                           RS-KFAC-style "re-establish curvature from
                           scratch" escape hatch.
  stage 3  **rollback**  — restore the newest *healthy* checkpoint
                           (``checkpoint.restore_latest_healthy``) when
                           the fault persists past the refresh.

Detection is **jit/shard_map-safe and in-graph**: per-bucket checks run
at the outer trace level off the post-step factor states (post
all-gather under the sharded curvature engine, exactly like
``Kfac._record_bucket_metrics``), NS-residual blowup rides the existing
``KFactorState.aux`` channels, and the same values feed the obs metric
buffer when a collector is active — so replicated and sharded runs
report identically.  The policy itself
(:class:`RemediationPolicy`) is host-side python: it consumes the tiny
:func:`health_report` dict the step returns (the trainer already syncs
the loss every step, so this adds no extra device round-trip) and
decides the *next* step's remediation.

**Inertness contract** (the PR 7 meter's, extended): a healthy run with
guards on is *bit-for-bit identical* to one with them off.  The guard
only reads hot-path values; the final select is ``where(ok, new, old)``
— an exact element pick, no arithmetic — and the stage-1 knob
multiplies φ by exactly 1.0 until escalated.  Asserted across all six
policy variants, the async pipeline, and the 8-device sharded engine in
tests/test_chaos.py and the ``step/health_on_vs_off`` bench row.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.core import kfactor
from repro.models import layers
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.optim import base as optbase

Array = jax.Array

#: remediation-ladder stage codes (the ``stage`` field of
#: ``remediation`` telemetry events)
STAGE_SKIP = 0
STAGE_DAMP = 1
STAGE_REFRESH = 2
STAGE_ROLLBACK = 3
STAGE_ELASTIC = 4


@dataclasses.dataclass(frozen=True)
class HealthConfig:
    """Thresholds for the in-graph guards + ladder pacing.

    The explosion thresholds are deliberately loose (guards are a last
    line of defense, not a clipper — ``KfacConfig.clip`` already bounds
    healthy updates); the ladder counters are in *consecutive faulty
    steps*.
    """
    grad_abs_max: float = 1e8        # |g|_max past this trips the guard
    update_abs_max: float = 1e8      # |Δ|_max past this trips the guard
    loss_div_factor: float = 30.0    # loss > factor × EMA ⇒ divergence
    loss_ema: float = 0.9            # EMA decay for the divergence ref
    ns_res_max: float = kfactor._NS_RES_MAX   # NS residual blowup
    escalation: float = 8.0          # φ multiplier per stage-1 action
    max_escalations: int = 2
    refresh_after: int = 3           # faulty streak ⇒ forced refresh
    rollback_after: int = 6          # faulty streak ⇒ checkpoint rollback
    recovery_steps: int = 4          # healthy streak ⇒ de-escalate φ


# ---------------------------------------------------------------------------
# in-graph report
# ---------------------------------------------------------------------------

def _count_nonfinite(tree) -> Array:
    n = jnp.zeros((), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(tree):
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
            n = n + jnp.sum(~jnp.isfinite(leaf)).astype(jnp.float32)
    return n


def _abs_max(tree) -> Array:
    m = jnp.zeros((), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(tree):
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
            m = jnp.maximum(m, jnp.max(jnp.abs(leaf)).astype(jnp.float32))
    return m


def factor_report(opt, factors) -> Dict[str, Array]:
    """Per-bucket factor-state checks off the live (post-step) states:
    nonfinite counts over (U, D[, M]) and, for NS buckets, the worst
    residual from the ``aux`` diagnostics channel.  Runs at the outer
    trace level — under the sharded curvature engine the states here are
    the post-all-gather ones, so every host computes the same report."""
    out: Dict[str, Array] = {}
    for bi, bucket in enumerate(opt.factor_buckets):
        bad = jnp.zeros((), jnp.float32)
        res = jnp.zeros((), jnp.float32)
        for e in bucket.entries:
            st = getattr(factors[e.name], e.side)
            bad = bad + _count_nonfinite((st.U, st.D))
            if bucket.spec.needs_m:
                bad = bad + _count_nonfinite(st.M)
            if bucket.spec.mode is kfactor.Mode.NS:
                res = jnp.maximum(res,
                                  jnp.max(st.aux[..., kfactor.AUX_RES]))
        out[f"bucket{bi}/factor_nonfinite"] = bad
        if bucket.spec.mode is kfactor.Mode.NS:
            out[f"bucket{bi}/ns_res"] = res
    return out


def health_report(hcfg: HealthConfig, opt, loss, grads, updates,
                  opt_state) -> Dict[str, Array]:
    """The step's health vector: a flat dict of f32 scalars with a fixed
    key set (same pytree for every step variant).  ``ok`` is the
    in-graph guard verdict — 1.0 iff the step is safe to apply."""
    rep: Dict[str, Array] = {}
    rep["grad_nonfinite"] = _count_nonfinite(grads)
    rep["grad_abs_max"] = _abs_max(grads)
    rep["update_nonfinite"] = _count_nonfinite(updates)
    rep["update_abs_max"] = _abs_max(updates)
    frep = factor_report(opt, opt_state.factors)
    rep.update(frep)
    factor_bad = jnp.zeros((), jnp.float32)
    for k, v in frep.items():
        if k.endswith("factor_nonfinite"):
            factor_bad = factor_bad + v
    ok = (jnp.isfinite(loss)
          & (rep["grad_nonfinite"] == 0)
          & (rep["grad_abs_max"] < hcfg.grad_abs_max)
          & (rep["update_nonfinite"] == 0)
          & (rep["update_abs_max"] < hcfg.update_abs_max)
          & (factor_bad == 0))
    rep["ok"] = ok.astype(jnp.float32)
    return rep


def _record_health(report: Dict[str, Array]) -> None:
    """Mirror the report into the obs metric buffer (no-op without an
    active collector — the metrics-off graph is untouched)."""
    if not obs_metrics.active():
        return
    obs_metrics.record("health/guard_trips", 1.0 - report["ok"])
    obs_metrics.record("health/grad_nonfinite", report["grad_nonfinite"])
    obs_metrics.record("health/update_nonfinite",
                       report["update_nonfinite"])
    for k, v in report.items():
        if k.endswith("factor_nonfinite"):
            obs_metrics.record(f"health/{k}", v)


def _select(ok, new, old):
    """Bitwise per-leaf pick: ``new`` where ok, else ``old`` — exact
    (no arithmetic), so ok=True returns ``new`` bit-for-bit."""
    return jax.tree_util.tree_map(lambda n, o: jnp.where(ok, n, o),
                                  new, old)


def make_resilient_kfac_step(loss_fn, opt, n_tokens: int,
                             health: Optional[HealthConfig] = None,
                             probe_dtype=jnp.float32, meter=None):
    """``make_scheduled_kfac_step`` with the in-graph guard wrapped
    around it.  Returns ``step(state, batch, work, landing=None,
    mbuf=None, damping_scale=None) -> (state, loss, report[, mbuf])`` —
    jit with ``static_argnames=("work",)``.

    A step whose report says not-ok applies nothing: params and the
    whole optimizer state (factors, inflight buffers, counters) revert
    to their pre-step values, so a poisoned batch can neither move the
    params nor seed the curvature statistics.  ``damping_scale`` is the
    ladder's stage-1 knob (traced, so escalation never recompiles)."""
    from repro.train import loop as loop_lib
    hcfg = health if health is not None else HealthConfig()

    def step(state, batch, work, landing=None, mbuf=None,
             damping_scale=None):
        rng, sub = jax.random.split(state.rng)
        probes = layers.make_probes(opt.taps, probe_dtype)
        loss, acts, gp, gprobe = loop_lib.kfac_grads(
            loss_fn, state.params, probes, batch)

        def body():
            with obs_trace.span("update"):
                updates, opt_state = opt.update(
                    gp, state.opt, state.params, acts=acts,
                    probe_grads=gprobe, n_tokens=n_tokens, rng=sub,
                    work=work, landing=landing,
                    damping_scale=damping_scale)
                report = health_report(hcfg, opt, loss, gp, updates,
                                       opt_state)
                _record_health(report)
                ok = report["ok"] > 0
                params = optbase.apply_updates(state.params, updates)
                params = _select(ok, params, state.params)
                opt_state = _select(ok, opt_state, state.opt)
            return params, opt_state, report

        if meter is None:
            params, opt_state, report = body()
            return (loop_lib.TrainState(params=params, opt=opt_state,
                                        rng=rng), loss, report)
        with meter.collecting() as col:
            params, opt_state, report = body()
        mbuf = meter.maybe_flush(meter.merge(mbuf, col), opt_state.step)
        return (loop_lib.TrainState(params=params, opt=opt_state,
                                    rng=rng), loss, report, mbuf)

    return step


# ---------------------------------------------------------------------------
# the staged policy (host side)
# ---------------------------------------------------------------------------

class RemediationPolicy:
    """Consumes one :func:`health_report` per step and decides the next
    step's remediation.  Pure host-side state machine; every enacted
    action lands in ``self.actions`` and (when a writer is attached) as
    a ``remediation`` telemetry event.

    The trainer's contract (see ``loop.run_kfac_training``):

      * pass ``jnp.float32(policy.damping_scale)`` into the resilient
        step each step;
      * before building a step's work mask, if :meth:`take_refresh` is
        true, substitute ``opt.remedial_work()``, clear the in-flight
        buffers, and drop any pending async futures;
      * after the step, call :meth:`observe`;
      * if :meth:`take_rollback` is true, restore the newest healthy
        checkpoint and call :meth:`notify_rollback`.
    """

    def __init__(self, cfg: Optional[HealthConfig] = None, writer=None):
        self.cfg = cfg if cfg is not None else HealthConfig()
        self.writer = writer
        self.damping_scale: float = 1.0
        self.actions: List[dict] = []
        self._streak = 0
        self._healthy = 0
        self._escalations = 0
        self._loss_ema: Optional[float] = None
        self._refresh_pending = False
        self._rollback_pending = False

    # -- event plumbing ----------------------------------------------------
    def _emit(self, step: int, stage: int, action: str, detail: str):
        rec = dict(step=int(step), stage=int(stage), action=action,
                   detail=detail)
        self.actions.append(rec)
        if self.writer is not None:
            self.writer.emit("remediation", **rec)

    # -- per-step observation ----------------------------------------------
    def observe(self, step: int, loss: float,
                report: Dict[str, float]) -> bool:
        """Feed one step's (host-fetched) loss + health report.  Returns
        True iff the step was faulty."""
        cfg = self.cfg
        ok = report.get("ok", 1.0) >= 1.0
        diverged = not math.isfinite(loss)
        if not diverged and self._loss_ema is not None:
            diverged = loss > cfg.loss_div_factor * max(self._loss_ema,
                                                        1e-12)
        ns_blow = any(v >= cfg.ns_res_max for k, v in report.items()
                      if k.endswith("/ns_res"))
        fault = (not ok) or diverged or ns_blow
        if not fault:
            self._loss_ema = (loss if self._loss_ema is None else
                              cfg.loss_ema * self._loss_ema
                              + (1.0 - cfg.loss_ema) * loss)
            self._streak = 0
            self._healthy += 1
            if (self.damping_scale != 1.0
                    and self._healthy >= cfg.recovery_steps):
                self.damping_scale = 1.0
                self._escalations = 0
                self._emit(step, STAGE_DAMP, "deescalate",
                           f"healthy for {self._healthy} steps: damping "
                           f"scale -> 1")
            return False
        self._healthy = 0
        self._streak += 1
        why = []
        if not ok:
            why.append("in-graph guard tripped "
                       f"(grad_nonfinite={report.get('grad_nonfinite', 0):g}"
                       f", update_nonfinite="
                       f"{report.get('update_nonfinite', 0):g})")
        if diverged:
            ref = self._loss_ema if self._loss_ema is not None else 0.0
            why.append(f"loss divergence ({loss:.4g} vs ema {ref:.4g})")
        if ns_blow:
            why.append("NS residual blowup")
        detail = "; ".join(why)
        if not ok:
            self._emit(step, STAGE_SKIP, "skip",
                       f"update skipped in-graph: {detail}")
        if self._streak >= cfg.rollback_after:
            self._rollback_pending = True
            self._streak = 0
            self._emit(step, STAGE_ROLLBACK, "rollback",
                       f"{detail}; restoring newest healthy checkpoint")
        elif self._streak % cfg.refresh_after == 0:
            self._refresh_pending = True
            self._emit(step, STAGE_REFRESH, "refresh",
                       f"{detail}; forcing out-of-cadence heavy refresh "
                       f"(in-flight snapshots discarded)")
        elif self._escalations < cfg.max_escalations:
            self._escalations += 1
            old = self.damping_scale
            self.damping_scale = old * cfg.escalation
            self._emit(step, STAGE_DAMP, "escalate",
                       f"{detail}; damping scale {old:g} -> "
                       f"{self.damping_scale:g}")
        return True

    # -- trainer hooks ------------------------------------------------------
    def take_refresh(self) -> bool:
        """True once per scheduled forced refresh (consumed)."""
        pending, self._refresh_pending = self._refresh_pending, False
        return pending

    def take_rollback(self) -> bool:
        """True once per scheduled checkpoint rollback (consumed)."""
        pending, self._rollback_pending = self._rollback_pending, False
        return pending

    def notify_rollback(self, step: int, restored_step: int,
                        path: str) -> None:
        self._emit(step, STAGE_ROLLBACK, "restored",
                   f"rolled back to healthy step {restored_step} "
                   f"from {path}")

    def count(self, action: str) -> int:
        return sum(1 for a in self.actions if a["action"] == action)
