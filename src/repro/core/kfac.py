"""The K-FAC optimizer family (K-FAC / R-KFAC / B-KFAC / B-R-KFAC /
B-KFAC-C / NS-KFAC) as a single policy-driven JAX optimizer.

Model contract
--------------
A K-FAC-compatible model provides *taps*: for every preconditioned matmul
``y = x @ W`` (W of shape (d_in, d_out), possibly stacked over scanned
layers / experts) the model

  * accepts a ``probes`` pytree — zeros of shape (*stack, n_stat, d_out)
    added to the layer output on an ``n_stat``-token slice, and
  * emits ``acts`` — the corresponding inputs, (*stack, n_stat, d_in).

``jax.grad`` w.r.t. a probe is exactly ∂L/∂y on that slice, so
(acts, probe-grads) are the paper's (A_k, G_k) K-factor square roots — the
functional replacement for PyTorch's forward/backward hooks.

Scheduling (paper §2.2/§6) is *static*: the trainer calls ``update`` with
a hashable :class:`repro.core.schedule.StepWork` mask derived from the
step number, so each step variant compiles to a lean HLO (production
pattern; also keeps the dry-run rooflines honest).  ``stats``/``light``
are global booleans; heavy work is *per factor bucket* as static slot
ranges, which is what lets the scheduler stagger heavy overwrites across
the T_inv window (constant small per-step cost instead of a spike) and
lets the distributed curvature engine shard them across the mesh.  The
legacy three python bools are still accepted for one deprecation cycle
(warn once, then converted to a uniform mask):

  do_stats  = k % T_updt == 0                      (EA absorb, all variants)
  do_light  = k % T_brand == 0   (B-variants: Brand update;   else no-op)
  do_heavy  = k % T_inv  == 0    (kfac: EVD, rkfac: RSVD, nskfac: NS)
            = k % T_rsvd == 0    (brkfac: RSVD overwrite)
            = k % T_corct == 0   (bkfacc: light correction)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import buckets, kfactor, policy, precond, schedule
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.optim import adamw as _adamw
from repro.optim import base as optbase

Array = jax.Array


# ---------------------------------------------------------------------------
# tap descriptions
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TapInfo:
    """Static description of one tapped matmul family."""
    param_path: str                 # "/"-joined path to W inside params
    d_in: int
    d_out: int
    stack: Tuple[int, ...] = ()     # leading stacked dims, e.g. (L,), (L, E)
    n_stat: int = 512               # stats tokens per layer per stats step
    linear_apply: bool = False      # Alg 8: step from factors, W stop-grad'd


@dataclasses.dataclass(frozen=True)
class KfacConfig:
    policy: policy.PolicyConfig = policy.PolicyConfig()
    lr: optbase.Schedule = optbase.constant(0.3)
    damping_phi: optbase.Schedule = optbase.constant(0.1)
    momentum: float = 0.0
    weight_decay: float = 7e-4
    clip: float = 0.07              # global-norm clip on the update
    spectrum_continuation: bool = True
    bucketed: bool = True           # cross-layer shape-class super-batching
    T_updt: int = 25
    T_inv: int = 250                # kfac / rkfac heavy period
    T_brand: int = 25               # B-variants light period
    T_rsvd: int = 250               # brkfac overwrite period
    T_corct: int = 500              # bkfacc correction period
    stagger: bool = False           # phase heavy work across the T window
    stagger_splits: int = 1         # max entry-aligned chunks per bucket
    async_heavy: bool = False       # two-phase launch/land heavy pipeline
    heavy_lag: int = 0              # steps between snapshot and swap-in
    # fallback optimizer for non-tapped params
    fallback_lr: optbase.Schedule = optbase.constant(1e-3)
    fallback_wd: float = 0.0

    def flags(self, step: int) -> Dict[str, bool]:
        """DEPRECATED legacy three-bool view of the step variant; the
        scheduler's StepWork masks (``Kfac.scheduler().work(step)``)
        subsumed it in PR 3.  Warns once, then delegates to
        schedule.legacy_flags (the variant → heavy-period mapping lives
        in one table in core/policy.py)."""
        from repro import specs as specs_lib
        specs_lib.warn_once(
            "KfacConfig.flags",
            "KfacConfig.flags(step) is deprecated; use "
            "Kfac.scheduler().work(step) (a StepWork mask) or "
            "Kfac.uniform_work(...)")
        return schedule.legacy_flags(self, step)


class TapState(NamedTuple):
    A: kfactor.KFactorState      # forward factor  (stacked over tap.stack)
    G: kfactor.KFactorState      # backward factor


class KfacState(NamedTuple):
    step: Array
    n_stats: Array               # how many stats batches absorbed
    phase: Array                 # step mod schedule cycle — lets an
                                 # elastic restart re-derive the staggered
                                 # work masks without the global step
    factors: Dict[str, TapState]
    momentum: Any                # tree over tapped params (or None)
    fallback: Any                # AdamW state over non-tapped params
    inflight: Dict[str, Any]     # bucket idx (str) → InflightState — the
                                 # async pipeline's double buffer; {} when
                                 # cfg.async_heavy is off, so pre-async
                                 # checkpoints keep restoring (no default:
                                 # a shared mutable {} on the class would
                                 # alias across every state)


# ---------------------------------------------------------------------------
# param-tree path helpers
# ---------------------------------------------------------------------------

def get_path(tree, path: str):
    node = tree
    for part in path.split("/"):
        node = node[part]
    return node


def set_path(tree, path: str, value):
    parts = path.split("/")
    def rec(node, i):
        if i == len(parts) - 1:
            new = dict(node)
            new[parts[i]] = value
            return new
        new = dict(node)
        new[parts[i]] = rec(node[parts[i]], i + 1)
        return new
    return rec(tree, 0)


def _split_params(params, taps: Dict[str, TapInfo]):
    """→ (tapped: {name: W}, rest: params-with-tapped-zeroed-out-paths)."""
    tapped = {name: get_path(params, t.param_path) for name, t in taps.items()}
    return tapped


def _untapped_mask(params, taps):
    """Boolean tree: True where the leaf is NOT owned by a tap."""
    paths = {t.param_path for t in taps.values()}
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def leaf_path(kp):
        return "/".join(str(k.key) for k in kp)

    return {leaf_path(kp) for kp, _ in flat} - paths


# ---------------------------------------------------------------------------
# the optimizer
# ---------------------------------------------------------------------------

class Kfac:
    """K-FAC optimizer over a tapped model. Not a pytree — holds statics.

    ``curvature`` (optional) is a distributed curvature engine (see
    ``repro.distributed.curvature.CurvatureEngine``) that shards each
    factor bucket's batch axis across a mesh axis; when attached, the
    bucketed factor work is delegated to it.  Duck-typed so core never
    imports the distributed package.

    ``mesh`` is the mesh the step runs over, engine or not (None: one
    device); ``repro.specs.DistSpec.attach`` sets it.  The kernels
    launched outside the engine run replicated over it.
    """

    def __init__(self, cfg: KfacConfig, taps: Dict[str, TapInfo],
                 curvature: Optional[Any] = None):
        self.cfg = cfg
        self.taps = dict(taps)
        self.curvature = curvature
        self.mesh = getattr(curvature, "mesh", None)
        self.specs = {}
        for name, t in self.taps.items():
            self.specs[name] = dict(
                A=policy.make_factor_spec(cfg.policy, t.d_in, t.n_stat),
                G=policy.make_factor_spec(cfg.policy, t.d_out, t.n_stat),
            )
        self._fallback = _adamw.adamw(cfg.fallback_lr,
                                      weight_decay=cfg.fallback_wd)
        # cross-layer shape-class buckets (static; resolved once here).
        # Factor work and preconditioning each collapse to one batched
        # launch per bucket instead of one per tap — O(#shape-classes)
        # instead of O(#layers) launches on the hot path.
        stacks = {n: t.stack for n, t in self.taps.items()}
        lin = {n: t.linear_apply for n, t in self.taps.items()}
        self.factor_buckets = buckets.build_factor_buckets(self.specs,
                                                           stacks)
        self.precond_buckets = buckets.build_precond_buckets(self.specs,
                                                             stacks, lin)
        # (name, side) → (bucket index, slot offset, slot count): the
        # per-tap path reads its heavy mask from the same bucket-indexed
        # StepWork the bucketed path consumes — one flag plumbing.
        self._slot = {}
        for bi, b in enumerate(self.factor_buckets):
            for e in b.entries:
                self._slot[(e.name, e.side)] = (bi, e.offset, e.count)
        # async pipeline: which buckets carry an in-flight double buffer,
        # and how many interim light panels each replays at landing
        self._async_buckets: Dict[int, int] = {
            bi: schedule.n_replay_panels(cfg, b.spec)
            for bi, b in enumerate(self.factor_buckets)
            if schedule.bucket_is_async(cfg, b.spec)}
        if self._async_buckets and not cfg.bucketed:
            raise ValueError("async_heavy requires bucketed=True (the "
                             "in-flight buffers live in bucket layout)")
        self._cycle = self.scheduler().cycle

    def scheduler(self, **kw) -> schedule.Scheduler:
        """A work scheduler over this optimizer's factor buckets; when a
        curvature engine is attached, heavy chunks auto-align to its
        ``align`` (slot-axis size × row-axis size on a 2D mesh) so
        staggered chunks stay SPMD-uniform AND split evenly across the
        row members."""
        if "align" not in kw and self.curvature is not None:
            kw["align"] = getattr(self.curvature, "align",
                                  self.curvature.n_devices)
        return schedule.Scheduler(self.cfg, self.factor_buckets, **kw)

    def uniform_work(self, do_stats: bool, do_light: bool, do_heavy: bool
                     ) -> schedule.StepWork:
        return schedule.uniform_work(do_stats, do_light, do_heavy,
                                     self.factor_buckets)

    def remedial_work(self) -> schedule.StepWork:
        """The forced-refresh mask of the remediation ladder (stage 2):
        full-range inline heavy + stats/light absorb, out of cadence —
        see :func:`repro.core.schedule.remedial_work`."""
        return schedule.remedial_work(self.cfg, self.factor_buckets)

    def clear_inflight(self, state: KfacState) -> KfacState:
        """Invalidate every in-flight heavy snapshot (the remediation
        ladder's "discard the poisoned inverse rep"): zeroed ``live``
        flags turn any still-scheduled landing into a per-slot no-op,
        so a snapshot taken before a detected fault can never swap
        corrupted state back over a freshly refreshed one."""
        if not state.inflight:
            return state
        inflight = {k: dataclasses.replace(
                        buf, live=jnp.zeros_like(buf.live))
                    for k, buf in state.inflight.items()}
        return state._replace(inflight=inflight)

    # -- state ------------------------------------------------------------
    def init(self, params) -> KfacState:
        factors = {}
        for name, t in self.taps.items():
            def stacked(spec):
                st = spec.init()
                for dim in reversed(t.stack):
                    st = jax.tree_util.tree_map(
                        lambda x: jnp.broadcast_to(x, (dim,) + x.shape), st)
                return st
            factors[name] = TapState(A=stacked(self.specs[name]["A"]),
                                     G=stacked(self.specs[name]["G"]))
        mom = None
        if self.cfg.momentum > 0:
            mom = {n: jnp.zeros_like(get_path(params, t.param_path),
                                     dtype=jnp.float32)
                   for n, t in self.taps.items()}
        # fallback adamw over the full tree (updates masked to untapped)
        fb = self._fallback.init(params)
        inflight = {str(bi): kfactor.make_inflight(
                        self.factor_buckets[bi].spec,
                        self.factor_buckets[bi].total, n_replay)
                    for bi, n_replay in self._async_buckets.items()}
        return KfacState(step=jnp.zeros((), jnp.int32),
                         n_stats=jnp.zeros((), jnp.int32),
                         phase=jnp.zeros((), jnp.int32),
                         factors=factors, momentum=mom, fallback=fb,
                         inflight=inflight)

    # -- per-tap pieces -----------------------------------------------------
    def _stats_factors(self, name, acts, probe_grads, n_tokens):
        """(X_A, X_G): K-factor square roots, (*stack, d, n_stat)."""
        t = self.taps[name]
        a = acts[name]                       # (*stack, n, d_in)
        g = probe_grads[name]                # (*stack, n, d_out)
        n = a.shape[-2]
        scale = 1.0 / jnp.sqrt(jnp.asarray(n, jnp.float32))
        X_A = jnp.swapaxes(a, -1, -2).astype(jnp.float32) * scale
        # probe grads are w.r.t. the *mean* loss → per-token grads are
        # O(1/n_tokens); rescale to per-token sum-loss grads (Martens-Grosse)
        X_G = (jnp.swapaxes(g, -1, -2).astype(jnp.float32)
               * jnp.asarray(n_tokens, jnp.float32) * scale)
        return X_A, X_G

    def _factor_update(self, name, side, st, X, key, first,
                       stats, light, heavy_b):
        """Per-tap factor update (comparison path): the tap's own stack is
        flattened into a batch of prod(stack) factors and stepped through
        the SAME per-bucket program the bucketed path runs
        (``kfactor.bucket_factor_step``) — one flag/mask plumbing for
        both paths, one launch per tap per side here.  ``heavy_b`` is a
        static python bool (all-or-nothing per tap: scheduler chunks are
        entry-aligned, so a tap's slots always share a phase)."""
        spec = self.specs[name][side]
        stack = self.taps[name].stack
        count = 1
        for dim in stack:
            count *= int(dim)
        flat = jax.tree_util.tree_map(
            lambda x: x.reshape((count,) + x.shape[len(stack):]), st)
        Xf = X.reshape((count,) + X.shape[len(stack):])
        keys = jax.random.split(key, count)
        flat = kfactor.bucket_factor_step(
            spec, flat, Xf, keys, first, stats, light,
            ((0, count),) if heavy_b else ())
        return jax.tree_util.tree_map(
            lambda x: x.reshape(stack + x.shape[1:]), flat)

    def _precondition(self, name, st: TapState, grad_w, phi,
                      g_factor=None, a_factor=None):
        """Preconditioned step for W (same shape as grad_w).

        Stacked-native end to end: damping, continuation, and the two-sided
        application are batched over the tap's stack, so scanned layers /
        expert stacks run as single batched (fused) kernel launches instead
        of vmapped 2D fallbacks.
        """
        cont = self.cfg.spectrum_continuation
        # NS-mode sides hold a dense damped inverse in U — plain GEMM apply
        dense_g = self.specs[name]["G"].mode is kfactor.Mode.NS
        dense_a = self.specs[name]["A"].mode is kfactor.Mode.NS
        if self.taps[name].linear_apply:
            # Alg 8: step from gradient factors; grad_w is unused (stop-grad)
            S = precond.precondition_linear_with_damping(
                g_factor, a_factor, st.G.U, st.G.D, st.A.U, st.A.D, phi,
                continuation=cont,
                dense_g=dense_g, dense_a=dense_a)
        else:
            J = jnp.swapaxes(grad_w, -1, -2).astype(jnp.float32)
            S = precond.precondition_with_damping(
                J, st.G.U, st.G.D, st.A.U, st.A.D, phi,
                continuation=cont,
                dense_g=dense_g, dense_a=dense_a)
        return jnp.swapaxes(S, -1, -2)       # back to (d_in, d_out) layout

    # -- bucketed (cross-layer) pieces --------------------------------------
    def collect_factor_operands(self, factors, acts, probe_grads,
                                n_tokens):
        """Per-(tap, side) state/stats-factor dicts in bucket-entry keying
        — shared by the replicated bucketed path and the distributed
        curvature engine."""
        states, X_all = {}, {}
        for name in sorted(self.taps):
            X_A, X_G = self._stats_factors(name, acts, probe_grads,
                                           n_tokens)
            X_all[(name, "A")], X_all[(name, "G")] = X_A, X_G
            states[(name, "A")] = factors[name].A
            states[(name, "G")] = factors[name].G
        return states, X_all

    def repack_factors(self, states) -> Dict[str, TapState]:
        return {name: TapState(A=states[(name, "A")],
                               G=states[(name, "G")])
                for name in self.taps}

    def _work_ranges(self, work: schedule.StepWork, bi: int):
        """(launch, land) per-bucket ranges — empty for legacy masks
        whose launch/land tuples were never populated."""
        launch = work.launch[bi] if bi < len(work.launch) else ()
        land = work.land[bi] if bi < len(work.land) else ()
        return launch, land

    def _bucketed_factor_work(self, factors, inflight, acts, probe_grads,
                              n_tokens, rng, first,
                              work: schedule.StepWork,
                              bucket_step=None, landing=None, phi=None):
        """Factor updates as one batched launch group per shape-class
        bucket: stats absorbs (EA SYRK), Brand panels + CholeskyQR2, and
        the scheduled heavy slot ranges each run over the bucket's flat
        batch axis; async buckets additionally run this step's pipeline
        phases (panel ring, launch snapshot, land swap) against their
        in-flight buffer.

        ``bucket_step(bi, bucket, st, X, keys, buf, landed)`` overrides
        the inner per-bucket program (the distributed curvature engine
        substitutes its shard_map-wrapped one) and returns ``(st, buf)``;
        the surrounding loop — operand collection, no-op skip, gather,
        per-slot key split, scatter — exists ONLY here, so the sharded
        path can never diverge from the replicated one structurally.

        ``landing`` optionally maps bucket idx (str) → tuple of
        pre-computed (U, D, aux) triples, one per land range, from an
        overlapped dispatch (train.loop.AsyncInverseRunner).  ``phi``
        (the step's damping ratio) only feeds telemetry — the
        inversion-error proxy needs the same λ the preconditioner will
        derive."""
        if bucket_step is None:
            def bucket_step(bi, bucket, st, X, keys, buf, landed):
                launch, land = self._work_ranges(work, bi)
                return kfactor.bucket_factor_step_async(
                    bucket.spec, st, X, keys, first, work.stats,
                    work.light, work.heavy[bi], launch, land, buf,
                    landed=landed)
        states, X_all = self.collect_factor_operands(factors, acts,
                                                     probe_grads, n_tokens)
        inflight = dict(inflight)
        bkeys = jax.random.split(rng, len(self.factor_buckets))
        for bi, (bkey, bucket) in enumerate(zip(bkeys,
                                                self.factor_buckets)):
            launch, land = self._work_ranges(work, bi)
            if not kfactor.has_work(bucket.spec, work.stats, work.light,
                                    bool(work.heavy[bi] or launch
                                         or land)):
                continue        # whole bucket is a no-op this step
            st = buckets.gather_states(bucket.entries, states)
            X = buckets.gather(bucket.entries, X_all)
            keys = jax.random.split(bkey, bucket.total)
            buf = inflight.get(str(bi))
            landed = None if landing is None else landing.get(str(bi))
            with obs_trace.span(f"kfac/factor/b{bi}_"
                                f"{bucket.spec.mode.value}"):
                st, buf = bucket_step(bi, bucket, st, X, keys, buf, landed)
            if buf is not None:
                inflight[str(bi)] = buf
            self._record_bucket_metrics(bi, bucket, st, work, land, phi)
            states.update(buckets.scatter_states(bucket.entries, st))
        return self.repack_factors(states), inflight

    # -- telemetry (repro.obs) ----------------------------------------------
    def _record_bucket_metrics(self, bi, bucket, st, work, land, phi):
        """Per-bucket metrics off the post-step state — for the sharded
        engine this is the post-all-gather state at the outer trace
        level, so nothing here ever records from inside shard_map.
        Every record is a no-op without an active collector, and the
        derived metrics below only *enter the graph* when one is active
        (the metrics-off step stays the exact un-instrumented graph)."""
        if not obs_metrics.active():
            return
        spec = bucket.spec
        fired = (sum(hi - lo for lo, hi in work.heavy[bi])
                 + sum(hi - lo for lo, hi in land))
        obs_metrics.record(f"bucket{bi}/heavy_slots", float(fired))
        if bi in self._async_buckets:
            obs_metrics.record(f"bucket{bi}/replay_depth",
                               float(self._async_buckets[bi]))
        if not fired:
            return
        if spec.mode is kfactor.Mode.NS:
            obs_metrics.record(f"bucket{bi}/ns_lam",
                               jnp.mean(st.aux[..., kfactor.AUX_LAM]))
            obs_metrics.record(f"bucket{bi}/ns_res",
                               jnp.max(st.aux[..., kfactor.AUX_RES]))
        if spec.mode in (kfactor.Mode.EVD, kfactor.Mode.RSVD,
                         kfactor.Mode.BRAND_RSVD):
            obs_metrics.record(f"bucket{bi}/trunc_mass",
                               jnp.max(st.aux[..., kfactor.AUX_TRUNC]))
        if spec.needs_m and phi is not None:
            obs_metrics.record(f"bucket{bi}/inv_err",
                               self._inv_error_proxy(spec, st, phi))

    def _inv_error_proxy(self, spec, st, phi):
        """Streaming inversion-error proxy: worst-slot
        ‖((M + λI) X − I)[rows]‖_F / √k over k ≤ 8 strided rows, where
        X is the held inverse representation and λ is exactly the
        damping the preconditioner derives (NS: the baked-in λ̂ from
        aux; low-rank: φ·max D plus the §3.5 continuation shift).
        O(k·d·w) per bucket and only computed on heavy-firing steps of
        an instrumented run — never on the metrics-off path."""
        d = spec.d
        k = min(8, d)
        idx = jnp.arange(k) * max(1, d // k)
        Mrows = jnp.take(st.M, idx, axis=-2)                 # (B, k, d)
        ek = jnp.eye(d, dtype=Mrows.dtype)[idx]              # (k, d)
        if spec.mode is kfactor.Mode.NS:
            lam = st.aux[..., kfactor.AUX_LAM]
            Y = (Mrows + lam[..., None, None] * ek) @ st.U
        else:
            D, lam = precond._damped(st.D, phi,
                                     self.cfg.spectrum_continuation)
            Y = precond.apply_inv_right(
                Mrows + lam[..., None, None] * ek, st.U, D, lam)
        R = Y - ek
        return jnp.max(jnp.sqrt(jnp.sum(R * R, axis=(-2, -1)) / k))

    def _bucketed_precondition(self, factors, grads, acts, probe_grads,
                               phi):
        """Preconditioned steps for every tap, one batched (fused) launch
        per (A-spec, G-spec, apply-mode) bucket.  Returns {name: S} with S
        in the tap's (…, d_in, d_out) parameter layout.

        Everything is gathered and applied directly in *parameter layout*:
        the inverse factors are symmetric, so  Ā⁻¹ gW Γ̄⁻¹  (the two-sided
        application with the factor roles swapped) equals the transposed
        textbook form  (Γ̄⁻¹ gWᵀ Ā⁻¹)ᵀ  without ever transposing.  This
        matters: a transpose *feeding a concatenate* must materialize
        (unlike the per-tap path, where XLA fuses it into the matmul), and
        a bucket's J gather is tens of MB per step on real models.
        """
        cont = self.cfg.spectrum_continuation
        out = {}
        for pbi, bucket in enumerate(self.precond_buckets):
            ent = bucket.entries
            # role swap: the positional "g" slot below carries the A factor
            # (and vice versa), so the NS dense flags swap with it
            dense_swap_g = bucket.spec_a.mode is kfactor.Mode.NS
            dense_swap_a = bucket.spec_g.mode is kfactor.Mode.NS
            key = lambda e: (e.name, "")
            U_g = buckets.gather(ent, {key(e): factors[e.name].G.U
                                       for e in ent})
            D_g = buckets.gather(ent, {key(e): factors[e.name].G.D
                                       for e in ent})
            U_a = buckets.gather(ent, {key(e): factors[e.name].A.U
                                       for e in ent})
            D_a = buckets.gather(ent, {key(e): factors[e.name].A.D
                                       for e in ent})
            with obs_trace.span(f"kfac/precond/b{pbi}"):
                if bucket.linear_apply:
                    # Alg 8 with roles swapped:  S = (Ā⁻¹ A)(Gᵀ Γ̄⁻¹) —
                    # the raw (…, n, d) factors concatenate contiguously
                    # and the single post-gather transpose fuses into
                    # the matmul.
                    gfac = jnp.swapaxes(buckets.gather(ent, {
                        key(e): probe_grads[e.name] for e in ent}),
                        -1, -2).astype(jnp.float32)      # (B, d_out, n)
                    afac = jnp.swapaxes(buckets.gather(ent, {
                        key(e): acts[e.name] for e in ent}),
                        -1, -2).astype(jnp.float32)      # (B, d_in, n)
                    S = precond.precondition_linear_with_damping(
                        afac, gfac, U_a, D_a, U_g, D_g, phi,
                        continuation=cont,
                        dense_g=dense_swap_g, dense_a=dense_swap_a)
                else:
                    J = buckets.gather(ent, {
                        key(e): get_path(grads,
                                         self.taps[e.name].param_path)
                        for e in ent}).astype(jnp.float32)
                    S = precond.precondition_with_damping(
                        J, U_a, D_a, U_g, D_g, phi,
                        continuation=cont,
                        dense_g=dense_swap_g, dense_a=dense_swap_a)
            out.update({name: Se for (name, _), Se
                        in buckets.scatter(ent, S).items()})
        return out

    # -- the update ---------------------------------------------------------
    def update(self, grads, state: KfacState, params, *, acts, probe_grads,
               n_tokens, rng, work: Optional[schedule.StepWork] = None,
               do_stats: Optional[bool] = None,
               do_light: Optional[bool] = None,
               do_heavy: Optional[bool] = None, landing=None,
               damping_scale=None):
        """One optimizer step.  ``work`` is a static, hashable StepWork
        mask (jit with ``static_argnames=("work",)``); the legacy three
        python bools are accepted as a shim and converted to the
        equivalent uniform (spiky) mask.  ``landing`` optionally carries
        pre-computed heavy results (bucket idx str → ((U, D, aux), …)
        per land range) from an overlapped dispatch; absent, landings
        compute in-graph from the in-flight snapshot.

        ``damping_scale`` (optional traced scalar) multiplies the
        scheduled damping ratio φ — the remediation ladder's stage-1
        escalation knob (train/health.py).  A scale of exactly 1.0 is
        bit-inert (float multiply by 1.0 is exact), which is what keeps
        the health-guarded step's healthy-run outputs identical to the
        unguarded step's.

        On a mesh (``mesh``, set by ``repro.specs.DistSpec.attach``) the
        Pallas kernels launched outside the curvature engine's
        ``shard_map`` bodies run replicated over it
        (``kernels.ops.kernel_mesh``)."""
        with ops.kernel_mesh(self.mesh):
            return self._update(grads, state, params, acts, probe_grads,
                                n_tokens, rng, work, do_stats, do_light,
                                do_heavy, landing, damping_scale)

    def _update(self, grads, state, params, acts, probe_grads, n_tokens,
                rng, work, do_stats, do_light, do_heavy, landing,
                damping_scale):
        cfg = self.cfg
        if work is None:
            from repro import specs as specs_lib
            specs_lib.warn_once(
                "Kfac.update:bools",
                "Kfac.update(do_stats=, do_light=, do_heavy=) is "
                "deprecated; pass work=Kfac.uniform_work(...) (a StepWork "
                "mask, jit static_argnames=('work',))")
            work = self.uniform_work(bool(do_stats), bool(do_light),
                                     bool(do_heavy))
        first = state.n_stats == 0
        phi = cfg.damping_phi(state.step)
        if damping_scale is not None:
            phi = phi * damping_scale
        lr = cfg.lr(state.step)
        if obs_metrics.active():
            slots = lambda t: float(sum(hi - lo for r in t
                                        for lo, hi in r))
            obs_metrics.record("work/stats_fired",
                               1.0 if work.stats else 0.0)
            obs_metrics.record("work/light_fired",
                               1.0 if work.light else 0.0)
            obs_metrics.record("work/heavy_slots", slots(work.heavy))
            obs_metrics.record("work/launch_slots", slots(work.launch))
            obs_metrics.record("work/land_slots", slots(work.land))
            obs_metrics.record("precond/damping_phi", phi)

        # 1) factor updates -------------------------------------------------
        factors = dict(state.factors)
        inflight = dict(state.inflight)
        if work.any and self.curvature is not None and cfg.bucketed:
            factors, inflight = self.curvature.factor_work(
                self, factors, inflight, acts, probe_grads, n_tokens, rng,
                first, work, landing=landing, phi=phi)
        elif work.any and cfg.bucketed:
            factors, inflight = self._bucketed_factor_work(
                factors, inflight, acts, probe_grads, n_tokens, rng,
                first, work, landing=landing, phi=phi)
        elif work.any:
            if work.any_async:
                raise ValueError("async launch/land masks require the "
                                 "bucketed optimizer path")
            keys = jax.random.split(rng, 2 * len(self.taps))
            for i, name in enumerate(sorted(self.taps)):
                X_A, X_G = self._stats_factors(name, acts, probe_grads,
                                               n_tokens)
                heavy = {side: work.entry_heavy(*self._slot[(name, side)])
                         for side in ("A", "G")}
                stA = self._factor_update(name, "A", factors[name].A, X_A,
                                          keys[2 * i], first, work.stats,
                                          work.light, heavy["A"])
                stG = self._factor_update(name, "G", factors[name].G, X_G,
                                          keys[2 * i + 1], first,
                                          work.stats, work.light,
                                          heavy["G"])
                factors[name] = TapState(A=stA, G=stG)

        # 2) preconditioned updates for tapped params -----------------------
        if cfg.bucketed:
            S_all = self._bucketed_precondition(factors, grads, acts,
                                                probe_grads, phi)
        else:
            S_all = {}
            for name, t in self.taps.items():
                gW = get_path(grads, t.param_path)
                gfac = afac = None
                if t.linear_apply:
                    a = acts[name]
                    g = probe_grads[name]
                    afac = jnp.swapaxes(a, -1, -2).astype(jnp.float32)
                    gfac = jnp.swapaxes(g, -1, -2).astype(jnp.float32)
                S_all[name] = self._precondition(name, factors[name], gW,
                                                 phi, g_factor=gfac,
                                                 a_factor=afac)
        updates = grads  # start from grads; overwrite tapped leaves
        new_mom = dict(state.momentum) if state.momentum is not None else None
        for name, t in self.taps.items():
            W = get_path(params, t.param_path)
            S = S_all[name] + cfg.weight_decay * W.astype(jnp.float32)
            if new_mom is not None:
                m = cfg.momentum * new_mom[name] + S
                new_mom[name] = m
                S = m
            updates = set_path(updates, t.param_path, S)

        # 3) clip + lr for tapped; AdamW for the rest ------------------------
        tapped_paths = {t.param_path for t in self.taps.values()}
        fb_updates, fb_state = self._fallback.update(grads, state.fallback,
                                                     params)

        def finalize(path_keys, kfac_u, fb_u):
            path = "/".join(str(k.key) for k in path_keys)
            if path in tapped_paths:
                return (-lr * kfac_u.astype(jnp.float32))
            return fb_u

        updates = jax.tree_util.tree_map_with_path(finalize, updates,
                                                   fb_updates)
        if cfg.clip > 0:
            updates = optbase.clip_by_global_norm(updates,
                                                  jnp.asarray(cfg.clip))

        new_state = KfacState(
            step=state.step + 1,
            n_stats=state.n_stats + jnp.asarray(work.stats, jnp.int32),
            phase=(state.phase + 1) % jnp.asarray(self._cycle, jnp.int32),
            factors=factors,
            momentum=new_mom,
            fallback=fb_state,
            inflight=inflight,
        )
        return updates, new_state
