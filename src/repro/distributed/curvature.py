"""Distributed curvature engine: shard the bucketed K-factor pipeline
across one or two mesh axes.

The paper's preconditioning cost is linear in layer size, but a replicated
optimizer still makes *every* device run *every* layer's curvature work —
stats absorbs, Brand panels/CholeskyQR2, and the heavy EVD/RSVD/correction
overwrites are recomputed N-fold on an N-device mesh.  KAISA and the
distributed K-FAC line (PAPERS.md) fix this by assigning each factor to
one device and broadcasting the small inverse representation; this module
is that idea applied to the *bucketed* pipeline of ``core/buckets.py``:

  * each factor bucket's flat batch axis is partitioned across the mesh's
    **curvature axis** with a round-robin slot → device assignment
    (``buckets.shard_perm``): slot ``s`` lives on device ``s % N``, so
    every device owns an equal ``⌈B/N⌉`` share of every bucket;
  * inside ``jax.shard_map`` each device runs the SAME
    per-bucket program as the replicated path
    (``kfactor.bucket_factor_step``) on its local shard — stats, Brand,
    and the scheduled heavy ranges all cost 1/N of the replicated work;
  * the updated low-rank reps (U, λ) are **all-gathered** — they are
    O(d·r) per factor, far cheaper to communicate than to recompute —
    while the dense EA factor M (O(d²)) is *never all-gathered across
    the curvature axis*: only the slot's owning device ever reads it, so
    its out_spec keeps it sharded there.

2D mesh (``row_axis``) — the scale-out generalization
-----------------------------------------------------
With a second mesh axis (canonically ``data`` × ``curv``), the engine
additionally shards each bucket's stacked dense M **by rows** over the
``row_axis``: a (B, d, d) bucket M lives as (B/N_curv, d/N_rows, d) per
device — per-device K-factor memory drops from O(d²) to O(d²/N) across
the whole mesh, not just 1/N_curv.  The pieces:

  * **stats** stay exact on row blocks: every element of X Xᵀ is an
    independent full-length dot product, so the EA absorb of a row block
    equals the row block of the EA absorb (``kfactor.ea_update_m_rows``
    — no reduction is ever split);
  * **heavy ops** (EVD / RSVD / Alg-6 correction / Newton–Schulz) need
    the full M of the firing slots, so the engine gathers *only those
    slots'* rows transiently (``all_gather`` over ``row_axis``), splits
    the firing slot range across the row members — heavy FLOPs shard
    over BOTH axes — and re-gathers the refreshed (U, λ) chunks.  The
    live M is untouched by every heavy op, so the row-sharded M never
    needs re-scattering;
  * **(U, λ) gathers** can be routed through the PowerSGD projection of
    ``distributed/compress.py`` (``compress_rank=q``): each device
    ships a rank-q (P, Q) pair instead of its (d × width) U block —
    O(d·q) instead of O(d·r) on the wire.  The projection is memoryless
    (recomputed from the exact local U each round, deterministic seeded
    basis, so the error does not accumulate across steps — the stream-EF
    machinery of ``compress_tree`` is for gradient *increments*) but
    lossy, so it is opt-in and excluded from the strict parity contract;
    λ/aux (O(width)) always ride uncompressed.  Every mesh member —
    owner included — uses the *decompressed* U, keeping the logically
    replicated out-spec consistent.

The async double-buffered pipeline composes: row-block stats run first,
and a step whose local shard launches or lands gathers the live and
in-flight M rows transiently around the unchanged
``bucket_factor_step_async`` program (heavy work in the async path
shards across the curvature axis only — the landing math is unchanged).

Work masks from ``core/schedule.py`` compose with sharding: a heavy range
aligned to ``align = N_curv · N_rows`` (the Scheduler's ``align``
contract, consumed by ``Kfac.scheduler``) maps to the same static local
row range on every curvature member AND splits evenly across row
members, so staggering and sharding multiply.

Numerics are exactly those of the replicated bucketed path (same per-slot
programs, same per-slot PRNG keys, row-block-deterministic reductions):
``tests/test_distributed_curvature.py`` and ``tests/test_mesh2d.py``
assert allclose parity (replicated ≡ 1D ≡ 2D) on an 8-device host mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import buckets, kfactor, schedule
from repro.core.kfactor import KFactorState
from repro.distributed import compress as compress_lib
from repro.obs import trace as obs_trace

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Static layout of one bucket's batch axis on the curvature axis."""
    total: int                   # true bucket batch
    n: int                       # devices on the curvature axis
    padded: int                  # total padded up to a multiple of n
    perm: Tuple[int, ...]        # device-major round-robin gather indices
    unperm: Tuple[int, ...]      # slot → device-major position

    @classmethod
    def build(cls, total: int, n: int) -> "ShardPlan":
        return cls(total=total, n=n,
                   padded=buckets.padded_total(total, n),
                   perm=tuple(buckets.shard_perm(total, n)),
                   unperm=tuple(buckets.shard_unperm(total, n)))

    @property
    def per_device(self) -> int:
        return self.padded // self.n

    def shard(self, tree):
        """(total, …) leaves → (padded, …) in device-major round-robin
        order (one static take; pad rows wrap onto real slots)."""
        idx = jnp.asarray(self.perm)
        return jax.tree_util.tree_map(
            lambda x: jnp.take(x, idx, axis=0), tree)

    def unshard(self, tree):
        """Inverse of :meth:`shard`; drops the pad rows."""
        idx = jnp.asarray(self.unperm)
        return jax.tree_util.tree_map(
            lambda x: jnp.take(x, idx, axis=0), tree)


class CurvatureEngine:
    """Runs ``Kfac``'s bucketed factor work sharded over ``mesh[axis]``
    (bucket slots), optionally × ``mesh[row_axis]`` (dense-M rows).

    Attach with ``Kfac(cfg, taps, curvature=engine)`` or
    ``opt.curvature = engine`` — ``Kfac.update`` delegates to
    :meth:`factor_work` whenever an engine is present (bucketed mode).
    The engine is static metadata only (mesh + per-bucket ShardPlans +
    row-block sizes); it owns no arrays.

    ``row_axis`` enables the 2D path: a bucket whose factor side d is
    divisible by the row-axis size keeps its dense M row-sharded there
    (``row_blocks[bi]`` = d / N_rows); non-divisible buckets fall back
    to row-replicated M (matching ``sharding.fit_spec``).
    ``compress_rank`` routes the U all-gather through the PowerSGD
    projection of ``distributed/compress.py`` (lossy, opt-in).
    """

    def __init__(self, mesh: Mesh, axis: str, factor_buckets,
                 row_axis: Optional[str] = None,
                 compress_rank: Optional[int] = None):
        if axis not in mesh.axis_names:
            raise ValueError(f"mesh has no axis {axis!r}; "
                             f"axes: {mesh.axis_names}")
        if row_axis is not None and row_axis not in mesh.axis_names:
            raise ValueError(f"mesh has no row axis {row_axis!r}; "
                             f"axes: {mesh.axis_names}")
        if row_axis == axis:
            raise ValueError("row_axis must differ from the curvature "
                             f"(slot) axis, both were {axis!r}")
        self.mesh = mesh
        self.axis = axis
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.n_devices = int(sizes[axis])
        self.row_axis = row_axis if (row_axis is not None
                                     and sizes[row_axis] > 1) else None
        self.n_rows = int(sizes[row_axis]) if self.row_axis else 1
        #: scheduler alignment: heavy ranges must split across slots
        #: (curvature axis) AND across row members (heavy chunking)
        self.align = self.n_devices * self.n_rows
        self.compress_rank = (int(compress_rank)
                              if compress_rank else None)
        self.specs = tuple(b.spec for b in factor_buckets)
        self.plans = tuple(ShardPlan.build(b.total, self.n_devices)
                           for b in factor_buckets)
        #: per-bucket local row-block height of the dense M, or None when
        #: the bucket's M stays row-replicated (no row axis / pure-Brand
        #: placeholder / d not divisible by the row-axis size)
        self.row_blocks = tuple(
            (s.d // self.n_rows)
            if (self.row_axis is not None and s.needs_m
                and s.d % self.n_rows == 0) else None
            for s in self.specs)

    @classmethod
    def for_kfac(cls, opt, mesh: Mesh, axis: str,
                 row_axis: Optional[str] = None,
                 compress_rank: Optional[int] = None) -> "CurvatureEngine":
        eng = cls(mesh, axis, opt.factor_buckets, row_axis=row_axis,
                  compress_rank=compress_rank)
        opt.curvature = eng
        opt.mesh = mesh
        return eng

    # -- job accounting (benchmarks / logs) --------------------------------
    def job_counts(self) -> Tuple[int, int]:
        """(replicated, per-device) factor-job slot counts: a replicated
        device steps every slot of every bucket; a sharded device steps
        its ⌈B/N⌉ local shard of each."""
        rep = sum(p.total for p in self.plans)
        dev = sum(p.per_device for p in self.plans)
        return rep, dev

    def m_bytes(self) -> Tuple[int, int]:
        """(replicated, per-device) dense-M bytes across all buckets —
        the memory the row sharding divides.  Per-device M is
        B/N_curv · d/N_rows · d floats for row-sharded buckets."""
        rep = dev = 0
        for spec, plan, rb in zip(self.specs, self.plans,
                                  self.row_blocks):
            if not spec.needs_m:
                continue
            rep += plan.total * spec.d * spec.d * 4
            rows = rb if rb is not None else spec.d
            dev += plan.per_device * rows * spec.d * 4
        return rep, dev

    def collective_bytes(self) -> Dict[str, int]:
        """Static per-full-refresh bytes-on-wire of the (U, λ, aux)
        gathers, computed from the exact traced array shapes:
        ``uncompressed`` is what the raw U gather moves, ``on_wire`` what
        the engine actually ships (rank-q (P, Q) pairs under
        ``compress_rank``, else the same).  λ/aux always ride raw."""
        raw_u = wire_u = small = 0
        for spec, plan in zip(self.specs, self.plans):
            B, d, w = plan.padded, spec.d, spec.width
            raw_u += B * d * w * 4
            small += B * (w + kfactor.AUX_WIDTH) * 4
            if self.compress_rank is not None:
                q = min(self.compress_rank, d, w)
                wire_u += B * (d + w) * q * 4
            else:
                wire_u += B * d * w * 4
        return {"uncompressed": raw_u + small,
                "on_wire": wire_u + small}

    # -- the sharded factor work -------------------------------------------
    def factor_work(self, opt, factors, inflight, acts, probe_grads,
                    n_tokens, rng, first, work: schedule.StepWork,
                    landing=None, phi=None):
        """Drop-in for ``Kfac._bucketed_factor_work``: same operands, same
        per-slot numerics, 1/N of the factor work per device.  The bucket
        loop (operand collection, no-op skip, gather/scatter, per-slot
        keys) is Kfac's own — only the inner per-bucket program is
        substituted with the shard_map-wrapped one.

        Async launch/land phases run *inside* the sharded program: each
        device snapshots and lands only its ⌈B/N⌉ local slots, so the
        heavy cost of a landing is 1/N of the replicated pipeline's, the
        landed low-rank reps ride the same all-gather as the synchronous
        path, and the in-flight snapshot of the dense M — like the live
        M — never leaves its owning device (and stays row-sharded on a
        2D mesh).  Pre-computed ``landing`` operands are a
        replicated-path optimization and are rejected here (the engine
        lands in-graph)."""
        if landing:
            raise ValueError("the distributed curvature engine computes "
                             "landings in-graph; overlapped landing "
                             "operands are a replicated-path feature")

        def bucket_step(bi, bucket, st, X, keys, buf, landed):
            launch, land = opt._work_ranges(work, bi)
            return self._bucket_step(bucket.spec, self.plans[bi],
                                     self.row_blocks[bi], st, X,
                                     keys, first, work.stats, work.light,
                                     work.heavy[bi], launch, land, buf)

        return opt._bucketed_factor_work(factors, inflight, acts,
                                         probe_grads, n_tokens, rng,
                                         first, work,
                                         bucket_step=bucket_step,
                                         phi=phi)

    # -- gather helpers (inside shard_map bodies) --------------------------
    def _gather_u(self, U_loc: Array) -> Array:
        """All-gather the local (B_loc, d, w) U blocks over the curvature
        axis — raw, or as rank-q PowerSGD factors (``compress_rank``).
        Every member (owner included) uses the decompressed result, so
        the logically-replicated out-spec stays consistent."""
        if self.compress_rank is None:
            return jax.lax.all_gather(U_loc, self.axis, axis=0, tiled=True)
        Pl, Ql = compress_lib.compress_batched(U_loc, self.compress_rank)
        Pg = jax.lax.all_gather(Pl, self.axis, axis=0, tiled=True)
        Qg = jax.lax.all_gather(Ql, self.axis, axis=0, tiled=True)
        return (Pg @ jnp.swapaxes(Qg, -1, -2)).astype(U_loc.dtype)

    def _gather_rep(self, st: KFactorState) -> KFactorState:
        """Gather the low-rank rep (U via :meth:`_gather_u`, λ/aux raw)
        over the curvature axis; M keeps its (possibly row-) shard."""
        U = self._gather_u(st.U)
        D = jax.lax.all_gather(st.D, self.axis, axis=0, tiled=True)
        aux = jax.lax.all_gather(st.aux, self.axis, axis=0, tiled=True)
        return KFactorState(U=U, D=D, M=st.M, aux=aux)

    def _heavy_rows(self, spec, st: KFactorState, keys: Array,
                    llo: int, lhi: int, rb: int) -> KFactorState:
        """One local heavy range on row-sharded M: gather the firing
        slots' M rows to full (transient — O(range·d²), not O(B·d²)),
        split the range across the row members so the heavy FLOPs shard
        over both axes, and re-gather the refreshed chunks.  No heavy op
        writes M, so the live row shard passes through untouched."""
        sub = jax.tree_util.tree_map(lambda x: x[llo:lhi], st)
        Mfull = jax.lax.all_gather(sub.M, self.row_axis, axis=1,
                                   tiled=True)
        subf = KFactorState(U=sub.U, D=sub.D, M=Mfull, aux=sub.aux)
        ksub = keys[llo:lhi]
        bh = lhi - llo
        if bh >= self.n_rows and bh % self.n_rows == 0:
            w = bh // self.n_rows
            o = jax.lax.axis_index(self.row_axis) * w
            chunk = jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_slice_in_dim(x, o, w, axis=0),
                subf)
            ck = jax.lax.dynamic_slice_in_dim(ksub, o, w, axis=0)
            out = kfactor.heavy_overwrite_batched(spec, chunk, ck)
            g0 = lambda x: jax.lax.all_gather(x, self.row_axis, axis=0,
                                              tiled=True)
            U, D, aux = g0(out.U), g0(out.D), g0(out.aux)
        else:
            # range shorter than (or misaligned with) the row-member
            # count: every row member computes the whole range — still
            # exact, just row-replicated work for this (tail) range
            out = kfactor.heavy_overwrite_batched(spec, subf, ksub)
            U, D, aux = out.U, out.D, out.aux
        return KFactorState(U=st.U.at[llo:lhi].set(U),
                            D=st.D.at[llo:lhi].set(D), M=st.M,
                            aux=st.aux.at[llo:lhi].set(aux))

    def _bucket_step(self, spec, plan: ShardPlan, rb: Optional[int],
                     st: KFactorState, X: Array, keys: Array,
                     first: Array, stats: bool, light: bool, ranges,
                     launch, land, buf):
        """One bucket's step under shard_map: each curvature member runs
        the shared per-bucket program on its ⌈B/N⌉ local slots, then
        all-gathers the O(d·r) low-rank rep; the O(d²) dense M — live
        and in-flight snapshot alike — stays device-sharded (and, with
        ``rb``, row-sharded on the row axis)."""
        loc = lambda r: buckets.localize_ranges(r, plan.total, plan.n)
        local_heavy, local_launch, local_land = loc(ranges), loc(launch), \
            loc(land)
        st = plan.shard(st)
        X = plan.shard(X)
        keys = plan.shard(keys)
        axis, row_axis = self.axis, self.row_axis
        m_spec = P(axis, row_axis) if rb is not None else P(axis)
        st_in = KFactorState(U=P(axis), D=P(axis), M=m_spec, aux=P(axis))
        st_out = KFactorState(U=P(), D=P(), M=m_spec, aux=P())

        def sync_local(st, X, keys, first):
            """The per-member synchronous program: the replicated bucket
            step when M is whole, the row-block decomposition of the
            same math when M is row-sharded."""
            if rb is None:
                return kfactor.bucket_factor_step(
                    spec, st, X, keys, first, stats, light, local_heavy)
            if stats:
                with obs_trace.span("stats_rows"):
                    r0 = jax.lax.axis_index(row_axis) * rb
                    M = kfactor.ea_update_m_rows(st.M, X, r0, rb,
                                                 spec.rho, first)
                    st = KFactorState(U=st.U, D=st.D, M=M, aux=st.aux)
            if (light or local_heavy) and spec.mode in kfactor._HAS_BRAND:
                with obs_trace.span("light_brand"):
                    st = kfactor.brand_step(spec, st, X, first)
            for llo, lhi in local_heavy:
                with obs_trace.span(f"heavy_{llo}_{lhi}"):
                    st = self._heavy_rows(spec, st, keys, llo, lhi, rb)
            return st

        if buf is None:
            def body(st, X, keys, first):
                st = sync_local(st, X, keys, first)
                return self._gather_rep(st)

            out = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(st_in, P(axis), P(axis), P()),
                out_specs=st_out,
                check_vma=False,
            )(st, X, keys, first)
            # U/D came back gathered in device-major layout; M sharded in
            # the same layout.  One static take restores slot order
            # everywhere.
            return plan.unshard(out), None

        buf = plan.shard(buf)
        buf_spec = jax.tree_util.tree_map(lambda _: P(axis), buf)
        if rb is not None:
            buf_spec = dataclasses.replace(buf_spec, M=m_spec)

        def body(st, X, keys, first, buf):
            if rb is None:
                st, buf = kfactor.bucket_factor_step_async(
                    spec, st, X, keys, first, stats, light, local_heavy,
                    local_launch, local_land, buf)
                return self._gather_rep(st), buf
            # 2D path: row-block stats first (exact), then — only when
            # this step's local shard fires or lands heavy work — gather
            # the live and in-flight M rows transiently around the
            # unchanged async program and re-slice both row blocks.
            # Launch-only / light-only steps run directly on row blocks
            # (the snapshot copy slices the slot axis only).
            if stats:
                with obs_trace.span("stats_rows"):
                    r0 = jax.lax.axis_index(row_axis) * rb
                    M = kfactor.ea_update_m_rows(st.M, X, r0, rb,
                                                 spec.rho, first)
                    st = KFactorState(U=st.U, D=st.D, M=M, aux=st.aux)
            if local_heavy or local_land:
                g1 = lambda x: jax.lax.all_gather(x, row_axis, axis=1,
                                                  tiled=True)
                stf = KFactorState(U=st.U, D=st.D, M=g1(st.M),
                                   aux=st.aux)
                buff = dataclasses.replace(buf, M=g1(buf.M))
                stf, buff = kfactor.bucket_factor_step_async(
                    spec, stf, X, keys, first, False, light,
                    local_heavy, local_launch, local_land, buff)
                r0 = jax.lax.axis_index(row_axis) * rb
                s1 = lambda x: jax.lax.dynamic_slice_in_dim(x, r0, rb,
                                                            axis=1)
                st = KFactorState(U=stf.U, D=stf.D, M=s1(stf.M),
                                  aux=stf.aux)
                buf = dataclasses.replace(buff, M=s1(buff.M))
            else:
                st, buf = kfactor.bucket_factor_step_async(
                    spec, st, X, keys, first, False, light, (),
                    local_launch, (), buf)
            return self._gather_rep(st), buf

        out, buf = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(st_in, P(axis), P(axis), P(), buf_spec),
            out_specs=(st_out, buf_spec),
            check_vma=False,
        )(st, X, keys, first, buf)
        return plan.unshard(out), plan.unshard(buf)

    def describe(self) -> str:
        parts = [f"axis={self.axis} n={self.n_devices}"]
        if self.row_axis is not None:
            parts.append(f"rows={self.row_axis} n_rows={self.n_rows}")
        if self.compress_rank is not None:
            parts.append(f"compress_q={self.compress_rank}")
        for p, rb in zip(self.plans, self.row_blocks):
            tail = f" rb={rb}" if rb is not None else ""
            parts.append(f"[B={p.total}→{p.padded} "
                         f"/dev={p.per_device}{tail}]")
        return " ".join(parts)
