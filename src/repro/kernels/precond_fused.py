"""Pallas TPU kernel: fused two-sided K-FAC preconditioning.

    S = Γ̄⁻¹ J Ā⁻¹
      = (U_g diag(s_g) U_gᵀ + I/λ_g) J (U_a diag(s_a) U_aᵀ + I/λ_a)

with J (p, d), U_g (p, w_g), U_a (d, w_a) and s = (D+λ)⁻¹ − 1/λ for each
side (paper Alg 1 lines 14-18, both factors at once).  The baseline path in
``core/precond.py`` runs this as two ``lowrank_apply`` round-trips with an
HBM-materialized intermediate M = J Ā⁻¹ plus two transposes; here the
factored order is flipped (left side first)

    Cg = diag(s_g) (U_gᵀ J)           (w_g, d)   — rank panel
    W  = U_g Cg + J/λ_g  = Γ̄⁻¹ J     (p, d)    — never leaves VMEM
    S  = (W U_a) diag(s_a) U_aᵀ + W/λ_a

so the launch sequence is one rank-panel contraction plus one J-resident
apply pass: each (bm, d) row stripe of J is fetched into VMEM once and both
the left combine and the right two-sided apply happen against that resident
stripe (W lives only in a VMEM scratch stripe).  No transposes, and the
(p, d) intermediate never touches HBM.

All operands carry a leading stack axis B (scanned layers / MoE experts /
plain B=1): the grid's leading dimension batches the whole fusion, and the
per-element damping scalars ride in as scalar-prefetch vectors indexed by
the stack coordinate.

Apply-pass grid: (B, p/bm, 2, d/bn).  Sweep t=0 accumulates
Tw = (W U_a) diag(s_a) over the d tiles while recording W into the stripe
scratch; sweep t=1 emits S tiles from Tw and the recorded stripe.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


Array = jax.Array


def _panel_kernel(ug_ref, j_ref, sg_ref, o_ref, acc_ref, *, n_i: int):
    """Cg[b, :, j-block] = diag(s_g) · Σ_i U_g[b, i]ᵀ J[b, i, j-block]."""
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        ug_ref[0], j_ref[0], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == n_i - 1)
    def _done():
        sg = sg_ref[0].astype(jnp.float32)               # (w_g, 1) column
        o_ref[0] = (sg * acc_ref[...]).astype(o_ref.dtype)


def _apply_kernel(ilam_g_ref, ilam_a_ref, j_ref, ug_ref, cg_ref, ua_ref,
                  sa_ref, o_ref, w_ref, tw_ref, *, bn: int, n_j: int):
    """Sweep 0: W stripe + Tw accumulation; sweep 1: S tiles."""
    b = pl.program_id(0)
    t = pl.program_id(2)
    j = pl.program_id(3)
    ilam_g = ilam_g_ref[b]
    ilam_a = ilam_a_ref[b]

    @pl.when(t == 0)
    def _sweep_w():
        j_blk = j_ref[0, :, pl.ds(j * bn, bn)].astype(jnp.float32)
        w_blk = jnp.dot(ug_ref[0].astype(jnp.float32), cg_ref[0],
                        preferred_element_type=jnp.float32) + ilam_g * j_blk
        w_ref[:, pl.ds(j * bn, bn)] = w_blk

        @pl.when(j == 0)
        def _init():
            tw_ref[...] = jnp.zeros_like(tw_ref)

        tw_ref[...] += jnp.dot(w_blk, ua_ref[0].astype(jnp.float32),
                               preferred_element_type=jnp.float32)
        # partial (valid-dtype) tile so the t=0 visit of the output block
        # never flushes uninitialized VMEM; t=1 overwrites it
        o_ref[0] = (ilam_a * w_blk).astype(o_ref.dtype)

    @pl.when(t == 1)
    def _sweep_out():
        sa = sa_ref[0].astype(jnp.float32)               # (1, w_a) row
        tw = tw_ref[...] * sa
        w_blk = w_ref[:, pl.ds(j * bn, bn)]
        acc = jax.lax.dot_general(
            tw, ua_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0] = (acc + ilam_a * w_blk).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "interpret"))
def precond_fused_pallas(J: Array, U_g: Array, s_g: Array, ilam_g: Array,
                         U_a: Array, s_a: Array, ilam_a: Array,
                         bm: int = 128, bn: int = 256,
                         interpret: bool = False) -> Array:
    """S = Γ̄⁻¹ J Ā⁻¹ for a whole stack in one batched launch sequence.

    J: (B, p, d), U_g: (B, p, w_g), s_g: (B, w_g), ilam_g: (B,),
    U_a: (B, d, w_a), s_a: (B, w_a), ilam_a: (B,).
    Requires p % bm == 0 and d % bn == 0 (ops.py pads / falls back).

    s_g and s_a enter the kernels as (B, w_g, 1) columns and (B, 1, w_a)
    rows: a (1, w) block of a (B, w) array would put the stack axis in the
    second-minor position with block size 1, which Mosaic refuses for
    B > 1 (neither 8-aligned nor the full dimension).
    """
    B, p, d = J.shape
    w_g = U_g.shape[-1]
    w_a = U_a.shape[-1]
    bm, bn = min(bm, p), min(bn, d)
    ilam_g = jnp.reshape(ilam_g, (B,)).astype(jnp.float32)
    ilam_a = jnp.reshape(ilam_a, (B,)).astype(jnp.float32)
    s_g = jnp.reshape(s_g, (B, w_g, 1))
    s_a = jnp.reshape(s_a, (B, 1, w_a))

    # Launch 1 — rank panel Cg = diag(s_g) U_gᵀ J, contraction over p
    # (no damping scalars involved).
    grid_p = (B, d // bn, p // bm)
    Cg = pl.pallas_call(
        functools.partial(_panel_kernel, n_i=grid_p[2]),
        grid=grid_p,
        in_specs=[
            pl.BlockSpec((1, bm, w_g), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bm, bn), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, w_g, 1), lambda b, j, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, w_g, bn), lambda b, j, i: (b, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, w_g, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((w_g, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(U_g, J, s_g)

    # Launch 2 — J-resident two-sided apply.
    grid_a = (B, p // bm, 2, d // bn)
    return pl.pallas_call(
        functools.partial(_apply_kernel, bn=bn, n_j=grid_a[3]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid_a,
            in_specs=[
                pl.BlockSpec((1, bm, d), lambda b, i, t, j, *_: (b, i, 0)),
                pl.BlockSpec((1, bm, w_g), lambda b, i, t, j, *_: (b, i, 0)),
                pl.BlockSpec((1, w_g, bn), lambda b, i, t, j, *_: (b, 0, j)),
                pl.BlockSpec((1, bn, w_a), lambda b, i, t, j, *_: (b, j, 0)),
                pl.BlockSpec((1, 1, w_a),
                             lambda b, i, t, j, *_: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn),
                                   lambda b, i, t, j, *_: (b, i, j)),
            scratch_shapes=[
                pltpu.VMEM((bm, d), jnp.float32),    # W row stripe
                pltpu.VMEM((bm, w_a), jnp.float32),  # Tw accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, p, d), J.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
    )(ilam_g, ilam_a, J, U_g, Cg, U_a, s_a)
