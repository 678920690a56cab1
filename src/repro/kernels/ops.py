"""Public kernel entry points with automatic dispatch.

Each op routes to its Pallas kernel when (a) kernels are enabled for the
backend and (b) shapes are tile-friendly; otherwise it falls back to the
pure-jnp oracle in ``ref.py`` (identical semantics, asserted by tests).

Stacked inputs
--------------
Every op accepts arbitrary leading stack axes (``(*stack, …)`` from scanned
layers or MoE expert stacks).  The stack is flattened to one batch axis and
the whole stack runs as a single batched Pallas launch (leading grid
dimension) instead of a vmap of per-layer launches.

Pad-to-tile
-----------
Misaligned dims no longer silently drop to the oracle: operands are
zero-padded to the next tile multiple, the kernel runs on the padded
shapes, and the result is sliced back.  Zero rows/columns are exact for
every op here (they contribute nothing to any product and the λ-residual
terms are sliced away), so padding never changes semantics.  Padding only
engages while it is profitable: if any dim would grow beyond ``_PAD_MAX``×
its size (tiny shapes), the op falls back to the oracle instead.

Dispatch policy (chosen by platform):
  * TPU backend            → Pallas (compiled).
  * ``REPRO_PALLAS=interpret`` env  → Pallas interpret mode (CPU validation).
  * ``REPRO_PALLAS=off``    → oracle always.
  * otherwise (CPU/GPU)    → oracle.  CPU interpret mode is orders of
    magnitude slower than jnp and is only meant for correctness tests.

``dispatch_tally()`` counts, while a program is traced inside it, which
route each op call site took — the kernel, or the oracle and the rule
that sent it there.

Several devices: XLA cannot partition a Mosaic kernel, so a program over
a mesh must call each one inside a ``shard_map``.  Inside
``kernel_mesh(mesh)`` a kernel launched outside any ``shard_map`` runs
inside one over that mesh with every operand replicated; launches that
already sit in a ``shard_map`` body run as they are.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import math
import os
import threading
from typing import Dict, Iterator, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels import ea_syrk as _ea
from repro.kernels import ns_inverse as _ns
from repro.kernels import brand_panel as _bp
from repro.kernels import cholqr as _cq
from repro.kernels import lowrank_apply as _la
from repro.kernels import precond_fused as _pf

Array = jax.Array

_LANE = 128   # TPU lane width; matmul major dims pad to this
_SUB = 8      # sublane quantum; rank/width dims pad to this
_PAD_MAX = 2.0  # max per-dim growth factor before falling back to ref


def _mode() -> str:
    env = os.environ.get("REPRO_PALLAS", "auto")
    if env == "off":
        return "ref"
    if env == "interpret":
        return "interpret"
    return "pallas" if jax.default_backend() == "tpu" else "ref"


_TALLY = threading.local()


@contextlib.contextmanager
def dispatch_tally() -> Iterator[Dict[str, collections.Counter]]:
    """Yield {op: Counter(route → call sites)} filled while tracing inside
    the block.  Routes: ``pallas`` / ``interpret`` (the kernel), ``ref``
    (the platform has no kernels), ``ref:pad`` (padding would grow a dim
    past ``_PAD_MAX``), ``ref:cholqr_max_n`` (the Gram would not fit
    VMEM), ``unfused:vmem`` (the fused stripes would not fit VMEM; two
    ``lowrank_apply`` calls run instead and are tallied themselves)."""
    prev = getattr(_TALLY, "counts", None)
    counts: Dict[str, collections.Counter] = collections.defaultdict(
        collections.Counter)
    _TALLY.counts = counts
    try:
        yield counts
    finally:
        _TALLY.counts = prev


def _note(op: str, route: str) -> None:
    counts = getattr(_TALLY, "counts", None)
    if counts is not None:
        counts[op][route] += 1


_MESH = threading.local()


@contextlib.contextmanager
def kernel_mesh(mesh) -> Iterator[None]:
    """Launch the kernels traced inside the block over ``mesh``: a launch
    outside any ``shard_map`` runs inside one whose operands and results
    are all replicated, so every device computes the whole call — the
    replicated math.  ``None`` or a one-device mesh changes nothing."""
    prev = getattr(_MESH, "mesh", None)
    _MESH.mesh = mesh if mesh is not None and mesh.size > 1 else None
    try:
        yield
    finally:
        _MESH.mesh = prev


def _launch(kernel, *args, **static):
    """``kernel(*args, **static)``, replicated over the ``kernel_mesh``
    when the call is not already inside a ``shard_map`` body."""
    call = functools.partial(kernel, **static)
    mesh = getattr(_MESH, "mesh", None)
    if mesh is None or jax.sharding.get_abstract_mesh().manual_axes:
        return call(*args)
    return jax.shard_map(call, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(*args)


def _route(op: str, mode: str, fits: bool, rule: str = "pad",
           unfused: bool = False) -> bool:
    """Record the route of one call site; True iff it takes the kernel.
    ``fits`` is False when the shape rule named ``rule`` sends the call
    to the oracle; ``unfused`` marks a fused op that runs as two
    ``lowrank_apply`` kernels instead."""
    if mode == "ref":
        route = "ref"
    elif not fits:
        route = "ref:" + rule
    else:
        route = "unfused:vmem" if unfused else mode
    _note(op, route)
    return not route.startswith("ref")


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_ok(*dims_mults: Tuple[int, int]) -> bool:
    """True iff padding every (dim, multiple) pair stays within _PAD_MAX."""
    for dim, mult in dims_mults:
        if dim <= 0 or _round_up(dim, mult) > _PAD_MAX * dim:
            return False
    return True


def _common_stack(*xs_cores: Tuple[Array, int]) -> Tuple[int, ...]:
    """Broadcast the leading (stack) axes of all operands to one shape, so
    an operand shared across the stack (e.g. one U for every scanned layer)
    batches correctly instead of mis-indexing a size-1 axis."""
    return jnp.broadcast_shapes(
        *(x.shape[:x.ndim - core] for x, core in xs_cores))


def _flat(x: Array, core: int, stack: Tuple[int, ...]) -> Array:
    """(*stack-broadcastable, *core_shape) → (B, *core_shape)."""
    tail = x.shape[x.ndim - core:]
    x = jnp.broadcast_to(x, stack + tail)
    b = math.prod(stack) if stack else 1
    return x.reshape((b,) + tail)


def _pad_tail(x: Array, *tail: int) -> Array:
    """Zero-pad the trailing len(tail) axes of x up to the given sizes."""
    pads = [(0, 0)] * (x.ndim - len(tail))
    pads += [(0, t - s) for s, t in zip(x.shape[x.ndim - len(tail):], tail)]
    if all(lo == 0 and hi == 0 for lo, hi in pads):
        return x
    return jnp.pad(x, pads)


def _pick_block(dim: int, preferred: int, quantum: int = _LANE) -> int:
    """Largest multiple of ``quantum`` ≤ preferred that divides ``dim``
    (dim is already a multiple of quantum)."""
    b = min(preferred, dim)
    b = (b // quantum) * quantum
    while b > quantum and dim % b:
        b -= quantum
    return max(b, quantum) if dim % quantum == 0 else dim


_FUSED_VMEM_BUDGET = 8 * 1024 * 1024  # conservative: leaves double-buffer room
_SYRK_VMEM_BUDGET = 6 * 1024 * 1024   # accumulator + double-buffered operands


def syrk_blocks(d: int, n: int) -> Tuple[int, int, int]:
    """Shape-aware (bm, bn, bk) for the EA-SYRK launch over padded (d, n).

    HBM traffic for the X row/column streams scales as 1/bm + 1/bn, so the
    M tile is maximized first; the contraction depth bk (which only
    amortizes accumulator init/writeback) then takes what is left of the
    VMEM budget.  Replaces the old fixed 256/256/256 pick — small stacked
    factors no longer get over-tiled and large ones no longer under-use
    VMEM.  Recorded in bench ``derived`` output for trackability.
    """
    bm = bn = bk = _LANE
    for pref_mn in (512, 256, 128):
        bm = bn = _pick_block(d, pref_mn)
        for pref_k in (512, 256, 128):
            bk = _pick_block(n, pref_k)
            # acc + M tile + out tile, plus double-buffered X row/col blocks
            vmem = 4 * (3 * bm * bn + 2 * (bm + bn) * bk)
            if vmem <= _SYRK_VMEM_BUDGET:
                return bm, bn, bk
    return bm, bn, bk


def panel_blocks(d: int, r: int, n: int) -> int:
    """Shape-aware row/contraction block for the Brand panel kernels over
    padded (d, r, n): the (r, n) accumulator is resident, so bk takes the
    remaining VMEM (double-buffered U and A stripes).  Replaces fixed 512."""
    for pref in (512, 256, 128):
        bk = _pick_block(d, pref)
        vmem = 4 * (r * n + 2 * bk * (r + n))
        if vmem <= _SYRK_VMEM_BUDGET:
            return bk
    return bk


def cholqr_blocks(d: int, n: int) -> int:
    """Shape-aware row/contraction block for the CholeskyQR2 kernels over
    padded (d, n): the SYRK pass holds the (n, n) fp32 Gram accumulator
    *and* its (n, n) output block (the apply pass's resident R⁻¹ + Q
    stripe fits in the same envelope), plus double-buffered A stripes."""
    for pref in (512, 256, 128):
        bk = _pick_block(d, pref)
        vmem = 4 * (2 * n * n + 2 * bk * 2 * n)
        if vmem <= _SYRK_VMEM_BUDGET:
            return bk
    return bk


_CHOLQR_MAX_N = 1024  # (n, n) fp32 Gram accumulator must fit VMEM


def _fused_bm(pp: int, pd: int, pwg: int, pwa: int, bn: int):
    """Row-block size for the fused apply pass such that its VMEM working
    set (J stripe + W scratch + side blocks, fp32) fits the budget; None if
    no bm ≥ 8 fits (dispatch then falls back to the unfused kernel path)."""
    for bm in (128, 64, 32, 16, 8):
        if bm > pp:
            continue
        vmem = 4 * (2 * bm * pd            # J stripe + W scratch
                    + bm * pwa + bm * pwg  # Tw + U_g row block
                    + pwg * bn + bn * pwa  # Cg + U_a column blocks
                    + bm * bn)             # output tile
        if vmem <= _FUSED_VMEM_BUDGET:
            return bm
    return None


def _stack_lam(lam, stack: Tuple[int, ...], b: int) -> Array:
    """Per-element scalar → (B,) float32 (broadcast if python/0-d scalar)."""
    lam = jnp.asarray(lam, jnp.float32)
    lam = jnp.broadcast_to(lam, stack) if stack else lam.reshape(())
    return lam.reshape((b,))


# ---------------------------------------------------------------------------
# ops
# ---------------------------------------------------------------------------

def ea_syrk(M: Array, X: Array, rho, first) -> Array:
    """M ← keep·M + coef·X Xᵀ (EA update, paper eq. 5).
    M: (*stack, d, d), X: (*stack, d, n)."""
    mode = _mode()
    d, n = X.shape[-2:]
    if not _route("ea_syrk", mode, _pad_ok((d, _LANE), (n, _LANE))):
        return ref.ea_syrk(M, X, rho, first)
    stack = _common_stack((M, 2), (X, 2))
    Xb = _flat(X, 2, stack)
    Mb = _flat(M, 2, stack)
    pd, pn = _round_up(d, _LANE), _round_up(n, _LANE)
    Xp = _pad_tail(Xb, pd, pn)
    Mp = _pad_tail(Mb, pd, pd)
    rho = jnp.asarray(rho, jnp.float32)
    firstf = jnp.asarray(first, jnp.float32)
    keep = rho * (1.0 - firstf)
    coef = 1.0 - keep
    bm, bn, bk = syrk_blocks(pd, pn)
    out = _launch(_ea.ea_syrk_batched_pallas, Mp, Xp, keep, coef, bm=bm,
                  bn=bn, bk=bk, interpret=(mode == "interpret"))
    return out[..., :d, :d].reshape(stack + (d, d))


def ns_step(Mhat: Array, X: Array) -> Array:
    """One Newton–Schulz step X ← 2X − X(M̂X) — two fused-epilogue GEMM
    launches of the ``ns_inverse`` kernel (ea_syrk tiling; same pad-to-tile
    dispatch).  Mhat, X: (*stack, d, d).  Zero padding is exact: padded
    rows/columns of M̂ and X are zero, stay zero through both products
    (2·0 − 0·0 = 0), and are sliced away."""
    mode = _mode()
    d = X.shape[-1]
    if not _route("ns_step", mode, _pad_ok((d, _LANE))):
        return ref.ns_step(Mhat, X)
    stack = _common_stack((Mhat, 2), (X, 2))
    Mb = _flat(Mhat, 2, stack)
    Xb = _flat(X, 2, stack)
    pd = _round_up(d, _LANE)
    Mp = _pad_tail(Mb, pd, pd)
    Xp = _pad_tail(Xb, pd, pd)
    bm, bn, bk = syrk_blocks(pd, pd)
    interp = mode == "interpret"
    # T = M̂ X  (C operand rides along unused: alpha = 0)
    T = _launch(_ns.gemm_update_batched_pallas, Xp, Mp, Xp, alpha=0.0,
                beta=1.0, bm=bm, bn=bn, bk=bk, interpret=interp)
    # X' = 2X − X T
    out = _launch(_ns.gemm_update_batched_pallas, Xp, Xp, T, alpha=2.0,
                  beta=-1.0, bm=bm, bn=bn, bk=bk, interpret=interp)
    return out[..., :d, :d].reshape(stack + (d, d))


def brand_panel(U: Array, A: Array):
    """(C, A⊥) = (UᵀA, A − U(UᵀA)).
    U: (*stack, d, r), A: (*stack, d, n)."""
    mode = _mode()
    d, r = U.shape[-2:]
    n = A.shape[-1]
    if not _route("brand_panel", mode,
                  _pad_ok((d, _LANE), (r, _SUB), (n, _LANE))):
        return ref.brand_panel(U, A)
    stack = _common_stack((U, 2), (A, 2))
    Ub = _flat(U, 2, stack)
    Ab = _flat(A, 2, stack)
    pd, pr, pn = (_round_up(d, _LANE), _round_up(r, _SUB),
                  _round_up(n, _LANE))
    Up = _pad_tail(Ub, pd, pr)
    Ap = _pad_tail(Ab, pd, pn)
    bk = panel_blocks(pd, pr, pn)
    C, Ap = _launch(_bp.brand_panel_batched_pallas, Up, Ap, bk=bk,
                    interpret=(mode == "interpret"))
    return (C[..., :r, :n].reshape(stack + (r, n)),
            Ap[..., :d, :n].reshape(stack + (d, n)))


def cholqr2(A: Array) -> Tuple[Array, Array]:
    """Tall-skinny QR  A ≈ Q R  by the CholeskyQR2 iteration with a
    clamped spectral root (one batched SYRK + apply launch pair per
    pass; the (n, n) root stays in XLA).  A: (*stack, d, n) → Q (*stack,
    d, n) in A.dtype, R (*stack, n, n) symmetric psd float32.  QᵀQ is a
    rank-k projector to machine precision for any fp32 input — sub-noise-
    floor directions map to an exactly-null subspace — and Q R
    reconstructs the retained spectral content of A (exact when nothing
    is clamped).
    """
    mode = _mode()
    d, n = A.shape[-2:]
    big = _round_up(n, _LANE) > _CHOLQR_MAX_N
    if not _route("cholqr2", mode,
                  not big and _pad_ok((d, _LANE), (n, _LANE)),
                  "cholqr_max_n" if big else "pad"):
        return ref.cholqr2(A)
    stack = _common_stack((A, 2))
    Ab = _flat(A, 2, stack).astype(jnp.float32)
    pd, pn = _round_up(d, _LANE), _round_up(n, _LANE)
    Ap = _pad_tail(Ab, pd, pn)
    bk = cholqr_blocks(pd, pn)
    Q, R = _launch(_cq.cholqr2_batched_pallas, Ap, n_true=n, bk=bk,
                   interpret=(mode == "interpret"))
    return (Q[..., :d, :n].astype(A.dtype).reshape(stack + (d, n)),
            R[..., :n, :n].reshape(stack + (n, n)))


def orthonormalize(Y: Array) -> Array:
    """Orthonormal basis of range(Y) via CholeskyQR2 — the Q-only entry
    point shared by the RSVD range finder and the PowerSGD compressor
    (both tall-skinny, both previously Householder ``jnp.linalg.qr``)."""
    return cholqr2(Y)[0]


def lowrank_apply(X: Array, U: Array, s: Array, lam) -> Array:
    """Y = (X U) diag(s) Uᵀ + X/λ.
    X: (*stack, p, d), U: (*stack, d, w), s: (*stack, w), lam: scalar or
    (*stack,)."""
    mode = _mode()
    p, d = X.shape[-2:]
    w = U.shape[-1]
    if not _route("lowrank_apply", mode,
                  _pad_ok((p, _LANE), (d, _LANE), (w, _SUB))):
        return ref.lowrank_apply(X, U, s, lam)
    stack = _common_stack((X, 2), (U, 2), (s, 1))
    Xb = _flat(X, 2, stack)
    Ub = _flat(U, 2, stack)
    sb = _flat(s, 1, stack)
    b = Xb.shape[0]
    pp, pd, pw = (_round_up(p, _LANE), _round_up(d, _LANE),
                  _round_up(w, _SUB))
    Xp = _pad_tail(Xb, pp, pd)
    Up = _pad_tail(Ub, pd, pw)
    sp = _pad_tail(sb, pw)
    ilam = 1.0 / _stack_lam(lam, stack, b)
    bm = _pick_block(pp, 256)
    bn = _pick_block(pd, 512)
    bk = _pick_block(pd, 512)
    out = _launch(_la.lowrank_apply_batched_pallas, Xp, Up, sp, ilam,
                  bm=bm, bn=bn, bk=bk, interpret=(mode == "interpret"))
    return out[..., :p, :d].reshape(stack + (p, d))


def precond_fused(J: Array, U_g: Array, s_g: Array, lam_g,
                  U_a: Array, s_a: Array, lam_a) -> Array:
    """S = Γ̄⁻¹ J Ā⁻¹ — the full two-sided application in one fused launch
    sequence (J read once per row stripe; the (p, d) intermediate never
    touches HBM).

    J: (*stack, p, d), U_g: (*stack, p, w_g), s_g: (*stack, w_g),
    U_a: (*stack, d, w_a), s_a: (*stack, w_a); λ's scalar or (*stack,).
    """
    mode = _mode()
    p, d = J.shape[-2:]
    w_g = U_g.shape[-1]
    w_a = U_a.shape[-1]
    pp, pd = _round_up(p, _LANE), _round_up(d, _LANE)
    pwg, pwa = _round_up(w_g, _SUB), _round_up(w_a, _SUB)
    bn = _pick_block(pd, 256)
    bm = _fused_bm(pp, pd, pwg, pwa, bn)
    fits = _pad_ok((p, _LANE), (d, _LANE), (w_g, _SUB), (w_a, _SUB))
    if not _route("precond_fused", mode, fits, unfused=bm is None):
        return ref.precond_fused(J, U_g, s_g, lam_g, U_a, s_a, lam_a)
    if bm is None:
        # d too large for the J-resident stripes — stay on kernels but
        # unfused: two lowrank_apply round-trips (the pre-fusion path)
        M = lowrank_apply(J, U_a, s_a, lam_a)
        Mt = jnp.swapaxes(M, -1, -2)
        return jnp.swapaxes(lowrank_apply(Mt, U_g, s_g, lam_g), -1, -2)
    stack = _common_stack((J, 2), (U_g, 2), (U_a, 2), (s_g, 1), (s_a, 1))
    Jb = _flat(J, 2, stack)
    Ugb = _flat(U_g, 2, stack)
    Uab = _flat(U_a, 2, stack)
    sgb = _flat(s_g, 1, stack)
    sab = _flat(s_a, 1, stack)
    b = Jb.shape[0]
    Jp = _pad_tail(Jb, pp, pd)
    Ugp = _pad_tail(Ugb, pp, pwg)
    Uap = _pad_tail(Uab, pd, pwa)
    sgp = _pad_tail(sgb, pwg)
    sap = _pad_tail(sab, pwa)
    ilam_g = 1.0 / _stack_lam(lam_g, stack, b)
    ilam_a = 1.0 / _stack_lam(lam_a, stack, b)
    out = _launch(_pf.precond_fused_pallas, Jp, Ugp, sgp, ilam_g, Uap,
                  sap, ilam_a, bm=bm, bn=bn,
                  interpret=(mode == "interpret"))
    return out[..., :p, :d].reshape(stack + (p, d))
