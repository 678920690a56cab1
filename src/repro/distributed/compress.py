"""Gradient compression for the DP all-reduce (PowerSGD-style low-rank with
error feedback, Vogels et al. 2019) — reusing the same range-finder
numerics as RS-KFAC (core/rsvd.py): one code path, shared tests.

For a gradient matrix G (m, n), rank-q compression all-reduces
P = G Q (m, q) and Q' = Gᵀ P (n, q) instead of G — a (m+n)·q / (m·n)
volume reduction.  The residual G − P Q'ᵀ is fed back into the next step's
gradient (error feedback keeps SGD convergent).

``compress_tree`` applies this to every ≥2D leaf above a size threshold;
small leaves all-reduce uncompressed.  The collective itself is XLA's —
this module only reshapes what enters it; under pjit the psum of the
factors is emitted instead of the psum of the full gradient.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class CompressConfig:
    rank: int = 8
    min_size: int = 65536       # leaves smaller than this stay dense
    n_power_iter: int = 1


def _as_matrix(g: Array) -> Tuple[Array, Tuple[int, ...]]:
    shape = g.shape
    m = shape[0] if g.ndim == 2 else int(jnp.prod(jnp.asarray(shape[:-1])))
    return g.reshape(m, shape[-1]), shape


def compress(g: Array, err: Array, q_prev: Optional[Array], cfg
             ) -> Tuple[Array, Array, Array]:
    """→ (P, Q, new_error).  Caller psums P (and Q on odd rounds)."""
    G2, shape = _as_matrix(g.astype(jnp.float32) + err.astype(jnp.float32))
    m, n = G2.shape
    q = min(cfg.rank, m, n)
    if q_prev is None or q_prev.shape != (n, q):
        # warm start: deterministic basis (seeded per shape)
        key = jax.random.PRNGKey(m * 1315423911 + n)
        q_prev = jax.random.normal(key, (n, q))
    # Orthonormalization stays Householder here, unlike the RS-KFAC range
    # finder (core/rsvd.py, routed through kernels/ops.py::orthonormalize):
    # PowerSGD measurably *relies* on QR's arbitrary orthonormal completion
    # — when power iteration aligns the rank-q basis toward the top
    # eigendirections, the invented orthogonal columns still pick up signal
    # through Q = G2ᵀP, while a spectral factorization maps them to an
    # exactly-null subspace and wastes the rank (rank-2 EF-SGD convergence
    # regresses ~0.05 → 0.06 relative residual).  These (m, ≤8) panels sit
    # far below the kernel pad-growth guard anyway, so there is no batched
    # Pallas launch to share.
    P = G2 @ q_prev                                   # (m, q)
    for _ in range(cfg.n_power_iter):
        P, _ = jnp.linalg.qr(P)
        P = G2 @ (G2.T @ P)
    P, _ = jnp.linalg.qr(P)                           # orthonormal basis
    Q = G2.T @ P                                      # (n, q)
    approx = (P @ Q.T).reshape(shape)
    new_err = G2.reshape(shape) - approx   # of g + err: nothing is dropped
    return P, Q, new_err


def decompress(P: Array, Q: Array, shape: Tuple[int, ...]) -> Array:
    return (P @ Q.T).reshape(shape)


def compress_batched(G: Array, rank: int, n_power_iter: int = 1
                     ) -> Tuple[Array, Array]:
    """Memoryless batched PowerSGD projection — the curvature engine's
    (U, λ) collective path.  G (*stack, m, n) → P (*stack, m, q),
    Q (*stack, n, q) with q = min(rank, m, n); the caller gathers the
    factors and every mesh member decompresses with ``P @ Qᵀ``.

    Unlike :func:`compress`, there is no error feedback: EF exists so a
    compressed *stream of increments* stays unbiased over time, but here
    each round re-projects the exact current state (the engine's local
    U block), so the per-round error never accumulates.  The basis is
    the same deterministic per-shape seed as :func:`compress`'s cold
    start, making the projection SPMD-uniform — every mesh member builds
    the identical basis with no communication."""
    m, n = G.shape[-2:]
    q = min(int(rank), m, n)
    key = jax.random.PRNGKey(m * 1315423911 + n)
    basis = jax.random.normal(key, (n, q)).astype(G.dtype)
    qr = lambda p: jnp.linalg.qr(p)[0]          # batched natively
    P = G @ basis
    for _ in range(n_power_iter):
        P = qr(P)
        P = G @ (jnp.swapaxes(G, -1, -2) @ P)
    P = qr(P)
    Q = jnp.swapaxes(G, -1, -2) @ P
    return P, Q


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CompressState:
    """Per-leaf carry of the error-feedback compressor: ``err`` is the
    residual fed back into the next round, ``q`` the previous round's Q
    factor — PowerSGD's warm start, which lets the single power
    iteration (n_power_iter=1) keep sharpening the rank-q basis across
    rounds; dropping it (the old ``compress_tree`` passed
    ``q_prev=None`` every round) silently restarts the iteration from
    the seeded basis each step.  Leaves the
    config leaves uncompressed carry a zero-size ``q`` sentinel so the
    pytree structure stays static under jit."""
    err: Any
    q: Any


def _compressible(g, cfg: CompressConfig) -> bool:
    return g.ndim >= 2 and g.size >= cfg.min_size


def _cold_q(g, cfg: CompressConfig) -> Array:
    """The deterministic seeded basis :func:`compress` cold-starts from —
    used as the *initial* warm-start carry so round 1 of the stateful
    path is bit-identical to the old stateless one."""
    shape = g.shape
    m = shape[0] if g.ndim == 2 else int(np_prod(shape[:-1]))
    n = shape[-1]
    q = min(cfg.rank, m, n)
    key = jax.random.PRNGKey(m * 1315423911 + n)
    return jax.random.normal(key, (n, q))


def np_prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


def init_state(params, cfg: CompressConfig) -> CompressState:
    """Fresh compressor carry: zero error feedback + the seeded cold-start
    basis per compressible leaf (zero-size sentinel otherwise)."""
    err = init_errors(params)
    q = jax.tree_util.tree_map(
        lambda p: _cold_q(p, cfg) if _compressible(p, cfg)
        else jnp.zeros((0,), jnp.float32), params)
    return CompressState(err=err, q=q)


def compress_tree(grads, state: CompressState, cfg: CompressConfig
                  ) -> Tuple[Any, CompressState]:
    """Apply error-feedback low-rank compression leaf-wise, threading the
    per-leaf warm-start Q through ``state`` (a :class:`CompressState`).

    Returns (approx_grads, new_state).  approx_grads replace the raw
    gradients *before* the (sharded) optimizer update, so the DP psum
    that XLA emits moves only the factor volume; new_state carries both
    the error feedback and the warm-started power-iteration basis into
    the next step (tests/test_mesh2d.py asserts the warm basis sharpens
    across rounds where cold restarts stay pinned at single-iteration
    quality).
    """
    def one(g, e, qp):
        if not _compressible(g, cfg):
            return g, jnp.zeros_like(e), qp
        P, Q, new_err = compress(g, e, qp if qp.size else None, cfg)
        return decompress(P, Q, g.shape).astype(g.dtype), new_err, Q

    flat = jax.tree_util.tree_map(one, grads, state.err, state.q)
    istuple = lambda t: isinstance(t, tuple)
    pick = lambda i: jax.tree_util.tree_map(lambda t: t[i], flat,
                                            is_leaf=istuple)
    return pick(0), CompressState(err=pick(1), q=pick(2))


def init_errors(params):
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
