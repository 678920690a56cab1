"""Plain reference of the optimizer the benchmark's training cells run,
over a configuration's reference model (``bench/reference/<config>.py``).
It imports nothing of the program.

B-KFAC (arXiv:2210.08494; K-FAC of arXiv:1503.05671), per tapped layer
y = x W with W of shape (d_in, d_out) and per factor side (A over d_in,
G over d_out).  A tap may be stacked: one leaf (*stack, d_in, d_out)
holds a layer per index of ``stack`` (scanned layers), with a factor of
each side per layer, held stacked as the program holds it; each layer's
factor steps and preconditions on its own, as below.

* statistics: X_A = xᵀ/√n, X_G = (∂L/∂y)ᵀ·n_tokens/√n over the layer's
  n = n_stat stats rows; the EA factor is M ← X Xᵀ on the first stats
  step and ρ M + (1−ρ) X Xᵀ after;
* the factor's mode follows the paper's applicability rule: the Brand
  update applies where d > r + n_stat; below it the factor is low-rank
  from M (rank r), and dense (EVD) where d ≤ r + r_o.  The factors
  that are not Brand are overwritten once, at step 0;
* the Brand update (Alg. 4): truncate (U, D) to its r strongest modes,
  then the exact eigen-decomposition of U diag(ρD) Uᵀ + (1−ρ) X Xᵀ,
  computed here from a Householder QR of [U, X] and the eigenvalues of
  the projected core; the first update is the SVD of X;
* the rank-r factor from M is R-KFAC's randomized SVD (Halko et al.):
  a Gaussian sketch of r + r_o columns, n_pwr_iter power iterations
  with a Householder QR each, the eigen-decomposition of the projected
  core.  The sketch is drawn as the program draws it from the seed: the
  step's key split over the factor groups (factors of one side, mode and
  rank, ordered by side, mode and rank), then over each group's slots
  (by layer name, A before G; a stacked tap takes one slot per layer,
  in the order of its flattened stack);
* preconditioning: λ = φ·max D, then the spectrum continuation of §3.5
  (shift D down by its smallest positive mode, add that to λ), and
  S = (U_G D_G U_Gᵀ + λ_G I)⁻¹ Wgradᵀ (U_A D_A U_Aᵀ + λ_A I)⁻¹;
* the step: −lr·(Sᵀ + wd·W) on tapped weights, AdamW on the rest, the
  whole update clipped to a global norm, added to the parameters; the
  AdamW (arXiv:1711.05101) has bias correction.

``dtype`` float32 is the reference (its matmuls at ``highest``
precision); bfloat16 is the control of the correctness check.  The
eigen-decompositions, QR and SVD, which have no bfloat16 form, then
round their inputs and outputs to bfloat16, and the control's symmetric
eigen-decompositions run on the host (LAPACK, in double precision): on
the TPU the divide-and-conquer ``eigh`` of a core wider than 256 with a
cluster of eigenvalues at zero (the FC layers' stats panels have 128 rows
of 256) can split the same block without end once its entries carry
bfloat16 rounding.
"""
from __future__ import annotations

import concurrent.futures
import functools
import json
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EVD, RSVD, BRAND = "evd", "rsvd", "brand"
_NEEDS_M = (EVD, RSVD)
_LAM_EPS = 1e-12


def mode_of(opt: dict, d: int) -> Tuple[str, int]:
    """(mode, width of the held eigenbasis) of a factor of side d."""
    if opt["kind"] != "kfac" or opt["variant"] != "bkfac":
        raise NotImplementedError(
            f"no reference for {opt['kind']}/{opt.get('variant')}")
    r, n = min(opt["r"], d), opt["n_stat"]
    mode = BRAND if d > r + n else RSVD
    if d <= r + opt["r_o"]:
        mode = EVD
    width = min(r + n, d) if mode == BRAND else min(r, d)
    return mode, width


def schedule(opt: dict, k: int) -> Tuple[bool, bool, bool]:
    """(stats, light, heavy) of step k."""
    return k % opt["T_updt"] == 0, k % opt["T_brand"] == 0, k == 0


def piecewise(sched, k: int) -> float:
    """A schedule's value at step k: a constant, or a staircase."""
    if not isinstance(sched, dict):
        return float(sched)
    return sched["values"][sum(k >= b for b in sched["boundaries"])]


def leaf_paths(tree) -> Dict[str, jax.Array]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(p.key) for p in kp): v for kp, v in flat}


def set_leaves(tree, leaves: Dict[str, jax.Array]):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [leaves["/".join(str(p.key) for p in kp)]
                  for kp, _ in flat])


def _f32(fn, *xs):
    """A decomposition with no bfloat16 form, on rounded inputs and with
    rounded outputs where the operands are bfloat16."""
    dt = xs[0].dtype
    out = fn(*(x.astype(jnp.float32) for x in xs))
    return jax.tree_util.tree_map(lambda y: y.astype(dt), out)


def _eigh_host(M):
    """``eigh`` of a float32 symmetric matrix on the host."""
    out = (jax.ShapeDtypeStruct(M.shape[:1], jnp.float32),
           jax.ShapeDtypeStruct(M.shape, jnp.float32))

    def eigh(m):
        vals, vecs = np.linalg.eigh(np.asarray(m, np.float64))
        return vals.astype(np.float32), vecs.astype(np.float32)
    return jax.pure_callback(eigh, out, M)


def _eigh_desc(M):
    eigh = jnp.linalg.eigh if M.dtype == jnp.float32 else _eigh_host
    vals, vecs = _f32(eigh, 0.5 * (M + M.T))
    return vals[::-1], vecs[:, ::-1]


def _pad(U, D, width):
    d, w = U.shape
    if w >= width:
        return U[:, :width], D[:width]
    return (jnp.concatenate([U, jnp.zeros((d, width - w), U.dtype)], 1),
            jnp.concatenate([D, jnp.zeros((width - w,), D.dtype)]))


def _brand(U, D, X, rho, r):
    Ut, Dt = U[:, :r], rho * D[:r]
    A = math.sqrt(1.0 - rho) * X
    Q, _ = _f32(functools.partial(jnp.linalg.qr, mode="reduced"),
                jnp.concatenate([Ut, A], axis=1))
    P, C = Q.T @ Ut, Q.T @ A
    vals, vecs = _eigh_desc((P * Dt) @ P.T + C @ C.T)
    return Q @ vecs, vals


def _orth(Y):
    Q, _ = _f32(functools.partial(jnp.linalg.qr, mode="reduced"), Y)
    return Q


def _rsvd(M, r, r_o, n_iter, key):
    """Randomized rank-r eigen-decomposition of the psd M, descending."""
    d = M.shape[0]
    omega = jax.random.normal(key, (d, min(r + r_o, d)),
                              jnp.float32).astype(M.dtype)
    Q = _orth(M @ omega)
    for _ in range(n_iter):
        Q = _orth(M @ Q)
    vals, vecs = _eigh_desc(Q.T @ (M @ Q))
    return Q @ vecs[:, :r], jnp.maximum(vals[:r], 0)


def slot_keys(taps, opt: dict, key) -> Dict[str, jax.Array]:
    """The keys of each factor's sketches at a step with key ``key``, of
    shape (*stack, *key): one per layer of the factor's stack."""
    groups: Dict[tuple, list] = {}
    for name, d_in, d_out, stack in sorted(taps):
        for side, d in (("A", d_in), ("G", d_out)):
            mode, _ = mode_of(opt, d)
            groups.setdefault((d, mode, min(opt["r"], d)), []).append(
                (f"{name}/{side}", stack))
    order = sorted(groups, key=lambda g: (g[0], opt["n_stat"], g[1], g[2]))
    out = {}
    for gkey, spec in zip(jax.random.split(key, len(order)), order):
        members = groups[spec]
        keys = jax.random.split(gkey, sum(math.prod(st)
                                          for _, st in members))
        at = 0
        for fk, st in members:
            n = math.prod(st)
            out[fk] = keys[at:at + n].reshape(st + keys.shape[1:])
            at += n
    return out


def _per_layer(fn, stack, *stacked):
    """``fn`` on each layer of the stacked trees, its outputs stacked back
    into one leaf each (``fn`` itself where the stack is empty)."""
    if not stack:
        return fn(*stacked)
    outs = [fn(*jax.tree_util.tree_map(lambda x, i=i: x[i], stacked))
            for i in np.ndindex(*stack)]
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs).reshape(stack + xs[0].shape),
        *outs)


def _layer0(tree, stack):
    """The first layer's slice of a stacked tree (the tree where the
    stack is empty)."""
    if not stack:
        return tree
    return jax.tree_util.tree_map(lambda x: x[(0,) * len(stack)], tree)


def _factor_step(f, X, key, mode, width, opt, first, stats, light, heavy):
    rho, r = opt["rho"], min(opt["r"], X.shape[0])
    f = dict(f)
    if stats and mode in _NEEDS_M:
        XX = X @ X.T
        f["M"] = XX if first else rho * f["M"] + (1.0 - rho) * XX
    if light and mode == BRAND:
        if first:
            Ux, s, _ = _f32(functools.partial(jnp.linalg.svd,
                                              full_matrices=False), X)
            f["U"], f["D"] = _pad(Ux, s * s, width)
        else:
            f["U"], f["D"] = _pad(*_brand(f["U"], f["D"], X, rho, r),
                                  width)
    if heavy and mode == EVD:
        vals, vecs = _eigh_desc(f["M"])
        f["U"], f["D"] = _pad(vecs[:, :width], vals[:width], width)
    elif heavy and mode == RSVD:
        f["U"], f["D"] = _pad(*_rsvd(f["M"], r, opt["r_o"],
                                     opt["n_pwr_iter"], key), width)
    return f


def _inv_right(J, U, D, phi):
    """J (U diag(D) Uᵀ + λI)⁻¹ with the damping and continuation."""
    lam = phi * jnp.maximum(jnp.max(D), _LAM_EPS)
    pos = D > 0
    dmin = jnp.min(jnp.where(pos, D, jnp.inf))
    dmin = jnp.where(jnp.isfinite(dmin), dmin, 0.0).astype(D.dtype)
    D, lam = jnp.maximum(D - dmin, 0), jnp.maximum(lam + dmin, _LAM_EPS)
    diag = 1.0 / jnp.maximum(D + lam, _LAM_EPS) - 1.0 / lam
    return ((J @ U) * diag) @ U.T + J / lam


def init_state(model, sizes, job, key, dtype):
    opt = job["optimizer"]
    params = model.init(sizes, key, dtype)
    leaves = leaf_paths(params)
    taps = model.layers(sizes)
    adam = set(leaves) - {model.weight_path(t[0]) for t in taps}
    state = {"params": params, "factors": {}}
    for name, d_in, d_out, stack in taps:
        for side, d in (("A", d_in), ("G", d_out)):
            mode, width = mode_of(opt, d)
            state["factors"][f"{name}/{side}"] = {
                "U": jnp.zeros((*stack, d, width), dtype),
                "D": jnp.zeros((*stack, width), dtype),
                "M": jnp.zeros((*stack, *((d, d) if mode in _NEEDS_M
                                          else (1, 1))), dtype)}
    state["m"] = {p: jnp.zeros_like(leaves[p]) for p in sorted(adam)}
    state["v"] = {p: jnp.zeros_like(leaves[p]) for p in sorted(adam)}
    return state


@functools.partial(jax.jit, static_argnames=("hp_key",))
def _adamw(g, m, v, p, t, hp_key, lr):
    hp = json.loads(hp_key)
    c1 = (1 - hp["b1"] ** t).astype(g.dtype)
    c2 = (1 - hp["b2"] ** t).astype(g.dtype)
    m = hp["b1"] * m + (1 - hp["b1"]) * g
    v = hp["b2"] * v + (1 - hp["b2"]) * g * g
    mhat = m / c1
    vhat = v / c2
    return -lr * (mhat / (jnp.sqrt(vhat) + hp["eps"])
                  + hp["weight_decay"] * p), m, v


@functools.partial(jax.jit, static_argnames=("model", "sizes_key",
                                             "n_stat"))
def _grads(params, batch, model, sizes_key, n_stat, n_tokens):
    """(loss, parameter gradients, stats panels X per factor, their
    norms): X_A = xᵀ/√n over the stats rows, X_G = (∂L/∂y)ᵀ·n_tokens/√n
    over the same output rows, each (*stack, d, n_stat); a stacked
    factor's norm is over all its layers."""
    sizes = json.loads(sizes_key)
    dtype = jax.tree_util.tree_leaves(params)[0].dtype
    taps = model.layers(sizes)
    probes = {name: jnp.zeros((*stack, n_stat, d_out), dtype)
              for name, _, d_out, stack in taps}
    grad_fn = jax.value_and_grad(model.loss_and_stats, argnums=(0, 1),
                                 has_aux=True)
    (loss, acts), (g, gprobe) = grad_fn(params, probes, batch, sizes,
                                        n_stat)
    rs = 1.0 / math.sqrt(n_stat)
    X = {}
    for name, _, _, _ in taps:
        X[f"{name}/A"] = jnp.swapaxes(acts[name].astype(dtype), -1, -2) * rs
        X[f"{name}/G"] = (jnp.swapaxes(gprobe[name], -1, -2)
                          * (n_tokens * rs).astype(dtype))
    norms = {k: _norm(x) for k, x in X.items()}
    return loss, g, X, norms


@functools.partial(jax.jit, static_argnames=("mode", "width", "opt_key",
                                             "flags"))
def _factor(f, X, key, mode, width, opt_key, flags):
    first, stats, light, heavy = flags
    return _factor_step(f, X, key, mode, width, json.loads(opt_key), first,
                        stats, light, heavy)


@jax.jit
def _precond(gW, W, fa, fg, phi, lr, wd):
    """−lr·(Sᵀ + wd·W) for one layer, from the (U, D) of its factors."""
    S = _inv_right(gW.T, fa["U"], fa["D"], phi)
    S = _inv_right(S.T, fg["U"], fg["D"], phi)            # (d_in, d_out)
    return -lr * (S + wd * W)


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@jax.jit
def _apply(params, updates, scale):
    return jax.tree_util.tree_map(
        lambda p, u: (p + (u * scale).astype(u.dtype)).astype(p.dtype),
        params, updates)


def _lowrank(f):
    """The (U, D) of a factor: what preconditioning reads, without M (a
    stacked M is then not sliced layer by layer for it)."""
    return {"U": f["U"], "D": f["D"]}


def _step(state, batch, k, flags, model, sizes, opt, n_tokens, key):
    """One step of the reference optimizer, orchestrated here over small
    jitted pieces (one program per distinct shape compiles faster than
    one program per step kind)."""
    params = state["params"]
    dtype = jax.tree_util.tree_leaves(params)[0].dtype
    loss, g, X, xnorms = _grads(params, batch, model,
                                json.dumps(sizes, sort_keys=True),
                                opt["n_stat"], jnp.float32(n_tokens))
    P, G = leaf_paths(params), leaf_paths(g)
    out = dict(state)
    t = jnp.asarray(k + 1, jnp.float32)
    hp = json.dumps(opt["fallback"], sort_keys=True)
    lr = jnp.asarray(opt["fallback"]["lr"], dtype)
    updates, newm, newv = {}, {}, {}
    for path in state["m"]:
        updates[path], newm[path], newv[path] = _adamw(
            G[path], state["m"][path], state["v"][path], P[path], t, hp, lr)
    opt_key = json.dumps(opt, sort_keys=True)
    lr = jnp.asarray(piecewise(opt["lr"], k), dtype)
    phi = jnp.asarray(piecewise(opt["damping_phi"], k), dtype)
    wd = jnp.asarray(opt["weight_decay"], dtype)
    factors = dict(state["factors"])
    taps = model.layers(sizes)
    keys = slot_keys(taps, opt, key)
    for name, d_in, d_out, stack in taps:
        for side, d in (("A", d_in), ("G", d_out)):
            mode, width = mode_of(opt, d)
            fk = f"{name}/{side}"
            factors[fk] = _per_layer(
                functools.partial(_factor, mode=mode, width=width,
                                  opt_key=opt_key, flags=flags),
                stack, factors[fk], X[fk], keys[fk])
        path = model.weight_path(name)
        updates[path] = _per_layer(
            functools.partial(_precond, phi=phi, lr=lr, wd=wd), stack,
            G[path], P[path], _lowrank(factors[f"{name}/A"]),
            _lowrank(factors[f"{name}/G"]))
    norm = jnp.sqrt(sum(_norm(u) ** 2 for u in updates.values()))
    scale = jnp.minimum(1.0, opt["clip"] / (norm + 1e-12))
    out["factors"] = factors
    out["X"] = xnorms
    out["params"] = _apply(params, set_leaves(params, updates), scale)
    out["m"], out["v"] = newm, newv
    out["grad_norms"] = {p: _norm(G[p]) for p in G}
    out["clip_scale"] = scale
    return out, loss


def _compile_side_by_side(model, sizes, opt, state, batch, n_tokens,
                          flag_sets, prec):
    """Call every distinct program of the steps once, on zeros, from a
    thread each, so their compilations run side by side (one by one, the
    eigen-decompositions of a wide model take many minutes to compile).
    A stacked tap's programs are those of one layer."""
    dtype = jax.tree_util.tree_leaves(state["params"])[0].dtype
    jobs = [lambda: _grads(state["params"], batch, model,
                           json.dumps(sizes, sort_keys=True), opt["n_stat"],
                           jnp.float32(n_tokens))]
    opt_key = json.dumps(opt, sort_keys=True)
    seen = set()
    for name, d_in, d_out, stack in model.layers(sizes):
        f = {side: _layer0(state["factors"][f"{name}/{side}"], stack)
             for side in "AG"}
        for side, d in (("A", d_in), ("G", d_out)):
            mode, width = mode_of(opt, d)
            X = jnp.zeros((d, opt["n_stat"]), dtype)
            for flags in flag_sets:
                if (d, mode, width, flags) not in seen:
                    seen.add((d, mode, width, flags))
                    jobs.append(functools.partial(
                        _factor, f[side], X, jax.random.PRNGKey(0),
                        mode=mode, width=width, opt_key=opt_key,
                        flags=flags))
        if (d_in, d_out) not in seen:
            seen.add((d_in, d_out))
            z = jnp.zeros((), dtype)
            jobs.append(functools.partial(
                _precond, jnp.zeros((d_in, d_out), dtype),
                jnp.zeros((d_in, d_out), dtype), _lowrank(f["A"]),
                _lowrank(f["G"]), phi=z, lr=z, wd=z))

    def one(job):
        with jax.default_matmul_precision(prec):
            return jax.block_until_ready(job())

    one(jobs[0])
    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        for fut in [pool.submit(one, job) for job in jobs[1:]]:
            fut.result()


def leaf_norm_diff(a, b) -> Dict[str, float]:
    A, B = leaf_paths(a), leaf_paths(b)
    return {p: float(jnp.sqrt(jnp.sum(jnp.square(
        A[p].astype(jnp.float32) - B[p].astype(jnp.float32))))) for p in A}


def run(model, sizes: dict, job: dict, key, loop_seed: int, pool,
        n_tokens: int, dtype=jnp.float32, detail: bool = False) -> dict:
    """The record of the first ``job["check_steps"]`` steps: each step's
    loss, the first step's gradient norm of every leaf and stats panel
    norm of every factor, and per leaf the norm of the parameters'
    change over all the steps and over the last step.  ``key`` draws the
    weights; ``loop_seed`` starts the chain of step keys (the program's
    training loop starts it from the same seed).  ``dtype`` is that of
    the parameters and of all arithmetic.  ``detail`` adds, for a look
    at where two runs part, each step's per-leaf change and clip scale
    and each factor's spectrum D after the first step."""
    opt = job["optimizer"]
    prec = "highest" if dtype == jnp.float32 else "default"
    rec = {"loss": []}
    steps = int(job["check_steps"])
    flag_sets, n_stats = [], 0
    for k in range(steps):
        flag_sets.append((n_stats == 0,) + schedule(opt, k))
        n_stats += int(flag_sets[-1][1])
    with jax.default_matmul_precision(prec):
        state = init_state(model, sizes, job, key, dtype)
        _compile_side_by_side(model, sizes, opt, state, pool[0], n_tokens,
                              sorted(set(flag_sets)), prec)
        p0 = prev = state["params"]
        rng = jax.random.PRNGKey(loop_seed)
        for k in range(steps):
            prev = state["params"]
            rng, sub = jax.random.split(rng)
            state, loss = _step(state, pool[k % len(pool)], k, flag_sets[k],
                                model, sizes, opt, n_tokens, sub)
            rec["loss"].append(float(loss))
            if k == 0:
                rec["grad"] = {p: float(v) for p, v in
                               state["grad_norms"].items()}
                rec["stats"] = {n: float(v) for n, v in state["X"].items()}
            if detail:
                rec.setdefault("step_change", []).append(
                    leaf_norm_diff(state["params"], prev))
                rec.setdefault("clip_scale", []).append(
                    float(state["clip_scale"]))
                if k == 0:
                    rec["D0"] = {fk: np.asarray(f["D"], np.float64)
                                 .ravel().tolist()
                                 for fk, f in state["factors"].items()}
        rec["change"] = leaf_norm_diff(state["params"], p0)
        rec["last"] = leaf_norm_diff(state["params"], prev)
    return rec
