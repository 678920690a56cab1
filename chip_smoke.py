#!/usr/bin/env python3
"""Smoke run of the K-FAC training step on a TPU, through its Pallas kernels.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded curvature engine, 4 chips

One chip: trains the paper's modified VGG16_bn (``examples/train_vgg_kfac.py
--preset paper``: stages 64…512, FC0 16384×2048, r=230, random weights from
a seed) at batch 128 on the seeded ``ImageStream`` for six steps under
``bkfac`` and ``nskfac``, through ``make_scheduled_kfac_step`` →
``Kfac.update``.  Step 0 is the warm-up heavy refresh and step 5 the first
stats/light step of the T_brand=5, T_inv=25 cadence.  It prints, per
distinct step program, the lowering and compile seconds (a variant's
programs compile side by side), the count of Pallas calls in the compiled
program, the kernel-or-oracle route of every op call site and the
program's memory analysis; then the losses.  Then it runs each of the six
kernels at that model's shapes and compares it with its ``kernels/ref.py``
oracle.

Four chips: the same six ``bkfac`` steps of the paper VGG with the
curvature engine on a ("curv",) mesh and on a ("data", "curv") mesh with
row-sharded dense M.  Each layout trains freely, and each of its steps is
also run from the replicated run's state and compared with the replicated
step (loss, update, every factor's M, D and U·diag(D)·Uᵀ), held to a
control: the replicated step from parameters changed by about two ulp.
It also reads the bytes of dense M each device holds.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The script exits non-zero, with no such line, when JAX finds no TPU or
when any check fails.
"""
from __future__ import annotations

import argparse
import collections
import concurrent.futures
import json
import math
import pathlib
import re
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
STEPS = 6       # step 0: warm-up heavy refresh; step 5: stats + light
BATCH = 128
# Four chips: each step of a sharded layout, started from the replicated
# run's state, may depart from the replicated step by CONTROL_FACTOR times
# what the control departs by — the replicated step from parameters
# changed by ~2 ulp — or by DIFF_FLOOR, whichever is larger.  Rounding
# moves the two by the same order (on four CPU devices, interpret mode,
# small preset, the sharded/control ratio was at most 1.2); a wrong slot,
# key or statistic moves a factor by O(1), hundreds of times the control.
CONTROL_FACTOR = 10.0
DIFF_FLOOR = 1e-6
KERNELS = ("ea_syrk", "ns_step", "brand_panel", "cholqr2", "lowrank_apply",
           "precond_fused")


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def _pallas_calls(hlo_text: str) -> int:
    return hlo_text.count('custom_call_target="tpu_custom_call"')


def _collectives(hlo_text: str) -> dict:
    names = re.findall(r"\b(all-gather|all-reduce|reduce-scatter|"
                       r"collective-permute|all-to-all)(?:-start)?\(",
                       hlo_text)
    return dict(sorted(collections.Counter(names).items()))


def _memory(compiled) -> str:
    ma = compiled.memory_analysis()
    if ma is None:
        return "not reported"
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes")
    return " ".join(f"{f.replace('_size_in_bytes', '')}="
                    f"{getattr(ma, f)}" for f in fields if hasattr(ma, f))


class Run:
    """One optimizer variant training the preset VGG through
    ``make_scheduled_kfac_step`` → ``Kfac.update``, on one device or,
    with a mesh, with the curvature engine sharding the factor work over
    its "curv" axis (and dense M rows over ``row_axis``)."""

    def __init__(self, name: str, variant: str, preset: str = "paper",
                 mesh=None, row_axis=None, batch: int = BATCH):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        import train_vgg_kfac
        from repro import specs
        from repro.data.synthetic import ImageStream
        from repro.distributed import sharding as shd
        from repro.train import loop

        init, loss_fn, _, opt = train_vgg_kfac.kfac_setup(variant, preset)
        self.name, self.opt = name, opt
        self.params0 = init(jax.random.PRNGKey(0))
        dev0 = jax.devices()[0]
        self._place = self._put_batch = lambda x: jax.device_put(x, dev0)
        jit_kw = {}
        if mesh is not None:
            specs.DistSpec(mesh=mesh, curvature_axis="curv",
                           row_axis=row_axis).attach(opt)
            rep = NamedSharding(mesh, P())
            st_sh = loop.TrainState(
                params=jax.tree_util.tree_map(lambda _: rep, self.params0),
                opt=shd.kfac_state_sharding(opt.init(self.params0), mesh,
                                            curvature_axis="curv",
                                            row_axis=row_axis),
                rng=rep)
            self._place = lambda st: jax.device_put(st, st_sh)
            self._put_batch = lambda b: jax.device_put(b, rep)
            jit_kw = dict(out_shardings=(st_sh, rep))
        self.sched = opt.scheduler()
        self.stream = ImageStream(batch=batch, seed=0)
        self.step = jax.jit(loop.make_scheduled_kfac_step(loss_fn, opt,
                                                          n_tokens=batch),
                            static_argnames=("work",), **jit_kw)
        self.programs = {}

    def state(self, params):
        import jax
        from repro.train import loop
        return self._place(loop.TrainState(
            params=params, opt=self.opt.init(params),
            rng=jax.random.PRNGKey(1)))

    def lower(self, steps: int, routes=None):
        """[(work, first step, lowered)] for each distinct step program
        of the first ``steps`` steps; logs each op's kernel-or-oracle
        routes and adds them to ``routes``."""
        from repro.kernels import ops
        state = self.state(self.params0)
        data = self._put_batch(self.stream.batch_at(0))
        out, seen = [], set()
        for k in range(steps):
            work = self.sched.work(k)
            if work in seen:
                continue
            seen.add(work)
            t0 = time.perf_counter()
            with ops.dispatch_tally() as tally:
                out.append((work, k, self.step.lower(state, data, work)))
            log(f"[{self.name}] program '{work.label}' (first at step {k})"
                f" lowered in {time.perf_counter() - t0:.2f} s, routes "
                + json.dumps(
                    {op: dict(c) for op, c in sorted(tally.items())}))
            if routes is not None:
                for op, c in tally.items():
                    for route, n in c.items():
                        routes.setdefault(op, {})
                        routes[op][route] = routes[op].get(route, 0) + n
        return out

    def compile(self, work, k: int, lowered) -> None:
        t0 = time.perf_counter()
        compiled = lowered.compile()
        secs = time.perf_counter() - t0
        text = compiled.as_text()
        n_pallas = _pallas_calls(text)
        log(f"[{self.name}] program '{work.label}' (first at step {k}): "
            f"compile {secs:.2f} s, {n_pallas} tpu_custom_call, "
            f"collectives {json.dumps(_collectives(text))}")
        log(f"[{self.name}]   memory_analysis {_memory(compiled)}")
        if n_pallas == 0:
            raise SmokeFailure(f"{self.name} program '{work.label}' holds "
                               "no Pallas kernel")
        self.programs[work] = compiled

    def step_from(self, k: int, state):
        """Step ``k`` from ``state`` (host arrays or any placement);
        returns (new state, loss)."""
        import jax
        work = self.sched.work(k)
        return jax.block_until_ready(self.programs[work](
            self._place(state), self._put_batch(self.stream.batch_at(k))))

    def run(self, steps: int, on_step=None):
        """Train ``steps`` steps from the seeded init with the compiled
        programs; ``on_step(k, state)`` sees the state after each step.
        Returns (losses, final TrainState)."""
        import jax
        state = self.state(self.params0)
        losses = []
        for k in range(steps):
            work = self.sched.work(k)
            data = self._put_batch(self.stream.batch_at(k))
            t0 = time.perf_counter()
            state, loss = jax.block_until_ready(
                self.programs[work](state, data))
            secs = time.perf_counter() - t0
            losses.append(float(loss))
            log(f"[{self.name}] step {k} ({work.label}) loss "
                f"{losses[-1]!r} ({secs:.4f} s)")
            if not math.isfinite(losses[-1]):
                raise SmokeFailure(f"{self.name} step {k}: loss "
                                   f"{losses[-1]}")
            if on_step is not None:
                on_step(k, state)
        return losses, state


def compile_all(jobs) -> None:
    """Compile [(run, work, first step, lowered)] side by side on the
    host's cores: a paper-VGG heavy step alone takes about two minutes."""
    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(len(jobs)) as pool:
        for f in [pool.submit(run.compile, *job) for run, *job in jobs]:
            f.result()
    log(f"[compile] {len(jobs)} programs side by side in "
        f"{time.perf_counter() - t0:.2f} s")


def _orth(key, shape):
    import jax
    import jax.numpy as jnp
    return jnp.linalg.qr(jax.random.normal(key, shape))[0]


def _inv_diag(key, shape, lam):
    """s = (D+λ)⁻¹ − 1/λ for a spectrum D in [0.1, 2] — what the
    preconditioner hands the apply kernels."""
    import jax
    D = jax.random.uniform(key, shape, minval=0.1, maxval=2.0)
    return 1.0 / (D + lam) - 1.0 / lam


def kernel_cases(key):
    """name → (op, kernel call, oracle call, operands, tol, reason), at the
    paper VGG's bucket shapes (stacked where it stacks)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    k = jax.random.split(key, 12)
    lam = 0.05
    # f32 GEMMs: a kernel reading a wrong tile is off by O(1) of the
    # largest entry, while rounding the operands to bf16 (the cheapest
    # precision Mosaic may pick for an f32 dot) stays under 4e-3 of it
    gemm, gemm_why = 1e-2, "f32 GEMM; bf16 operand rounding < 4e-3"
    X = jax.random.normal(k[0], (2, 2304, 256))
    M0 = X @ jnp.swapaxes(X, -1, -2) / 256.0
    G = jax.random.normal(k[1], (2, 2304, 2304)) / math.sqrt(2304.0)
    Mhat = G @ jnp.swapaxes(G, -1, -2) + jnp.eye(2304)   # spectrum [1, 5]
    E = jax.random.normal(k[10], (2, 2304, 2304)) / math.sqrt(2304.0)
    X0 = (jnp.eye(2304) + 0.05 * (E + jnp.swapaxes(E, -1, -2))) / 3.0
    U230 = _orth(k[2], (3, 4608, 230))
    A = jax.random.normal(k[3], (3, 4608, 256))
    Xl = jax.random.normal(k[4], (3, 512, 4608))
    U486 = _orth(k[5], (3, 4608, 486))
    s486 = _inv_diag(k[6], (3, 486), lam)
    J = jax.random.normal(k[7], (3, 4608, 512))
    Ua = _orth(k[8], (3, 512, 486))
    sa = _inv_diag(k[9], (3, 486), lam)
    return {
        # nskfac's dense-M bucket (d=2304, two slots)
        "ea_syrk": ("ea_syrk",
                    lambda M, X: ops.ea_syrk(M, X, 0.95, False),
                    lambda M, X: ref.ea_syrk(M, X, 0.95, False),
                    (M0, X), gemm, gemm_why),
        # two chained GEMMs, X' = 2X − X(M̂X), on the same bucket
        "ns_step": ("ns_step", ops.ns_step, ref.ns_step, (Mhat, X0),
                    gemm, gemm_why + " per GEMM; X near the cold start "
                    "α·I, α = 2/(1+5), so no cancellation"),
        # the stacked conv3_1/conv4_* Brand bucket (d=4608, three slots)
        "brand_panel": ("brand_panel", ops.brand_panel, ref.brand_panel,
                        (U230, A), gemm, gemm_why),
        # the Gram's rounding passes through an inverse square root
        # (scaled by the panel's condition number, ≈1.6 for a Gaussian
        # 4608×256 panel) before the second pass repairs it
        "cholqr2": ("cholqr2", ops.cholqr2, ref.cholqr2, (A,), 2e-2,
                    "Gram rounding × cond ≈ 1.6 through the root"),
        # nskfac's Brand side next to a dense NS side, stacked (B=3)
        "lowrank_apply": ("lowrank_apply",
                          lambda X, U, s: ops.lowrank_apply(X, U, s, lam),
                          lambda X, U, s: ref.lowrank_apply(X, U, s, lam),
                          (Xl, U486, s486), gemm,
                          gemm_why + "; the X/λ term is exact"),
        # bkfac's two-sided application on the stacked conv bucket
        "precond_fused": ("precond_fused",
                          lambda J, Ug, sg, Ua, sa: ops.precond_fused(
                              J, Ug, sg, lam, Ua, sa, lam),
                          lambda J, Ug, sg, Ua, sa: ref.precond_fused(
                              J, Ug, sg, lam, Ua, sa, lam),
                          (J, U486, s486, Ua, sa), 2e-2,
                          "four chained f32 GEMMs, each < 4e-3"),
    }


def check_kernels(cases) -> None:
    """Each kernel on the chip against its oracle at highest precision."""
    import jax
    import numpy as np
    from repro.kernels import ops

    for name, (op, kern, oracle, args, tol, why) in cases.items():
        with ops.dispatch_tally() as tally:
            got = jax.block_until_ready(jax.jit(kern)(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args)
        err = 0.0
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            g, w = np.asarray(g), np.asarray(w)
            if not np.isfinite(g).all():
                raise SmokeFailure(f"kernel {name}: non-finite output")
            err = max(err, float(np.max(np.abs(g - w)) / np.max(np.abs(w))))
        log(f"[kernel] {name} routes {json.dumps(dict(tally[op]))} "
            f"max|kernel-ref|/max|ref| {err!r} (tol {tol}: {why})")
        if dict(tally[op]) != {"pallas": 1}:
            raise SmokeFailure(f"kernel {name} did not take the kernel")
        if not err <= tol:
            raise SmokeFailure(f"kernel {name}: error {err} > {tol}")


def one_chip() -> None:
    import jax
    routes = {}
    for variant in ("bkfac", "nskfac"):
        run = Run(variant, variant)
        compile_all([(run, *job) for job in run.lower(STEPS, routes)])
        losses = run.run(STEPS)[0]
        log(f"[{variant}] losses {json.dumps(losses)}")
    log("[routes] both configs " + json.dumps(routes, sort_keys=True))
    for op in KERNELS:
        if not routes.get(op, {}).get("pallas"):
            raise SmokeFailure(f"{op} never took the kernel: "
                               f"{routes.get(op)}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"[memory] peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    check_kernels(kernel_cases(jax.random.PRNGKey(42)))


def _m_bytes_per_device(state) -> dict:
    """Bytes of dense EA factor M each device holds, read from the arrays'
    shards (placeholders of pure-Brand factors excluded)."""
    per = {}
    for tap in state.opt.factors.values():
        for st in (tap.A, tap.G):
            if st.M.shape[-1] <= 1:
                continue
            for sh in st.M.addressable_shards:
                per[sh.device.id] = per.get(sh.device.id, 0) \
                    + sh.data.nbytes
    return dict(sorted(per.items()))


def _perturbed(state):
    """``state`` with its params × (1 ± 2⁻²²) entry by entry: a change of
    about two units in the last place, the size of what reordering one
    f32 sum changes."""
    import jax
    leaves, tree = jax.tree_util.tree_flatten(state.params)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    return state._replace(params=jax.tree_util.tree_unflatten(tree, [
        x * (1.0 + 2.0 ** -22 * jax.random.rademacher(k, x.shape, x.dtype))
        for k, x in zip(keys, leaves)]))


def _rel(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    scale = float(np.max(np.abs(want)))
    return float(np.max(np.abs(got - want))) / (scale or 1.0)


def _factor_diffs(got, want) -> dict:
    """Per factor side: relative max difference of the dense M, of the
    spectrum D, and of U·diag(D)·Uᵀ applied to 8 fixed random vectors
    (basis rotations inside a degenerate eigenspace do not count)."""
    import numpy as np
    out = {}
    for tap in sorted(want):
        for side in ("A", "G"):
            g, w = getattr(got[tap], side), getattr(want[tap], side)
            Z = np.random.default_rng(0).standard_normal(
                (w.U.shape[-2], 8)).astype(np.float32)
            act = lambda f: np.asarray(f.U) @ (
                np.asarray(f.D)[..., :, None]
                * (np.swapaxes(np.asarray(f.U), -1, -2) @ Z))
            out[f"{tap}/{side}"] = {
                "M": _rel(g.M, w.M) if w.M.shape[-1] > 1 else 0.0,
                "D": _rel(g.D, w.D), "UDUz": _rel(act(g), act(w))}
    return out


def _update_diff(got, want, before) -> float:
    """|params got − params want| / |params want − params before|, over
    all leaves at once: the shared random init would hide any
    difference, and a leaf a step barely moves (a conv bias: ~1e-7)
    would turn rounding into a large ratio."""
    import jax
    import numpy as np
    flat = lambda t: np.concatenate(
        [np.ravel(np.asarray(x)) for x in jax.tree_util.tree_leaves(t)])
    g, w, b = flat(got), flat(want), flat(before)
    return float(np.linalg.norm(g - w) / np.linalg.norm(w - b))


def _one_steps(run, ref_states, tag, perturb=False, verbose=False) -> list:
    """For each step k, ``run`` steps from the replicated run's state
    before step k (perturbed by ~2 ulp for the control); returns per step
    the relative loss difference, the update difference and the largest
    factor differences against the replicated state after step k.  The
    differences do not compound from step to step, as a free run's do."""
    import jax
    out = []
    for k, (before, after, ref_loss) in enumerate(ref_states):
        start = _perturbed(before) if perturb else before
        st, loss = run.step_from(k, start)
        st = jax.device_get(st)
        fd = _factor_diffs(st.opt.factors, after.opt.factors)
        worst = {m: max(d[m] for d in fd.values())
                 for m in ("M", "D", "UDUz")}
        r = {"loss": abs(float(loss) - ref_loss) / abs(ref_loss),
             "update": _update_diff(st.params, after.params,
                                    before.params), **worst}
        out.append(r)
        log(f"[{tag}] one step {k} ({run.sched.work(k).label}) vs "
            "replicated: " + " ".join(f"{m} {v!r}" for m, v in r.items()))
        if verbose and k == 0:
            for f, d in fd.items():
                log(f"[{tag}]   after step 0 {f}: " + " ".join(
                    f"{m} {v!r}" for m, v in d.items()))
    return out


def four_chips(preset: str = "paper") -> None:
    """Sharded ≡ replicated on four chips, for the 1D and 2D layouts,
    held to a control: the replicated step from parameters changed by
    about two ulp, which shows how far rounding alone moves a step."""
    import jax
    from repro.launch import mesh as mesh_lib

    if len(jax.devices()) != 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, found "
                           f"{len(jax.devices())}")
    runs = [Run("replicated", "bkfac", preset),
            Run("1d", "bkfac", preset,
                mesh=mesh_lib.make_mesh((4,), ("curv",))),
            Run("2d", "bkfac", preset,
                mesh=mesh_lib.make_mesh((2, 2), ("data", "curv")),
                row_axis="data")]
    compile_all([(run, *job) for run in runs for job in run.lower(STEPS)])

    rep = runs[0]
    host = [jax.device_get(rep.state(rep.params0))]
    ref_losses, ref_state = rep.run(STEPS, on_step=lambda k, st: host.append(
        jax.device_get(st)))
    ref_states = [(host[k], host[k + 1], ref_losses[k])
                  for k in range(STEPS)]
    m_rep = _m_bytes_per_device(ref_state)
    log(f"[replicated] losses {json.dumps(ref_losses)}; M bytes/device "
        f"{m_rep}")
    ctl = _one_steps(rep, ref_states, "control", perturb=True,
                     verbose=True)
    for run in runs[1:]:
        losses, state = run.run(STEPS)
        m_dev = _m_bytes_per_device(state)
        m_full, m_step = run.opt.curvature.m_bytes()
        drift = _update_diff(state.params, ref_state.params, host[0].params)
        log(f"[{run.name}] losses {json.dumps(losses)}; free run after "
            f"{STEPS} steps: |param diff| / |replicated update| {drift!r}")
        log(f"[{run.name}] M bytes/device held between steps (read from "
            f"the shards) {m_dev}; inside the step (computed from shapes "
            f"by the engine) {m_step} of {m_full}")
        got = _one_steps(run, ref_states, run.name, verbose=True)
        for k, (g, c) in enumerate(zip(got, ctl)):
            bad = {m: (g[m], c[m]) for m in g
                   if not g[m] <= max(CONTROL_FACTOR * c[m], DIFF_FLOOR)}
            if bad:
                raise SmokeFailure(f"{run.name} step {k} departs from "
                                   f"replicated beyond the control: {bad}")
        if run.name == "2d" and not max(m_dev.values()) < sum(
                m_rep.values()):
            raise SmokeFailure(f"2d: dense M not spread: {m_dev}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing "
              "was run", file=sys.stderr)
        return 1
    for sub in ("src", "examples"):
        sys.path.insert(0, str(ROOT / sub))
    from repro.launch import compile_cache
    log(f"device_kind {dev.device_kind!r}, {len(jax.devices())} devices, "
        f"compile cache {compile_cache.enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    log(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
