#!/usr/bin/env python3
"""The readings the correctness limits of a cell are set from (its runs
do not run this):

    python bench/readings.py --workload vgg16bn-mod.bkfac \\
        --seeds 11 12 13 ... --control-seeds 21 22 23 --fault-seeds 31 32 33

For every ``--seeds`` seed, the program's checked first steps against
the reference: the lower readings.  For every ``--control-seeds`` seed,
the control, the reference computed in the precision below the one the
configuration states (its file's ``control``), put in the program's
place: the upper readings.  For every ``--fault-seeds`` seed, the
reference with a fault planted where it runs: half of each batch left
out and the mean taken over the rest (``half``), one label of each
batch altered where the batch is made (``label``), and a step that
leaves the parameters unchanged (``stale``; it reads 1 on ``change``
and ``last`` by their definition, and is run for the other numbers).
For every ``--nudge-seeds`` seed, a look at how far
rounding-sized differences carry: the reference against itself with
every initial weight scaled by 1 + 1e-7·z (z standard normal), with
where the two part, step by step.  The reference runs once per seed.
One JSON line per reading, on standard output.
"""
from __future__ import annotations

import argparse
import faulthandler
import gc
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

NUDGE = 1e-7


def _half(pool):
    """Half the images and labels of each batch."""
    return tuple(tuple(x[: x.shape[0] // 2] for x in b) for b in pool)


def _alter(pool, data: dict):
    """One label of each batch changed to the next class."""
    n = int(data["classes"])
    return tuple((x, y.at[0].set((y[0] + 1) % n)) for x, y in pool)


class Nudged:
    """The reference model with every initial weight scaled by
    1 + ``eps``·z, z standard normal from a key of its own."""

    def __init__(self, model, eps: float):
        self.model, self.eps = model, eps

    def __getattr__(self, name):
        return getattr(self.model, name)

    def init(self, sizes, key, dtype):
        import jax
        params = self.model.init(sizes, key, dtype)
        leaves, treedef = jax.tree_util.tree_flatten(params)
        keys = jax.random.split(jax.random.PRNGKey(20260), len(leaves))
        return treedef.unflatten([
            p * (1 + self.eps * jax.random.normal(k, p.shape, p.dtype))
            for p, k in zip(leaves, keys)])


def parting(got: dict, ref: dict) -> dict:
    """Where two detailed records part: per step the worst gap of that
    step's per-leaf change over the moving leaves, with its leaf, and
    both clip scales; the five factors whose spectrum after step 0
    differs most, as ‖D − D_ref‖ / ‖D_ref‖."""
    import math

    import compare
    moving = [k for k in ref["change"] if k not in compare.null_leaves(ref)]
    steps = []
    for k, (a, b) in enumerate(zip(got["step_change"], ref["step_change"])):
        g, at = compare.gap(a, b, moving)
        steps.append({"step": k, "gap": g, "at": at,
                      "clip": [got["clip_scale"][k], ref["clip_scale"][k]]})
    spectra = {}
    for fk, d_ref in ref["D0"].items():
        d = got["D0"][fk]
        den = math.sqrt(sum(x * x for x in d_ref)) or 1.0
        spectra[fk] = math.sqrt(sum((x - y) ** 2 for x, y in
                                    zip(d, d_ref))) / den
    worst = sorted(spectra.items(), key=lambda kv: -kv[1])[:5]
    return {"steps": steps, "spectra": worst}


class Seed:
    """What every reading of one seed shares: keys, the checked batches
    and the reference's record."""

    def __init__(self, cell, seed: int, detail: bool):
        import jax

        import data as data_lib
        import manifest
        self.cell, self.seed = cell, seed
        adapter = manifest.config_module(cell.config)
        self.model = manifest.reference_module(cell.config)
        self.train = manifest.reference_train()
        self.params_key, self.data_key = jax.random.split(
            data_lib.seed_key(seed))
        self.loop_seed = seed % (2 ** 31 - 1)
        self.steps = int(cell.job["check_steps"])
        self.data = dict(cell.job["data"], **adapter.data_shape(cell.sizes))
        self.n_tokens = adapter.n_tokens(self.data)
        self.pool = data_lib.make_pool(self.data, self.data_key)[:self.steps]
        t0 = time.perf_counter()
        self.ref = self.reference(self.model, self.pool, detail=detail)
        self.ref_s = round(time.perf_counter() - t0, 2)

    def reference(self, model, pool, **kw) -> dict:
        return self.train.run(model, self.cell.sizes, self.cell.job,
                              self.params_key, self.loop_seed, pool,
                              self.n_tokens, **kw)


def readings(cell, seed: int, kind: str, shared: Seed = None) -> dict:
    """One reading: the numbers of ``compare.numbers`` for the program
    (``kind="program"``), the control, a planted fault or the nudged
    reference."""
    import jax.numpy as jnp

    import compare
    import manifest
    import program
    s = shared or Seed(cell, seed, detail=kind == "nudge")
    t0 = time.perf_counter()
    extra = {}
    if kind == "program":
        adapter = manifest.config_module(cell.config)
        built = program.build(adapter, cell.sizes, cell.job, s.params_key,
                              s.data_key)
        rec, _, state = program.run(built, s.steps, 0.0, None,
                                    program.CompileCounter(),
                                    lambda m: None, s.loop_seed)
        got = rec.rec
        del state, built
        gc.collect()
    elif kind == "control":
        got = s.reference(s.model, s.pool,
                          dtype=jnp.dtype(cell.sizes["control"]["params"]))
    elif kind == "half":
        got = s.reference(s.model, _half(s.pool))
    elif kind == "label":
        got = s.reference(s.model, _alter(s.pool, s.data))
    elif kind == "stale":
        apply = s.train._apply
        s.train._apply = lambda params, updates, scale: params
        try:
            got = s.reference(s.model, s.pool)
        finally:
            s.train._apply = apply
    elif kind == "nudge":
        got = s.reference(Nudged(s.model, NUDGE), s.pool, detail=True)
        extra = {"eps": NUDGE, "parting": parting(got, s.ref)}
    else:
        raise ValueError(kind)
    nums = compare.numbers(got, s.ref)
    correct, _ = compare.check(got, s.ref, cell.limits)
    return {"workload": cell.name, "kind": kind, "seed": seed,
            "numbers": {k: v[0] for k, v in nums.items()},
            "at": {k: v[1] for k, v in nums.items()},
            "correct_under_limits": correct,
            "loss": got["loss"], "ref_loss": s.ref["loss"],
            "s": round(time.perf_counter() - t0, 2), "ref_s": s.ref_s,
            **extra}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--nudge-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--watchdog", type=float, default=0.0,
                    help="dump every thread's stack to standard error "
                         "each time a reading has taken this many seconds")
    args = ap.parse_args(argv)
    import jaxcache
    jaxcache.use_checkout_cache()
    import jax
    import manifest
    if jax.devices()[0].platform != "tpu":
        print("readings: JAX found no TPU", file=sys.stderr)
        return 1
    cell = manifest.load_cell(args.workload)
    jobs = ([(s, "program") for s in args.seeds]
            + [(s, "nudge") for s in args.nudge_seeds]
            + [(s, "control") for s in args.control_seeds]
            + [(s, k) for s in args.fault_seeds
               for k in ("half", "label", "stale")])
    detail = set(args.nudge_seeds)
    shared = {}
    for seed, kind in jobs:
        if args.watchdog:
            faulthandler.dump_traceback_later(args.watchdog, repeat=True)
        if seed not in shared:
            shared[seed] = Seed(cell, seed, detail=seed in detail)
        print(json.dumps(readings(cell, seed, kind, shared[seed])),
              flush=True)
        faulthandler.cancel_dump_traceback_later()
    return 0


if __name__ == "__main__":
    sys.exit(main())
