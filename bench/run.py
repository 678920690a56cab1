#!/usr/bin/env python3
"""The chip benchmark of the K-FAC training step.

    python bench/run.py --workload vgg16bn-mod.bkfac --seed 7 \\
        --seconds 30 --trace 0

Runs one cell of ``BENCHMARK.json`` on the chips of the machine it is
started on: builds the cell's model (params from the seed, on the
device), its optimizer and a pool of batches; trains through the checked
first steps and then whole cadence cycles for ``--seconds`` in one call
of the product loop; compares what the first steps produced with the
plain reference (``bench/compare.py``); prints its last line as one JSON
object.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` its per-layer metrics from a profiler trace of a shorter window.

Exits non-zero with no result line where JAX finds no TPU, fewer chips
than the cell asks for, or a compilation inside the measured window.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

#: the traced run's window: whole cycles covering about this many seconds
TRACE_SECONDS = 3.0


class NoChip(RuntimeError):
    pass


class CompiledInWindow(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info(need: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < need:
        raise NoChip(f"the cell asks for {need} chips, JAX found "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": need}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, cell=None) -> dict:
    """One run of a cell; returns the result object.  ``cell`` may carry
    a changed ``manifest.Cell`` (the tests run tiny sizes on the CPU)."""
    import jaxcache
    jaxcache.use_checkout_cache()
    import jax

    import compare
    import data as data_lib
    import devtrace
    import manifest
    import program
    from repro.kernels import ops
    from repro.launch import compile_cache

    cell = cell or manifest.load_cell(workload)
    if require_tpu:
        device = device_info(cell.chips)
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind, "count": 1}
    cache = compile_cache.enable_compile_cache()
    log(f"device {json.dumps(device)}; compile cache {cache}")
    counter = program.CompileCounter()
    adapter = manifest.config_module(cell.config)
    params_key, data_key = jax.random.split(data_lib.seed_key(seed))
    built = program.build(adapter, cell.sizes, cell.job, params_key,
                          data_key)
    steps_checked = int(cell.job["check_steps"])
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        seconds = min(seconds, TRACE_SECONDS)
    loop_seed = seed % (2 ** 31 - 1)
    with ops.dispatch_tally() as tally:
        rec, win, state = program.run(
            built, steps_checked, seconds, trace_dir, counter, log,
            loop_seed=loop_seed)
    log("kernel routes " + json.dumps(
        {op: dict(c) for op, c in sorted(tally.items())}))
    setup_s = win.t0 - T_START
    if win.compiles:
        raise CompiledInWindow(f"{win.compiles} traces or compiles inside "
                               "the measured window")
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    device["memory_peak_bytes"] = peak
    n = len(win.times)
    bad = sum(1 for x in win.losses if x != x or abs(x) == float("inf"))
    log(f"window {n} steps in {win.t1 - win.t0:.4f} s; set-up "
        f"{setup_s:.3f} s; last loss {win.losses[-1]!r}")
    sched = built.opt.scheduler()
    kinds = {}
    for i, t in enumerate(win.times):
        kinds.setdefault(sched.work(steps_checked + i).label,
                         []).append(t * 1e3)
    log("median ms by step kind " + json.dumps(
        {k: statistics.median(v) for k, v in kinds.items()}))
    metrics, breakdown = {}, None
    if trace:
        parsed = devtrace.load_trace_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        summ = devtrace.summary(parsed)
        device["busy_s"], device["window_s"] = summ["busy_s"], summ["window_s"]
        breakdown = devtrace.breakdown(parsed)
        ctx = {"parsed": parsed, "built": built, "cell": cell,
               "device_kind": device["kind"], "steps": summ["steps"],
               "window_s": summ["window_s"]}
        for m in cell.per_layer:
            value = manifest.metric_module(m["name"]).value(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        log(f"traced {summ['steps']} steps: busy {summ['busy_s']} s of "
            f"{summ['window_s']} s")
    else:
        values = {
            "setup_s": setup_s,
            "step_ms": (win.t1 - win.t0) / n * 1e3,
            "step_ms_p95": statistics.quantiles(
                [t * 1e3 for t in win.times], n=20,
                method="inclusive")[18] if n > 1 else float("nan"),
            "peak_hbm_gb": peak / 1e9,
        }
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    # the reference runs once the program's state is freed
    pool = built.pool[:steps_checked]
    n_tokens = built.n_tokens
    del state, built
    gc.collect()
    t_ref = time.perf_counter()
    ref = manifest.reference_train().run(
        manifest.reference_module(cell.config), cell.sizes, cell.job,
        params_key, loop_seed, pool, n_tokens)
    log(f"reference {time.perf_counter() - t_ref:.2f} s")
    correct, checks = compare.check(rec.rec, ref, cell.limits)
    out = {"correct": correct, "attempted": n, "failed": bad,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                     for k, v in checks.items()}
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r} "
              f"(worst at {v['at']})", file=sys.stderr, flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except (NoChip, CompiledInWindow) as e:
        print(f"bench: {e}; no result", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
