"""Drives the program under test: builds a cell's model and optimizer
from its files, and runs the product training loop through the checked
first steps and the measured window in one call.

The loop is ``repro.train.loop.run_kfac_training`` with no ``obs``,
``ckpt`` or ``resilience`` spec, the path a user gets by default.
Batches come from a pool made on the device during set-up, so making
data is not timed.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.core import kfac as kfac_lib
from repro.core import policy as policy_lib
from repro.optim import base as optbase
from repro.train import loop

import data as data_lib
import devtrace as trace_lib


@dataclasses.dataclass
class Built:
    params: Any
    loss_fn: Callable
    taps: Dict[str, Any]
    opt: Any
    n_tokens: int
    pool: tuple
    cycle: int                  # steps in one cadence cycle
    flops_per_step: float


def _schedule(s) -> optbase.Schedule:
    if isinstance(s, dict):
        return optbase.piecewise(s["boundaries"], s["values"])
    return optbase.constant(float(s))


def make_optimizer(opt: dict, taps):
    """The program's K-FAC for a traffic file's ``optimizer`` block."""
    if opt["kind"] != "kfac":
        raise ValueError(f"no optimizer of kind {opt['kind']!r}")
    fb = opt["fallback"]
    if (fb["b1"], fb["b2"], fb["eps"]) != (0.9, 0.95, 1e-8):
        raise ValueError("the K-FAC fallback AdamW has fixed b1, b2, eps")
    cfg = kfac_lib.KfacConfig(
        policy=policy_lib.PolicyConfig(
            variant=opt["variant"], r=opt["r"], r_o=opt["r_o"],
            n_pwr_iter=opt["n_pwr_iter"], rho=opt["rho"],
            max_dense_dim=opt["max_dense_dim"]),
        lr=_schedule(opt["lr"]), damping_phi=_schedule(opt["damping_phi"]),
        momentum=opt["momentum"], weight_decay=opt["weight_decay"],
        clip=opt["clip"],
        spectrum_continuation=opt["spectrum_continuation"],
        T_updt=opt["T_updt"], T_inv=opt["T_inv"], T_brand=opt["T_brand"],
        T_rsvd=opt["T_rsvd"], T_corct=opt["T_corct"],
        fallback_lr=_schedule(fb["lr"]), fallback_wd=fb["weight_decay"])
    return kfac_lib.Kfac(cfg, taps)


def build(adapter, sizes: dict, job: dict, params_key, data_key) -> Built:
    opt_spec = job["optimizer"]
    init, loss_fn, taps = adapter.build_model(sizes, int(opt_spec["n_stat"]))
    data = dict(job["data"], **adapter.data_shape(sizes))
    opt = make_optimizer(opt_spec, taps)
    params = jax.jit(init)(params_key)
    pool = data_lib.make_pool(data, data_key)
    return Built(params=params, loss_fn=loss_fn, taps=taps, opt=opt,
                 n_tokens=adapter.n_tokens(data), pool=pool,
                 cycle=opt.scheduler().cycle,
                 flops_per_step=adapter.model_flops(sizes, taps, data))


def _path(kp) -> str:
    return "/".join(str(p.key) for p in kp)


@jax.jit
def _leaf_norms(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)


@jax.jit
def _norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def _flat(tree) -> Dict[str, float]:
    return {_path(kp): float(v) for kp, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


#: β1 of the fallback AdamW, fixed in the program (``make_optimizer``)
FALLBACK_B1 = 0.9


class Recorder:
    """Keeps what the checked first steps produced: each loss; after step
    0 the gradients the fallback AdamW's state holds (m = (1−β1)·g over
    the whole tree, the tapped weights included, though their updates
    come from K-FAC) and each factor's stats panel norm (√trace M, or √ΣD
    where the factor keeps no M); the per-leaf change over all the steps
    and over the last."""

    def __init__(self, built: Built, steps: int):
        self.built, self.steps = built, steps
        self.p0 = built.params
        self.prev = built.params
        self.rec: Dict[str, Any] = {"loss": []}

    def on_step(self, k: int, state, loss) -> None:
        if k >= self.steps:
            return
        self.rec["loss"].append(float(loss))
        if k == 0:
            self.rec["grad"] = {
                p: v / (1 - FALLBACK_B1) for p, v in
                _flat(_norms(state.opt.fallback.mu)).items()}
            self.rec["stats"] = self._stats(state.opt)
        if k == self.steps - 1:
            self.rec["change"] = _flat(_leaf_norms(state.params, self.p0))
            self.rec["last"] = _flat(_leaf_norms(state.params, self.prev))
            self.p0 = self.prev = None
        else:
            self.prev = state.params

    def _stats(self, opt_state) -> Dict[str, float]:
        out = {}
        for name in self.built.taps:
            for side in ("A", "G"):
                f = getattr(opt_state.factors[name], side)
                if f.M.shape[-1] > 1:
                    sq = jnp.trace(f.M, axis1=-2, axis2=-1)
                else:
                    sq = jnp.sum(f.D, axis=-1)
                out[f"{name}/{side}"] = math.sqrt(float(jnp.sum(sq)))
        return out


class CompileCounter:
    """Counts JAX's tracing, compiling and compile-cache reads."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.count += 1


@dataclasses.dataclass
class Window:
    t0: float = 0.0             # host clock at the window's start
    t1: float = 0.0             # ... and at its end
    times: List[float] = dataclasses.field(default_factory=list)
    losses: List[float] = dataclasses.field(default_factory=list)
    compiles: int = 0
    trace_dir: Optional[str] = None


def run(built: Built, steps_checked: int, seconds: float,
        trace_dir: Optional[str], counter: CompileCounter,
        log: Callable[[str], None], loop_seed: int):
    """Checked steps, then whole cadence cycles until ``seconds`` have
    passed, in one loop.  Returns (recorder, window, final state)."""
    rec = Recorder(built, steps_checked)
    win = Window(trace_dir=trace_dir)
    cycle, pool = built.cycle, built.pool
    state_box = {"stop": False, "last": 0.0, "c0": 0}
    first = steps_checked

    def batches():
        k = 0
        while not state_box["stop"]:
            with jax.profiler.TraceAnnotation(trace_lib.BATCH_SPAN):
                batch = pool[k % len(pool)]
            yield batch
            k += 1

    def callback(k, state, loss):
        with jax.profiler.TraceAnnotation(trace_lib.CALLBACK_SPAN):
            t = time.perf_counter()
            rec.on_step(k, state, loss)
            if k < first:
                log(f"step {k} loss {float(loss)!r} "
                    f"({(t - state_box['last']) * 1e3:.3f} ms)")
            if k == first - 1:
                win.t0 = t
                state_box["c0"] = counter.count
            elif k >= first:
                win.times.append(t - state_box["last"])
                win.losses.append(float(loss))
                done = k - first + 1
                if done % cycle == 0 and t - win.t0 >= seconds:
                    win.t1 = t
                    win.compiles = counter.count - state_box["c0"]
                    state_box["stop"] = True
            state_box["last"] = t
        if trace_dir is not None:
            if k == first - 1:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            elif state_box["stop"]:
                jax.profiler.stop_trace()

    state_box["last"] = time.perf_counter()
    state, _ = loop.run_kfac_training(
        built.loss_fn, built.opt, built.params, batches(),
        n_tokens=built.n_tokens, seed=loop_seed, callback=callback)
    return rec, win, state
