"""The program's own names in a profile: the device scopes of the training
step (``model``, ``update`` and the Brand light update's four phases,
read from the compiled step's ``op_name`` metadata) and the host spans
of one training-loop iteration, in order.  The scopes are names only:
tests/test_golden_regression.py and the metrics-on-equals-off tests of
tests/test_obs.py pin the numbers."""
import contextlib
import re
import types

import jax
import jax.numpy as jnp
import pytest

from repro.core import kfac as kfac_lib, policy as policy_lib
from repro.launch import train as launch_train
from repro.models import layers
from repro.models.cnn import VggConfig, make_vgg
from repro.obs import trace as obs_trace
from repro.optim import adamw, base as optbase
from repro.train import health as health_lib
from repro.train import loop
from repro.train import straggler as strag_lib

#: a CNN whose FC0 factor (d = 256) takes the Brand update (d > r + n_stat)
VGG = VggConfig(stages=(8, 16), n_classes=10, fc_hidden=20, n_stat=16,
                pool=(2, 1), img=8)
R, BATCH = 8, 4

#: the step's named ops outside ``model`` and ``update``: the rng split
#: (its call and the slices that unpack its keys)
ALLOWED = ("jit(_threefry_split)", "slice")

SPANS = [obs_trace.SCHEDULE, obs_trace.DISPATCH, obs_trace.LOSS_SYNC,
         obs_trace.CALLBACK]


def _vgg_opt(taps):
    return kfac_lib.Kfac(kfac_lib.KfacConfig(
        policy=policy_lib.PolicyConfig(variant="bkfac", r=R,
                                       max_dense_dim=64),
        T_updt=5, T_brand=5, T_inv=25), taps)


def _op_names(compiled_text: str):
    """Every ``op_name`` of the step's operations (parameters carry the
    argument's name instead, which does not start with ``jit(``)."""
    return [n for n in re.findall(r'op_name="([^"]*)"', compiled_text)
            if n.startswith("jit(")]


def _rest(name: str) -> str:
    """The path below the step's own ``jit(...)``."""
    return name.split("/", 1)[1] if "/" in name else ""


@pytest.fixture(scope="module")
def light_step_names():
    init, loss_fn, _, taps = make_vgg(VGG)
    opt = _vgg_opt(taps)
    params = init(jax.random.PRNGKey(0))
    state = loop.TrainState(params=params, opt=opt.init(params),
                            rng=jax.random.PRNGKey(1))
    batch = (jnp.zeros((BATCH, VGG.img, VGG.img, 3)),
             jnp.zeros((BATCH,), jnp.int32))
    step = jax.jit(loop.make_scheduled_kfac_step(loss_fn, opt, BATCH),
                   static_argnames=("work",))
    work = opt.uniform_work(True, True, False)
    return _op_names(step.lower(state, batch, work).compile().as_text())


def _has_part(name, part):
    return part in name.split("/")[:-1]


SCOPES = {
    "forward": lambda n: (_has_part(n, "jvp(model)")
                          and "transpose(" not in n),
    "backward": lambda n: _has_part(n, "transpose(jvp(model))"),
    "update": lambda n: _has_part(n, "update") and "kfac/" not in n,
    "brand_panel": lambda n: _has_part(n, "brand_panel"),
    "brand_qr": lambda n: _has_part(n, "brand_qr"),
    "brand_core": lambda n: _has_part(n, "brand_core"),
    "brand_rotate": lambda n: _has_part(n, "brand_rotate"),
}


@pytest.mark.parametrize("scope", sorted(SCOPES))
def test_light_step_carries_scope(light_step_names, scope):
    assert any(SCOPES[scope](n) for n in light_step_names), scope


def test_light_step_names_every_op(light_step_names):
    """Every named op is the model's, the update's, or the rng split's;
    the K-FAC work sits inside the update, the Brand phases inside the
    light update."""
    stray = [n for n in light_step_names
             if not _rest(n).startswith(("jvp(model)/",
                                         "transpose(jvp(model))/",
                                         "update/") + ALLOWED)]
    assert not stray, stray[:10]
    for n in light_step_names:
        if "kfac/" in n:
            assert _rest(n).startswith("update/kfac/"), n
        if "/brand_" in n:
            assert "/light_brand/" in n, n


def _mlp():
    params = {"fc0": {"w": layers.dense_init(jax.random.PRNGKey(0), 6, 8)},
              "fc1": {"w": layers.dense_init(jax.random.PRNGKey(1), 8, 3)}}
    taps = {"fc0": kfac_lib.TapInfo("fc0/w", 6, 8, n_stat=4),
            "fc1": kfac_lib.TapInfo("fc1/w", 8, 3, n_stat=4)}

    def loss_fn(params, probes, batch):
        x, y = batch
        acts = {}
        h, acts["fc0"] = layers.tapped_matmul(params["fc0"]["w"], x,
                                              probes.get("fc0"), 4)
        h, acts["fc1"] = layers.tapped_matmul(params["fc1"]["w"],
                                              jax.nn.relu(h),
                                              probes.get("fc1"), 4)
        return jnp.mean(jnp.square(h - y)), acts

    batch = (jax.random.normal(jax.random.PRNGKey(2), (8, 6)),
             jnp.zeros((8, 3)))
    return params, taps, loss_fn, batch


def _mlp_kfac(taps):
    pol = policy_lib.PolicyConfig(variant="bkfac", r=2, max_dense_dim=512)
    return kfac_lib.Kfac(kfac_lib.KfacConfig(
        policy=pol, lr=optbase.constant(0.05), T_updt=1, T_inv=2,
        T_brand=1, T_rsvd=2, T_corct=2), taps)


def _scoped(names):
    return {s for s in ("jvp(model)", "transpose(jvp(model))", "update")
            if any(_has_part(n, s) for n in names)}


def test_baseline_step_names_model_and_update():
    params, _, loss_fn, batch = _mlp()
    opt = adamw.adamw(optbase.constant(1e-3))
    state = loop.TrainState(params=params, opt=opt.init(params),
                            rng=jax.random.PRNGKey(1))
    step = jax.jit(loop.make_baseline_step(loss_fn, opt))
    names = _op_names(step.lower(state, batch).compile().as_text())
    assert _scoped(names) == {"jvp(model)", "transpose(jvp(model))",
                              "update"}


def test_resilient_step_names_model_and_update():
    params, taps, loss_fn, batch = _mlp()
    opt = _mlp_kfac(taps)
    state = loop.TrainState(params=params, opt=opt.init(params),
                            rng=jax.random.PRNGKey(1))
    step = jax.jit(health_lib.make_resilient_kfac_step(loss_fn, opt, 8),
                   static_argnames=("work",))
    work = opt.uniform_work(True, True, False)
    names = _op_names(step.lower(state, batch, work).compile().as_text())
    assert _scoped(names) == {"jvp(model)", "transpose(jvp(model))",
                              "update"}
    assert all(_rest(n).startswith("update/kfac/")
               for n in names if "kfac/" in n)


@pytest.fixture
def recorded_spans(monkeypatch):
    seen = []

    @contextlib.contextmanager
    def record(name):
        seen.append(name)
        yield

    monkeypatch.setattr(obs_trace, "host_span", record)
    return seen


def test_training_loop_spans_each_step_in_order(recorded_spans):
    params, taps, loss_fn, batch = _mlp()
    calls = []
    loop.run_kfac_training(loss_fn, _mlp_kfac(taps), params, [batch] * 3,
                           n_tokens=8,
                           callback=lambda k, s, l: calls.append(k))
    assert calls == [0, 1, 2]
    assert recorded_spans == SPANS * 3


def test_launcher_loop_spans_each_step_in_order(recorded_spans):
    """``launch/train.run_steps`` emits the same spans, its step
    bookkeeping under ``train/callback``."""
    work = types.SimpleNamespace(label="idle")
    sched = types.SimpleNamespace(work=lambda k: work)
    stream = types.SimpleNamespace(batch_at=lambda k: k)
    args = types.SimpleNamespace(steps=3, ckpt_dir=None, ckpt_every=1)
    losses = []
    launch_train.run_steps(
        args, sched, strag_lib.StragglerDetector(), stream,
        lambda state, batch, work, landing: (state + 1, jnp.float32(batch)),
        0, None, 0, 0.0, losses)
    assert losses == [0.0, 1.0, 2.0]
    assert recorded_spans == SPANS * 3
