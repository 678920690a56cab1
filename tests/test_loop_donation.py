"""The training loop donates the optimizer state and the key it owns to
the jitted step, and nothing a caller may hold: the params it hands to
callbacks, the batches, a supplied state, or what the async runner reads.
On the CPU a donated input is deleted, so each test reads the buffers."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import specs
from repro.core import kfac as kfac_lib
from repro.core import policy
from repro.models import layers
from repro.models.cnn import make_vgg
from repro.obs import events as ev_lib
from repro.optim import base as optbase
from repro.train import loop

from test_obs import N_BS, _batches, _cfg, _make_mlp, _mlp_loss
from test_trace_names import VGG, _vgg_opt

STEPS = 5


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def _deleted(tree):
    return [x.is_deleted() for x in _leaves(tree)]


def _kept_run(steps=STEPS, **kw):
    """A run whose callback keeps every step's state, as the benchmark's
    recorder keeps the params; returns (initial params, states, final
    state, losses)."""
    params, taps = _make_mlp()
    opt = kfac_lib.Kfac(_cfg("kfac"), taps)
    seen = []
    final, losses = loop.run_kfac_training(
        _mlp_loss, opt, params, _batches(steps), n_tokens=N_BS,
        callback=lambda k, s, l: seen.append(s), **kw)
    return params, seen, final, losses


def test_jit_donates_opt_and_keeps_params():
    params, seen, final, losses = _kept_run()
    assert len(seen) == STEPS
    # every params tree the callback kept, and the initial one, reads
    for tree in [params] + [s.params for s in seen]:
        assert not any(_deleted(tree))
        for x in _leaves(tree):
            assert np.isfinite(np.asarray(x)).all()
    # each step took over the previous step's optimizer state and key
    for s in seen[:-1]:
        assert all(_deleted(s.opt)) and s.rng.is_deleted()
    assert not any(_deleted(final.opt)) and not final.rng.is_deleted()

    # bit for bit a hand loop over the undonated jitted step
    _, taps = _make_mlp()
    opt = kfac_lib.Kfac(_cfg("kfac"), taps)
    step = jax.jit(loop.make_scheduled_kfac_step(_mlp_loss, opt, N_BS),
                   static_argnames=("work",))
    state = loop.TrainState(params=params, opt=opt.init(params),
                            rng=jax.random.PRNGKey(0))
    sched = opt.scheduler()
    ref = []
    for k, batch in enumerate(_batches(STEPS)):
        state, loss = step(state, batch, sched.work(k))
        ref.append(float(loss))
    assert losses == ref
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        final.params, state.params)


def _async_setup():
    """One tap under the async heavy pipeline, so ``overlap=True`` gets a
    runner."""
    taps = {"fc": kfac_lib.TapInfo("fc/w", 24, 8, n_stat=8)}
    cfg = kfac_lib.KfacConfig(
        policy=policy.PolicyConfig(variant="kfac", r=4),
        lr=optbase.constant(0.05), T_updt=1, T_inv=4, stagger=True,
        async_heavy=True, heavy_lag=2)
    key = jax.random.PRNGKey(0)
    params = {"fc": {"w": jax.random.normal(key, (24, 8)) * 0.1}}

    def loss_fn(p, probes, batch):
        x, y = batch
        h, act = layers.tapped_matmul(p["fc"]["w"], x, probes.get("fc"), 8)
        return jnp.mean((h - y) ** 2), {"fc": act}

    batches = [(jax.random.normal(jax.random.fold_in(key, i), (8, 24)),
                jax.random.normal(jax.random.fold_in(key, 50 + i), (8, 8)))
               for i in range(6)]
    return kfac_lib.Kfac(cfg, taps), params, loss_fn, batches


@pytest.mark.parametrize("mode", ["overlap", "nojit"])
def test_no_donation_deletes_nothing(mode):
    opt, params, loss_fn, batches = _async_setup()
    seen = []
    final, _ = loop.run_kfac_training(
        loss_fn, opt, params, batches, n_tokens=8,
        overlap=mode == "overlap", jit=mode != "nojit",
        callback=lambda k, s, l: seen.append(s))
    assert len(seen) == len(batches)
    for s in seen + [final]:
        assert not any(_deleted(s))
    assert not any(_deleted(params))


def test_supplied_state_is_copied_not_consumed():
    params, taps = _make_mlp()
    opt = kfac_lib.Kfac(_cfg("kfac"), taps)
    mid, _ = loop.run_kfac_training(_mlp_loss, opt, params, _batches(2),
                                    n_tokens=N_BS)
    before = jax.device_get(mid)
    end, losses = loop.run_kfac_training(_mlp_loss, opt, None,
                                         _batches(3), n_tokens=N_BS,
                                         state=mid)
    assert len(losses) == 3 and not any(_deleted(mid))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
        mid, before)
    assert int(end.opt.step) == int(mid.opt.step) + 3


def _donation_event(tmp_path, **kw):
    path = str(tmp_path / "events.jsonl")
    with ev_lib.TelemetryWriter(path, console=False) as w:
        loop.run_kfac_training(kw.pop("loss_fn"), kw.pop("opt"),
                               kw.pop("params"), kw.pop("batches"),
                               obs=specs.ObsSpec(writer=w), **kw)
    evs = [e for e in ev_lib.read_events(path)
           if e["type"] == "loop_donation"]
    assert len(evs) == 1
    return evs[0]


def test_donation_event_counts_opt_and_rng(tmp_path):
    params, taps = _make_mlp()
    opt = kfac_lib.Kfac(_cfg("kfac"), taps)
    owned = _leaves((opt.init(params), jax.random.PRNGKey(0)))
    ev = _donation_event(tmp_path, loss_fn=_mlp_loss, opt=opt,
                         params=params, batches=_batches(2),
                         n_tokens=N_BS)
    assert ev["donated_leaves"] == len(owned) > 2
    assert ev["donated_bytes"] == sum(x.nbytes for x in owned)
    assert ev["kept_leaves"] == len(_leaves(params))
    assert "reason" not in ev


@pytest.mark.parametrize("mode", ["overlap", "nojit"])
def test_donation_event_says_why_not(tmp_path, mode):
    opt, params, loss_fn, batches = _async_setup()
    n_all = len(_leaves((params, opt.init(params), jax.random.PRNGKey(0))))
    ev = _donation_event(tmp_path, loss_fn=loss_fn, opt=opt, params=params,
                         batches=batches[:2], n_tokens=8,
                         overlap=mode == "overlap", jit=mode != "nojit")
    assert ev["donated_leaves"] == 0 and ev["donated_bytes"] == 0
    assert ev["kept_leaves"] == n_all
    assert ev["reason"] == ("runner" if mode == "overlap" else "nojit")


def _buffers(leaf):
    return tuple(s.data.unsafe_buffer_pointer()
                 for s in leaf.addressable_shards)


def _init_shares_no_buffer(opt, params):
    """No leaf of ``opt.init(params)`` is a buffer of ``params`` or of
    another leaf, so the loop donates the state it builds as it is: a
    shared buffer would delete the caller's params, or be donated twice,
    which the runtime refuses."""
    seen = [b for x in _leaves(params) for b in _buffers(x)]
    seen += [b for x in _leaves(opt.init(params)) for b in _buffers(x)]
    assert len(set(seen)) == len(seen)


@pytest.mark.parametrize("variant", policy.VARIANTS)
def test_init_shares_no_buffer_mlp(variant):
    params, taps = _make_mlp()
    _init_shares_no_buffer(kfac_lib.Kfac(_cfg(variant), taps), params)


def test_init_shares_no_buffer_vgg():
    """The stacked taps of a CNN, whose factor states are broadcast."""
    init, _, _, taps = make_vgg(VGG)
    _init_shares_no_buffer(_vgg_opt(taps), init(jax.random.PRNGKey(0)))
