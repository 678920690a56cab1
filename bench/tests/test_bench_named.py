"""The readers of the program's own scopes and loop spans (``forward_ms``
to ``loop_idle_ms``, through ``loopspans.py``), on a hand-made trace with
known answers and on two recorded chip traces: one from before the
program named its work, one with the names."""
import gzip
import json
import pathlib

import pytest

import devtrace
import loopspans
import manifest
from test_bench_reducers import _ctx, _hand_trace, _op

FIXTURE = pathlib.Path(__file__).resolve().parent / "fixtures"

#: the readers of the program's own scopes and loop spans
NAMED = ("forward_ms", "backward_ms", "update_ms", "brand_core_ms",
         "brand_linear_ms", "dispatch_idle_ms", "loop_idle_ms")

_LIGHT = "jit(step)/update/kfac/factor/b1_brand/light_brand/"


def _named_trace():
    """Two steps in a 200 ns window, named as the program names them.
    Step 1 (10-90): forward 10, backward 15, update 5, a Brand light
    update (panel 2, QR 3, core 30 in a while loop, rotation 4, one op
    of no phase 1), preconditioning 5, a copy with no path 5.  Step 2
    (120-160): forward 10, backward 15, update 5, a copy 10.  The device
    is idle 3-10 and 103-120 inside the step calls, and 0-3, 90-103,
    160-200 outside them."""
    ops = [_op(10, 10, "jit(step)/jvp(model)/dot_general:"),
           _op(20, 15, "jit(step)/transpose(jvp(model))/dot_general:"),
           _op(35, 5, "jit(step)/update/add:"),
           _op(40, 2, _LIGHT + "brand_panel/pallas_call:"),
           _op(42, 3, _LIGHT + "brand_qr/pallas_call:"),
           _op(45, 30, "while.7"),
           _op(46, 20, _LIGHT + "brand_core/jit(eigh)/dot_general:"),
           _op(75, 4, _LIGHT + "brand_rotate/dot_general:"),
           _op(79, 1, _LIGHT + "mul:"),
           _op(80, 5, "jit(step)/update/kfac/precond/b0/dot_general:"),
           _op(85, 5, "copy.1"),
           _op(120, 10, "jit(step)/jvp(model)/jvp(relu)/max:"),
           _op(130, 15, "jit(step)/transpose(jvp(model))/conv:"),
           _op(145, 5, "jit(step)/update/mul:"),
           _op(150, 10, "copy.2")]
    devtrace._self_times(ops)
    host = []
    for base in (0.0, 100.0):
        host += [[base, 1.0, devtrace.BATCH_SPAN],
                 [base + 1, 2.0, "train/schedule"],
                 [base + 3, 9.0 if base == 0 else 22.0, "train/dispatch"],
                 [base + 5, 3.0, "PjitFunction(step)"]]
    host += [[12.0, 83.0, "train/loss_sync"],
             [95.0, 5.0, "train/callback"],
             [95.0, 5.0, devtrace.CALLBACK_SPAN],
             [125.0, 40.0, "train/loss_sync"],
             [165.0, 35.0, "train/callback"],
             [165.0, 35.0, devtrace.CALLBACK_SPAN]]
    return {"ops": ops,
            "modules": [[10.0, 80.0, "jit_step(1)", "1"],
                        [120.0, 40.0, "jit_step(2)", "2"]],
            "host": sorted(host), "chips": 1}


def test_named_trace_metrics():
    p = _named_trace()
    ctx = _ctx(p)
    value = lambda m: manifest.metric_module(m).value(ctx)
    assert ctx["steps"] == 2 and devtrace.window(p) == (0.0, 200.0)
    assert value("forward_ms") == pytest.approx(10e-6)
    assert value("backward_ms") == pytest.approx(15e-6)
    assert value("update_ms") == pytest.approx(5e-6)
    assert value("model_ms") == pytest.approx(37.5e-6)
    assert value("brand_core_ms") == pytest.approx(30e-6)
    assert value("brand_linear_ms") == pytest.approx(9e-6)
    assert value("dispatch_idle_ms") == pytest.approx(12e-6)
    assert value("loop_idle_ms") == pytest.approx(28e-6)
    assert value("device_idle_share") == pytest.approx(40.0)
    assert loopspans.clock_offsets(p) == [
        {"start_after_dispatch_ms": pytest.approx(-2e-6),
         "sync_after_end_ms": pytest.approx(5e-6)},
        {"start_after_dispatch_ms": pytest.approx(-5e-6),
         "sync_after_end_ms": pytest.approx(5e-6)}]


def test_named_readers_find_nothing_to_read():
    """A program that lacks the scopes and the loop spans (the hand trace
    of the other readers names its ops ``jvp()``, as before the ``model``
    scope)."""
    ctx = _ctx(_hand_trace())
    for m in NAMED:
        assert manifest.metric_module(m).value(ctx) is None, m


def _fixture(name):
    raw = gzip.decompress((FIXTURE / name).read_bytes())
    return devtrace.finish(json.loads(raw))


def test_recorded_chip_trace_without_the_names():
    """The first fixture was recorded before the program named its
    scopes and loop spans: every reader of them finds nothing."""
    ctx = _ctx(_fixture("vgg16bn-mod.bkfac.2steps.json.gz"))
    for m in NAMED:
        assert manifest.metric_module(m).value(ctx) is None, m


def test_recorded_chip_trace_with_the_names():
    """Two steps of vgg16bn-mod.bkfac on a TPU v5e, recorded with the
    program's scopes and loop spans: an idle step of about 45.5 ms on the
    device, then a stats + Brand light step of about 231.5 ms.  The
    readers split what ``model_ms``, the light update and the idle time
    hold, and the host spans share the device's clock."""
    p = _fixture("vgg16bn-mod.bkfac.2steps.spans.json.gz")
    steps = devtrace.steps_in_window(p)
    assert [round(s[1] / 1e6, 1) for s in steps] == [45.5, 231.5]
    ctx = _ctx(p)
    value = lambda m: manifest.metric_module(m).value(ctx)
    for m in NAMED:
        assert value(m) > 0.0, m
    assert (value("forward_ms") + value("backward_ms") + value("update_ms")
            <= value("model_ms"))
    light = [t for t in devtrace.per_step_scope_ns(p, devtrace.is_light_brand)
             if t > 0]
    assert (value("brand_core_ms") + value("brand_linear_ms")
            <= sum(light) / len(light) / 1e6)
    # the core's eigen-decomposition and CholeskyQR2's Gram roots
    assert 125.0 < value("brand_core_ms") < 140.0
    assert 45.0 < value("brand_linear_ms") < 55.0
    lo, hi = devtrace.window(p)
    idle = value("device_idle_share") / 100.0 * (hi - lo) / 1e6 / ctx["steps"]
    assert (value("dispatch_idle_ms") + value("loop_idle_ms")
            == pytest.approx(idle, rel=0.01))
    for c in loopspans.clock_offsets(p):
        assert c["start_after_dispatch_ms"] <= 1.0, c
        assert c["sync_after_end_ms"] > 0.0, c
