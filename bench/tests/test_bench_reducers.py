"""The trace reductions and the per-layer readers, on a hand-made trace
with known answers and on a small recorded chip trace (the fixture)."""
import gzip
import json
import pathlib
import types

import pytest

import devtrace
import manifest

FIXTURE = pathlib.Path(__file__).resolve().parent / "fixtures"


def _op(start, dur, scope):
    return [float(start), float(dur), scope, "1", 0.0, 0.0]


def _hand_trace():
    """Two steps in a 100 ns window: step 1 runs a model op, a factor op
    inside a control-flow op, and a precond op; step 2 a model op."""
    ops = [_op(0, 20, "jit(step)/jvp()/dot_general:"),
           _op(20, 30, "conditional.1"),
           _op(22, 10, "jit(step)/kfac/factor/b1_brand/light_brand/dot:"),
           _op(50, 10, "jit(step)/kfac/precond/b0/pallas_call:"),
           _op(70, 20, "jit(step)/jvp()/dot_general:")]
    devtrace._self_times(ops)
    return {"ops": ops,
            "modules": [[0.0, 60.0, "jit_step(1)", "1"],
                        [65.0, 30.0, "jit_step(2)", "2"]],
            "host": [[0.0, 1.0, devtrace.BATCH_SPAN],
                     [60.0, 5.0, "PjitFunction(step)"],
                     [95.0, 5.0, devtrace.CALLBACK_SPAN]],
            "chips": 1}


def test_hand_trace_reductions():
    p = _hand_trace()
    assert devtrace.window(p) == (0.0, 100.0)
    assert devtrace.busy_ns(p) == 80.0
    assert [op[6] for op in p["ops"]] == [20.0, 20.0, 10.0, 10.0, 20.0]
    assert p["ops"][1][2] == p["ops"][2][2]      # the cond takes its body's
    assert devtrace.scope_ns(p, devtrace.is_factor) == 30.0
    assert devtrace.scope_ns(p, devtrace.is_precond) == 10.0
    assert devtrace.per_step_scope_ns(p, devtrace.is_factor) == [30.0, 0.0]
    b = devtrace.breakdown(p)
    assert b["device_ops"][0] == ["jvp()/dot_general", 40e-9]
    assert dict(b["idle_gaps"]) == {"PjitFunction(step)": 10e-9,
                                    "bench/callback": 10e-9}


def _ctx(parsed):
    cell = manifest.load_cell("vgg16bn-mod.bkfac")
    adapter = manifest.config_module(cell.config)
    _, _, taps = adapter.build_model(cell.sizes, 256)
    built = types.SimpleNamespace(
        taps=taps,
        flops_per_step=adapter.model_flops(cell.sizes, taps, {"batch": 128}))
    summ = devtrace.summary(parsed)
    return {"parsed": parsed, "built": built, "cell": cell,
            "device_kind": "TPU v5 lite", "steps": summ["steps"],
            "window_s": summ["window_s"]}


def test_hand_trace_metrics():
    ctx = _ctx(_hand_trace())
    value = lambda m: manifest.metric_module(m).value(ctx)
    assert value("device_idle_share") == pytest.approx(20.0)
    assert value("model_ms") == pytest.approx(40e-6 / 2)
    assert value("factor_ms") == pytest.approx(30e-6 / 2)
    assert value("factor_busy_step_ms") == pytest.approx(30e-6)
    assert value("precond_ms") == pytest.approx(10e-6 / 2)


def test_readers_find_nothing_to_read():
    p = _hand_trace()
    p["ops"] = [op for op in p["ops"] if "kfac" not in op[2]]
    ctx = _ctx(p)
    for m in ("factor_ms", "factor_busy_step_ms", "precond_ms",
              "precond_roofline", "brand_roofline"):
        assert manifest.metric_module(m).value(ctx) is None


def _fixture():
    raw = gzip.decompress((FIXTURE / "vgg16bn-mod.bkfac.2steps.json.gz")
                          .read_bytes())
    return devtrace.finish(json.loads(raw))


def test_recorded_chip_trace():
    """Two steps of vgg16bn-mod.bkfac on a TPU v5e: an idle step of
    about 45.5 ms on the device, then a stats + Brand light step of about
    232 ms, 182 ms of it under kfac/factor/."""
    p = _fixture()
    steps = devtrace.steps_in_window(p)
    assert [round(s[1] / 1e6, 1) for s in steps] == [45.5, 232.0]
    factor = devtrace.per_step_scope_ns(p, devtrace.is_factor)
    assert factor[0] == 0.0 and 180e6 < factor[1] < 185e6
    light = devtrace.per_step_scope_ns(p, devtrace.is_light_brand)
    assert light[0] == 0.0 and 0 < light[1] <= factor[1]
    ctx = _ctx(p)
    value = lambda m: manifest.metric_module(m).value(ctx)
    assert 40.0 < value("model_ms") < 50.0
    assert 0.0 < value("device_idle_share") < 100.0
    for m in ("step_mfu", "precond_roofline", "brand_roofline"):
        assert 0.0 < value(m) < 100.0, m
    b = devtrace.breakdown(p)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0].startswith("kfac/factor/")
