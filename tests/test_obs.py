"""Curvature telemetry subsystem (repro/obs/): event-log schema
round-trip, in-graph Meter semantics, and the load-bearing acceptance
claim — telemetry is numerically inert: metrics-on training must equal
metrics-off training bit-for-bit, replicated and 8-device sharded.
"""
import json
import os

import numpy as np
import pytest

# must precede backend init in THIS process; harmless if jax was already
# initialized with one device (the mesh tests then skip)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp

from repro.core import kfac as kfac_lib, policy
from repro.launch import mesh as mesh_lib
from repro.models import layers
from repro.obs import events as ev_lib
from repro.obs import metrics as m_lib
from repro.obs import summary as sum_lib
from repro.optim import base as optbase
from repro.train import loop
from repro import specs

D_IN, D_H, D_OUT, N_BS, N_STAT = 12, 32, 4, 16, 16

#: fast-tier variant subset for the train-twice parity tests; the
#: telemetry-smoke / distributed CI jobs run this file unfiltered.
_FAST_VARIANTS = {"bkfac"}


def _marked_variants():
    return [v if v in _FAST_VARIANTS
            else pytest.param(v, marks=pytest.mark.slow)
            for v in policy.VARIANTS]


# ---------------------------------------------------------------------------
# event-log schema
# ---------------------------------------------------------------------------

_SAMPLE_EVENTS = {
    "run_start": dict(config={"arch": "t", "steps": 2}),
    "run_end": dict(steps=2, loss_first=1.0, loss_last=0.5,
                    s_per_step=0.01),
    "log": dict(msg="hello"),
    "step": dict(step=0, loss=1.25, dt_s=0.01, phase="heavy"),
    "loop_donation": dict(donated_leaves=12, donated_bytes=4096,
                          kept_leaves=2),
    "metrics": dict(step=10, window_steps=10,
                    values={"work/stats_fired": 5.0},
                    kinds={"work/stats_fired": "counter"}),
    "sched": dict(detail="T_inv=5 buckets=2"),
    "async_launch": dict(step=3, bucket=0, lo=0, hi=8),
    "async_land": dict(step=5, bucket=0, lo=0, hi=8, overlapped=True),
    "async_miss": dict(step=5, bucket=1, lo=0, hi=8),
    "ckpt_save": dict(step=10, path="/tmp/x"),
    "ckpt_restore": dict(step=10, path="/tmp/x"),
    "repartition": dict(detail="8 -> 6 devices"),
    "remediation": dict(step=4, stage=1, action="escalate",
                        detail="damping scale 1 -> 8"),
    "serve_request": dict(uid=1, wait_s=0.0, total_s=0.2, n_new=32,
                          tenant=0, kind="infer"),
    "tenant_update": dict(tenant=0, step=3, loss=1.5, phase="light"),
}


def test_every_event_type_round_trips(tmp_path):
    """One of each type through the writer, read back validated — and the
    sample dict must cover the registry exactly, so adding a type without
    a test shows up here."""
    assert set(_SAMPLE_EVENTS) == set(ev_lib.EVENT_TYPES)
    path = tmp_path / "events.jsonl"
    with ev_lib.TelemetryWriter(str(path), console=False) as w:
        for etype, fields in _SAMPLE_EVENTS.items():
            w.emit(etype, **fields)
    evs = list(ev_lib.read_events(str(path)))
    assert [e["type"] for e in evs] == list(_SAMPLE_EVENTS)
    for e in evs:
        assert e["schema"] == ev_lib.SCHEMA_VERSION
        assert isinstance(e["t"], float)


def test_writer_rejects_malformed_events(tmp_path):
    w = ev_lib.TelemetryWriter(str(tmp_path / "e.jsonl"), console=False)
    with pytest.raises(ev_lib.EventSchemaError):
        w.emit("no_such_type", x=1)
    with pytest.raises(ev_lib.EventSchemaError):
        w.emit("step", step=0, loss=1.0)       # missing dt_s, phase
    w.close()
    # nothing reached the log
    assert list(ev_lib.read_events(str(tmp_path / "e.jsonl"))) == []


def test_reader_flags_corrupt_lines(tmp_path):
    path = tmp_path / "e.jsonl"
    path.write_text('{"schema": 1, "t": 0.0, "type": "log", "msg": "ok"}\n'
                    "not json\n")
    with pytest.raises(ev_lib.EventSchemaError, match="e.jsonl:2"):
        list(ev_lib.read_events(str(path)))
    # unknown type with validation off passes through
    path.write_text(json.dumps({"schema": 1, "t": 0.0, "type": "xx"}) +
                    "\n")
    assert len(list(ev_lib.read_events(str(path), validate=False))) == 1


def test_console_renders_familiar_lines():
    lines = []
    w = ev_lib.TelemetryWriter(console=True, console_fn=lines.append)
    w.log("resuming")
    w.emit("step", step=7, loss=2.5, dt_s=0.012, phase="light")
    w.emit("metrics", step=7, window_steps=5, values={}, kinds={})
    w.close()
    assert lines[0] == "[train] resuming"
    assert lines[1].startswith("[train] step     7")
    assert "light" in lines[1]
    assert len(lines) == 2            # metrics stay off the console


# ---------------------------------------------------------------------------
# Meter: in-graph accumulation, cadence, counter/gauge semantics
# ---------------------------------------------------------------------------

def _toy_meter(sink, every):
    catalog = (m_lib.MetricSpec("c", m_lib.COUNTER),
               m_lib.MetricSpec("g", m_lib.GAUGE))
    return m_lib.Meter(catalog, sink, every=every)


def test_meter_counter_gauge_flush_cadence():
    got = []
    meter = _toy_meter(lambda s, w, v: got.append((s, w, v)), every=3)

    def step(mbuf, k):
        with meter.collecting() as col:
            m_lib.record("c", 2.0)
            m_lib.record("c", 1.0)          # counters add within a step
            m_lib.record("g", jnp.float32(k))
        return meter.maybe_flush(meter.merge(mbuf, col), k)

    mbuf = meter.init()
    for k in range(7):
        mbuf = jax.block_until_ready(step(mbuf, jnp.int32(k)))
    # windows closed at steps 2 and 5 (3 merges each)
    assert [(s, w) for s, w, _ in got] == [(2, 3), (5, 3)]
    assert got[0][2]["c"] == 9.0            # 3 steps x (2+1)
    assert got[1][2]["c"] == 9.0            # counter reset between windows
    assert got[1][2]["g"] == 5.0            # gauge: last value wins
    meter.drain(mbuf, 6)                    # 1-step partial window
    assert got[-1][0] == 6 and got[-1][1] == 1 and got[-1][2]["c"] == 3.0


def test_record_is_noop_without_collector():
    calls = []
    m_lib.record("anything", lambda: calls.append(1) or 1.0)
    assert not calls                        # thunk never evaluated
    assert not m_lib.active()


def test_record_under_jit_with_collector():
    meter = _toy_meter(lambda *a: None, every=10)

    @jax.jit
    def f(x, mbuf):
        with meter.collecting() as col:
            m_lib.record("g", x * 2.0)
            m_lib.record("not_in_catalog", x)    # silently ignored
        return meter.merge(mbuf, col)

    out = f(jnp.float32(3.0), meter.init())
    assert float(out["g"]) == 6.0
    assert int(out["_steps"]) == 1


def test_catalog_for_all_variants_unique_and_typed():
    taps = {"fc": kfac_lib.TapInfo("fc/w", 24, 16, n_stat=N_STAT)}
    for variant in policy.VARIANTS:
        opt = kfac_lib.Kfac(_cfg(variant), taps)
        catalog = m_lib.catalog_for(opt)
        names = [s.name for s in catalog]
        assert len(names) == len(set(names)), variant
        assert all(s.kind in (m_lib.COUNTER, m_lib.GAUGE)
                   for s in catalog), variant
        if variant == "nskfac":
            assert any(n.endswith("/ns_res") for n in names)
        if variant in ("kfac", "rkfac", "brkfac"):
            assert any(n.endswith("/trunc_mass") for n in names)


# ---------------------------------------------------------------------------
# the acceptance claim: telemetry is numerically inert
# ---------------------------------------------------------------------------

def _make_mlp():
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    params = {
        "fc0": {"w": layers.dense_init(ks[0], D_IN, D_H)},
        "fc1": {"w": layers.dense_init(ks[1], D_H, D_OUT)},
    }
    taps = {
        "fc0": kfac_lib.TapInfo("fc0/w", D_IN, D_H, n_stat=N_STAT),
        "fc1": kfac_lib.TapInfo("fc1/w", D_H, D_OUT, n_stat=N_STAT),
    }
    return params, taps


def _mlp_loss(params, probes, batch):
    x, y = batch
    acts = {}
    h, acts["fc0"] = layers.tapped_matmul(params["fc0"]["w"], x,
                                          probes.get("fc0"), N_STAT)
    h = jax.nn.relu(h)
    h, acts["fc1"] = layers.tapped_matmul(params["fc1"]["w"], h,
                                          probes.get("fc1"), N_STAT)
    return jnp.mean(jnp.square(h - y)), acts


def _batches(n):
    key = jax.random.PRNGKey(3)
    W = jax.random.normal(key, (D_IN, D_OUT)) / np.sqrt(D_IN)
    out = []
    for i in range(n):
        x = jax.random.normal(jax.random.fold_in(key, i + 1),
                              (N_BS, D_IN))
        out.append((x, jnp.tanh(x @ W)))
    return out


def _cfg(variant, **kw):
    pol = policy.PolicyConfig(variant=variant, r=8, max_dense_dim=512)
    kwargs = dict(policy=pol, lr=optbase.constant(0.05),
                  damping_phi=optbase.constant(0.1), weight_decay=1e-4,
                  clip=10.0, T_updt=1, T_inv=4, T_brand=1, T_rsvd=4,
                  T_corct=4, fallback_lr=optbase.constant(1e-2))
    kwargs.update(kw)
    return kfac_lib.KfacConfig(**kwargs)


def _train(variant, telemetry_path=None, steps=9, mesh=None,
           curvature_axis=None, **cfg_kw):
    params, taps = _make_mlp()
    opt = kfac_lib.Kfac(_cfg(variant, **cfg_kw), taps)
    writer = (ev_lib.TelemetryWriter(telemetry_path, console=False)
              if telemetry_path else None)
    state, losses = loop.run_kfac_training(
        _mlp_loss, opt, params, _batches(steps), n_tokens=N_BS, seed=0,
        dist=specs.DistSpec(mesh=mesh, curvature_axis=curvature_axis),
        obs=specs.ObsSpec(writer=writer,
                          metrics_every=3 if writer else 0))
    if writer is not None:
        writer.close()
    return state, losses


def _assert_identical(sa, la, sb, lb):
    """Metrics-on must be *bit-identical* to metrics-off: telemetry only
    reads hot-path values, so the optimizer's graph outputs are the same
    program."""
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        jax.device_get(sa.params), jax.device_get(sb.params))


@pytest.mark.parametrize("variant", _marked_variants())
def test_metrics_on_equals_metrics_off(variant, tmp_path):
    path = str(tmp_path / "events.jsonl")
    s_off, l_off = _train(variant)
    s_on, l_on = _train(variant, telemetry_path=path)
    _assert_identical(s_off, l_off, s_on, l_on)
    evs = list(ev_lib.read_events(path))          # validates schema
    metrics = [e for e in evs if e["type"] == "metrics"]
    assert metrics, "meter never flushed"
    # counters summed over the run cover every step
    total_stats = sum(e["values"]["work/stats_fired"] for e in metrics)
    assert total_stats > 0
    assert len([e for e in evs if e["type"] == "step"]) == len(l_on)


@pytest.mark.parametrize("variant", ["bkfac",
                                     pytest.param(
                                         "nskfac",
                                         marks=pytest.mark.slow)])
def test_async_metrics_on_equals_off(variant, tmp_path):
    """Same claim through the async launch/land pipeline (in-graph
    landings; the snapshot/land machinery records launch/land slots)."""
    path = str(tmp_path / "events.jsonl")
    kw = dict(async_heavy=True, heavy_lag=2, stagger=True,
              stagger_splits=2)
    s_off, l_off = _train(variant, steps=10, **kw)
    s_on, l_on = _train(variant, telemetry_path=path, steps=10, **kw)
    _assert_identical(s_off, l_off, s_on, l_on)
    assert [e for e in ev_lib.read_events(path) if e["type"] == "metrics"]


@pytest.mark.slow
@pytest.mark.parametrize("variant", ["bkfac", "nskfac"])
def test_sharded_metrics_on_equals_off(variant, tmp_path):
    """The claim on an 8-device host mesh: aux diagnostics ride the
    engine's all-gather, metrics are recorded at the outer trace level,
    and the io_callback flush emits schema-valid windows under
    shard_map-based training."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 host devices")
    mesh = mesh_lib.make_mesh((8,), ("curv",))
    path = str(tmp_path / "events.jsonl")
    s_off, l_off = _train(variant, mesh=mesh, curvature_axis="curv")
    s_on, l_on = _train(variant, telemetry_path=path, mesh=mesh,
                        curvature_axis="curv")
    _assert_identical(s_off, l_off, s_on, l_on)
    metrics = [e for e in ev_lib.read_events(path)
               if e["type"] == "metrics"]
    assert metrics, "no flush under shard_map"
    for e in metrics:
        assert set(e["values"]) == set(e["kinds"])
        assert all(np.isfinite(v) for v in e["values"].values())


# ---------------------------------------------------------------------------
# summary CLI on a real run's log
# ---------------------------------------------------------------------------

def test_summary_reports_a_real_run(tmp_path, capsys):
    path = str(tmp_path / "events.jsonl")
    _train("bkfac", telemetry_path=path)
    report = sum_lib.summarize(path)
    assert report["steps"]["count"] == 9
    assert set(report["steps"]["phases"])       # phase-keyed timings
    assert report["metrics"]["windows"] >= 2
    assert "work/stats_fired" in report["metrics"]["values"]
    text = sum_lib.render(report)
    assert "telemetry summary" in text and "work/stats_fired" in text
    # the CLI entry: report and validate modes both succeed
    assert sum_lib.main([path]) == 0
    assert sum_lib.main([path, "--validate"]) == 0
    capsys.readouterr()


def test_summary_validate_fails_on_bad_log(tmp_path, capsys):
    path = tmp_path / "bad.jsonl"
    path.write_text('{"schema": 1, "t": 0.0, "type": "mystery"}\n')
    assert sum_lib.main([str(path), "--validate"]) == 1
    capsys.readouterr()
