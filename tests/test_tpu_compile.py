"""Ahead-of-time compiles of the six Pallas kernels for a TPU v5e chip.

The TPU compiler is installed with jax, and it compiles for a chip that is
described rather than attached, so these tests catch what interpret mode
cannot — block shapes Mosaic refuses, VMEM overruns — without a chip.
Shapes are the buckets of the paper's modified VGG16_bn (stages 64…512,
FC0 16384×2048, r=230, n_stat=256), stacked where that model stacks.
Nothing runs, so results are not checked here (``chip_smoke.py`` does that
on the chip).  A whole K-FAC step over the described chip's four devices
is compiled too, with and without the curvature engine: XLA cannot
partition a Mosaic kernel, so every kernel of a multi-device step must
reach the compiler inside a ``shard_map``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers import every
test file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import specs
from repro.core import kfac as kfac_lib
from repro.core import policy
from repro.distributed import sharding as shd
from repro.kernels import ops
from repro.models import layers
from repro.optim import base as optbase
from repro.train import loop


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


_R, _W, _N = 230, 486, 256        # truncation rank, Brand width, n_stat


def _fused(J, Ug, sg, Ua, sa):
    return ops.precond_fused(J, Ug, sg, 0.5, Ua, sa, 0.25)


# case → (op, call, operand shapes)
_CASES = {
    # NS-mode dense factor bucket (nskfac, d=2304, two slots)
    "ea_syrk": ("ea_syrk", lambda M, X: ops.ea_syrk(M, X, 0.95, False),
                [(2, 2304, 2304), (2, 2304, _N)]),
    "ns_step": ("ns_step", ops.ns_step, [(2, 2304, 2304), (2, 2304, 2304)]),
    # Brand light update of FC0's A side (d=16384) and of the stacked
    # conv3_1/conv4_* bucket (d=4608, three slots)
    "brand_panel_fc0": ("brand_panel", ops.brand_panel,
                        [(1, 16384, _R), (1, 16384, _N)]),
    "brand_panel_b3": ("brand_panel", ops.brand_panel,
                       [(3, 4608, _R), (3, 4608, _N)]),
    "cholqr2_fc0": ("cholqr2", ops.cholqr2, [(1, 16384, _N)]),
    "cholqr2_b3": ("cholqr2", ops.cholqr2, [(3, 4608, _N)]),
    # nskfac's Brand side next to a dense NS side: stacked bucket and FC0
    "lowrank_apply_b3": ("lowrank_apply",
                         lambda X, U, s: ops.lowrank_apply(X, U, s, 0.5),
                         [(3, 512, 4608), (3, 4608, _W), (3, _W)]),
    "lowrank_apply_fc0": ("lowrank_apply",
                          lambda X, U, s: ops.lowrank_apply(X, U, s, 0.5),
                          [(1, 2048, 16384), (1, 16384, _W), (1, _W)]),
    # bkfac's two-sided application: stacked conv bucket and FC0
    "precond_fused_b3": ("precond_fused", _fused,
                         [(3, 4608, 512), (3, 4608, _W), (3, _W),
                          (3, 512, _W), (3, _W)]),
    # the per-slot vectors' block layout at a wider stack (B=8)
    "lowrank_apply_b8": ("lowrank_apply",
                         lambda X, U, s: ops.lowrank_apply(X, U, s, 0.5),
                         [(8, 512, 4608), (8, 4608, _W), (8, _W)]),
    "precond_fused_b8": ("precond_fused", _fused,
                         [(8, 4608, 512), (8, 4608, _W), (8, _W),
                          (8, 512, _W), (8, _W)]),
    "precond_fused_fc0": ("precond_fused", _fused,
                          [(1, 16384, 2048), (1, 16384, _W), (1, _W),
                           (1, 2048, _W), (1, _W)]),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache,
                                 monkeypatch):
    op, fn, shapes = _CASES[case]
    monkeypatch.setattr(ops, "_mode", lambda: "pallas")
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    with ops.dispatch_tally() as tally:
        lowered = jax.jit(fn).lower(*args)
    assert dict(tally[op]) == {"pallas": 1}, dict(tally)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


# A three-layer MLP whose factor sides take every K-FAC kernel path of a
# stats + Brand step: d=256/512 sides are Brand (brand_panel, cholqr2),
# the d=128 side keeps a dense M (ea_syrk), and every tap is
# preconditioned by precond_fused.
_WIDTHS, _BATCH, _NSTAT = (256, 512, 512, 128), 128, 128


def _mlp_kfac():
    taps = {f"fc{i}": kfac_lib.TapInfo(f"fc{i}/w", a, b, n_stat=_NSTAT)
            for i, (a, b) in enumerate(zip(_WIDTHS, _WIDTHS[1:]))}

    def init(key):
        ks = jax.random.split(key, len(taps))
        return {f"fc{i}": {"w": layers.dense_init(k, a, b)}
                for i, (k, a, b) in enumerate(zip(ks, _WIDTHS, _WIDTHS[1:]))}

    def loss_fn(params, probes, batch):
        h, acts = batch[0], {}
        for i in range(len(taps)):
            name = f"fc{i}"
            h, acts[name] = layers.tapped_matmul(
                params[name]["w"], h, probes.get(name), _NSTAT)
            if i < len(taps) - 1:
                h = jax.nn.relu(h)
        return jnp.mean((h - batch[1]) ** 2), acts

    cfg = kfac_lib.KfacConfig(
        policy=policy.PolicyConfig(variant="bkfac", r=64),
        lr=optbase.constant(0.05), damping_phi=optbase.constant(0.1),
        T_updt=1, T_brand=1)
    return init, loss_fn, kfac_lib.Kfac(cfg, taps)


# case → (mesh shape, axis names, curvature axis, row axis, batch axis)
_MESH_CASES = {
    "data_parallel_no_engine": ((4,), ("data",), None, None, "data"),
    "engine_curv": ((4,), ("curv",), "curv", None, None),
    "engine_data_x_curv": ((2, 2), ("data", "curv"), "curv", "data",
                           "data"),
}


@pytest.mark.parametrize("case", sorted(_MESH_CASES))
def test_kfac_step_compiles_on_four_devices(case, topo, no_persistent_cache,
                                            monkeypatch):
    shape, axes, curv, rows, batch_axis = _MESH_CASES[case]
    monkeypatch.setattr(ops, "_mode", lambda: "pallas")
    mesh = Mesh(np.array(topo.devices).reshape(shape), axes,
                axis_types=(AxisType.Auto,) * len(axes))
    init, loss_fn, opt = _mlp_kfac()
    specs.DistSpec(mesh=mesh, curvature_axis=curv,
                   row_axis=rows).attach(opt)
    params = jax.eval_shape(init, jax.random.PRNGKey(0))
    state = loop.TrainState(params=params,
                            opt=jax.eval_shape(opt.init, params),
                            rng=jax.eval_shape(jax.random.PRNGKey, 1))
    batch = (jax.ShapeDtypeStruct((_BATCH, _WIDTHS[0]), jnp.float32),
             jax.ShapeDtypeStruct((_BATCH, _WIDTHS[-1]), jnp.float32))
    rep = NamedSharding(mesh, P())
    st_sh = loop.TrainState(
        params=jax.tree_util.tree_map(lambda _: rep, params),
        opt=shd.kfac_state_sharding(state.opt, mesh, curvature_axis=curv,
                                    row_axis=rows),
        rng=rep)
    b_sh = NamedSharding(mesh, P(batch_axis))
    put = lambda t, sh: jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        t, sh)
    step = jax.jit(loop.make_scheduled_kfac_step(loss_fn, opt,
                                                 n_tokens=_BATCH),
                   static_argnames=("work",), out_shardings=(st_sh, rep))
    work = opt.uniform_work(True, True, False)
    with ops.dispatch_tally() as tally:
        lowered = step.lower(put(state, st_sh), put(batch, (b_sh, b_sh)),
                             work)
    # the 2D engine absorbs stats into row blocks of M without ea_syrk
    kernels = {"brand_panel", "cholqr2", "precond_fused"} | (
        set() if rows else {"ea_syrk"})
    for op in kernels:
        assert tally[op]["pallas"] > 0, (op, dict(tally[op]))
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
