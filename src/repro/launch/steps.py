"""Step-function builders for training / prefill / decode, with mesh
shardings — shared by the dry-run, the trainer, and the serving engine.

Everything here works on abstract values (jax.eval_shape) so the dry-run
never allocates the 671B parameter trees it lowers.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import specs as specs_lib
from repro.configs.base import ArchConfig, ShapeCell, SHAPES
from repro.core import kfac as kfac_lib
from repro.core import policy as policy_lib
from repro.distributed import sharding as shd
from repro.models import layers
from repro.models.lm import LM
from repro.models.sharding_policy import ShardPolicy, NO_SHARD
from repro.optim import base as optbase
from repro.train import loop as loop_lib


def shard_policy_for(mesh: Optional[Mesh], shard_kv_seq: bool = False,
                     seq_shard_residual: bool = True) -> ShardPolicy:
    if mesh is None:
        return NO_SHARD
    dp = tuple(a for a in mesh.axis_names if a != "model")
    tp = "model" if "model" in mesh.axis_names else None
    sizes = tuple(zip(mesh.axis_names, mesh.devices.shape))
    return ShardPolicy(dp=dp, tp=tp, seq_shard_residual=seq_shard_residual,
                       shard_kv_seq=shard_kv_seq, axis_sizes=sizes)


def default_kfac_config(arch: ArchConfig, variant: str = "bkfac"
                        ) -> kfac_lib.KfacConfig:
    pol = policy_lib.PolicyConfig(variant=variant, r=256,
                                  max_dense_dim=8192)
    return kfac_lib.KfacConfig(
        policy=pol,
        lr=optbase.constant(0.3),
        damping_phi=optbase.constant(0.1),
        weight_decay=7e-4, clip=0.07,
        T_updt=25, T_inv=250, T_brand=25, T_rsvd=250, T_corct=500,
        fallback_lr=optbase.constant(1e-3))


@dataclasses.dataclass
class BuiltTrain:
    lm: LM
    opt: kfac_lib.Kfac
    step_fn: Any                 # (params, opt_state, batch, rng) -> ...
    abstract_params: Any
    abstract_opt: Any
    in_shardings: Any
    out_shardings: Any
    batch_specs: Dict[str, jax.ShapeDtypeStruct]


def train_batch_specs(arch: ArchConfig, cell: ShapeCell
                      ) -> Dict[str, jax.ShapeDtypeStruct]:
    B, T = cell.global_batch, cell.seq_len
    i32 = jnp.int32
    f32 = jnp.float32
    if arch.is_encdec:
        Td = max(T // arch.dec_ratio, 8)
        return {"frames": jax.ShapeDtypeStruct((B, T, arch.d_model), f32),
                "tokens": jax.ShapeDtypeStruct((B, Td), i32),
                "targets": jax.ShapeDtypeStruct((B, Td), i32)}
    if arch.frontend == "vision":
        Tt = T - arch.n_prefix
        return {"embeds": jax.ShapeDtypeStruct(
                    (B, arch.n_prefix, arch.d_model), f32),
                "tokens": jax.ShapeDtypeStruct((B, Tt), i32),
                "targets": jax.ShapeDtypeStruct((B, Tt), i32)}
    return {"tokens": jax.ShapeDtypeStruct((B, T), i32),
            "targets": jax.ShapeDtypeStruct((B, T), i32)}


def n_tokens_of(arch: ArchConfig, cell: ShapeCell) -> int:
    specs = train_batch_specs(arch, cell)
    return int(specs["tokens"].shape[0] * specs["tokens"].shape[1])


def build_train_step(arch: ArchConfig, mesh: Optional[Mesh] = None,
                     variant: str = "bkfac", unroll: bool = False,
                     cell: Optional[ShapeCell] = None,
                     flags: Optional[Dict[str, bool]] = None,
                     work=None, curvature_axis: Optional[str] = None,
                     remat: bool = True, plan: str = "tp",
                     async_heavy: bool = False,
                     heavy_lag: int = 0,
                     dist: Optional[specs_lib.DistSpec] = None
                     ) -> BuiltTrain:
    """``work`` (a schedule.StepWork) supersedes ``flags`` when given —
    the dry-run lowers the exact staggered step variant the scheduler
    would dispatch.  ``dist`` (a :class:`repro.specs.DistSpec`) is the
    spec-level spelling of the ``mesh``/``curvature_axis`` pair: its mesh
    shards the model (plan-dependent) and its curvature_axis shards the
    bucketed factor work via the distributed curvature engine
    (row_axis/curvature_compress ride along).  The loose pair keeps
    working but may not be mixed with ``dist``.  ``async_heavy``/
    ``heavy_lag`` enable the double-buffered heavy pipeline (the dry-run
    then lowers launch/land step variants and the optimizer state
    carries the in-flight buffers)."""
    if dist is not None:
        if mesh is not None or curvature_axis is not None:
            raise ValueError("build_train_step: pass dist= OR the loose "
                             "mesh=/curvature_axis= pair, not both")
        mesh, curvature_axis = dist.mesh, dist.curvature_axis
    else:
        dist = specs_lib.DistSpec(mesh=mesh, curvature_axis=curvature_axis)
    cell = cell or SHAPES["train_4k"]
    flags = flags or dict(do_stats=True, do_light=True, do_heavy=False)
    if plan == "fsdp" and mesh is not None:
        sizes = tuple(zip(mesh.axis_names, mesh.devices.shape))
        sp = ShardPolicy(dp=tuple(mesh.axis_names), tp=None,
                         seq_shard_residual=False, axis_sizes=sizes)
    else:
        sp = shard_policy_for(mesh)
    lm = LM(arch, sp, remat=remat, unroll=unroll)
    kcfg = default_kfac_config(arch, variant)
    if async_heavy:
        kcfg = dataclasses.replace(kcfg, async_heavy=True,
                                   heavy_lag=heavy_lag)
    opt = kfac_lib.Kfac(kcfg, lm.taps)
    dist.attach(opt)
    n_tokens = n_tokens_of(arch, cell)
    step_work = work if work is not None else opt.uniform_work(**flags)

    def train_step(params, opt_state, batch, rng):
        probes = layers.make_probes(opt.taps, jnp.float32)
        loss, acts, gp, gprobe = loop_lib.kfac_grads(
            lm.loss_fn, params, probes, batch)
        updates, opt_state = opt.update(
            gp, opt_state, params, acts=acts, probe_grads=gprobe,
            n_tokens=n_tokens, rng=rng, work=step_work)
        params = optbase.apply_updates(params, updates)
        return params, opt_state, loss

    key = jax.random.PRNGKey(0)
    abstract_params = jax.eval_shape(lm.init, key)
    abstract_opt = jax.eval_shape(opt.init, abstract_params)
    batch_specs = train_batch_specs(arch, cell)
    in_sh = out_sh = None
    if mesh is not None:
        if plan == "fsdp":
            p_sh = shd.params_sharding_fsdp(abstract_params, mesh)
            o_sh = shd.params_sharding_fsdp(abstract_opt, mesh)
            dp_all = tuple(mesh.axis_names)
            b_sh = jax.tree_util.tree_map(
                lambda leaf: NamedSharding(
                    mesh, P(*((dp_all,) + (None,) * (leaf.ndim - 1)))),
                batch_specs)
        else:
            p_sh = shd.params_sharding(abstract_params, mesh)
            o_sh = shd.kfac_state_sharding(abstract_opt, mesh,
                                           curvature_axis=curvature_axis)
            b_sh = shd.batch_sharding(batch_specs, mesh)
        r_sh = NamedSharding(mesh, P())
        in_sh = (p_sh, o_sh, b_sh, r_sh)
        out_sh = (p_sh, o_sh, NamedSharding(mesh, P()))
    return BuiltTrain(lm=lm, opt=opt, step_fn=train_step,
                      abstract_params=abstract_params,
                      abstract_opt=abstract_opt,
                      in_shardings=in_sh, out_shardings=out_sh,
                      batch_specs=batch_specs)


@dataclasses.dataclass
class BuiltServe:
    lm: LM
    step_fn: Any
    abstract_params: Any
    arg_specs: Tuple
    in_shardings: Any
    out_shardings: Any


def build_prefill_step(arch: ArchConfig, mesh: Optional[Mesh] = None,
                       cell: Optional[ShapeCell] = None,
                       unroll: bool = False) -> BuiltServe:
    cell = cell or SHAPES["prefill_32k"]
    sp = shard_policy_for(mesh)
    lm = LM(arch, sp, remat=False, unroll=unroll)
    batch_specs = train_batch_specs(arch, cell)
    batch_specs.pop("targets")

    def prefill(params, batch):
        logits, _, _, _ = lm.forward(params, batch, train=False)
        return logits

    abstract_params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    in_sh = out_sh = None
    if mesh is not None:
        p_sh = shd.params_sharding(abstract_params, mesh)
        b_sh = shd.batch_sharding(batch_specs, mesh)
        dp = tuple(a for a in mesh.axis_names if a != "model")
        in_sh = (p_sh, b_sh)
        logits_shape = (cell.global_batch, 1, arch.vocab)
        out_sh = NamedSharding(mesh, shd.fit_spec(P(dp, None, "model"),
                                                  logits_shape, mesh))
    return BuiltServe(lm=lm, step_fn=prefill,
                      abstract_params=abstract_params,
                      arg_specs=(batch_specs,), in_shardings=in_sh,
                      out_shardings=out_sh)


def kv_rep_for(arch: ArchConfig, mesh: Optional[Mesh]) -> int:
    """Smallest KV-head replication r with (Hk·r) divisible by the model
    axis and r dividing the GQA group (so H/(Hk·r) stays integral)."""
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    tp = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    Hk, G = arch.n_kv_heads, arch.n_heads // arch.n_kv_heads
    for r in range(1, G + 1):
        if G % r == 0 and (Hk * r) % tp == 0:
            return r
    return 1


def build_decode_step(arch: ArchConfig, mesh: Optional[Mesh] = None,
                      cell: Optional[ShapeCell] = None,
                      unroll: bool = False,
                      cache_layout: str = "seq",
                      window_caches: bool = False) -> BuiltServe:
    cell = cell or SHAPES["decode_32k"]
    B, S = cell.global_batch, cell.seq_len
    shard_seq = cell.name == "long_500k"
    kv_rep = 1
    if cache_layout == "heads" and not shard_seq:
        kv_rep = kv_rep_for(arch, mesh)
        if kv_rep == 1 and mesh is not None:
            tp = dict(zip(mesh.axis_names,
                          mesh.devices.shape)).get("model", 1)
            if arch.n_kv_heads % tp != 0:
                # heads unrealizable → shard head_dim (always 128/256)
                cache_layout = "hd" if arch.hd % tp == 0 else "seq"
    small_thr = 0   # batch layout for small rings: REFUTED (see §Perf)
    sp = shard_policy_for(mesh, shard_kv_seq=shard_seq)
    if sp.active:
        sp = ShardPolicy(**{**sp.__dict__, "kv_cache_layout": cache_layout,
                            "kv_small_seq_threshold": small_thr})
    lm = LM(arch, sp, remat=False, unroll=unroll)
    cross_len = S if arch.is_encdec else 0
    S_self = max(S // arch.dec_ratio, 64) if arch.is_encdec else S

    def decode(params, cache, token, t):
        return lm.decode_step(params, cache, token, t)

    abstract_params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    abstract_cache = jax.eval_shape(
        lambda: lm.init_cache(B, S_self, cross_len=cross_len,
                              window_caches=window_caches, kv_rep=kv_rep))
    token_spec = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    t_spec = jax.ShapeDtypeStruct((), jnp.int32)
    in_sh = out_sh = None
    if mesh is not None:
        p_sh = shd.params_sharding(abstract_params, mesh)
        c_sh = shd.cache_sharding(abstract_cache, mesh,
                                  shard_seq=shard_seq,
                                  layout=cache_layout,
                                  small_seq_threshold=small_thr)
        dp = tuple(a for a in mesh.axis_names if a != "model")
        tok_sh = NamedSharding(mesh, P() if shard_seq else P(dp, None))
        in_sh = (p_sh, c_sh, tok_sh, NamedSharding(mesh, P()))
        out_logits = P() if shard_seq else P(dp, None, None)
        out_sh = (NamedSharding(mesh, out_logits), c_sh)
    return BuiltServe(lm=lm, step_fn=decode,
                      abstract_params=abstract_params,
                      arg_specs=(abstract_cache, token_spec, t_spec),
                      in_shardings=in_sh, out_shardings=out_sh)
