"""Step builders + sharding rules: the bridge between model code and pjit.

Everything the trainer, the server and the compile tests need for one (arch x shape x mesh)
cell: abstract input/state trees with NamedShardings attached, and the jit'd
``train_step`` / ``prefill_step`` / ``decode_step`` with in/out shardings.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeCell
from repro.models import model as M
from repro.models.layers import batch_axes_for
from repro.optim import adamw

PyTree = Any


# --------------------------------------------------------------------------- #
# Sharding rules (logical axis -> mesh axis)
# --------------------------------------------------------------------------- #
def train_rules(fsdp_axis: Any = "data", tensor_axis: str = "model") -> Dict:
    """FSDP on the data axis + tensor/expert parallel on the model axis."""
    return {
        "vocab": tensor_axis,
        "residual": fsdp_axis,
        "heads": tensor_axis,
        "kv": tensor_axis,
        "ffn": tensor_axis,
        "experts": tensor_axis,
        "expert_ffn": tensor_axis,  # fallback when E doesn't divide (mixtral)
        "dinner": tensor_axis,
        "layers": None,
        None: None,
    }


def decode_rules(fsdp_axis: Any = "data", tensor_axis: str = "model") -> Dict:
    """Inference keeps the same 2-D weight layout (baseline; see §Perf)."""
    return train_rules(fsdp_axis, tensor_axis)


@dataclass(frozen=True)
class CellPlan:
    """Resolved plan for one (arch x shape x mesh) cell."""

    cfg: ModelConfig
    shape: ShapeCell
    batch_axes: Optional[Tuple[str, ...]]
    rules: Dict
    act: M.ActSharding
    q_chunk: int
    ce_chunk: int
    remat_policy: object = None
    kv_dtype: object = None   # jnp.int8 => quantized KV cache (§Perf)


def plan_cell(
    cfg: ModelConfig,
    shape: ShapeCell,
    mesh: Mesh,
    *,
    overrides: Optional[Dict] = None,
) -> CellPlan:
    """Baseline sharding plan for a cell; ``overrides`` feed §Perf hillclimbs."""
    overrides = overrides or {}
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    baxes = batch_axes_for(shape.global_batch, sizes)
    seq_axis = overrides.get("seq_axis", "model")
    rules = overrides.get("rules") or (
        train_rules() if shape.kind == "train" else decode_rules()
    )
    groups = 1
    if baxes is not None:
        groups = 1
        for a in baxes:
            groups *= sizes[a]
    moe_a2a = None
    if overrides.get("moe_impl") == "a2a" and cfg.num_experts % sizes.get("model", 1) == 0:
        moe_a2a = dict(
            mesh=mesh, batch_axes=baxes, model_axis="model",
            seq_axis=seq_axis if shape.kind in ("train", "prefill") else None,
        )
    if shape.kind == "train" or shape.kind == "prefill":
        act = M.ActSharding(
            residual=P(baxes, seq_axis, None),
            logits=P(baxes, None, "model"),
            moe_tokens=P(baxes, None, None),
            moe_buf=P(baxes, "model", None, None),
            moe_groups=groups,
            moe_a2a=moe_a2a,
            kv_cache=P(None, baxes, seq_axis, None, None),
        )
    else:  # decode
        act = M.ActSharding(
            decode_residual=P(baxes, None, None),
            moe_tokens=P(baxes, None, None),
            moe_buf=P(baxes, "model", None, None),
            moe_groups=groups,
            kv_cache=P(None, baxes, "model", None, None),
        )
    act = overrides.get("act", act)
    remat_policy = overrides.get("remat_policy")
    default_qc = 1024 if shape.seq_len >= 4096 else 0
    if shape.kind == "train" and cfg.d_model >= 7168:
        default_qc = 512  # bound f32 score transients for the widest models
    q_chunk = overrides.get("q_chunk", default_qc)
    ce_chunk = overrides.get("ce_chunk", 512)
    kv_dtype = overrides.get("kv_dtype")
    return CellPlan(cfg, shape, baxes, rules, act, q_chunk, ce_chunk,
                    remat_policy, kv_dtype)


# --------------------------------------------------------------------------- #
# Abstract inputs (ShapeDtypeStructs with shardings — no allocation)
# --------------------------------------------------------------------------- #
def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))


def input_specs(plan: CellPlan, mesh: Mesh) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    cfg, shape = plan.cfg, plan.shape
    B = shape.global_batch
    bspec = P(plan.batch_axes)
    if shape.kind in ("train", "prefill"):
        S = shape.seq_len
        text = S - cfg.vision_prefix if cfg.vision_prefix else S
        specs = {
            "tokens": _sds((B, text), jnp.int32, mesh, P(plan.batch_axes, None)),
        }
        if cfg.vision_prefix:
            specs["pixel_embeds"] = _sds(
                (B, cfg.vision_prefix, cfg.d_model), jnp.bfloat16,
                mesh, P(plan.batch_axes, None, None),
            )
        if shape.kind == "train":
            specs["labels"] = _sds((B, text), jnp.int32, mesh, P(plan.batch_axes, None))
            specs["mask"] = _sds((B, text), jnp.float32, mesh, P(plan.batch_axes, None))
        return specs
    # decode: one new token against a seq_len KV cache
    return {
        "tokens": _sds((B, 1), jnp.int32, mesh, P(plan.batch_axes, None)),
        "cur_index": jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P())),
    }


def abstract_sharded_params(plan: CellPlan, mesh: Mesh, dtype=jnp.float32) -> PyTree:
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    specs = M.param_partition_specs(plan.cfg, plan.rules, axis_sizes)
    absp = M.abstract_params(plan.cfg, dtype)
    return jax.tree.map(
        lambda sds, spec: jax.ShapeDtypeStruct(
            sds.shape, sds.dtype, sharding=NamedSharding(mesh, spec)
        ),
        absp,
        specs,
    )


def abstract_train_state(plan: CellPlan, mesh: Mesh) -> Dict[str, PyTree]:
    params = abstract_sharded_params(plan, mesh, jnp.float32)
    opt = adamw.abstract_opt_state(params)
    # moments shard exactly like params (ZeRO)
    opt = {
        "m": jax.tree.map(
            lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=p.sharding),
            opt["m"], params),
        "v": jax.tree.map(
            lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=p.sharding),
            opt["v"], params),
        "count": jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P())),
    }
    return {"params": params, "opt": opt}


def abstract_sharded_cache(plan: CellPlan, mesh: Mesh) -> PyTree:
    cfg, shape = plan.cfg, plan.shape
    kv_dtype = plan.kv_dtype or jnp.bfloat16
    cache = M.abstract_decode_cache(cfg, shape.global_batch, shape.seq_len, kv_dtype)
    specs = cache_partition_specs(plan)
    return jax.tree.map(
        lambda sds, spec: jax.ShapeDtypeStruct(
            sds.shape, sds.dtype, sharding=NamedSharding(mesh, spec)
        ),
        cache,
        specs,
    )


def cache_partition_specs(plan: CellPlan) -> PyTree:
    cfg = plan.cfg
    specs: Dict[str, P] = {}
    if cfg.has_attention:
        kv = P(None, plan.batch_axes, "model", None, None)  # seq over model
        specs["k"] = kv
        specs["v"] = kv
        if plan.kv_dtype is not None and plan.kv_dtype != jnp.bfloat16:
            sc = P(None, plan.batch_axes, "model", None)
            specs["k_scale"] = sc
            specs["v_scale"] = sc
    if cfg.has_ssm:
        specs["conv"] = P(None, plan.batch_axes, None, "model")
        specs["ssm"] = P(None, plan.batch_axes, "model", None)
        if cfg.ssm_heads:  # mamba-2: (L, B, heads, head_dim, state)
            specs["ssm"] = P(None, plan.batch_axes, "model", None, None)
    return specs


# --------------------------------------------------------------------------- #
# Step builders
# --------------------------------------------------------------------------- #
def make_train_step(plan: CellPlan, opt_cfg: adamw.AdamWConfig):
    cfg = plan.cfg

    def train_step(state: Dict[str, PyTree], batch: Dict[str, jax.Array]):
        def lf(params):
            return M.loss_fn(
                cfg, params, batch,
                shardings=plan.act,
                q_chunk=plan.q_chunk,
                ce_chunk=plan.ce_chunk,
                remat_policy=plan.remat_policy,
            )

        (_, metrics), grads = jax.value_and_grad(lf, has_aux=True)(state["params"])
        new_params, new_opt, opt_metrics = adamw.adamw_update(
            opt_cfg, state["params"], grads, state["opt"]
        )
        metrics = dict(metrics, **opt_metrics)
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


def make_prefill_step(plan: CellPlan):
    cfg = plan.cfg

    def prefill_step(params: PyTree, batch: Dict[str, jax.Array]):
        return M.prefill(
            cfg, params, batch["tokens"],
            pixel_embeds=batch.get("pixel_embeds"),
            shardings=plan.act,
            q_chunk=plan.q_chunk or 1024,
        )

    return prefill_step


def make_decode_step(plan: CellPlan):
    cfg = plan.cfg

    def dstep(params: PyTree, cache: PyTree, batch: Dict[str, jax.Array]):
        return M.decode_step(
            cfg, params, cache, batch["tokens"], batch["cur_index"],
            shardings=plan.act,
        )

    return dstep


def lower_cell(
    plan: CellPlan,
    mesh: Mesh,
    *,
    opt_cfg: Optional[adamw.AdamWConfig] = None,
    donate: bool = True,
):
    """Lower the cell's step over ``mesh``. Returns jax ``Lowered``."""
    cfg, shape = plan.cfg, plan.shape
    opt_cfg = opt_cfg or adamw.AdamWConfig()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            state = abstract_train_state(plan, mesh)
            batch = input_specs(plan, mesh)
            fn = make_train_step(plan, opt_cfg)
            jf = jax.jit(fn, donate_argnums=(0,) if donate else ())
            return jf.lower(state, batch)
        if shape.kind == "prefill":
            params = abstract_sharded_params(plan, mesh, jnp.bfloat16)
            batch = input_specs(plan, mesh)
            fn = make_prefill_step(plan)
            return jax.jit(fn).lower(params, batch)
        # decode
        params = abstract_sharded_params(plan, mesh, jnp.bfloat16)
        cache = abstract_sharded_cache(plan, mesh)
        batch = input_specs(plan, mesh)
        fn = make_decode_step(plan)
        jf = jax.jit(fn, donate_argnums=(1,) if donate else ())
        return jf.lower(params, cache, batch)
