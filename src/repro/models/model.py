"""Model construction: param defs, init, abstract shapes, sharding, forward.

A single table of ``ParamDef``s per architecture drives three things:
  * real initialization (smoke tests, the 100M training example),
  * abstract ``ShapeDtypeStruct`` trees (compile-only lowering),
  * logical-axis -> mesh-axis sharding specs (pjit in/out shardings).

The decoder stack is a ``lax.scan`` over layer-stacked parameters with
rematerialization, so compile time and HLO size stay bounded for 80-layer
configs while the roofline analyzer scales while-body costs by trip count.
A stack of more than one kind of layer (``cfg.layer_types``: Mamba-2 mixers
and attention) is unrolled in its published order instead, each mixer's
parameters and decode state stacked over the layers of its kind.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import ssm as ssm_mod
from repro.models.attention import attention_xla, decode_attention_xla
from repro.models.layers import (
    constrain,
    cross_entropy_chunked,
    embed_tokens,
    rms_norm,
    rms_norm_headwise,
    swiglu_mlp,
)
from repro.models.moe import moe_ffn

PyTree = Any


# --------------------------------------------------------------------------- #
# Param defs
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class ParamDef:
    path: Tuple[str, ...]
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "normal"  # normal | out_normal | zeros | ones | ssm_A | dt_bias


def param_defs(cfg: ModelConfig) -> List[ParamDef]:
    D, L, V = cfg.d_model, cfg.num_layers, cfg.vocab_size
    defs: List[ParamDef] = [
        ParamDef(("embed",), (V, D), ("vocab", "residual")),
        ParamDef(("final_norm",), (D,), (None,), "ones"),
    ]
    if not cfg.tie_embeddings:
        defs.append(ParamDef(("lm_head",), (D, V), ("residual", "vocab")))

    Lx = (L,)
    lax_ = ("layers",)
    if cfg.layer_types:
        return defs + _interleaved_defs(cfg)

    if cfg.has_attention:
        H, KV, hd = cfg.q_dim, cfg.kv_dim, cfg.head_dim
        defs += [
            ParamDef(("layers", "attn", "wq"), Lx + (D, H), lax_ + ("residual", "heads")),
            ParamDef(("layers", "attn", "wk"), Lx + (D, KV), lax_ + ("residual", "kv")),
            ParamDef(("layers", "attn", "wv"), Lx + (D, KV), lax_ + ("residual", "kv")),
            ParamDef(("layers", "attn", "wo"), Lx + (H, D), lax_ + ("heads", "residual"), "out_normal"),
            ParamDef(("layers", "ln1"), Lx + (D,), lax_ + (None,), "ones"),
        ]
        if cfg.qkv_bias:
            defs += [
                ParamDef(("layers", "attn", "bq"), Lx + (H,), lax_ + ("heads",), "zeros"),
                ParamDef(("layers", "attn", "bk"), Lx + (KV,), lax_ + ("kv",), "zeros"),
                ParamDef(("layers", "attn", "bv"), Lx + (KV,), lax_ + ("kv",), "zeros"),
            ]
        if cfg.qk_norm:
            defs += [
                ParamDef(("layers", "attn", "q_norm"), Lx + (hd,), lax_ + (None,), "ones"),
                ParamDef(("layers", "attn", "k_norm"), Lx + (hd,), lax_ + (None,), "ones"),
            ]

    if cfg.has_ssm:
        Di, R, N, K = cfg.d_inner, cfg.dt_rank, cfg.ssm_state, cfg.ssm_conv
        defs += [
            ParamDef(("layers", "ssm", "in_proj"), Lx + (D, 2 * Di), lax_ + ("residual", "dinner")),
            ParamDef(("layers", "ssm", "conv_w"), Lx + (Di, K), lax_ + ("dinner", None)),
            ParamDef(("layers", "ssm", "conv_b"), Lx + (Di,), lax_ + ("dinner",), "zeros"),
            ParamDef(("layers", "ssm", "x_proj"), Lx + (Di, R + 2 * N), lax_ + ("dinner", None)),
            ParamDef(("layers", "ssm", "dt_proj"), Lx + (R, Di), lax_ + (None, "dinner")),
            ParamDef(("layers", "ssm", "dt_bias"), Lx + (Di,), lax_ + ("dinner",), "dt_bias"),
            ParamDef(("layers", "ssm", "A_log"), Lx + (Di, N), lax_ + ("dinner", None), "ssm_A"),
            ParamDef(("layers", "ssm", "D"), Lx + (Di,), lax_ + ("dinner",), "ones"),
            ParamDef(("layers", "ssm", "out_proj"), Lx + (Di, D), lax_ + ("dinner", "residual"), "out_normal"),
        ]
        if cfg.family == "ssm":
            defs.append(ParamDef(("layers", "ln1"), Lx + (D,), lax_ + (None,), "ones"))

    if cfg.family == "hybrid":
        defs += [
            ParamDef(("layers", "attn_branch_norm"), Lx + (D,), lax_ + (None,), "ones"),
            ParamDef(("layers", "ssm_branch_norm"), Lx + (D,), lax_ + (None,), "ones"),
        ]

    if cfg.is_moe:
        E, Fm = cfg.num_experts, cfg.moe_d_ff
        defs += [
            ParamDef(("layers", "moe", "router"), Lx + (D, E), lax_ + ("residual", None)),
            ParamDef(("layers", "moe", "wi"), Lx + (E, D, Fm), lax_ + ("experts", "residual", "expert_ffn")),
            ParamDef(("layers", "moe", "wg"), Lx + (E, D, Fm), lax_ + ("experts", "residual", "expert_ffn")),
            ParamDef(("layers", "moe", "wo"), Lx + (E, Fm, D), lax_ + ("experts", "expert_ffn", "residual"), "out_normal"),
            ParamDef(("layers", "ln2"), Lx + (D,), lax_ + (None,), "ones"),
        ]
    elif cfg.d_ff > 0:
        F = cfg.d_ff
        defs += [
            ParamDef(("layers", "mlp", "wi"), Lx + (D, F), lax_ + ("residual", "ffn")),
            ParamDef(("layers", "mlp", "wg"), Lx + (D, F), lax_ + ("residual", "ffn")),
            ParamDef(("layers", "mlp", "wo"), Lx + (F, D), lax_ + ("ffn", "residual"), "out_normal"),
            ParamDef(("layers", "ln2"), Lx + (D,), lax_ + (None,), "ones"),
        ]
    return defs


def _interleaved_defs(cfg: ModelConfig) -> List[ParamDef]:
    """Layers of more than one kind, in ``cfg.layer_types`` order: norms and
    the MLP stacked over every layer, each mixer stacked over the layers of
    its kind only (``layers/attn``, ``layers/mamba``)."""
    D, L, F = cfg.d_model, cfg.num_layers, cfg.d_ff
    lax_ = ("layers",)
    defs = [
        ParamDef(("layers", "ln1"), (L, D), lax_ + (None,), "ones"),
        ParamDef(("layers", "ln2"), (L, D), lax_ + (None,), "ones"),
        ParamDef(("layers", "mlp", "wi"), (L, D, F), lax_ + ("residual", "ffn")),
        ParamDef(("layers", "mlp", "wg"), (L, D, F), lax_ + ("residual", "ffn")),
        ParamDef(("layers", "mlp", "wo"), (L, F, D), lax_ + ("ffn", "residual"), "out_normal"),
    ]
    La = (len(cfg.layer_slots("attention")),)
    if La[0]:
        H, KV = cfg.q_dim, cfg.kv_dim
        defs += [
            ParamDef(("layers", "attn", "wq"), La + (D, H), lax_ + ("residual", "heads")),
            ParamDef(("layers", "attn", "wk"), La + (D, KV), lax_ + ("residual", "kv")),
            ParamDef(("layers", "attn", "wv"), La + (D, KV), lax_ + ("residual", "kv")),
            ParamDef(("layers", "attn", "wo"), La + (H, D), lax_ + ("heads", "residual"), "out_normal"),
        ]
    Lm = (len(cfg.layer_slots("mamba")),)
    if Lm[0]:
        Di, Hs, C, K = cfg.d_inner, cfg.ssm_heads, cfg.ssm_conv_dim, cfg.ssm_conv
        defs += [
            ParamDef(("layers", "mamba", "in_proj"), Lm + (D, Di + C + Hs), lax_ + ("residual", None)),
            ParamDef(("layers", "mamba", "conv_w"), Lm + (C, K), lax_ + (None, None)),
            ParamDef(("layers", "mamba", "conv_b"), Lm + (C,), lax_ + (None,), "zeros"),
            ParamDef(("layers", "mamba", "dt_bias"), Lm + (Hs,), lax_ + (None,), "dt_bias"),
            ParamDef(("layers", "mamba", "A_log"), Lm + (Hs,), lax_ + (None,), "ssm_A"),
            ParamDef(("layers", "mamba", "D"), Lm + (Hs,), lax_ + (None,), "ones"),
            ParamDef(("layers", "mamba", "norm"), Lm + (Di,), lax_ + ("dinner",), "ones"),
            ParamDef(("layers", "mamba", "out_proj"), Lm + (Di, D), lax_ + ("dinner", "residual"), "out_normal"),
        ]
    return defs


def _set_path(tree: Dict, path: Tuple[str, ...], value) -> None:
    node = tree
    for k in path[:-1]:
        node = node.setdefault(k, {})
    node[path[-1]] = value


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32) -> PyTree:
    """Real initialization (use only for reduced configs on CPU)."""
    params: Dict = {}
    defs = param_defs(cfg)
    keys = jax.random.split(key, len(defs))
    for d, k in zip(defs, keys):
        if d.init == "normal":
            v = jax.random.normal(k, d.shape, dtype) * 0.02
        elif d.init == "out_normal":
            v = jax.random.normal(k, d.shape, dtype) * (0.02 / np.sqrt(2 * cfg.num_layers))
        elif d.init == "zeros":
            v = jnp.zeros(d.shape, dtype)
        elif d.init == "ones":
            v = jnp.ones(d.shape, dtype)
        elif d.init == "ssm_A":
            n = d.shape[-1]
            a = jnp.tile(jnp.log(jnp.arange(1, n + 1, dtype=dtype)), d.shape[:-1] + (1,))
            v = a
        elif d.init == "dt_bias":
            # inverse softplus of dt in [1e-3, 1e-1]
            dt = jnp.exp(
                jax.random.uniform(k, d.shape, dtype)
                * (np.log(0.1) - np.log(1e-3))
                + np.log(1e-3)
            )
            v = dt + jnp.log(-jnp.expm1(-dt))
        else:  # pragma: no cover
            raise ValueError(d.init)
        _set_path(params, d.path, v)
    return params


def abstract_params(cfg: ModelConfig, dtype=jnp.float32) -> PyTree:
    params: Dict = {}
    for d in param_defs(cfg):
        _set_path(params, d.path, jax.ShapeDtypeStruct(d.shape, dtype))
    return params


def logical_specs(cfg: ModelConfig) -> PyTree:
    specs: Dict = {}
    for d in param_defs(cfg):
        _set_path(specs, d.path, d.logical)
    return specs


def param_partition_specs(
    cfg: ModelConfig,
    rules: Dict[Optional[str], Optional[Any]],
    axis_sizes: Optional[Dict[str, int]] = None,
) -> PyTree:
    """Map logical axes -> mesh axes per ``rules`` (e.g. train FSDP+TP).

    Shape-aware: a dim whose size does not divide its mesh axis is left
    unsharded (jit argument shardings require even division), and a mesh
    axis claimed by two dims of the same tensor goes to the earlier dim
    (e.g. mixtral's E=8 cannot take ``model``=16, so the per-expert FFN
    dim inherits it; qwen3's E=128 can, so the FFN dim is dropped).
    """
    specs: Dict = {}
    for d in param_defs(cfg):
        axes = []
        used = set()
        for dim, logical in zip(d.shape, d.logical):
            ax = rules.get(logical, None)
            if ax is None:
                axes.append(None)
                continue
            sizes = [axis_sizes.get(a, 1) for a in (ax if isinstance(ax, tuple) else (ax,))] if axis_sizes else [1]
            total = 1
            for s in sizes:
                total *= s
            key = ax if isinstance(ax, tuple) else (ax,)
            if (axis_sizes is not None and dim % total != 0) or any(a in used for a in key):
                axes.append(None)
                continue
            used.update(key)
            axes.append(ax)
        _set_path(specs, d.path, P(*axes))
    return specs


# --------------------------------------------------------------------------- #
# Activation sharding bundle
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class ActSharding:
    """PartitionSpecs for activation constraint points (None => unconstrained)."""

    residual: Optional[P] = None      # (B, S, D)
    logits: Optional[P] = None        # (B, chunk, V) inside the CE scan
    moe_tokens: Optional[P] = None    # (G, Tg, D) grouped tokens
    moe_buf: Optional[P] = None       # (G, E, C, D) dispatch buffer
    moe_groups: int = 1
    # §Perf: shard_map expert-parallel a2a (dict: mesh/batch_axes/model_axis/
    # seq_axis); None => global-view dispatch
    moe_a2a: Optional[Any] = None
    kv_cache: Optional[P] = None      # (L, B, S, KV, hd)
    decode_residual: Optional[P] = None  # (B, 1, D)

    def res(self, x):
        return constrain(x, self.residual) if self.residual is not None else x

    def dres(self, x):
        return constrain(x, self.decode_residual) if self.decode_residual is not None else x


NO_SHARDING = ActSharding()


# --------------------------------------------------------------------------- #
# Blocks
# --------------------------------------------------------------------------- #
def _attn_branch(
    cfg: ModelConfig,
    lp: Dict,
    h: jax.Array,
    positions: jax.Array,
    *,
    q_chunk: int,
) -> jax.Array:
    B, S, _ = h.shape
    ap = lp["attn"]
    q = jnp.einsum("bsd,dh->bsh", h, ap["wq"])
    k = jnp.einsum("bsd,dh->bsh", h, ap["wk"])
    v = jnp.einsum("bsd,dh->bsh", h, ap["wv"])
    if cfg.qkv_bias:
        q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm_headwise(q, ap["q_norm"], cfg.norm_eps)
        k = rms_norm_headwise(k, ap["k_norm"], cfg.norm_eps)
    if cfg.rope:
        q = apply_rope_cfg(cfg, q, positions)
        k = apply_rope_cfg(cfg, k, positions)
    out = attention_xla(
        q, k, v, causal=True, window=cfg.sliding_window, q_chunk=q_chunk,
        scale=cfg.attention_multiplier,
    )
    out = out.reshape(B, S, cfg.q_dim)
    return jnp.einsum("bsh,hd->bsd", out, ap["wo"]), (k, v)


def apply_rope_cfg(cfg: ModelConfig, x: jax.Array, positions: jax.Array) -> jax.Array:
    from repro.models.layers import apply_rope

    return apply_rope(x, positions, cfg.rope_theta)


def _times(x: jax.Array, m: Optional[float]) -> jax.Array:
    """``x * m``; no operation where the config has no multiplier."""
    return x if m is None else x * m


def _residual(cfg: ModelConfig, x: jax.Array, y: jax.Array) -> jax.Array:
    return x + _times(y, cfg.residual_multiplier)


def _embed(cfg: ModelConfig, params: PyTree, tokens: jax.Array, compute_dtype) -> jax.Array:
    x = embed_tokens(params["embed"], tokens, compute_dtype)
    return _times(x, cfg.embedding_multiplier)


def _scale_logits(cfg: ModelConfig, logits: jax.Array) -> jax.Array:
    return logits if cfg.logits_scaling is None else logits / cfg.logits_scaling


def _mamba2_dims(cfg: ModelConfig) -> Dict[str, Any]:
    return dict(heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
                state=cfg.ssm_state, groups=cfg.ssm_groups, eps=cfg.norm_eps)


def _layer_params(cfg: ModelConfig, layers: PyTree, i: int) -> Dict:
    """Layer ``i`` of an interleaved stack: its norms and MLP, and the mixer
    of its kind at that kind's own index."""
    kind = cfg.layer_types[i]
    slot = cfg.layer_types[:i].count(kind)
    mixer = "attn" if kind == "attention" else "mamba"
    lp = {k: layers[k][i] for k in ("ln1", "ln2")}
    lp["mlp"] = jax.tree.map(lambda a: a[i], layers["mlp"])
    lp[mixer] = jax.tree.map(lambda a: a[slot], layers[mixer])
    return lp


def _ffn_branch(cfg: ModelConfig, lp: Dict, x: jax.Array, shardings: ActSharding):
    aux = jnp.zeros((), jnp.float32)
    if cfg.is_moe:
        mp = lp["moe"]
        if shardings.moe_a2a is not None:
            from repro.models.moe import moe_ffn_a2a

            y, aux = moe_ffn_a2a(
                x, mp["router"], mp["wi"], mp["wg"], mp["wo"],
                num_experts=cfg.num_experts,
                top_k=cfg.num_experts_per_tok,
                capacity_factor=cfg.capacity_factor,
                **shardings.moe_a2a,
            )
        else:
            y, aux = moe_ffn(
                x,
                mp["router"],
                mp["wi"],
                mp["wg"],
                mp["wo"],
                num_experts=cfg.num_experts,
                top_k=cfg.num_experts_per_tok,
                capacity_factor=cfg.capacity_factor,
                groups=shardings.moe_groups,
                token_spec=shardings.moe_tokens,
                buf_spec=shardings.moe_buf,
            )
    else:
        mp = lp["mlp"]
        y = swiglu_mlp(x, mp["wi"], mp["wg"], mp["wo"])
    return y, aux


def block_fwd(
    cfg: ModelConfig,
    x: jax.Array,
    lp: Dict,
    positions: jax.Array,
    shardings: ActSharding,
    *,
    q_chunk: int = 0,
    collect_cache: bool = False,
    kind: Optional[str] = None,
):
    """One decoder block: the family's, or the ``kind`` of an interleaved
    stack's layer. Returns (x, aux_loss, cache | None): (k, v) of an
    attention layer, (conv, ssm) state of a mamba-2 layer."""
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    cache = None
    if kind == "mamba":
        mix, cache = ssm_mod.mamba2_block(
            h, lp["mamba"], chunk=cfg.ssm_chunk, **_mamba2_dims(cfg))
    elif kind is None and cfg.family == "hybrid":
        attn_out, cache = _attn_branch(cfg, lp, h, positions, q_chunk=q_chunk)
        ssm_out = ssm_mod.mamba_block(
            h, lp["ssm"], dt_rank=cfg.dt_rank, ssm_state=cfg.ssm_state
        )
        mix = 0.5 * (
            rms_norm(attn_out, lp["attn_branch_norm"], cfg.norm_eps)
            + rms_norm(ssm_out, lp["ssm_branch_norm"], cfg.norm_eps)
        )
    elif kind is None and cfg.family == "ssm":
        mix = ssm_mod.mamba_block(
            h, lp["ssm"], dt_rank=cfg.dt_rank, ssm_state=cfg.ssm_state
        )
    else:
        mix, cache = _attn_branch(cfg, lp, h, positions, q_chunk=q_chunk)
    x = shardings.res(_residual(cfg, x, mix))

    aux = jnp.zeros((), jnp.float32)
    if cfg.is_moe or cfg.d_ff > 0:
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        y, aux = _ffn_branch(cfg, lp, h2, shardings)
        x = shardings.res(_residual(cfg, x, y))
    if not collect_cache:
        cache = None
    return x, aux, cache


# --------------------------------------------------------------------------- #
# Forward (train / prefill)
# --------------------------------------------------------------------------- #
def forward_hidden(
    cfg: ModelConfig,
    params: PyTree,
    tokens: jax.Array,
    *,
    pixel_embeds: Optional[jax.Array] = None,
    shardings: ActSharding = NO_SHARDING,
    compute_dtype=jnp.bfloat16,
    q_chunk: int = 0,
    collect_cache: bool = False,
    remat: bool = True,
    remat_policy: Optional[str] = None,
):
    """Embed -> scan(blocks) -> final norm.

    Returns (hidden (B, S, D), aux_loss, cache | None): (k, v) each
    (L,B,S,KV,hd); for an interleaved stack, ``{kind: per-layer caches
    stacked over that kind's layers}``. ``remat_policy``: None (save
    nothing, recompute all) | "dots" (save dot outputs — trades activation
    memory for recompute traffic; §Perf knob).
    """
    x = _embed(cfg, params, tokens, compute_dtype)
    if cfg.vision_prefix and pixel_embeds is not None:
        x = jnp.concatenate([pixel_embeds.astype(compute_dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    x = shardings.res(x)

    layers = jax.tree.map(lambda p: p.astype(compute_dtype), params["layers"])

    def body_inner(x, lp, kind=None):
        x, aux, cache = block_fwd(
            cfg, x, lp, positions, shardings,
            q_chunk=q_chunk, collect_cache=collect_cache, kind=kind,
        )
        return x, aux, cache

    def remat_wrap(fn):
        if remat and remat_policy == "dots":
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        return jax.checkpoint(fn) if remat else fn

    if cfg.layer_types:
        # unrolled in the published order; each kind's caches stacked
        aux_sum = jnp.zeros((), jnp.float32)
        per_kind: Dict[str, List] = {}
        for i, kind in enumerate(cfg.layer_types):
            layer = remat_wrap(functools.partial(body_inner, kind=kind))
            x, aux, cache = layer(x, _layer_params(cfg, layers, i))
            aux_sum = aux_sum + aux
            if collect_cache:
                per_kind.setdefault(kind, []).append(cache)
        caches = {k: jax.tree.map(lambda *a: jnp.stack(a), *c)
                  for k, c in per_kind.items()} if collect_cache else None
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, aux_sum, caches

    wrapped = remat_wrap(body_inner)

    def body(carry, lp):
        x, aux_sum = carry
        x, aux, cache = wrapped(x, lp)
        return (x, aux_sum + aux), cache

    (x, aux_sum), caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), layers)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux_sum, caches


def lm_head_weight(cfg: ModelConfig, params: PyTree) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


def loss_fn(
    cfg: ModelConfig,
    params: PyTree,
    batch: Dict[str, jax.Array],
    *,
    shardings: ActSharding = NO_SHARDING,
    compute_dtype=jnp.bfloat16,
    aux_weight: float = 0.01,
    q_chunk: int = 0,
    ce_chunk: int = 512,
    remat_policy: Optional[str] = None,
):
    """Next-token cross-entropy (+ MoE aux). batch: tokens, labels, mask."""
    hidden, aux, _ = forward_hidden(
        cfg,
        params,
        batch["tokens"],
        pixel_embeds=batch.get("pixel_embeds"),
        shardings=shardings,
        compute_dtype=compute_dtype,
        q_chunk=q_chunk,
        remat_policy=remat_policy,
    )
    head = lm_head_weight(cfg, params).astype(compute_dtype)
    if cfg.logits_scaling is not None:
        hidden = hidden * (1.0 / cfg.logits_scaling)
    labels = batch["labels"]
    mask = batch["mask"].astype(jnp.float32)
    if cfg.vision_prefix:
        # loss only over text positions; vision prefix is unsupervised
        hidden = hidden[:, cfg.vision_prefix :]
    nll_sum, n_tok = cross_entropy_chunked(
        hidden, head, labels, mask, chunk=ce_chunk, logits_spec=shardings.logits
    )
    loss = nll_sum / jnp.maximum(n_tok, 1.0)
    total = loss + aux_weight * aux / max(cfg.num_layers, 1)
    metrics = {"loss": loss, "aux_loss": aux, "tokens": n_tok}
    return total, metrics


# --------------------------------------------------------------------------- #
# Prefill
# --------------------------------------------------------------------------- #
def prefill(
    cfg: ModelConfig,
    params: PyTree,
    tokens: jax.Array,
    *,
    pixel_embeds: Optional[jax.Array] = None,
    shardings: ActSharding = NO_SHARDING,
    compute_dtype=jnp.bfloat16,
    q_chunk: int = 1024,
    max_seq: Optional[int] = None,
):
    """Returns (last-position logits (B, V), cache).

    The cache holds K/V of the attention layers, (L, B, S, KV, hd) each;
    an interleaved stack's also holds the conv and SSM state of its mamba
    layers, each kind stacked over its own layers. With ``max_seq`` it is
    ``make_decode_cache``'s layout, K/V padded to ``max_seq`` positions, so
    that ``decode_step`` continues from it at position S.
    """
    if max_seq is not None and cfg.has_ssm and not cfg.layer_types:
        raise NotImplementedError(f"{cfg.name}: prefill keeps no mamba-1 state")
    hidden, _, caches = forward_hidden(
        cfg,
        params,
        tokens,
        pixel_embeds=pixel_embeds,
        shardings=shardings,
        compute_dtype=compute_dtype,
        q_chunk=q_chunk,
        collect_cache=cfg.has_attention or bool(cfg.layer_types),
        remat=False,
    )
    head = lm_head_weight(cfg, params).astype(compute_dtype)
    logits = jnp.einsum("bd,dv->bv", hidden[:, -1], head).astype(jnp.float32)
    logits = _scale_logits(cfg, logits)
    if caches is None:
        return logits, None
    cache: Dict[str, Any] = {}
    if cfg.layer_types:
        if "attention" in caches:
            cache["k"], cache["v"] = caches["attention"]
        if "mamba" in caches:
            cache["conv"], cache["ssm"] = caches["mamba"]
    else:
        cache["k"], cache["v"] = caches
    if "k" in cache:
        k, v = cache["k"], cache["v"]
        if max_seq is not None:
            pad = [(0, 0)] * k.ndim
            pad[2] = (0, max_seq - k.shape[2])
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        if shardings.kv_cache is not None:
            k = constrain(k, shardings.kv_cache)
            v = constrain(v, shardings.kv_cache)
        cache["k"], cache["v"] = k, v
    return logits, cache


# --------------------------------------------------------------------------- #
# Decode
# --------------------------------------------------------------------------- #
#: decode-cache entries of each kind of layer
KIND_CACHE = {"attention": ("k", "v", "k_scale", "v_scale"), "mamba": ("conv", "ssm")}


def make_decode_cache(
    cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16
) -> PyTree:
    """Zero-initialized decode cache.

    ``dtype=jnp.int8`` stores quantized K/V with per-(position, kv-head)
    fp32 scales — halves the dominant decode-HBM term (§Perf); dequant
    happens per attention call (fused into the kernel's VMEM tiles on TPU).
    An interleaved stack holds K/V for its attention layers only and conv
    and SSM state for its mamba layers only.
    """
    cache: Dict[str, Any] = {}
    L = cfg.num_layers
    La = len(cfg.layer_slots("attention")) if cfg.layer_types else L
    if cfg.has_attention and La:
        shape = (La, batch, max_seq, cfg.num_kv_heads, cfg.head_dim)
        cache["k"] = jnp.zeros(shape, dtype)
        cache["v"] = jnp.zeros(shape, dtype)
        if dtype == jnp.int8:
            sshape = (La, batch, max_seq, cfg.num_kv_heads)
            cache["k_scale"] = jnp.ones(sshape, jnp.float32)
            cache["v_scale"] = jnp.ones(sshape, jnp.float32)
    if cfg.layer_types:
        Lm = len(cfg.layer_slots("mamba"))
        if Lm:
            cache["conv"] = jnp.zeros(
                (Lm, batch, cfg.ssm_conv - 1, cfg.ssm_conv_dim),
                jnp.bfloat16 if dtype == jnp.int8 else dtype)
            cache["ssm"] = jnp.zeros(
                (Lm, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32)
    elif cfg.has_ssm:
        cache["conv"] = jnp.zeros((L, batch, cfg.ssm_conv - 1, cfg.d_inner), jnp.bfloat16)
        cache["ssm"] = jnp.zeros((L, batch, cfg.d_inner, cfg.ssm_state), jnp.float32)
    return cache


def abstract_decode_cache(
    cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16
) -> PyTree:
    return jax.eval_shape(lambda: make_decode_cache(cfg, batch, max_seq, dtype))


def _decode_block(
    cfg: ModelConfig,
    x: jax.Array,
    lp: Dict,
    cl: Dict,
    cur_index: jax.Array,
    shardings: ActSharding,
    kind: Optional[str] = None,
):
    """x: (B,1,D); cl: per-layer cache slices; ``kind`` as in ``block_fwd``.
    Returns (x, new_cl)."""
    B = x.shape[0]
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    new_cl: Dict[str, jax.Array] = {}
    pos = jnp.full((B, 1), cur_index, dtype=jnp.int32)

    quantized = "k_scale" in cl

    def attn(h):
        ap = lp["attn"]
        q = jnp.einsum("bsd,dh->bsh", h, ap["wq"])
        k = jnp.einsum("bsd,dh->bsh", h, ap["wk"])
        v = jnp.einsum("bsd,dh->bsh", h, ap["wv"])
        if cfg.qkv_bias:
            q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
        q = q.reshape(B, 1, cfg.num_heads, cfg.head_dim)
        k = k.reshape(B, 1, cfg.num_kv_heads, cfg.head_dim)
        v = v.reshape(B, 1, cfg.num_kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = rms_norm_headwise(q, ap["q_norm"], cfg.norm_eps)
            k = rms_norm_headwise(k, ap["k_norm"], cfg.norm_eps)
        if cfg.rope:
            q = apply_rope_cfg(cfg, q, pos)
            k = apply_rope_cfg(cfg, k, pos)
        new_scales = {}
        if quantized:
            # per-(position, kv-head) int8 quantization of the new K/V
            def quant(t):
                scale = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1) / 127.0
                scale = jnp.maximum(scale, 1e-8)
                q8 = jnp.clip(
                    jnp.round(t.astype(jnp.float32) / scale[..., None]), -127, 127
                ).astype(jnp.int8)
                return q8, scale
            k, ks = quant(k)
            v, vs = quant(v)
            new_scales["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
                cl["k_scale"], ks, cur_index, axis=1)
            new_scales["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
                cl["v_scale"], vs, cur_index, axis=1)
        kc = jax.lax.dynamic_update_slice_in_dim(cl["k"], k, cur_index, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(cl["v"], v, cur_index, axis=1)
        if quantized:
            k_use = (kc.astype(jnp.float32)
                     * new_scales["k_scale"][..., None]).astype(jnp.bfloat16)
            v_use = (vc.astype(jnp.float32)
                     * new_scales["v_scale"][..., None]).astype(jnp.bfloat16)
        else:
            k_use, v_use = kc, vc
        out = decode_attention_xla(q.astype(k_use.dtype), k_use, v_use,
                                   cur_index, window=cfg.sliding_window,
                                   scale=cfg.attention_multiplier)
        out = out.reshape(B, 1, cfg.q_dim)
        return jnp.einsum("bsh,hd->bsd", out.astype(h.dtype), ap["wo"]), kc, vc, new_scales

    def ssm_step(h):
        return ssm_mod.mamba_decode_step(
            h, lp["ssm"], cl["conv"], cl["ssm"],
            dt_rank=cfg.dt_rank, ssm_state=cfg.ssm_state,
        )

    if kind == "mamba":
        mix, conv_s, ssm_s = ssm_mod.mamba2_decode_step(
            h, lp["mamba"], cl["conv"], cl["ssm"], **_mamba2_dims(cfg))
        new_cl.update(conv=conv_s, ssm=ssm_s)
    elif kind is None and cfg.family == "hybrid":
        attn_out, kc, vc, scales = attn(h)
        ssm_out, conv_s, ssm_s = ssm_step(h)
        new_cl.update(k=kc, v=vc, conv=conv_s, ssm=ssm_s, **scales)
        mix = 0.5 * (
            rms_norm(attn_out, lp["attn_branch_norm"], cfg.norm_eps)
            + rms_norm(ssm_out, lp["ssm_branch_norm"], cfg.norm_eps)
        )
    elif kind is None and cfg.family == "ssm":
        mix, conv_s, ssm_s = ssm_step(h)
        new_cl.update(conv=conv_s, ssm=ssm_s)
    else:
        mix, kc, vc, scales = attn(h)
        new_cl.update(k=kc, v=vc, **scales)
    x = shardings.dres(_residual(cfg, x, mix))

    if cfg.is_moe or cfg.d_ff > 0:
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        y, _ = _ffn_branch(cfg, lp, h2, shardings)
        x = shardings.dres(_residual(cfg, x, y))
    return x, new_cl


def decode_step(
    cfg: ModelConfig,
    params: PyTree,
    cache: PyTree,
    tokens: jax.Array,
    cur_index: jax.Array,
    *,
    shardings: ActSharding = NO_SHARDING,
    compute_dtype=jnp.bfloat16,
):
    """One token for every sequence. tokens: (B, 1) -> (logits (B,V), cache)."""
    x = _embed(cfg, params, tokens, compute_dtype)
    x = shardings.dres(x)
    layers = jax.tree.map(lambda p: p.astype(compute_dtype), params["layers"])

    if cfg.layer_types:
        # unrolled in the published order; each layer reads and writes
        # only its own kind's cache, at that kind's index
        new: Dict[str, List] = {k: [] for k in cache}
        for i, kind in enumerate(cfg.layer_types):
            slot = cfg.layer_types[:i].count(kind)
            cl = {k: cache[k][slot] for k in KIND_CACHE[kind] if k in cache}
            x, new_cl = _decode_block(cfg, x, _layer_params(cfg, layers, i), cl,
                                      cur_index, shardings, kind=kind)
            for k, v in new_cl.items():
                new[k].append(v)
        new_cache = {k: jnp.stack(v) for k, v in new.items()}
    else:
        cache_f = jax.tree.map(lambda c: c, cache)

        def body(x, inp):
            lp, cl = inp
            x, new_cl = _decode_block(cfg, x, lp, cl, cur_index, shardings)
            return x, new_cl

        x, new_cache = jax.lax.scan(body, x, (layers, cache_f))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = lm_head_weight(cfg, params).astype(compute_dtype)
    logits = jnp.einsum("bsd,dv->bsv", x, head)[:, 0].astype(jnp.float32)
    return _scale_logits(cfg, logits), new_cache
