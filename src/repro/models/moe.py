"""Token-choice MoE with group-local, capacity-bounded dispatch.

Dispatch is performed PER DATA-PARALLEL GROUP (``groups`` = number of batch
shards): each group routes its own tokens, computes position-in-expert with
a group-local cumulative sum (no cross-shard prefix sums), and scatters into
a per-group (E, C, D) buffer. The buffer is replicated across the ``model``
axis at dispatch (tokens are batch-sharded there), then *sliced* to the
local expert shard for the expert FFNs — a free reshard — so the heavy
matmuls are expert-parallel over ``model``. The combine gathers expert
outputs back (one all-gather of the (E_local -> E) outputs per layer), which
the §Perf pass attacks with a shard_map all-to-all.

Capacity semantics follow GShard/Switch: C = ceil(cf * T_g * k / E); tokens
beyond capacity are dropped (their combine weight is zero).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import constrain


def top_k_routing(router_logits: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """router_logits: (..., E) -> (weights (...,k) fp32 normalized, ids (...,k))."""
    gates = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    weights, ids = jax.lax.top_k(gates, k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, ids


def load_balance_loss(logits: jax.Array, ids: jax.Array, num_experts: int) -> jax.Array:
    """Switch-style auxiliary loss over all tokens."""
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    me = jnp.mean(gates.reshape(-1, num_experts), axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(ids, num_experts, dtype=jnp.float32), axis=-2)
        .reshape(-1, num_experts),
        axis=0,
    )
    return num_experts * jnp.sum(me * ce)


def moe_ffn(
    x: jax.Array,
    router: jax.Array,
    wi: jax.Array,
    wg: jax.Array,
    wo: jax.Array,
    *,
    num_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
    groups: int = 1,
    token_spec: Optional[P] = None,
    buf_spec: Optional[P] = None,
) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (B, S, D), aux_loss.

    router: (D, E); wi/wg: (E, D, F); wo: (E, F, D).
    """
    B, S, D = x.shape
    E, k = num_experts, top_k
    G = groups if B % max(groups, 1) == 0 else 1
    Tg = (B // G) * S
    xg = x.reshape(G, Tg, D)
    if token_spec is not None:
        xg = constrain(xg, token_spec)

    logits = jnp.einsum("gtd,de->gte", xg, router)          # (G, Tg, E)
    weights, ids = top_k_routing(logits, k)                  # (G, Tg, k)
    aux = load_balance_loss(logits, ids, E)

    capacity = max(k, int(capacity_factor * Tg * k / E))

    flat_ids = ids.reshape(G, Tg * k)
    flat_w = weights.reshape(G, Tg * k)
    onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)    # (G, Tg*k, E)
    pos_all = jnp.cumsum(onehot, axis=1) - onehot            # group-local
    pos = jnp.take_along_axis(pos_all, flat_ids[..., None], axis=2)[..., 0]
    keep = pos < capacity
    pos = jnp.where(keep, pos, capacity - 1)

    tok = jnp.arange(Tg * k) // k                            # slot -> token
    xs = jnp.take(xg, tok, axis=1) * keep[..., None].astype(x.dtype)

    # G is a true batch dim of the scatter (vmap -> operand_batching_dims),
    # so SPMD keeps the dispatch local to each data shard instead of
    # replicating the (G, E, C, D) buffer.
    def scatter_group(ids_g, pos_g, xs_g):
        return jnp.zeros((E, capacity, D), dtype=x.dtype).at[ids_g, pos_g].add(
            xs_g, mode="drop"
        )

    buf = jax.vmap(scatter_group)(flat_ids, pos, xs)
    if buf_spec is not None:
        # free reshard: buf is model-replicated after dispatch; slicing the
        # expert dim onto the model axis localizes the FFN compute
        buf = constrain(buf, buf_spec)

    h = jnp.einsum("gecd,edf->gecf", buf, wi)
    g = jnp.einsum("gecd,edf->gecf", buf, wg)
    h = h * g * jax.nn.sigmoid(g.astype(jnp.float32)).astype(h.dtype)  # h*silu(g)
    ye = jnp.einsum("gecf,efd->gecd", h, wo)
    if buf_spec is not None:
        ye = constrain(ye, buf_spec)

    def gather_group(ye_g, ids_g, pos_g):
        return ye_g[ids_g, pos_g]

    ys = jax.vmap(gather_group)(ye, flat_ids, pos)
    ys = ys * (flat_w * keep)[..., None].astype(ye.dtype)
    out = ys.reshape(G, Tg, k, D).sum(axis=2)       # combine the k slots
    return out.reshape(B, S, D), aux


# --------------------------------------------------------------------------- #
# §Perf: expert-parallel MoE via shard_map all-to-all
# --------------------------------------------------------------------------- #
def moe_ffn_a2a(
    x: jax.Array,
    router: jax.Array,
    wi: jax.Array,
    wg: jax.Array,
    wo: jax.Array,
    *,
    num_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
    mesh,
    batch_axes: Tuple[str, ...],
    model_axis: str = "model",
    seq_axis: Optional[str] = "model",
) -> Tuple[jax.Array, jax.Array]:
    """Dropped-token-bounded MoE with explicit expert-parallel all-to-all.

    Replaces the global-view dispatch (whose combine XLA lowers as a psum of
    the k-expanded token tensor — measured 1.27 TB/step of all-reduce on
    qwen3-30B) with the canonical EP exchange:

      route locally -> scatter into per-expert send slabs -> all_to_all over
      the model axis -> local expert FFNs -> reverse all_to_all -> local
      weighted combine.

    Wire cost: 2 * T_local * k * cf * D bytes per device per layer — no
    all-reduce, no model-replicated buffers. Tokens stay sequence-sharded.
    """
    import numpy as np
    from jax.sharding import PartitionSpec

    B, S, D = x.shape
    E, k = num_experts, top_k
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_model = sizes[model_axis]
    n_batch = 1
    for a in batch_axes or ():
        n_batch *= sizes[a]
    assert E % n_model == 0, (E, n_model)
    e_loc = E // n_model
    t_loc = (B // n_batch) * (S // (n_model if seq_axis else 1))
    cap = max(1, int(capacity_factor * t_loc * k / E))

    def local(x_l, router_l, wi_l, wg_l, wo_l):
        # x_l: (B_loc, S_loc, D); wi_l: (e_loc, D, F)
        b_l, s_l, _ = x_l.shape
        t = b_l * s_l
        xf = x_l.reshape(t, D)
        logits = jnp.einsum("td,de->te", xf, router_l)
        weights, ids = top_k_routing(logits, k)
        # load-balance loss: pmean the me/ce VECTORS before their product so
        # the result equals the global-batch loss exactly
        gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        me = jnp.mean(gates, axis=0)
        ce = jnp.mean(
            jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=1), axis=0
        )
        axes = (model_axis,) + tuple(batch_axes or ())
        for a in axes:
            me = jax.lax.pmean(me, a)
            ce = jax.lax.pmean(ce, a)
        aux = E * jnp.sum(me * ce)

        flat_ids = ids.reshape(t * k)
        flat_w = weights.reshape(t * k)
        onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)
        pos = jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0) - onehot, flat_ids[:, None], axis=1
        )[:, 0]
        keep = pos < cap
        pos_c = jnp.where(keep, pos, cap - 1)
        tok = jnp.arange(t * k) // k
        xs = jnp.take(xf, tok, axis=0) * keep[:, None].astype(xf.dtype)

        send = jnp.zeros((E, cap, D), xf.dtype).at[flat_ids, pos_c].add(
            xs, mode="drop"
        )
        # exchange: each peer gets its expert slab; we receive every peer's
        # slab for OUR experts
        recv = jax.lax.all_to_all(
            send, model_axis, split_axis=0, concat_axis=1, tiled=True
        )  # (e_loc, n_model*cap, D)

        h = jnp.einsum("ecd,edf->ecf", recv, wi_l)
        g = jnp.einsum("ecd,edf->ecf", recv, wg_l)
        h = h * g * jax.nn.sigmoid(g.astype(jnp.float32)).astype(h.dtype)
        ye = jnp.einsum("ecf,efd->ecd", h, wo_l)

        back = jax.lax.all_to_all(
            ye, model_axis, split_axis=1, concat_axis=0, tiled=True
        )  # (E, cap, D): our tokens, processed
        ys = back[flat_ids, pos_c] * (flat_w * keep)[:, None].astype(back.dtype)
        out = ys.reshape(t, k, D).sum(axis=1)
        return out.reshape(b_l, s_l, D), aux

    bspec = tuple(batch_axes) if batch_axes else None
    x_spec = PartitionSpec(bspec, seq_axis, None)
    w_spec = PartitionSpec(model_axis, None, None)
    # replication check off: the combine emits an unreplicated scalar aux loss
    out, aux = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(x_spec, PartitionSpec(None, None), w_spec, w_spec,
                  PartitionSpec(model_axis, None, None)),
        out_specs=(x_spec, PartitionSpec()),
        check_vma=False,
    )(x, router, wi, wg, wo)
    return out, aux
