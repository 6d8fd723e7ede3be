"""Seeded weights in the program's parameter layout, made on the device.

The layout is the tree the model code reads (``embed``, ``final_norm``,
layer-stacked ``layers/attn``, ``layers/mlp``, ``ln1``, ``ln2``), in the
type asked for: the trainer's float32 master weights (``master_dtype``) or
the published type a replica is synced in (``torch_dtype``). Values are
uniform with the spread of a 0.02-std normal, drawn in float32 and then
rounded; norm scales are ones. One jitted call makes the whole tree from
the seed, so the same seed gives the same bits on the same device.

Leaf names follow the file system's layout: '/'-joined dict keys, sorted,
under ``params/``; a save of ``{"params": tree}`` stores each under that
name.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import numpy as np

STD = 0.02


def dims(c: dict) -> Dict[str, int]:
    """The sizes the layout needs, from a configuration file's keys."""
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // heads
    return {"L": c["num_hidden_layers"], "D": c["hidden_size"],
            "V": c["vocab_size"], "F": c["intermediate_size"],
            "H": heads * hd, "KV": kv * hd, "heads": heads, "kv_heads": kv,
            "hd": hd}


def layout(c: dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    """``(leaf name, shape, init)`` in name order; init is ``uniform`` or
    ``ones``."""
    d = dims(c)
    L, D = d["L"], d["D"]
    out = [("embed", (d["V"], D), "uniform"), ("final_norm", (D,), "ones"),
           ("layers/attn/wq", (L, D, d["H"]), "uniform"),
           ("layers/attn/wk", (L, D, d["KV"]), "uniform"),
           ("layers/attn/wv", (L, D, d["KV"]), "uniform"),
           ("layers/attn/wo", (L, d["H"], D), "uniform"),
           ("layers/ln1", (L, D), "ones"), ("layers/ln2", (L, D), "ones"),
           ("layers/mlp/wi", (L, D, d["F"]), "uniform"),
           ("layers/mlp/wg", (L, D, d["F"]), "uniform"),
           ("layers/mlp/wo", (L, d["F"], D), "uniform")]
    if c.get("qkv_bias"):
        out += [("layers/attn/bq", (L, d["H"]), "uniform"),
                ("layers/attn/bk", (L, d["KV"]), "uniform"),
                ("layers/attn/bv", (L, d["KV"]), "uniform")]
    if not c.get("tie_word_embeddings"):
        out.append(("lm_head", (D, d["V"]), "uniform"))
    return sorted(out)


def state_names(c: dict) -> List[str]:
    """Leaf names of ``{"params": tree}`` as the file system stores them."""
    return ["params/" + n for n, _, _ in layout(c)]


def state_bytes(c: dict, dtype: str = "float32") -> int:
    size = np.dtype(dtype).itemsize
    return sum(size * int(np.prod(s)) for _, s, _ in layout(c))


def nest(flat: Dict[str, object]) -> dict:
    """``{"a/b": x}`` -> ``{"a": {"b": x}}``."""
    tree: dict = {}
    for name, leaf in flat.items():
        node = tree
        *path, last = name.split("/")
        for k in path:
            node = node.setdefault(k, {})
        node[last] = leaf
    return tree


def flatten(tree, prefix: str = "") -> Dict[str, object]:
    """The inverse of ``nest``, in name order."""
    if isinstance(tree, dict):
        out: Dict[str, object] = {}
        for k in sorted(tree):
            out.update(flatten(tree[k], f"{prefix}{k}/"))
        return out
    return {prefix.rstrip("/"): tree}


def seed_key(seed: int):
    """A PRNG key from any whole number of up to 64 bits."""
    import jax

    key = jax.random.key(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


@functools.lru_cache(maxsize=None)
def _maker(spec: Tuple[Tuple[str, Tuple[int, ...], str], ...], dtype: str):
    import jax
    import jax.numpy as jnp

    lim = STD * 3 ** 0.5

    @jax.jit
    def make(key):
        flat = {}
        for i, (name, shape, init) in enumerate(spec):
            if init == "ones":
                flat[name] = jnp.ones(shape, dtype)
            else:
                flat[name] = jax.random.uniform(
                    jax.random.fold_in(key, i), shape, jnp.float32, -lim, lim
                ).astype(dtype)
        return nest(flat)

    return make


def make_params(c: dict, seed: int, dtype: str = "float32"):
    """The model's parameter tree in ``dtype`` on the default device."""
    return _maker(tuple(layout(c)), dtype)(seed_key(seed))
