"""Seeded weights for IBM Granite configurations, in the program's layout.

Granite-3 (``GraniteForCausalLM``) has the dense layout of ``weights.py``
(no biases, tied embeddings). Granite-4.0-H (``granitemoehybrid``)
interleaves Mamba-2 mixers and attention layers (``layer_types``); the
program stacks the norms and the MLP over every layer and each mixer over
the layers of its kind:

  ``layers/ln1``, ``layers/ln2``, ``layers/mlp/{wg,wi,wo}``  (L, ...)
  ``layers/attn/{wq,wk,wv,wo}``                          (attention layers, ...)
  ``layers/mamba/{in_proj,conv_w,conv_b,dt_bias,A_log,D,norm,out_proj}``
                                                         (mamba layers, ...)

``in_proj`` maps the hidden state to ``[z | x B C | dt]``
(``d_inner + conv_dim + heads``, conv_dim = ``d_inner + 2 groups d_state``);
``conv_w`` is the depthwise conv, (conv_dim, d_conv).

Values, drawn in float32 from the seed in one jitted call and rounded to
the type asked for: matrices uniform with the spread of a 0.02-std
normal, as in ``weights.py``; the embedding with that spread divided by
``embedding_multiplier``, so that the token's own embedding enters the
residual stream at 0.02 as in the dense cells (at 12 x 0.02 it outweighs
the layers' outputs, which Granite scales by 0.22, and through the tied
head every greedy token repeats the one before it, so that no precision
could be told from another); norm scales and the skip ``D`` ones; the conv
PyTorch's ``Conv1d`` default, uniform in +-1/sqrt(d_conv) for weight and
bias; ``A_log`` and ``dt_bias`` Mamba-2's initialisation (Dao & Gu,
arXiv:2405.21060): A uniform in [1, 16], dt log-uniform in [1e-3, 1e-1]
stored as its inverse softplus.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import numpy as np

from harness import weights

STD = weights.STD


def hybrid(c: dict) -> bool:
    return bool(c.get("layer_types"))


def dims(c: dict) -> Dict[str, int]:
    """``weights.dims`` with the MLP's width taken from
    ``shared_intermediate_size`` where the file has one (granitemoehybrid
    with no routed experts computes only that MLP), and Mamba-2's sizes."""
    d = dict(weights.dims(c))
    d["F"] = c.get("shared_intermediate_size") or c["intermediate_size"]
    if hybrid(c):
        kinds = c["layer_types"]
        heads, hd = c["mamba_n_heads"], c["mamba_d_head"]
        g, n = c["mamba_n_groups"], c["mamba_d_state"]
        d.update(La=kinds.count("attention"), Lm=kinds.count("mamba"),
                 Hs=heads, P=hd, Di=heads * hd, G=g, N=n, K=c["mamba_d_conv"],
                 C=heads * hd + 2 * g * n)
    return d


def layout(c: dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    """``(leaf name, shape, init)`` in name order. Inits: ``embed``,
    ``uniform``, ``ones``, ``conv``, ``a_log``, ``dt_bias``."""
    if not hybrid(c):
        return [(n, s, "embed" if n == "embed" else i) for n, s, i in weights.layout(c)]
    d = dims(c)
    L, D, F, La, Lm = d["L"], d["D"], d["F"], d["La"], d["Lm"]
    out = [("embed", (d["V"], D), "embed"), ("final_norm", (D,), "ones"),
           ("layers/ln1", (L, D), "ones"), ("layers/ln2", (L, D), "ones"),
           ("layers/mlp/wi", (L, D, F), "uniform"),
           ("layers/mlp/wg", (L, D, F), "uniform"),
           ("layers/mlp/wo", (L, F, D), "uniform")]
    if La:
        out += [("layers/attn/wq", (La, D, d["H"]), "uniform"),
                ("layers/attn/wk", (La, D, d["KV"]), "uniform"),
                ("layers/attn/wv", (La, D, d["KV"]), "uniform"),
                ("layers/attn/wo", (La, d["H"], D), "uniform")]
    if Lm:
        out += [("layers/mamba/in_proj", (Lm, D, d["Di"] + d["C"] + d["Hs"]), "uniform"),
                ("layers/mamba/conv_w", (Lm, d["C"], d["K"]), "conv"),
                ("layers/mamba/conv_b", (Lm, d["C"]), "conv"),
                ("layers/mamba/dt_bias", (Lm, d["Hs"]), "dt_bias"),
                ("layers/mamba/A_log", (Lm, d["Hs"]), "a_log"),
                ("layers/mamba/D", (Lm, d["Hs"]), "ones"),
                ("layers/mamba/norm", (Lm, d["Di"]), "ones"),
                ("layers/mamba/out_proj", (Lm, d["Di"], D), "uniform")]
    if not c.get("tie_word_embeddings"):
        out.append(("lm_head", (D, d["V"]), "uniform"))
    return sorted(out)


def state_names(c: dict) -> List[str]:
    """Leaf names of ``{"params": tree}`` as the file system stores them."""
    return ["params/" + n for n, _, _ in layout(c)]


def state_bytes(c: dict, dtype: str = "float32") -> int:
    size = np.dtype(dtype).itemsize
    return sum(size * int(np.prod(s)) for _, s, _ in layout(c))


@functools.lru_cache(maxsize=None)
def _maker(spec: Tuple[Tuple[str, Tuple[int, ...], str], ...], dtype: str,
           d_conv: int, emb_mult: float):
    import jax
    import jax.numpy as jnp

    def uniform(key, shape, lo, hi):
        return jax.random.uniform(key, shape, jnp.float32, lo, hi)

    @jax.jit
    def make(key):
        flat = {}
        for i, (name, shape, init) in enumerate(spec):
            k = jax.random.fold_in(key, i)
            if init == "ones":
                v = jnp.ones(shape, jnp.float32)
            elif init == "uniform":
                v = uniform(k, shape, -STD * 3 ** 0.5, STD * 3 ** 0.5)
            elif init == "embed":
                lim = STD * 3 ** 0.5 / emb_mult
                v = uniform(k, shape, -lim, lim)
            elif init == "conv":
                v = uniform(k, shape, -d_conv ** -0.5, d_conv ** -0.5)
            elif init == "a_log":
                v = jnp.log(uniform(k, shape, 1.0, 16.0))
            elif init == "dt_bias":
                dt = jnp.exp(uniform(k, shape, np.log(1e-3), np.log(1e-1)))
                v = dt + jnp.log(-jnp.expm1(-dt))
            else:  # pragma: no cover
                raise ValueError(init)
            flat[name] = v.astype(dtype)
        return weights.nest(flat)

    return make


def make_params(c: dict, seed: int, dtype: str = "float32"):
    """The model's parameter tree in ``dtype`` on the default device."""
    return _maker(tuple(layout(c)), dtype, dims(c).get("K", 0),
                  float(c["embedding_multiplier"]))(weights.seed_key(seed))
