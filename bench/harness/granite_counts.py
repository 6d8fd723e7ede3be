"""FLOPs a Granite-4.0-H (Mamba-2 + attention) forward pass needs,
computed from shapes: the yardstick of ``hybrid.decode_mfu``. Two per
multiply-add; every weight matrix once; elementwise work (norms, gates,
activations, softmax) is left out, as ``counts.decode_flops`` leaves it
out.

Per token, by layer kind (D hidden, F MLP width, V vocabulary):

  every layer   MLP            2 * 3 D F
  attention     projections    2 D (2 H + 2 KV)     (H = heads x head_dim)
                scores, values 2 * 2 heads head_dim * context
  mamba-2       in_proj        2 D (Di + C + Hs)    (C = Di + 2 G N conv channels)
                conv           2 K C
                out_proj       2 Di D
  LM head                      2 D V

A Mamba-2 decode step (the recurrence, per head of P channels and N
states): the state update ``exp(dt A) s + (dt x) (outer) B`` is 3 per state
element (scale, product, add) and the readout ``s C`` 2, so 5 Hs P N; the
skip ``D x`` 2 Di. A prefill runs the chunked SSD form instead, which per
token of a chunk of Q positions needs ``C B^T`` (2 Q G N / 2, causal half),
its product with the decayed inputs (2 Q Hs P / 2), the chunk's state
(2 Hs P N) and the carried state's readout (2 Hs P N); attention's
scores and values cover the causal half, ``context`` growing with the
position.
"""
from __future__ import annotations

from harness.granite_weights import dims


def _per_token_weights(c: dict) -> float:
    d = dims(c)
    D = d["D"]
    mlp = 2 * 3 * D * d["F"]
    attn = 2 * D * (2 * d["H"] + 2 * d["KV"])
    mamba = 2 * D * (d["Di"] + d["C"] + d["Hs"]) + 2 * d["K"] * d["C"] + 2 * d["Di"] * D
    return d["L"] * mlp + d["La"] * attn + d["Lm"] * mamba + 2 * D * d["V"]


def decode_flops(c: dict, context: int) -> float:
    """FLOPs of one decoded token whose attention layers attend to
    ``context`` positions (itself included)."""
    d = dims(c)
    attn = 2 * 2 * d["heads"] * d["hd"] * context
    ssm = 5 * d["Hs"] * d["P"] * d["N"] + 2 * d["Di"]
    return _per_token_weights(c) + d["La"] * attn + d["Lm"] * ssm


def prefill_flops(c: dict, tokens: int, chunk: int) -> float:
    """FLOPs of a prefill of ``tokens`` positions (one sequence) through
    the chunked SSD scan of ``chunk`` positions; the LM head at the last
    position only."""
    d = dims(c)
    per_token = _per_token_weights(c) - 2 * d["D"] * d["V"]
    attn = 2 * 2 * d["heads"] * d["hd"] * tokens * (tokens + 1) / 2
    q = min(chunk, tokens)
    ssd = (2 * q * d["G"] * d["N"] / 2 + 2 * q * d["Hs"] * d["P"] / 2
           + 4 * d["Hs"] * d["P"] * d["N"] + 2 * d["Di"])
    return (tokens * per_token + d["La"] * attn + d["Lm"] * tokens * ssd
            + 2 * d["D"] * d["V"])
