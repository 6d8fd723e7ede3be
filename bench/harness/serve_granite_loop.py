"""The ``serve_granite`` loop: the ``serve`` loop on IBM Granite's own
equations, with prompts of ``prompt_tokens`` tokens.

Mix parameters: those of ``serve_loop``, and ``prompt_tokens``: each
request's prompts are that many seeded tokens, prefilled into the decode
cache before ``tokens`` greedy tokens are decoded through it (one token:
no prefill, the decode path alone).

The replica, the trainer process, the schedule of versions and the
version checks are ``serve_loop``'s (``identify``, ``version_flat``,
``measure`` and the schedule are its own functions). What this loop
brings is Granite's: the program's ``ModelConfig`` with Granite's four
multipliers and, for Granite-4.0-H, its interleaved Mamba-2 and NoPE
attention layers (``model_config``); the weights in that layout
(``granite_weights.py``); the plain reference (``granite_reference.py``);
and prompts longer than one token, which is why the window is its own
(``serve_loop.window`` draws one-token prompts).

Checked against references once the window has closed, as in
``serve_loop``: ``version_errors``, ``param_mismatch``, ``no_requests``;
``malformed_outputs`` counts served rows that are not ``batch x (prompt +
tokens)`` starting with their prompt; ``logit_gap`` is the widest gap by
which a served token's logit lies below the reference's best, over a
seeded sample (the first request of every version served, and more), with
a limit of its own (``LOGIT_GAP_LIMIT``); under ``control``, the gap of the
token that the reference in float8 puts first.
"""
from __future__ import annotations

import dataclasses
import json
import subprocess
import sys
import threading
import time
from typing import List

import numpy as np

from harness import granite_reference, granite_weights, procs, weights
from harness.serve_loop import (CONTROL_CAST, WARM, _expected, _ref, _schedule,
                                identify, measure, version_flat)

from repro.configs.base import ModelConfig

if "layer_types" not in {f.name for f in dataclasses.fields(ModelConfig)}:
    raise ImportError("this program's model has no interleaved layer types or "
                      "Granite multipliers: it cannot serve Granite's equations")

__all__ = ["setup", "window", "measure", "check", "model_config", "prompts",
           "identify", "version_flat"]

#: widest logit gap of a sound run. Granite divides its logits by 8
#: (``logits_scaling``) and draws its embedding 12 times narrower, so the
#: dense cells' 0.1 says nothing here. Read on the chip at each cell's size
#: (one TPU v5e, 13 sound runs and 4 float8 controls over both Granite
#: cells): sound decodes at most 0.0019, the float8 control at least
#: 0.0171. 0.006, near their geometric mean, lies about 3 times above the
#: one and 3 times below the other (PERF.md gives every reading).
LOGIT_GAP_LIMIT = 0.006


def model_config(c: dict) -> ModelConfig:
    """The program's ``ModelConfig`` for a Granite configuration file."""
    unsupported = {k: c[k] for k in ("attention_bias", "mlp_bias", "mamba_proj_bias")
                   if c.get(k)}
    if c.get("num_local_experts"):
        unsupported["num_local_experts"] = c["num_local_experts"]
    if c.get("layer_types") and not c.get("mamba_conv_bias"):
        unsupported["mamba_conv_bias"] = c.get("mamba_conv_bias")
    if unsupported:
        raise ValueError(f"{c['name']}: not served by this loop: {unsupported}")
    d = granite_weights.dims(c)
    extra = {}
    if granite_weights.hybrid(c):
        extra = dict(family="hybrid", layer_types=tuple(c["layer_types"]),
                     ssm_heads=d["Hs"], ssm_head_dim=d["P"], d_inner=d["Di"],
                     ssm_state=d["N"], ssm_groups=d["G"], ssm_conv=d["K"],
                     ssm_chunk=c["mamba_chunk_size"])
    return ModelConfig(
        name=c["name"], family=extra.pop("family", "dense"), num_layers=d["L"],
        d_model=d["D"], num_heads=d["heads"], num_kv_heads=d["kv_heads"],
        head_dim=d["hd"], d_ff=d["F"], vocab_size=d["V"],
        tie_embeddings=bool(c.get("tie_word_embeddings")),
        rope=c.get("position_embedding_type", "rope") != "nope",
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        embedding_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        attention_multiplier=float(c["attention_multiplier"]),
        logits_scaling=float(c["logits_scaling"]), source=c["source"], **extra)


def prompts(r, i: int) -> np.ndarray:
    """The ``i``-th request's prompts, ``prompt_tokens`` seeded tokens each."""
    rng = np.random.default_rng([r.seed, 1, i])
    return rng.integers(0, r.config["vocab_size"],
                        (r.mix["batch"], r.mix["prompt_tokens"]), dtype=np.int32)


# ---------------------------------------------------------------------- #
def setup(r) -> None:
    import jax

    from repro.launch import serve as S
    from repro.serving.engine import SnapshotServer

    c, m = r.config, r.mix
    params = granite_weights.make_params(c, r.seed, c["torch_dtype"])
    flat = weights.flatten({"params": jax.device_get(params)})
    shapes = {n: a.shape for n, a in flat.items()}
    schedule = _schedule(r, shapes, np.dtype(c["torch_dtype"]).itemsize)
    elems, expect = _expected(flat, schedule, m["delta"])
    r.extra.update(schedule=schedule, elems=elems, expect=expect, last_k=0,
                   state_bytes=sum(a.nbytes for a in flat.values()))
    trainer = r.rundir.spawn(
        [sys.executable, str(procs.HERE / "trainer.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        env=procs.child_env(JAX_PLATFORMS="cpu"), cwd=str(procs.REPO))
    head = {"port": r.port, "block_bytes": c["block_bytes"],
            "cache_blocks": c["client_cache_blocks"], "delta": m["delta"],
            "leaves": [[n, str(a.dtype), list(a.shape)] for n, a in flat.items()],
            "schedule": schedule}
    trainer.stdin.write(json.dumps(head).encode() + b"\n")
    for a in flat.values():
        trainer.stdin.write(memoryview(np.ascontiguousarray(a).reshape(-1).view(np.uint8)))
    trainer.stdin.flush()
    del flat
    decode_fn = S.make_decode_fn(model_config(c), m["tokens"])
    np.asarray(decode_fn({"params": params}, prompts(r, WARM[0])))
    del params
    ready = json.loads(trainer.stdout.readline() or "{}")
    if not ready.get("ready"):
        raise RuntimeError("the trainer did not publish the base version")
    template = weights.nest({n: None for n in granite_weights.state_names(c)})
    replica = SnapshotServer(r.client(), decode_fn, template)
    replica.refresh()
    if identify(r, weights.flatten(replica.params)) != 0:
        raise RuntimeError("the replica did not load the base version")
    replica.serve(prompts(r, WARM[1]))
    r.extra.update(trainer=trainer, replica=replica, base_publish_s=ready["seconds"],
                   base_version=ready["version"])


def window(r, deadline: float) -> None:
    """``serve_loop.window`` with this loop's prompts."""
    trainer, replica = r.extra["trainer"], r.extra["replica"]
    publishes: List[dict] = []

    def collect() -> None:
        for line in trainer.stdout:
            publishes.append(json.loads(line))

    r.extra.update(publishes=publishes, t0=time.monotonic())
    trainer.stdin.write(f"GO {r.extra['t0']} {deadline} {r.mix['publish_period_s']}\n".encode())
    trainer.stdin.flush()
    reader = threading.Thread(target=collect, daemon=True)
    reader.start()
    r.extra["collector"] = reader
    loaded = _ref(replica.local)
    k, i = 0, 0
    while time.monotonic() < deadline:
        now = _ref(replica.local)
        if now != loaded:
            with r.span("refresh"):
                replica.refresh()
            loaded = now
            got = identify(r, weights.flatten(replica.params))
            r.ops.append({"op": "refresh", "k": got, "polled": now,
                          "end": time.monotonic()})
            if got is not None:
                r.extra["last_k"] = got
            k = got
        p = prompts(r, i)
        with r.span("serve"):
            out = replica.serve(p)
        r.ops.append({"op": "request", "i": i, "k": k, "prompts": p, "out": out,
                      "end": time.monotonic()})
        i += 1


def check(r) -> None:
    import jax

    reqs = r.done("request")
    period = r.mix["publish_period_s"]
    late = sum(p["end"] > p["due"] + period for p in r.extra["publishes"])
    r.attempted = len(reqs) + len(r.extra["publishes"])
    r.failed = late
    k_of = {p["version"]: p["k"] for p in r.extra["publishes"]}
    k_of[r.extra["base_version"]] = 0
    errors, last = 0, 0
    for o in sorted(r.done("refresh") + reqs, key=lambda o: o["end"]):
        if o["op"] == "refresh" and (o["k"] is None or o["k"] < k_of.get(o["polled"], 0)):
            errors += 1  # the refresh did not reach the version it saw published
        elif o["op"] == "request":
            if o["k"] is None or o["k"] < last:
                errors += 1
            else:
                last = o["k"]

    base = weights.flatten({"params": jax.device_get(
        granite_weights.make_params(r.config, r.seed, r.config["torch_dtype"]))})
    served = weights.flatten(r.extra.pop("replica").params)
    r.extra["last_k"] = 0
    now = identify(r, served)  # the version the replica holds at the end
    errors += now is None
    want = version_flat(r, base, last if now is None else now)
    r.check("version_errors", errors, 0)
    bits = lambda a: a.view(f"u{a.dtype.itemsize}")  # noqa: E731
    r.check("param_mismatch", sum(
        int(np.count_nonzero(bits(served[n]) != bits(want[n]))) for n in want), 0)
    del served, want

    # a seeded sample: the first request of every version served, and more
    good = [j for j, o in enumerate(reqs) if o["k"] is not None]
    first = sorted({reqs[j]["k"]: j for j in reversed(good)}.values())
    rest = [j for j in good if j not in first]
    more = max(0, r.mix["sample_requests"] - len(first))
    rng = np.random.default_rng([r.seed, 3])
    pick = [reqs[j] for j in sorted(first + [
        rest[x] for x in rng.choice(len(rest), min(more, len(rest)), replace=False)])]
    gap = 0.0
    bad_shape = 0
    n_prompt, n_new = r.mix["prompt_tokens"], r.mix["tokens"]
    for k in sorted({o["k"] for o in pick}):
        p = jax.device_put(weights.nest(version_flat(r, base, k))["params"])
        for o in (o for o in pick if o["k"] == k):
            out = np.asarray(o["out"])
            if out.shape != (r.mix["batch"], n_prompt + n_new) or \
                    not np.array_equal(out[:, :n_prompt], o["prompts"]):
                bad_shape += 1
                continue
            gap = max(gap, granite_reference.widest_gap(
                r.config, p, out, n_prompt, CONTROL_CAST if r.control else None))
        del p
    r.check("malformed_outputs", bad_shape, 0)
    r.check("logit_gap", gap, LOGIT_GAP_LIMIT)
    if not pick:
        r.check("no_requests", 1, 0)
    r.extra["sampled_tokens"] = len(pick) * r.mix["batch"] * n_new
