"""The ``serve`` loop: a snapshot replica serving while a trainer publishes.

Mix parameters: ``batch`` and ``tokens`` (greedy tokens decoded after each
one-token prompt), ``publish_period_s`` (the trainer publishes a new version
at each multiple of it after the window opens), ``dirty_frac`` and
``delta`` (each version adds ``delta`` to one element in each of that share
of the state's blocks, drawn from the seed), ``sample_requests``.

The replica is this process, the one that holds the chip: a
``SnapshotServer`` over its own client, with the program's
``make_decode_fn``. Closed loop: before each request it refreshes if the
served state's pointer moved. The trainer is a process of its own
(``trainer.py``) that gets the seeded weights over a pipe and publishes
them in the configuration's published type (``torch_dtype``), as a weight
sync to a replica ships them.

Checked against references once the window has closed:
  * ``version_errors``: refreshes that did not reach the version whose
    pointer they saw, and requests served from a state that is no version
    the trainer published or an older one than the request before (every
    element any version changes is read after each refresh);
  * ``param_mismatch``: elements of the last version served that differ
    from that version rebuilt from the seed;
  * ``logit_gap``: the widest gap by which a served token's logit lies
    below the plain reference's best, over a seeded sample of requests
    (one per version served first); under ``control``, the gap of the
    token that the reference in float8 puts first;
  * ``no_requests``: there only when the window completed no request, so
    that nothing could be compared.
"""
from __future__ import annotations

import json
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from harness import perturb, procs, reference, weights

#: widest logit gap of a sound run (the limit's derivation is in PERF.md)
LOGIT_GAP_LIMIT = 0.1
CONTROL_CAST = "float8_e4m3fn"


def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    d = weights.dims(c)
    return ModelConfig(
        name=c["name"], family="dense", num_layers=d["L"], d_model=d["D"],
        num_heads=d["heads"], num_kv_heads=d["kv_heads"], head_dim=d["hd"],
        d_ff=d["F"], vocab_size=d["V"], qkv_bias=bool(c.get("qkv_bias")),
        tie_embeddings=bool(c.get("tie_word_embeddings")),
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        source=c["source"])


#: request numbers of the two warm-up requests, out of the window's range
WARM = (10 ** 9, 10 ** 9 + 1)


def prompts(r, i: int) -> np.ndarray:
    """The ``i``-th request's one-token prompts."""
    rng = np.random.default_rng([r.seed, 1, i])
    return rng.integers(0, r.config["vocab_size"], (r.mix["batch"], 1), dtype=np.int32)


def _schedule(r, shapes: Dict[str, tuple], itemsize: int) -> List[list]:
    kmax = int(r.seconds // r.mix["publish_period_s"]) + 1
    return [perturb.draw(shapes, r.config["block_bytes"], r.mix["dirty_frac"],
                         np.random.default_rng([r.seed, 2, k]), itemsize)
            for k in range(1, kmax + 1)]


def _expected(flat: Dict[str, np.ndarray], schedule: List[list], delta: float):
    """Per version ``k`` (0 = the base), the value of every element that
    any version changes."""
    elems = sorted({tuple(e) for ch in schedule for e in ch})
    cur = {e: flat[e[0]].reshape(-1)[e[1]] for e in elems}
    out = [dict(cur)]
    for ch in schedule:
        for e in ch:
            cur[tuple(e)] = perturb.bump(cur[tuple(e)], delta)
        out.append(dict(cur))
    return elems, out


def identify(r, flat: Dict[str, np.ndarray]) -> Optional[int]:
    """The version whose changed elements ``flat`` holds, or None."""
    elems, want = r.extra["elems"], r.extra["expect"]
    got = {e: flat[e[0]].reshape(-1)[e[1]] for e in elems}
    for k in range(r.extra["last_k"], len(want)):
        if all(got[e] == want[k][e] for e in elems):
            return k
    return None


def version_flat(r, base: Dict[str, np.ndarray], k: int) -> Dict[str, np.ndarray]:
    """Version ``k`` of the state, rebuilt from ``base`` (version 0, from
    the seed) on the host, with the trainer's arithmetic."""
    flat = dict(base)
    for ch in r.extra["schedule"][:k]:
        for name in {n for n, _ in ch}:
            flat[name] = flat[name].copy()
        for name, i in ch:
            a = flat[name].reshape(-1)
            a[i] = perturb.bump(a[i], r.mix["delta"])
    return flat


def _ref(local) -> Optional[str]:
    from repro.core.runtime import runtime_for
    from repro.core.tensorstate import TensorStore
    from repro.launch.serve import NAME, ROOT

    out: Dict[str, Optional[str]] = {}

    def read(fs) -> None:
        store = TensorStore(fs, prefix=ROOT)
        out["ref"] = store.read_meta(NAME).get("ref") if store.exists(NAME) else None

    runtime_for(local).invoke(read, read_only=True)
    return out["ref"]


# ---------------------------------------------------------------------- #
def setup(r) -> None:
    import jax

    from repro.launch import serve as S
    from repro.serving.engine import SnapshotServer

    c, m = r.config, r.mix
    params = weights.make_params(c, r.seed, c["torch_dtype"])
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
    template = weights.nest({n: None for n in weights.state_names(c)})
    replica = SnapshotServer(r.client(), decode_fn, template)
    replica.refresh()
    if identify(r, weights.flatten(replica.params)) != 0:
        raise RuntimeError("the replica did not load the base version")
    replica.serve(prompts(r, WARM[1]))
    r.extra.update(trainer=trainer, replica=replica, base_publish_s=ready["seconds"],
                   base_version=ready["version"])


def window(r, deadline: float) -> None:
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


def measure(r) -> None:
    trainer = r.extra["trainer"]
    trainer.stdin.close()
    trainer.wait(timeout=300)
    r.extra["collector"].join(timeout=60)
    reqs = r.done("request")
    r.e2e["serve_tok_s"] = len(reqs) * r.mix["batch"] * r.mix["tokens"] / r.seconds


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
        weights.make_params(r.config, r.seed, r.config["torch_dtype"]))})
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
    for k in sorted({o["k"] for o in pick}):
        p = jax.device_put(weights.nest(version_flat(r, base, k))["params"])
        for o in (o for o in pick if o["k"] == k):
            out = np.asarray(o["out"])
            if out.shape != (r.mix["batch"], r.mix["tokens"] + 1) or \
                    not np.array_equal(out[:, :1], o["prompts"]):
                bad_shape += 1
                continue
            gap = max(gap, reference.widest_gap(
                r.config, p, out, CONTROL_CAST if r.control else None))
        del p
    r.check("malformed_outputs", bad_shape, 0)
    r.check("logit_gap", gap, LOGIT_GAP_LIMIT)
    if not pick:
        r.check("no_requests", 1, 0)
    r.extra["sampled_tokens"] = len(pick) * r.mix["batch"] * r.mix["tokens"]
