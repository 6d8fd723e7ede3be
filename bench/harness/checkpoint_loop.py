"""The ``checkpoint`` loop: a trainer that checkpoints, and cold restores.

Mix parameters: ``update`` (``sparse``: add 1 to one element in each of a
``dirty_frac`` share of the state's blocks, drawn from the seed and the
step; ``dense``: negate and scale every element, so every block changes),
``save`` (``delta``: ``CheckpointManager.save`` against the last step, the
block-delta kernel ``detector`` finding the dirty blocks; ``full``: every
block written), ``restore`` (``each``: after every save a cold worker, on a
new connection with an empty LRU, restores that step and puts it on the
device; ``last``: only the last step, after the window).

Closed loop: update on the device, wait for it, then time the pull
(``device_get``) and the durable return of ``save``; then the restore,
timed from the ``restore`` call until the state is on the device.

Checked against references once the window has closed:
  * ``dirty_mismatch``: per delta save, leaves whose detected dirty blocks
    differ from the blocks the update changed, plus blocks written beyond
    or short of them;
  * ``save_incomplete``: full saves that wrote fewer bytes than the state;
  * ``restore_mismatch``: elements of a restored state whose bits differ
    from the state that was saved, compared on the device.
Under ``control`` every save is given the state rounded to bfloat16.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Tuple

import numpy as np

from harness import perturb, weights

ROOT = "/mnt/tsfs/ckpt"
DENSE_SCALE = 1.0 + 2.0 ** -8


@functools.lru_cache(maxsize=None)
def _programs(spec: Tuple[Tuple[str, int], ...]):
    """Jitted update, compare and rounding programs over a state of leaves
    ``spec`` ((name, elements) in name order)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sparse(tree, gidx):
        flat, off = weights.flatten(tree), 0
        for name, size in spec:
            loc = gidx - off
            loc = jnp.where((loc >= 0) & (loc < size), loc, size)
            a = flat[name]
            flat[name] = a.reshape(-1).at[loc].add(1.0, mode="drop").reshape(a.shape)
            off += size
        return weights.nest(flat)

    @jax.jit
    def dense(tree):
        return jax.tree.map(lambda a: -a * DENSE_SCALE, tree)

    @jax.jit
    def differ(x, y):
        bits = lambda a: jax.lax.bitcast_convert_type(a, jnp.uint32)  # noqa: E731
        return sum(jnp.sum(bits(a) != bits(b), dtype=jnp.int32)
                   for a, b in zip(jax.tree.leaves(x), jax.tree.leaves(y)))

    @jax.jit
    def bf16(tree):
        return jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), tree)

    return sparse, dense, differ, bf16


def _spec(r) -> Tuple[Tuple[str, int], ...]:
    """(leaf name in the parameter tree, elements), in name order."""
    return tuple((n, int(np.prod(s))) for n, s, _ in weights.layout(r.config))


def _draw(r, step: int) -> List[Tuple[str, int]]:
    shapes = {"params/" + n: s for n, s, _ in weights.layout(r.config)}
    return perturb.draw(shapes, r.config["block_bytes"], r.mix["dirty_frac"],
                        np.random.default_rng([r.seed, 4, step]))


def _global(r, elements: List[Tuple[str, int]]) -> np.ndarray:
    offsets, off = {}, 0
    for name, size in _spec(r):
        offsets["params/" + name] = off
        off += size
    return np.array([offsets[n] + i for n, i in elements], np.int32)


# ---------------------------------------------------------------------- #
def setup(r) -> None:
    import jax

    from repro.state.checkpoint import CheckpointManager, dirty_block_indices

    c, m = r.config, r.mix
    if sum(size for _, size in _spec(r)) >= 2 ** 31:
        raise ValueError("the sparse update indexes elements with int32")
    sparse, dense, differ, bf16 = _programs(_spec(r))
    state = weights.make_params(c, r.seed, c["master_dtype"])
    cm = CheckpointManager(r.client(), root=ROOT, block_bytes=c["block_bytes"],
                           dirty_impl=m.get("detector", "auto"))
    if m["save"] == "delta":
        host = jax.device_get(state)
        cm.save(0, {"params": host})
        for a in weights.flatten(host).values():
            dirty_block_indices(a, a, c["block_bytes"], cm.dirty_impl)
        del host
    if m["update"] == "sparse":
        jax.block_until_ready(sparse(state, _global(r, _draw(r, 0)) * 0 - 1))
    else:
        jax.block_until_ready(dense(state))
    jax.block_until_ready(differ(state, state))
    if r.control:
        jax.block_until_ready(bf16(state))
    r.extra.update(state=state, cm=cm, step=1,
                   state_bytes=sum(4 * size for _, size in _spec(r)),
                   template=weights.nest({n: None for n in weights.state_names(c)}))


def _restore(r, step: int, state):
    """A cold worker restores ``step`` and puts it on the device; returns
    the op record and the count of elements that differ, on the device."""
    import jax

    from repro.state.checkpoint import CheckpointManager

    *_, differ, _ = _programs(_spec(r))
    worker = r.client()
    cm = CheckpointManager(worker, root=ROOT, block_bytes=r.config["block_bytes"])
    t0 = time.perf_counter()
    with r.span("restore"):
        restored, got = cm.restore(r.extra["template"], step)
        with r.span("put"):
            t1 = time.perf_counter()
            dev = jax.block_until_ready(jax.device_put(restored))
            t2 = time.perf_counter()
    op = {"op": "restore", "step": got, "s": t2 - t0, "put_s": t2 - t1,
          "bytes": r.extra["state_bytes"],
          "rpcs": worker.backend.connection_stats()["rpcs"]}
    worker.backend.close()
    return op, differ(dev["params"], state)


def window(r, deadline: float) -> None:
    import jax

    m = r.mix
    sparse, dense, _, bf16 = _programs(_spec(r))
    cm, step = r.extra["cm"], r.extra["step"]
    while time.monotonic() < deadline:
        drawn = _draw(r, step) if m["update"] == "sparse" else None
        if drawn is not None:
            state = sparse(r.extra["state"], _global(r, drawn))
        else:
            state = dense(r.extra["state"])
        r.extra["state"] = jax.block_until_ready(state)
        c0 = r.counters()
        t0 = time.perf_counter()
        with r.span("save"):
            with r.span("pull"):
                host = jax.device_get(bf16(state) if r.control else state)
                t1 = time.perf_counter()
            info = cm.save(step, {"params": host}, delta_from_last=m["save"] == "delta")
        t2 = time.perf_counter()
        r.ops.append({"op": "save", "step": step,
                      "kind": "delta" if info.dirty is not None else "full",
                      "s": t2 - t0, "pull_s": t1 - t0, "bytes": info.bytes_total,
                      "bytes_written": info.bytes_written,
                      "blocks_written": info.blocks_written, "dirty": info.dirty,
                      "want": None if drawn is None else
                      perturb.blocks_of(drawn, r.config["block_bytes"]),
                      "counters": r.counters_since(c0), "state": state,
                      "end": time.monotonic()})
        del host
        for old in [o for o in r.ops if o["op"] == "save"][:-2]:
            old.pop("state", None)  # the device keeps the last two saved states
        if m["restore"] == "each":
            op, diff = _restore(r, step, state)
            r.ops.append(dict(op, diff=diff, end=time.monotonic()))
        step += 1


def measure(r) -> None:
    deltas = [o for o in r.done("save") if o["kind"] == "delta"]
    fulls = [o for o in r.done("save") if o["kind"] == "full"]
    restores = r.done("restore")
    if deltas:
        r.e2e["ckpt_delta_s"] = sum(o["s"] for o in deltas) / len(deltas)
    if fulls:
        r.e2e["ckpt_save_MBps"] = sum(o["bytes"] for o in fulls) / sum(
            o["s"] for o in fulls) / 1e6
    if restores:
        r.e2e["restore_MBps"] = sum(o["bytes"] for o in restores) / sum(
            o["s"] for o in restores) / 1e6


def check(r) -> None:
    saves = r.done("save")
    restores = r.done("restore")
    r.attempted = len(saves) + len(restores)
    mismatch = 0
    for o in saves:
        if o["kind"] != "delta" or o["want"] is None:
            continue
        got: Dict[str, List[int]] = o["dirty"]
        names = set(got) | set(o["want"])
        mismatch += sum(sorted(got.get(n, [])) != o["want"].get(n, []) for n in names)
        mismatch += abs(o["blocks_written"] - sum(len(b) for b in o["want"].values()))
    r.check("dirty_mismatch", mismatch, 0)
    r.check("save_incomplete", sum(
        o["kind"] == "full" and o["bytes_written"] != o["bytes"] for o in saves), 0)
    diffs = [int(o["diff"]) for o in restores]
    if r.mix["restore"] == "last" and saves:
        _, diff = _restore(r, saves[-1]["step"], saves[-1]["state"])
        diffs.append(int(diff))
    r.check("restore_mismatch", sum(diffs), 0)
    if not saves:
        r.check("no_saves", 1, 0)
    for o in r.ops:
        o.pop("state", None)
