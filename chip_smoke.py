#!/usr/bin/env python3
"""Chip smoke test: the FaaSFS model-state path, once, on one TPU.

    python chip_smoke.py [--seed N]

Runs qwen2-1.5b at its published widths (f32 params from
``init_params``, seeded) through the entry points a user calls:

  0. a real ``python -m repro.core.server`` process (2 shards, WAL in a
     temporary directory) is started BEFORE this process imports JAX —
     the server is plain Python, so this process alone holds the chip;
     every client reaches it over ``RemoteBackend`` -> ``LocalServer``
     -> ``FunctionRuntime``;
  1. publish   the params saved as a new version of the served state
               (``save_tree``, the trainer's commit);
  2. serve     a ``SnapshotServer`` replica refreshes and serves 3 batches
               of greedy decode; tokens must equal a decode of the
               in-memory params;
  3. checkpoint  ``CheckpointManager(dirty_impl="pallas")`` full save, a
               seeded ~1% of 256 KiB blocks perturbed, delta save; the
               kernel's dirty set must equal the exact numpy set in every
               leaf and the blocks written the perturbed count;
  4. refresh   the trainer commits the perturbed params as a delta
               version, the replica refreshes and serves again; tokens
               must equal a decode of the perturbed in-memory params;
  5. restore   zero-copy ``CheckpointManager.restore``, digest-equal to
               the saved state.

Each phase prints one line with its host-clock seconds (work ends on the
host, after ``block_until_ready`` where the device is involved) and what
it checked. Any failed check exits non-zero; so does a run where JAX finds
no TPU, and a copy of this script without the repo's ``src/``. The last
line on success is ``{"ok": true, "device": {...}}``.

The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache`` next to this script.
"""
from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import os
import resource
import subprocess
import sys
import tempfile
import time
import traceback
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional, Tuple

REPO = Path(__file__).resolve().parent
SRC = REPO / "src"

ARCH = "qwen2-1.5b"
BLOCK_BYTES = 256 * 1024      # server block size == checkpoint block size
CACHE_BLOCKS = 256            # per-client LRU cap: 64 MiB at 256 KiB blocks
PERTURB_LEAVES = 4            # the smallest leaves holding a full block
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
BATCH, TOKENS, REQUESTS = 4, 16, 3


class SmokeFailure(Exception):
    """A phase's result disagreed with its reference."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(line: str) -> None:
    print(line, flush=True)


def start_server(wal_dir: str, *, shards: int = 2,
                 block_bytes: int = BLOCK_BYTES) -> Tuple[subprocess.Popen, int]:
    """Start a FaaSFS server process and return it with its port. WAL
    compaction is off: its checkpoint packs the whole store into one
    record, which cannot hold a multi-GiB state."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(SRC), env.get("PYTHONPATH", "")) if p)
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.core.server", "--wal", wal_dir,
         "--shards", str(shards), "--block-size", str(block_bytes),
         "--checkpoint-bytes", "0", "--checkpoint-records", "0",
         "--log-level", "warn"],
        stdout=subprocess.PIPE, text=True, env=env, cwd=str(REPO),
    )
    line = proc.stdout.readline()
    if not line.startswith("LISTENING"):
        proc.kill()
        proc.wait()
        raise RuntimeError(f"server did not start: {line!r}")
    return proc, int(line.split()[1])


def stop_server(proc: subprocess.Popen) -> int:
    """SIGTERM (drain + WAL flush), then the server's peak RSS in MiB."""
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    proc.stdout.close()
    return peak_rss_mib(resource.RUSAGE_CHILDREN)


def peak_rss_mib(who: int = resource.RUSAGE_SELF) -> int:
    return resource.getrusage(who).ru_maxrss // 1024


@contextlib.contextmanager
def phase(name: str) -> Iterator[Dict[str, Any]]:
    info: Dict[str, Any] = {}
    t0 = time.perf_counter()
    yield info
    dt = time.perf_counter() - t0
    info["peak_rss_mib"] = peak_rss_mib()
    log(f"phase={name} ok seconds={dt:.3f} "
        + " ".join(f"{k}={v}" for k, v in info.items()))


def digest(tree: Any) -> str:
    from repro.core.tensorstate import flatten_with_names

    h = hashlib.blake2b(digest_size=16)
    for name, a in flatten_with_names(tree):
        h.update(f"{name}:{a.dtype}:{a.shape}".encode())
        h.update(memoryview(a.reshape(-1).view("u1")))
    return h.hexdigest()


def perturb(params: Any, block_bytes: int, frac: float,
            seed: int) -> Tuple[Any, Dict[str, List[int]]]:
    """A seeded ~``frac`` of all ``block_bytes`` blocks of the params,
    drawn from the ``PERTURB_LEAVES`` smallest float32 leaves holding a
    full block; each drawn block gets +0.5 on one element. Returns the
    new params (untouched leaves shared) and ``{leaf: blocks}``."""
    import numpy as np

    from repro.core.tensorstate import flatten_with_names, unflatten_like

    flat = dict(flatten_with_names(params))
    total = sum(-(-a.nbytes // block_bytes) for a in flat.values())
    pool = sorted((a.nbytes, n) for n, a in flat.items()
                  if a.dtype == np.float32 and a.nbytes >= block_bytes)
    names = [n for _, n in pool[:PERTURB_LEAVES]]
    slots = [(n, b) for n in names
             for b in range(-(-flat[n].nbytes // block_bytes))]
    rng = np.random.default_rng(seed)
    k = min(len(slots), max(1, round(frac * total)))
    touched: Dict[str, List[int]] = {}
    for i in sorted(rng.choice(len(slots), size=k, replace=False)):
        n, b = slots[i]
        touched.setdefault(n, []).append(b)
    per_block = block_bytes // 4
    for n, blocks in touched.items():
        a = flat[n].copy()
        v = a.reshape(-1)
        for b in blocks:
            lo = b * per_block
            v[lo + int(rng.integers(min(per_block, v.size - lo)))] += 0.5
        flat[n] = a
    return unflatten_like(params, flat), touched


def run_phases(cfg, port: int, *, impl: str = "pallas", seed: int = 0,
               block_bytes: int = BLOCK_BYTES, dirty_frac: float = 0.01,
               batch: int = BATCH, tokens: int = TOKENS,
               requests: int = REQUESTS) -> Dict[str, Any]:
    """Phases 1-5 against the server on ``port``; raises ``SmokeFailure``
    on any mismatch with a reference. Returns a summary."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.client import LocalServer
    from repro.core.remote import RemoteBackend
    from repro.core.tensorstate import changed_blocks, flatten_with_names
    from repro.kernels.block_delta.ops import compute_block_delta
    from repro.launch import serve
    from repro.models import model as M
    from repro.serving.engine import SnapshotServer
    from repro.state.checkpoint import CheckpointManager

    conns: List[RemoteBackend] = []

    def client() -> LocalServer:
        rb = RemoteBackend("127.0.0.1", port)
        conns.append(rb)
        return LocalServer(rb, max_blocks=CACHE_BLOCKS)

    try:
        t0 = time.perf_counter()
        params_dev = M.init_params(cfg, jax.random.PRNGKey(seed))
        host = jax.device_get(params_dev)
        for a in jax.tree.leaves(host):
            a.setflags(write=False)  # an immutable host snapshot
        state = {"params": host}
        state_bytes = sum(a.nbytes for _, a in flatten_with_names(state))
        decode_fn = serve.make_decode_fn(cfg, tokens)
        prompts = [serve.make_prompts(cfg, batch, seed + r)
                   for r in range(requests)]
        want = [decode_fn({"params": params_dev}, p) for p in prompts]
        del params_dev
        log(f"setup seconds={time.perf_counter() - t0:.3f} arch={cfg.name} "
            f"params={state_bytes} leaves={len(flatten_with_names(state))} "
            f"reference_decodes={requests}")

        with phase("publish") as info:
            trainer = client()
            st = serve.publish(trainer, host)
            check(st["bytes_written"] == state_bytes,
                  f"publish wrote {st['bytes_written']} of {state_bytes}")
            info.update(bytes=st["bytes_written"], txns=st["txns"])

        with phase("serve") as info:
            replica = SnapshotServer(client(), decode_fn, state)
            v1 = replica.refresh()
            got = [replica.serve(p) for p in prompts]
            check(all(np.array_equal(g, w) for g, w in zip(got, want)),
                  "served tokens differ from the in-memory decode")
            info.update(version=v1, requests=requests,
                        tokens=f"{batch}x{tokens}", tokens_equal=True)

        with phase("checkpoint") as info:
            cm = CheckpointManager(client(), root="/mnt/tsfs/ckpt",
                                   block_bytes=block_bytes, dirty_impl=impl)
            full = cm.save(0, state)
            check(full.bytes_written == state_bytes, "full save incomplete")
            new_params, touched = perturb(host, block_bytes, dirty_frac, seed)
            new_state = {"params": new_params}
            delta = cm.save(1, new_state)
            n_dirty = sum(len(b) for b in touched.values())
            old_flat = dict(flatten_with_names(state))
            kernel_leaves = 0
            for name, arr in flatten_with_names(new_state):
                exact = changed_blocks(arr, old_flat[name], block_bytes)
                check(delta.dirty.get(name) == exact,
                      f"{name}: detector {delta.dirty.get(name)} != {exact}")
                check(exact == touched.get(name.split("/", 1)[1], []),
                      f"{name}: dirty {exact} != perturbed")
                kernel_leaves += arr.dtype == np.float32 \
                    and arr.size >= block_bytes // 4
            check(delta.blocks_written == n_dirty,
                  f"delta wrote {delta.blocks_written} blocks, "
                  f"{n_dirty} perturbed")
            x = jax.ShapeDtypeStruct((1, block_bytes // 4), jnp.float32)
            hlo = compute_block_delta.lower(x, x, impl=impl).compile().as_text()
            custom = "tpu_custom_call" in hlo
            check(custom or impl != "pallas",
                  "the pallas detector did not compile to a TPU kernel")
            info.update(full_bytes=full.bytes_written, full_txns=full.txns,
                        full_s=f"{full.wall_s:.3f}", delta_s=f"{delta.wall_s:.3f}",
                        detector=impl, tpu_custom_call=custom,
                        kernel_leaves=kernel_leaves, dirty_blocks=n_dirty,
                        delta_bytes=delta.bytes_written, dirty_sets_equal=True)

        params_dev = jax.tree.map(jnp.asarray, new_params)
        want = [decode_fn({"params": params_dev}, p) for p in prompts]
        del params_dev
        with phase("refresh") as info:
            st = serve.publish(trainer, new_params, baseline=old_flat)
            check(st["blocks_written"] == n_dirty and st["base"] is not None,
                  f"trainer commit wrote {st['blocks_written']} blocks, "
                  f"chained to {st['base']}")
            v2 = replica.refresh()
            check(v2 > v1, f"refresh stayed at version {v1}")
            got = [replica.serve(p) for p in prompts]
            check(all(np.array_equal(g, w) for g, w in zip(got, want)),
                  "refreshed tokens differ from the perturbed decode")
            info.update(version=v2, commit_blocks=st["blocks_written"],
                        commit_txns=st["txns"], tokens_equal=True)
        del replica, got

        with phase("restore") as info:
            restored, step = cm.restore(state)
            check(step == 1, f"restored step {step}")
            leaves = flatten_with_names(restored)
            check(not any(a.flags.writeable for _, a in leaves),
                  "restore did not return arena views")
            check(digest(restored) == digest(new_state),
                  "restored state differs from the saved state")
            info.update(step=step, zero_copy=True, digest_equal=True)
        return {"params_bytes": state_bytes, "dirty_blocks": n_dirty}
    finally:
        for rb in conns:
            rb.close()


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro" / "core" / "server.py").is_file():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="faasfs-smoke-") as tmp:
        proc, port = start_server(os.path.join(tmp, "wal"))
        try:
            import jax

            devs = jax.devices()
            dev = devs[0]
            if dev.platform != "tpu":
                print(f"chip_smoke: no TPU (platform={dev.platform})",
                      file=sys.stderr)
                return 1
            if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
                jax.config.update("jax_compilation_cache_dir",
                                  str(REPO / ".jax_cache"))
            compiles: List[float] = []
            jax.monitoring.register_event_duration_secs_listener(
                lambda ev, secs, **kw: compiles.append(secs)
                if ev == COMPILE_EVENT else None)
            log(f"device platform={dev.platform} kind={dev.device_kind} "
                f"count={len(devs)}")
            from repro.configs.base import get_config

            run_phases(get_config(ARCH), port, impl="pallas", seed=args.seed)
            log(f"compile seconds={sum(compiles):.3f} programs={len(compiles)}")
        except Exception:
            traceback.print_exc()
            return 1
        finally:
            server_rss = stop_server(proc)
    log(f"peak_rss_mib parent={peak_rss_mib()} server={server_rss} "
        f"total_seconds={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
