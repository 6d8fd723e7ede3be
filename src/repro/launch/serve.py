"""Serving launcher: batched greedy decode from FaaSFS parameter snapshots.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --tokens 16

The CLI serves the arch's ``reduced_config`` from an in-process backend;
``serve`` takes any ``ModelConfig`` and any ``LocalServer``, and
``publish`` / ``make_decode_fn`` are the pieces ``chip_smoke.py`` drives
at full width over a real server.
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, get_config, list_configs, reduced_config
from repro.core import obs
from repro.core.backend import BackendService
from repro.core.client import LocalServer
from repro.core.runtime import runtime_for
from repro.core.tensorstate import save_tree
from repro.core.types import CachePolicy
from repro.models import model as M
from repro.serving.engine import SnapshotServer

ROOT, NAME = "/mnt/tsfs/train", "state"


def publish(local: LocalServer, params: Any, *,
            baseline: Optional[Dict[str, np.ndarray]] = None) -> Dict[str, int]:
    """Commit ``{"params": params}`` as a new version of the served state
    (``save_tree``): a replica refreshing meanwhile loads the old version
    or the new one whole. ``baseline`` (the flat leaves of the version the
    served state holds, i.e. the last publish when this caller is the only
    publisher) writes only the changed blocks, compared at the backend's
    block size."""
    return save_tree(runtime_for(local), ROOT, NAME, {"params": params},
                     baseline=baseline, block_bytes=local.backend.block_size)


def make_decode_fn(cfg: ModelConfig, tokens: int) -> Callable[[Any, np.ndarray], np.ndarray]:
    """``decode_fn(state, prompts)`` for ``SnapshotServer``: greedy-decode
    ``tokens`` tokens after each prompt's first token; returns the
    ``(batch, tokens + 1)`` token ids on the host.

    The state handed in is a pinned snapshot: its leaves are never written
    in place (arena views are sealed read-only), and a new version is a new
    tree of new leaves. So ``decode_fn`` holds one device copy, that of the
    last tree it served, with that tree's host leaves, and uploads again only
    when a leaf is not the same object as the one held; it drops the held
    copy before it uploads, so the device never holds two served versions.
    ``replica.upload`` spans every call, with ``{"bytes": n}`` put on the
    device: the state's bytes on an upload, 0 on reuse."""
    max_len = tokens + 8

    @jax.jit
    def decode_one(p, cache, tok, idx):
        return M.decode_step(cfg, p, cache, tok, idx)

    # (host leaves, structure, device copy) of the last tree served; the
    # leaves are held so that no other object can take their ids
    held = None

    def decode_fn(state, prompts):
        nonlocal held
        leaves, treedef = jax.tree.flatten(state["params"])
        args = {"bytes": 0}
        with obs.span("replica.upload", "replica", args=args) as up:
            if held is None or held[1] != treedef or any(
                    a is not b for a, b in zip(held[0], leaves)):
                held = None  # free the old copy before the new one lands
                held = (leaves, treedef,
                        jax.tree.unflatten(treedef, [jnp.asarray(a) for a in leaves]))
                args["bytes"] = sum(a.nbytes for a in leaves)
            p = held[2]
            if up.trace_id:
                # while the span records, end it when the weights are on the
                # device; the first decode step waits on these same arrays
                # anyway, and untraced the host goes on to dispatch it
                p = jax.block_until_ready(p)
        with obs.span("replica.decode", "replica"):
            cache = M.make_decode_cache(cfg, prompts.shape[0], max_len)
            toks = jnp.asarray(prompts[:, :1])
            out = [np.asarray(toks)]
            for i in range(tokens):
                logits, cache = decode_one(p, cache, toks, jnp.int32(i))
                toks = jnp.argmax(logits, axis=-1)[:, None]
                out.append(np.asarray(toks))
            return np.concatenate(out, axis=1)

    return decode_fn


def make_prompts(cfg: ModelConfig, batch: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, 1), dtype=np.int32)


def serve(cfg: ModelConfig, local: LocalServer, *, batch: int = 4,
          tokens: int = 16, seed: int = 0) -> np.ndarray:
    """Publish seeded params for ``cfg`` through ``local``, then serve one
    batch from a snapshot replica on the same backend."""
    params = jax.device_get(M.init_params(cfg, jax.random.PRNGKey(seed)))
    publish(local, params)
    srv = SnapshotServer(LocalServer(local.backend),
                         make_decode_fn(cfg, tokens), {"params": params})
    version = srv.refresh()
    t0 = time.perf_counter()
    seqs = srv.serve(make_prompts(cfg, batch, seed))
    dt = time.perf_counter() - t0
    dev = jax.devices()[0]
    print(f"arch={cfg.name} snapshot v{version}: decoded "
          f"{batch}x{tokens} tokens in {dt:.2f}s "
          f"({batch * tokens / dt:.0f} tok/s on {dev.platform} "
          f"{dev.device_kind} x{len(jax.devices())})")
    return seqs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_configs())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()

    backend = BackendService(block_size=1 << 18, policy=CachePolicy.EAGER)
    seqs = serve(reduced_config(get_config(args.arch)), LocalServer(backend),
                 batch=args.batch, tokens=args.tokens)
    for row in seqs[:2]:
        print("  ", row.tolist())


if __name__ == "__main__":
    main()
