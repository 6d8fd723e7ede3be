"""Serving launcher: batched greedy decode from FaaSFS parameter snapshots.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --tokens 16
  PYTHONPATH=src python -m repro.launch.serve --arch granite-4.0-h-micro --prompt 12

The CLI serves the arch's ``reduced_config`` from an in-process backend;
``serve`` takes any ``ModelConfig`` and any ``LocalServer``, and
``publish`` / ``make_decode_fn`` are the pieces ``chip_smoke.py`` drives
at full width over a real server.
"""
from __future__ import annotations

import argparse
import functools
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
    ``tokens`` tokens after each prompt; returns the ``(batch, prompt +
    tokens)`` token ids on the host.

    A one-token prompt is decoded from an empty cache. A longer one is
    prefilled first, in one call (``replica.prefill``), into a cache of
    the decode layout: K/V of the attention layers and the conv and SSM
    state of the mamba layers, each kind for its own layers only; its last
    position's logits give the first token and the rest are decoded
    through that cache. ``replica.decode`` carries ``{kv_bytes,
    ssm_bytes}``, the two kinds of decode state it runs on, and
    ``host_reads``, the device-to-host reads made inside it.

    The token loop dispatches step ``i + 1`` right after step ``i``, with
    no host read between them: each jitted step (``decode_one``, one
    decode step and its argmax) takes the last step's ids as a device
    array, so the device runs the steps back to back while the host queues
    them. The request's ids come to the host in one read at the end of
    ``replica.decode`` (``host_reads`` 1), so the span still ends when the
    whole answer is on the host. ``replica.prefill`` ends on its own read
    of the first token, so that it holds the prefill's device time.

    The state handed in is a pinned snapshot: its leaves are never written
    in place (arena views are sealed read-only), and a new version is a new
    tree of new leaves. So ``decode_fn`` holds one device copy, that of the
    last tree it served, with that tree's host leaves, and uploads again only
    when a leaf is not the same object as the one held; it drops the held
    copy before it uploads, so the device never holds two served versions.
    ``replica.upload`` spans every call, with ``{"bytes": n}`` put on the
    device: the state's bytes on an upload, 0 on reuse."""

    @jax.jit
    def decode_one(p, cache, tok, idx):
        logits, cache = M.decode_step(cfg, p, cache, tok, idx)
        return jnp.argmax(logits, axis=-1)[:, None], cache

    @functools.partial(jax.jit, static_argnums=2)
    def prefill_prompt(p, prompts, max_len):
        return M.prefill(cfg, p, prompts, max_seq=max_len)

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
        start = prompts.shape[1]
        max_len = start - 1 + tokens + 8
        out = [np.asarray(prompts)]
        if start > 1:
            with obs.span("replica.prefill", "replica", args={"tokens": start}):
                logits, cache = prefill_prompt(p, jnp.asarray(prompts), max_len)
                toks = jnp.argmax(logits, axis=-1)[:, None]
                out.append(np.asarray(toks))
            steps = range(start, start + tokens - 1)
        else:
            cache = None
            toks = jnp.asarray(prompts[:, :1])
            steps = range(tokens)
        decode_args = {"kv_bytes": 0, "ssm_bytes": 0, "host_reads": 0}
        with obs.span("replica.decode", "replica", args=decode_args):
            if cache is None:
                cache = M.make_decode_cache(cfg, prompts.shape[0], max_len)
            for k, a in cache.items():
                decode_args["ssm_bytes" if k in M.KIND_CACHE["mamba"] else "kv_bytes"] += a.nbytes
            ids = []
            for i in steps:
                toks, cache = decode_one(p, cache, toks, np.int32(i))
                ids.append(toks)
            if ids:
                out.append(jax.device_get(jnp.concatenate(ids, axis=1)))
                decode_args["host_reads"] += 1
            return np.concatenate(out, axis=1)

    return decode_fn


def make_prompts(cfg: ModelConfig, batch: int, seed: int, length: int = 1) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, length), dtype=np.int32)


def serve(cfg: ModelConfig, local: LocalServer, *, batch: int = 4,
          tokens: int = 16, seed: int = 0, prompt: int = 1) -> np.ndarray:
    """Publish seeded params for ``cfg`` through ``local``, then serve one
    batch of ``prompt``-token prompts from a snapshot replica on the same
    backend."""
    params = jax.device_get(M.init_params(cfg, jax.random.PRNGKey(seed)))
    publish(local, params)
    srv = SnapshotServer(LocalServer(local.backend),
                         make_decode_fn(cfg, tokens), {"params": params})
    version = srv.refresh()
    t0 = time.perf_counter()
    seqs = srv.serve(make_prompts(cfg, batch, seed, prompt))
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
    ap.add_argument("--prompt", type=int, default=1,
                    help="tokens in each prompt; more than one is prefilled")
    args = ap.parse_args()

    backend = BackendService(block_size=1 << 18, policy=CachePolicy.EAGER)
    seqs = serve(reduced_config(get_config(args.arch)), LocalServer(backend),
                 batch=args.batch, tokens=args.tokens, prompt=args.prompt)
    for row in seqs[:2]:
        print("  ", row.tolist())


if __name__ == "__main__":
    main()
