"""Ours (beyond-paper): delta checkpointing + block_delta compression.

Quantifies the paper's block-granular cache-update mechanism applied to ML
state, ON THE REAL STACK: a ``BackendServer`` on a localhost socket with a
segmented WAL, driven through ``RemoteBackend`` — so every byte shipped is
a byte that actually crossed a socket and landed in the durable log.

Three sections:

  * **client delta saves** — bytes shipped per ``CheckpointManager.save``
    as a function of the fraction of parameters that changed, vs the
    NFS-style whole-state reload. The 1%-dirty ratio is an absolute gate
    (``delta_ckpt_dirty1pct_ratio`` <= 0.05 in ``check_regression.py``):
    checkpoint cost must scale with the write rate, not the state size.
  * **WAL delta checkpoints** — a full ``run_checkpoint`` cycle vs the
    delta cycle that follows a small dirty write. The delta serializes
    only chains dirtied past the base's version floor, so its on-disk
    bytes are gated the same way (``delta_ckpt_wal_delta_ratio``).
  * **block_delta kernel** — int8-quantized dirty blocks (lossy wire
    compression on top of exact block granularity).
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time
from typing import List

import numpy as np

from repro.core.backend import BackendService
from repro.core.client import LocalServer
from repro.core.posix import O_CREAT
from repro.core.remote import RemoteBackend
from repro.core.runtime import runtime_for
from repro.core.server import BackendServer
from repro.state.checkpoint import CheckpointManager

PARAMS = 1 << 20     # 4 MiB model for the harness (exact block multiple)
BLOCK_ELEMS = 4096   # 16 KiB blocks
FRACS = (0.01, 0.1, 0.5, 1.0)
RUN_KERNEL = True


def _dirty(base: np.ndarray, frac: float, rng) -> np.ndarray:
    """Contiguous slab update: the realistic ML sparsity pattern (an
    updated expert / embedding rows / one layer), block-aligned by
    nature. (A uniformly-scattered 1% change dirties EVERY 16KiB block —
    block granularity only pays when updates have spatial locality,
    which is exactly the MoE/embedding case; see EXPERIMENTS.md.)"""
    new = base.copy()
    n_changed = int(len(base) * frac)
    start = int(rng.integers(0, len(base) - n_changed + 1))
    new[start : start + n_changed] += (
        rng.normal(size=n_changed).astype(np.float32) * 0.01
    )
    return new


def run() -> List[str]:
    rows: List[str] = []
    rng = np.random.default_rng(0)
    base = rng.normal(size=(PARAMS,)).astype(np.float32)
    block_bytes = BLOCK_ELEMS * 4
    full_bytes = PARAMS * 4

    tmp = tempfile.mkdtemp(prefix="bench-delta-ckpt-")
    server = BackendServer(
        BackendService(block_size=block_bytes),
        wal_path=os.path.join(tmp, "wal"),
        checkpoint_bytes=0, checkpoint_records=0,  # cycles run by hand
    ).start()
    rb = RemoteBackend("127.0.0.1", server.port)
    try:
        # -- client delta saves over the socket ------------------------ #
        for frac in FRACS:
            new = _dirty(base, frac, rng)
            cm = CheckpointManager(
                LocalServer(rb),
                root=f"/mnt/tsfs/ckpt{frac}",
                block_bytes=block_bytes,
            )
            cm.save(0, {"w": base})
            t0 = time.perf_counter()
            info = cm.save(1, {"w": new})
            save_ms = (time.perf_counter() - t0) * 1e3
            rows.append(
                f"delta_ckpt_frac{frac},{info.bytes_written},bytes "
                f"vs_full={full_bytes} "
                f"ratio={info.bytes_written / full_bytes:.3f} "
                f"save_ms={save_ms:.1f}"
            )
            if frac == 0.01:
                rows.append(
                    f"delta_ckpt_dirty1pct_ratio,"
                    f"{info.bytes_written / full_bytes:.4f},ratio "
                    f"gate: 1% dirty ships <=5% of full-state bytes"
                )

        # -- WAL checkpoint cycles: full, then delta ------------------- #
        s_full = server.run_checkpoint(full=True)
        # write one block, then cycle again: the delta must serialize
        # only the chains past the base's version floor
        def touch(fs):
            fd = fs.open("/mnt/tsfs/ckpt0.01/touched", O_CREAT)
            fs.pwrite(fd, b"\x42" * block_bytes, 0)
            fs.close(fd)

        runtime_for(LocalServer(rb)).invoke(touch)
        s_delta = server.run_checkpoint()
        assert s_delta["base_seg"] == s_full["seg"], "delta did not chain"
        rows.append(
            f"delta_ckpt_wal_full_bytes,{s_full['bytes']},bytes "
            f"seg={s_full['seg']}"
        )
        rows.append(
            f"delta_ckpt_wal_delta_bytes,{s_delta['bytes']},bytes "
            f"base_seg={s_delta['base_seg']} chain_len={s_delta['chain_len']}"
        )
        rows.append(
            f"delta_ckpt_wal_delta_ratio,"
            f"{s_delta['bytes'] / max(s_full['bytes'], 1):.4f},ratio "
            f"gate: 1-block dirty cycle vs full snapshot"
        )
    finally:
        rb.close()
        server.shutdown()
        shutil.rmtree(tmp, ignore_errors=True)

    # -- block_delta kernel compression (int8 quantized dirty blocks) -- #
    if RUN_KERNEL:
        import jax.numpy as jnp

        from repro.kernels.block_delta.ops import (
            blockify, compute_block_delta, pack_dirty,
        )

        rng = np.random.default_rng(0)
        for frac in FRACS:
            new = _dirty(base, frac, rng)
            nb = blockify(new, BLOCK_ELEMS)
            ob = blockify(base, BLOCK_ELEMS)
            q, norm2, scale, _ = compute_block_delta(
                jnp.asarray(nb), jnp.asarray(ob), impl="xla"
            )
            dirty_idx, qd, sd = pack_dirty(
                np.asarray(q), np.asarray(norm2), np.asarray(scale)
            )
            comp_bytes = qd.size + sd.size * 4 + dirty_idx.size * 4
            rows.append(
                f"delta_int8_frac{frac},{comp_bytes},bytes "
                f"ratio={comp_bytes / full_bytes:.4f} "
                f"dirty_blocks={len(dirty_idx)}"
            )
    return rows


def _smoke() -> None:
    """Shrink the model for CI. The gated rows are same-run ratios
    (shipped bytes / full-state bytes), so they hold at any size."""
    global PARAMS
    PARAMS = 1 << 18     # 1 MiB


def main(argv: List[str]) -> None:
    t0 = time.perf_counter()
    if "--smoke" in argv:
        _smoke()
    rows = run()
    for r in rows:
        print(r)
    from benchmarks.run import _write_artifact

    _write_artifact("delta_ckpt", rows, time.perf_counter() - t0, None)


if __name__ == "__main__":
    main(sys.argv[1:])
