"""Transactional, block-granular checkpointing on the FaaSFS core.

Checkpoints are FaaSFS state objects:

  * a checkpoint is atomically visible or not at all (no torn
    checkpoints on worker failure; the paper's atomic commit applied to
    training state): ``save_tree`` writes step N as a new version of
    ``step_N``, in several transactions when its write set exceeds
    ``TXN_WRITE_BYTES``, and the last one points ``step_N`` at it and
    flips ``latest``. Saving a step again writes another version, and
    steps chained to the old one keep reading it,
  * consecutive saves ship only dirty blocks: the ``block_delta`` kernel
    (or the exact numpy compare) flags dirty blocks against the previous
    step's baseline, and ONLY those blocks' exact new bytes are written,
    chained to the previous step's version — checkpoint cost scales with
    the update rate, not the parameter count,
  * ``restore`` pins a snapshot timestamp (multiversion read) so a restore
    is consistent even while training keeps committing, and loads through
    the zero-copy arena path (``TensorStore.load(zero_copy=True)``),
  * a ``latest`` pointer file is atomically renamed into place (POSIX rename
    atomicity, validated by the namespace OCC checks).

The manager runs on ``FunctionRuntime`` — implicit BEGIN/COMMIT, Conflict
restart, warm-container caches, read-only inference for restores — against
any ``BackendAPI`` (in-process, remote socket, sharded cluster).
"""
from __future__ import annotations

import json
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.core.client import LocalServer
from repro.core.posix import FaaSFS, O_CREAT, O_TRUNC
from repro.core.runtime import FunctionRuntime, InvocationStats, runtime_for
from repro.core.tensorstate import (
    TensorStore, changed_blocks, flatten_with_names, save_tree, unflatten_like,
)

PyTree = Any


@dataclass
class SaveInfo:
    step: int
    commit_ts: int
    bytes_total: int
    bytes_written: int
    blocks_written: int
    wall_s: float
    #: transactions the save took (more than one past ``TXN_WRITE_BYTES``)
    txns: int = 1
    #: per-leaf dirty block indices a delta save wrote; ``None`` when full
    #: (first save, the same step again, or a chain cut by
    #: ``DELTA_CHAIN_MAX``)
    dirty: Optional[Dict[str, List[int]]] = None


def dirty_block_indices(
    new: np.ndarray,
    old: np.ndarray,
    block_bytes: int,
    impl: str = "auto",
) -> Optional[List[int]]:
    """Block indices (of ``block_bytes`` granularity over the raw leaf
    bytes) where ``new`` differs from ``old``; ``None`` means "unknown,
    write conservatively" (shape/dtype changed).

    ``impl="auto"`` is the exact numpy byte-compare. A ``block_delta``
    kernel impl (``"pallas"`` / ``"xla"`` / ``"pallas_interpret"``) flags
    the blocks of float32 leaves of at least one block on the device,
    where any element's bits differ — as exact as the byte compare, NaNs,
    signed zeros and subnormal steps included; a kernel that fails
    raises. Only that per-block count comes back to the host — the
    int8-quantized delta the kernel also emits is lossy, so the blocks
    themselves are always written as exact new bytes."""
    new = np.asarray(new)
    old = np.asarray(old)
    if new.dtype != old.dtype or new.shape != old.shape:
        return None
    if impl != "auto" and new.dtype == np.float32 \
            and block_bytes % 4 == 0 and new.size >= block_bytes // 4:
        from repro.kernels.block_delta.ops import blockify, compute_block_delta

        block_elems = block_bytes // 4
        *_, nchanged = compute_block_delta(
            blockify(new.reshape(-1), block_elems),
            blockify(old.reshape(-1), block_elems),
            impl=impl,
        )
        return [int(i) for i in np.flatnonzero(np.asarray(nchanged))]
    return changed_blocks(new, old, block_bytes)


def _frozen(a: np.ndarray) -> bool:
    """Whether nothing can write ``a``'s memory: it and every array it
    views are read-only, and no writable Python buffer owns the memory
    (JAX host arrays, sealed arena views). Such a leaf is kept as the
    next save's baseline without a copy."""
    while isinstance(a, np.ndarray):
        if a.flags.writeable:
            return False
        a = a.base
    if isinstance(a, memoryview):
        return a.readonly
    return not isinstance(a, bytearray)


class CheckpointManager:
    """Step-indexed checkpoints with kernel-flagged delta commits and
    snapshot restores, running on ``FunctionRuntime``.

    ``target`` is a ``FunctionRuntime`` or a bare ``LocalServer`` (a
    cached runtime is built over it). ``dirty_impl`` selects the dirty
    detector (``"auto"`` = exact numpy; ``"xla"``/``"pallas"`` = the
    block_delta kernel). ``max_staleness_s`` lets ``latest_step`` /
    ``restore`` be served from the lease tier's bounded-staleness view.

    A delta save of step N writes a version of ``step_N`` chained to the
    version this manager saved last (``save_tree`` ``base``), the one its
    dirty blocks were found against, whatever other writers saved since:
    only those blocks are stored, and a restore reads the rest from the
    chain."""

    def __init__(
        self,
        target,
        root: str = "/mnt/tsfs/ckpt",
        block_bytes: int = 256 * 1024,
        dirty_impl: str = "auto",
        max_staleness_s: Optional[float] = None,
    ):
        if max_staleness_s is not None and not isinstance(
            target, FunctionRuntime
        ):
            self.runtime = runtime_for(target, max_staleness_s=max_staleness_s)
        else:
            self.runtime = runtime_for(target)
        self.local: LocalServer = self.runtime.local
        self.root = root.rstrip("/")
        self.block_bytes = block_bytes
        self.dirty_impl = dirty_impl
        self._baseline: Dict[str, np.ndarray] = {}
        self._last_step: Optional[int] = None
        #: the version the last save wrote, which ``_baseline`` holds
        self._last_version: Optional[str] = None

    # ------------------------------------------------------------------ #
    def save(self, step: int, state: PyTree, *, delta_from_last: bool = True) -> SaveInfo:
        t0 = time.perf_counter()
        leaves = flatten_with_names(state)
        dirty: Optional[Dict[str, List[int]]] = None
        base = None
        if delta_from_last and self._last_version is not None \
                and self._last_step != step:
            base = self._last_version
            # dirty detection happens ONCE, outside the transaction:
            # a Conflict restart re-runs only the block writes
            dirty = {}
            for lname, arr in leaves:
                old = self._baseline.get(lname)
                if old is None:
                    continue
                idx = dirty_block_indices(
                    arr, old, self.block_bytes, self.dirty_impl
                )
                if idx is not None:
                    dirty[lname] = idx

        def flip_latest(fs: FaaSFS) -> None:
            # atomically flip the latest pointer (POSIX rename semantics)
            tmp = f"{self.root}/.latest.tmp"
            fd = fs.open(tmp, O_CREAT | O_TRUNC)
            fs.write(fd, json.dumps({"step": step}).encode())
            fs.close(fd)
            if fs.exists(f"{self.root}/latest"):
                fs.unlink(f"{self.root}/latest")
            fs.rename(tmp, f"{self.root}/latest")

        inv = InvocationStats()
        stats = save_tree(
            self.runtime, self.root, f"step_{step}", state,
            finish=flip_latest, stats=inv, block_bytes=self.block_bytes,
            dirty_blocks=dirty, base=base,
        )
        self._baseline = {}  # release the old one before taking the new
        self._baseline = {n: a if _frozen(a) else a.copy() for n, a in leaves}
        self._last_step, self._last_version = step, stats["version"]
        return SaveInfo(
            step=step,
            commit_ts=inv.commit_ts,
            bytes_total=stats["bytes_total"],
            bytes_written=stats["bytes_written"],
            blocks_written=stats["blocks_written"],
            wall_s=time.perf_counter() - t0,
            txns=stats["txns"],
            dirty=dirty if stats["base"] else None,
        )

    # ------------------------------------------------------------------ #
    def latest_step(self) -> Optional[int]:
        out: Dict[str, Optional[int]] = {"step": None}

        def do_read(fs: FaaSFS) -> None:
            if not fs.exists(f"{self.root}/latest"):
                return
            fd = fs.open(f"{self.root}/latest")
            n = fs.fstat(fd)["st_size"]
            out["step"] = json.loads(fs.pread(fd, n, 0))["step"]
            fs.close(fd)

        self.runtime.invoke(do_read, read_only=True)
        return out["step"]

    def restore(
        self,
        template: PyTree,
        step: Optional[int] = None,
        *,
        zero_copy: bool = True,
    ) -> Tuple[PyTree, int]:
        """Snapshot-consistent restore; returns (state, step).

        With ``zero_copy=True`` (default) leaf arrays are READONLY views
        over arena buffers filled straight off the wire — ``.copy()``
        any leaf you need to mutate in place (functional updates, the
        normal jax style, need nothing)."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError("no checkpoint committed yet")
        holder: Dict[str, Any] = {}

        def do_load(fs: FaaSFS) -> None:
            store = TensorStore(fs, prefix=self.root)
            holder["flat"] = store.load(f"step_{step}", zero_copy=zero_copy)

        self.runtime.invoke(do_load, read_only=True)
        return unflatten_like(template, holder["flat"]), step
