"""Snapshot serving: inference replicas on multiversioned parameter state.

A serving replica pins a committed version (the paper's snapshot reads):
requests are served from a consistent parameter snapshot even while training
transactions keep committing. ``refresh()`` advances to the newest committed
version by reloading the whole state zero-copy (``TensorStore.load``): every
block lands in an arena buffer again, changed or not.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

from repro.core import obs
from repro.core.client import LocalServer
from repro.core.posix import FaaSFS
from repro.core.runtime import runtime_for
from repro.core.tensorstate import TensorStore, unflatten_like

PyTree = Any


@dataclass
class ServeStats:
    requests: int = 0
    refreshes: int = 0


class SnapshotServer:
    """Batched decode against a pinned parameter snapshot."""

    def __init__(
        self,
        local: LocalServer,
        decode_fn: Callable[[PyTree, Any], Any],
        template: PyTree,
        *,
        root: str = "/mnt/tsfs/train",
        name: str = "state",
    ):
        self.local = local
        self.decode_fn = decode_fn
        self.template = template
        self.root = root.rstrip("/")
        self.name = name
        self.params: Optional[PyTree] = None
        self.version: int = -1
        self.stats = ServeStats()

    # ------------------------------------------------------------------ #
    def refresh(self) -> int:
        """Load the latest committed snapshot, whole and zero-copy."""
        holder: Dict[str, Any] = {}

        def do_read(fs: FaaSFS) -> None:
            store = TensorStore(fs, prefix=self.root)
            holder["flat"] = store.load(self.name, zero_copy=True)
            holder["ts"] = fs.txn.read_ts

        with obs.span("replica.refresh", "replica"):
            runtime_for(self.local).invoke(do_read, read_only=True)
            self.params = unflatten_like(self.template, holder["flat"])
        self.version = holder["ts"]
        self.stats.refreshes += 1
        return self.version

    # ------------------------------------------------------------------ #
    def serve(self, batch: Any) -> Any:
        if self.params is None:
            self.refresh()
        out = self.decode_fn(self.params, batch)
        self.stats.requests += 1
        return out
