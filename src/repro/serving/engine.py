"""Snapshot serving: inference replicas on multiversioned parameter state.

A serving replica pins a committed version (the paper's snapshot reads):
requests are served from a consistent parameter snapshot even while training
transactions keep committing. ``refresh()`` advances to the newest committed
version zero-copy (``TensorStore.load``), handing in the version it holds:
where the new one is a delta chained back to it, only the blocks written
since land, leaves none of them touched are kept as the same arrays, and
the rest of each touched leaf is copied from the held one. Otherwise (the
first refresh, an in-place save, a chain cut by a full version) every
block lands again.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

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
        # (save name, flat leaves) of the last load, for the next refresh
        self._held: Optional[Tuple[str, Dict[str, Any]]] = None

    # ------------------------------------------------------------------ #
    def refresh(self) -> int:
        """Load the latest committed snapshot zero-copy, landing only what
        changed since the version held. ``replica.refresh`` carries
        ``{delta, versions, bytes_landed, bytes_copied, leaves_kept}``
        (``TensorStore.loaded``)."""
        holder: Dict[str, Any] = {}

        def do_read(fs: FaaSFS) -> None:
            store = TensorStore(fs, prefix=self.root)
            holder["flat"] = store.load(self.name, zero_copy=True,
                                        held=self._held)
            holder["loaded"] = store.loaded
            holder["ts"] = fs.txn.read_ts

        args: Dict[str, Any] = {}
        with obs.span("replica.refresh", "replica", args=args):
            runtime_for(self.local).invoke(do_read, read_only=True)
            self.params = unflatten_like(self.template, holder["flat"])
            loaded = dict(holder["loaded"])
            self._held = (loaded.pop("version"), holder["flat"])
            args.update(loaded)
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
