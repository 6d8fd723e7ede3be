"""TensorState: pytrees as FaaSFS files — the ML adaptation of the paper.

Every pytree leaf maps to one file (``<prefix>/<name>/<leaf.path>``) whose
bytes are the raw array data, plus a ``.meta`` JSON file (dtype/shape/tree
structure). Files are block-partitioned by the store, so the paper's
block-granular machinery gives us, for free:

  * **delta checkpointing** — a commit only ships blocks whose bytes
    changed (cf. the paper's fine-grained cache updates vs. NFS whole-file
    invalidation),
  * **snapshot restore** — read-only transactions pin a commit timestamp
    and read a consistent parameter version while training keeps
    committing (the paper's multiversion snapshot reads),
  * **optimistic concurrent writers** — parameter partitions act like the
    paper's TPC-C warehouses: disjoint-block commits interleave without
    locks; conflicting commits abort and retry.

A save is in place or versioned. ``TensorStore.save`` writes into
``name`` inside the caller's transaction, so concurrent writers of
disjoint blocks commit without conflict. ``save_tree`` writes a new
version ``name@<token>`` in as many transactions as its size needs and,
in the last one, points ``name``'s ``.meta`` at it: readers load the old
version or the new one whole, and a version is never rewritten. A delta
version lists its written blocks per leaf and names the version it was
compared with as its ``base``; ``load`` reads every other block from
that chain.

The on-device companion is the ``block_delta`` Pallas kernel, which computes
per-block dirty masks / int8-quantized deltas so only changed blocks cross
the wire (gradient/update compression keyed to block layout).
"""
from __future__ import annotations

import json
import secrets
import weakref
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

from repro.core import obs
from repro.core.arena import BlockArena, default_arena
from repro.core.posix import FaaSFS, O_CREAT, O_TRUNC
from repro.core.types import TENSOR_BLOCK_BYTES, NotFound

PyTree = Any

#: write-set bytes one ``save_tree`` transaction carries. A commit is one
#: wire frame and one WAL record, and the client holds its write set until
#: it commits, so a larger save is split (read each time ``save_tree`` runs)
TXN_WRITE_BYTES = 64 << 20

#: delta versions chained after a full one before ``save_tree`` writes the
#: next in full, so a load walks a bounded chain
DELTA_CHAIN_MAX = 8


def flatten_with_names(tree: PyTree, prefix: str = "") -> List[Tuple[str, np.ndarray]]:
    """Deterministic (name, leaf) pairs; names are '/'-joined dict paths."""
    out: List[Tuple[str, np.ndarray]] = []
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.extend(flatten_with_names(tree[k], f"{prefix}{k}/"))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.extend(flatten_with_names(v, f"{prefix}{i}/"))
    else:
        out.append((prefix.rstrip("/"), np.asarray(tree)))
    return out


def _leaf_u8(arr: np.ndarray) -> np.ndarray:
    """The leaf's raw bytes as a flat uint8 array (no copy when the leaf
    is already C-contiguous)."""
    return np.ascontiguousarray(arr).reshape(-1).view(np.uint8)


def changed_blocks(new: np.ndarray, old: np.ndarray,
                   block_bytes: int) -> Optional[List[int]]:
    """Exact byte compare: indices of ``block_bytes`` blocks over the raw
    leaf bytes where ``new`` differs from ``old``; ``None`` when dtype or
    shape differ (the whole leaf must be written)."""
    new, old = np.asarray(new), np.asarray(old)
    if new.dtype != old.dtype or new.shape != old.shape:
        return None
    a, b = _leaf_u8(new), _leaf_u8(old)
    full = len(a) // block_bytes
    cut = full * block_bytes
    diff = np.any(
        a[:cut].reshape(full, block_bytes) != b[:cut].reshape(full, block_bytes),
        axis=1,
    )
    out = [int(i) for i in np.flatnonzero(diff)]
    if cut < len(a) and not np.array_equal(a[cut:], b[cut:]):
        out.append(full)
    return out


def _runs(blocks: Iterable[int]) -> List[Tuple[int, int]]:
    """Sorted block indices as half-open ``[start, stop)`` runs."""
    out: List[Tuple[int, int]] = []
    for b in sorted(set(int(b) for b in blocks)):
        if out and out[-1][1] == b:
            out[-1] = (out[-1][0], b + 1)
        else:
            out.append((b, b + 1))
    return out


@dataclass
class SavePlan:
    """What a save writes: per leaf, the byte spans of its blocks to write,
    and the ``.meta`` that makes the save visible to ``load``."""

    meta: Dict[str, Any]
    #: (leaf name, leaf bytes, [(start, stop) byte spans])
    leaves: List[Tuple[str, np.ndarray, List[Tuple[int, int]]]] = \
        field(default_factory=list)
    stats: Dict[str, int] = field(default_factory=dict)

    def parts(self, part_bytes: int) -> List[List[Tuple[str, np.ndarray, int, int]]]:
        """Split the writes into transactions of at most ``part_bytes``
        (a span is cut at block boundaries; one block never splits)."""
        bb = self.meta["block_bytes"]
        step = max(bb, part_bytes // bb * bb)
        parts: List[List[Tuple[str, np.ndarray, int, int]]] = [[]]
        used = 0
        for lname, raw, spans in self.leaves:
            for lo, hi in spans:
                while lo < hi:
                    if used >= step:
                        parts.append([])
                        used = 0
                    n = min(hi - lo, step - used)
                    parts[-1].append((lname, raw, lo, lo + n))
                    used += -(-n // bb) * bb
                    lo += n
        return parts


def plan_save(
    tree: PyTree,
    *,
    baseline: Optional[Dict[str, np.ndarray]] = None,
    block_bytes: int = TENSOR_BLOCK_BYTES,
    dirty_blocks: Optional[Dict[str, Iterable[int]]] = None,
    base: Optional[str] = None,
) -> SavePlan:
    """Plan a save (see ``TensorStore.save`` for the arguments)."""
    leaves = flatten_with_names(tree)
    meta: Dict[str, Any] = {"leaves": [], "block_bytes": block_bytes}
    if base is not None:
        meta["base"] = base
    plan = SavePlan(meta, stats={"leaves": len(leaves), "bytes_total": 0,
                                 "bytes_written": 0, "blocks_written": 0})
    for lname, arr in leaves:
        raw = _leaf_u8(arr)
        n = len(raw)
        entry: Dict[str, Any] = {"name": lname, "dtype": str(arr.dtype),
                                 "shape": list(arr.shape)}
        blocks: Optional[List[int]] = None
        if dirty_blocks and lname in dirty_blocks:
            blocks = sorted(set(int(b) for b in dirty_blocks[lname]))
        elif baseline is not None and lname in baseline:
            blocks = changed_blocks(arr, baseline[lname], block_bytes)
        if blocks is None:
            spans = [(0, n)] if n else []
            nblocks = -(-n // block_bytes)
        else:
            spans = [(lo * block_bytes, min(hi * block_bytes, n))
                     for lo, hi in _runs(blocks) if lo * block_bytes < n]
            nblocks = sum(-(-(hi - lo) // block_bytes) for lo, hi in spans)
            if base is not None:
                entry["blocks"] = blocks
        meta["leaves"].append(entry)
        plan.leaves.append((lname, raw, spans))
        plan.stats["bytes_total"] += n
        plan.stats["bytes_written"] += sum(hi - lo for lo, hi in spans)
        plan.stats["blocks_written"] += nblocks
    return plan


class TensorStore:
    """Save/load pytrees through a FaaSFS transaction.

    ``arena`` backs the zero-copy restore path (``load(zero_copy=True)``):
    leaf bytes land in pooled writable-once buffers straight off the
    wire and the returned arrays alias the sealed buffers (readonly) —
    see ``docs/mlstate.md`` for the lifetime rules. Buffers are returned
    to the pool automatically when the last array view over them is
    garbage-collected."""

    def __init__(self, fs: FaaSFS, prefix: str = "/mnt/tsfs/state",
                 arena: Optional[BlockArena] = None):
        self.fs = fs
        self.prefix = prefix.rstrip("/")
        self.arena = arena
        #: what the last ``load`` read (see ``load``)
        self.loaded: Optional[Dict[str, Any]] = None

    # ------------------------------------------------------------------ #
    def _meta_path(self, name: str) -> str:
        return f"{self.prefix}/{name}/.meta"

    def _leaf_path(self, name: str, leaf: str) -> str:
        return f"{self.prefix}/{name}/{leaf}"

    # ------------------------------------------------------------------ #
    def save(
        self,
        name: str,
        tree: PyTree,
        *,
        baseline: Optional[Dict[str, np.ndarray]] = None,
        block_bytes: int = TENSOR_BLOCK_BYTES,
        dirty_blocks: Optional[Dict[str, Iterable[int]]] = None,
    ) -> Dict[str, int]:
        """Write a pytree into ``name`` in place, in this transaction. With
        ``baseline`` (previous leaf arrays), only blocks whose bytes
        changed are written — the delta-commit path.

        ``dirty_blocks`` short-circuits the byte-compare: for a leaf
        listed there, ONLY the given block indices are written (exact
        new bytes — the mask is a detector, never a value source), so a
        kernel-computed dirty mask (``compute_block_delta``) drives the
        write set without touching the clean bytes at all. Leaves absent
        from the dict fall back to baseline comparison. Both apply only
        where ``name`` already holds an in-place save, whose blocks the
        unwritten ones keep; otherwise every block is written.

        Returns stats: leaves, bytes_total, bytes_written, blocks_written.
        """
        if (baseline is not None or dirty_blocks) and not (
                self.exists(name) and "ref" not in self.read_meta(name)):
            baseline = dirty_blocks = None
        plan = plan_save(tree, baseline=baseline, block_bytes=block_bytes,
                         dirty_blocks=dirty_blocks)
        self.write_part(name, [(lname, raw, lo, hi)
                               for lname, raw, spans in plan.leaves
                               for lo, hi in spans])
        self.write_meta(name, plan.meta)
        return plan.stats

    def write_part(self, name: str,
                   part: List[Tuple[str, np.ndarray, int, int]]) -> None:
        """Write ``(leaf, leaf bytes, start, stop)`` spans of a ``SavePlan``."""
        for lname, raw, lo, hi in part:
            fd = self.fs.open(self._leaf_path(name, lname), O_CREAT)
            self.fs.pwrite(fd, memoryview(raw)[lo:hi], lo)
            self.fs.ftruncate(fd, len(raw))
            self.fs.close(fd)

    def write_meta(self, name: str, meta: Dict[str, Any]) -> None:
        mfd = self.fs.open(self._meta_path(name), O_CREAT | O_TRUNC)
        self.fs.write(mfd, json.dumps(meta).encode())
        self.fs.close(mfd)

    # ------------------------------------------------------------------ #
    def read_meta(self, name: str) -> Dict[str, Any]:
        mfd = self.fs.open(self._meta_path(name))
        size = self.fs.fstat(mfd)["st_size"]
        meta = json.loads(self.fs.pread(mfd, size, 0))
        self.fs.close(mfd)
        return meta

    def resolve(self, name: str) -> Tuple[str, Dict[str, Any]]:
        """``(save, meta)`` that ``name`` holds: ``name`` itself after an
        in-place save, else the version its ``.meta`` points at."""
        meta = self.read_meta(name)
        ref = meta.get("ref")
        return (name, meta) if ref is None else (ref, self.read_meta(ref))

    def _sources(self, name: str, leaf: Dict[str, Any], nbytes: int,
                 metas: Dict[str, Dict[str, Any]],
                 need: Optional[Iterable[int]] = None,
                 ) -> List[Tuple[str, List[Tuple[int, int]]]]:
        """Where each block of ``leaf`` (or each of ``need``, default all)
        lives along the base chain: ``[(save name, [(start, stop) byte
        spans])]``, newest first."""
        bb = metas[name]["block_bytes"]
        need = set(range(-(-nbytes // bb)) if need is None else need)
        out = []
        cur, entry = name, leaf
        seen = {name}
        while need:
            have = entry.get("blocks")
            take = need if have is None else need.intersection(have)
            if take:
                out.append((cur, [(lo * bb, min(hi * bb, nbytes))
                                  for lo, hi in _runs(take)]))
                need = need - take
            if not need:
                break
            cur = metas[cur].get("base")
            if cur is None or cur in seen:
                raise NotFound(f"{name}: blocks of {leaf['name']} in no base")
            seen.add(cur)
            if cur not in metas:
                metas[cur] = self.read_meta(cur)
            entry = next((l for l in metas[cur]["leaves"]
                          if l["name"] == leaf["name"]), None)
            if entry is None:
                raise NotFound(f"{cur}: leaf {leaf['name']} missing")
        return out

    def _changed_since(self, name: str, metas: Dict[str, Dict[str, Any]],
                       held: Tuple[str, Dict[str, np.ndarray]],
                       ) -> Optional[Tuple[int, Dict[str, set]]]:
        """``(versions, {leaf: blocks})``: the versions of ``name``'s chain
        newer than the held one, and per leaf the union of the blocks they
        wrote. ``None`` where the held arrays cannot stand for the blocks
        left out: an in-place save on either side (it is rewritten under
        its name), a chain that ends before the held version, or a leaf
        that is new or of another dtype or shape."""
        held_name, flat = held
        if "depth" not in metas[name]:
            return None
        wanted = {l["name"]: l for l in metas[name]["leaves"]}
        for lname, l in wanted.items():
            a = flat.get(lname)
            if a is None or str(a.dtype) != l["dtype"] or \
                    list(a.shape) != l["shape"]:
                return None
        dirty: Dict[str, set] = {lname: set() for lname in wanted}
        cur, versions = name, 0
        while cur != held_name:
            meta = metas[cur]
            leaves = {l["name"]: l for l in meta["leaves"]}
            if meta.get("base") is None or not wanted.keys() <= leaves.keys():
                return None
            bb = meta["block_bytes"]
            for lname in wanted:
                l = leaves[lname]
                n = int(np.prod(l["shape"], dtype=np.int64)) * \
                    np.dtype(l["dtype"]).itemsize
                dirty[lname].update(l.get("blocks", range(-(-n // bb))))
            versions += 1
            cur = meta["base"]
            if cur not in metas:
                metas[cur] = self.read_meta(cur)
        return versions, dirty

    def load(self, name: str, *, zero_copy: bool = False,
             held: Optional[Tuple[str, Dict[str, np.ndarray]]] = None,
             ) -> Dict[str, np.ndarray]:
        """Read all leaves as a flat {name: array} dict.

        ``zero_copy=True`` is the arena path: the ``.meta`` layout keys a
        tensor-sized span read per leaf — every block of the leaf goes
        out in ONE ``fetch_blocks`` round trip (one per run of blocks
        when the save is chained) and each payload lands directly in the
        leaf's arena buffer (no per-block ``bytes``, no assembly copy, no
        ``.copy()``). Returned arrays are READONLY views over sealed
        arena buffers; they stay valid as long as any view is alive and
        the backing buffer is recycled when the last one dies. Callers
        that need to mutate must ``.copy()``.

        ``held`` is ``(version, flat)``: the ``version`` an earlier load
        reported in ``loaded``, and the leaves that load returned (sealed,
        so never written since).
        Where ``name`` now holds a delta chained back to that version, only
        the blocks the versions since then wrote are read: a leaf none of
        them touched comes back as the held array itself, and another lands
        in a new buffer as a copy of the held bytes with those blocks read
        over it (or whole, with no copy, when they are all of its blocks).
        Otherwise every block is read, as without ``held``.

        Afterwards ``loaded`` describes the load: ``version`` (the save it
        read), ``delta`` (1 when ``held`` was used), ``versions`` (the
        versions newer than the held one), ``bytes_landed`` (read from the
        file system), ``bytes_copied`` (from held leaves) and
        ``leaves_kept`` (held arrays returned as they were)."""
        with obs.span("tensor.load", "tensor"):
            return self._load(name, zero_copy, held)

    def _load(self, name: str, zero_copy: bool,
              held: Optional[Tuple[str, Dict[str, np.ndarray]]],
              ) -> Dict[str, np.ndarray]:
        name, meta = self.resolve(name)
        metas = {name: meta}
        since = None if held is None else self._changed_since(name, metas, held)
        versions, dirty = (0, None) if since is None else since
        self.loaded = {"version": name, "delta": int(since is not None),
                       "versions": versions, "bytes_landed": 0,
                       "bytes_copied": 0, "leaves_kept": 0}
        # tensor-sized readahead: the meta layout names every leaf, so
        # one lookup_many primes the whole checkpoint's name->fid map
        # before any data moves (readdir-free, single round trip)
        paths = [self._leaf_path(name, l["name"]) for l in meta["leaves"]]
        if hasattr(self.fs, "txn"):
            self.fs.txn.lookup_many(paths)
        out: Dict[str, np.ndarray] = {}
        arena = None
        if zero_copy:
            arena = self.arena if self.arena is not None else default_arena()
            txn = self.fs.txn
            sunk0, copied0 = txn.bytes_sunk, txn.bytes_copied_into
        bb = meta["block_bytes"]
        for leaf in meta["leaves"]:
            dt = np.dtype(leaf["dtype"])
            count = int(np.prod(leaf["shape"], dtype=np.int64)) \
                if leaf["shape"] else 1
            n = count * dt.itemsize
            need = None if dirty is None else \
                {b for b in dirty[leaf["name"]] if b * bb < n}
            keep = need is not None and not need
            # the held bytes stand for the blocks no newer version wrote
            old = None if keep or need is None or len(need) * bb >= n \
                else _leaf_u8(held[1][leaf["name"]])
            srcs = [] if keep else self._sources(
                name, leaf, n, metas, None if old is None else need)
            args = {"bytes": n, "landed": sum(hi - lo for _, spans in srcs
                                              for lo, hi in spans),
                    "copied": 0 if old is None else n}
            self.loaded["bytes_landed"] += args["landed"]
            self.loaded["bytes_copied"] += args["copied"]
            with obs.span("tensor.land", "tensor", args=args):
                if keep:
                    self.loaded["leaves_kept"] += 1
                    out[leaf["name"]] = held[1][leaf["name"]]
                elif arena is not None:
                    buf = arena.alloc(n, round_to=self.fs.txn.block_size)
                    if old is not None:
                        buf.view(0, n)[:] = old
                    for src, spans in srcs:
                        fd = self.fs.open(self._leaf_path(src, leaf["name"]))
                        for lo, hi in spans:
                            # block-aligned capacity: every block in the span
                            # is a full-size sink destination, incl. the tail
                            self.fs.pread_into(fd, hi - lo, lo,
                                               buf.view(lo, buf.capacity - lo))
                        self.fs.close(fd)
                    mv = buf.seal()
                    root = np.frombuffer(mv, dtype=dt, count=count)
                    # recycle the buffer when the last aliasing view dies:
                    # every numpy view of ``root`` keeps ``root`` alive
                    # (base chains collapse to the owning array), so this
                    # fires only once nothing can read the memory
                    weakref.finalize(root, buf.release)
                    out[leaf["name"]] = root.reshape(leaf["shape"])
                else:
                    data = bytearray(n if old is None else old)
                    for src, spans in srcs:
                        fd = self.fs.open(self._leaf_path(src, leaf["name"]))
                        for lo, hi in spans:
                            data[lo:hi] = self.fs.pread(fd, hi - lo, lo)
                        self.fs.close(fd)
                    out[leaf["name"]] = np.frombuffer(
                        data, dtype=dt, count=count
                    ).reshape(leaf["shape"])
        if arena is not None:
            arena.note_fill(txn.bytes_sunk - sunk0)
            arena.note_copy(txn.bytes_copied_into - copied0)
        return out

    def exists(self, name: str) -> bool:
        return self.fs.exists(self._meta_path(name))


def save_tree(
    runtime,
    prefix: str,
    name: str,
    tree: PyTree,
    *,
    finish: Optional[Callable[[FaaSFS], None]] = None,
    stats=None,
    base: Optional[str] = None,
    **plan_kw: Any,
) -> Dict[str, Any]:
    """Save ``tree`` as a new version of ``name`` through ``runtime`` (a
    ``FunctionRuntime``). The version, ``name@<token>``, is written in as
    many transactions as ``TXN_WRITE_BYTES`` needs; the last one writes
    its ``.meta``, points ``name`` at it and runs ``finish(fs)`` (e.g. a
    pointer flip). Readers of ``name`` load the old version or the new
    one whole, and saves chained to the old one keep reading it.

    ``baseline`` / ``dirty_blocks`` (see ``TensorStore.save``) make the
    version a delta of ``base``, the version they were compared with: a
    version this function returned, or a name, read as the version it
    holds now (default: ``name``). Only the changed blocks are written,
    and ``load`` reads the others from that version's chain. It is
    written in full instead where ``base`` holds no version, or where the
    chain would grow past ``DELTA_CHAIN_MAX`` deltas.

    ``stats`` (an ``InvocationStats``) receives the last transaction's.
    Returns the plan's stats plus ``txns``, ``version`` (the one written)
    and ``base`` (the version the delta is chained to, or None)."""
    base = name if base is None else base
    found: Dict[str, Any] = {}
    if plan_kw.get("baseline") is not None or plan_kw.get("dirty_blocks"):
        def resolve(fs: FaaSFS) -> None:
            store = TensorStore(fs, prefix=prefix)
            if store.exists(base):
                found["version"], found["meta"] = store.resolve(base)

        # read-write: validated at commit, never a stale lease view
        runtime.invoke(resolve, read_only=False)
    version = found.get("version")
    meta = found.get("meta", {})
    # only a version carries a depth: an in-place save can be rewritten
    depth = meta.get("depth", DELTA_CHAIN_MAX) + 1
    if depth > DELTA_CHAIN_MAX or \
            meta.get("block_bytes") != plan_kw.get("block_bytes",
                                                   TENSOR_BLOCK_BYTES):
        plan_kw.pop("baseline", None)
        plan_kw.pop("dirty_blocks", None)
        version, depth = None, 0
    with obs.span("tensor.plan", "tensor"):
        plan = plan_save(tree, base=version, **plan_kw)
        plan.meta["depth"] = depth
        parts = plan.parts(TXN_WRITE_BYTES)
    target = f"{name}@{secrets.token_hex(8)}"

    def write(fs: FaaSFS, part, last: bool) -> None:
        store = TensorStore(fs, prefix=prefix)
        with obs.span("tensor.stage", "tensor"):
            store.write_part(target, part)
        if last:
            store.write_meta(target, plan.meta)
            store.write_meta(name, {"ref": target})
            if finish is not None:
                finish(fs)

    for i, part in enumerate(parts):
        last = i == len(parts) - 1
        runtime.invoke(write, part, last, stats=stats if last else None)
    return dict(plan.stats, txns=len(parts), version=target, base=version)


def unflatten_like(template: PyTree, flat: Dict[str, np.ndarray]) -> PyTree:
    """Rebuild a pytree with ``template``'s structure from named leaves."""
    def rebuild(node, prefix: str):
        if isinstance(node, dict):
            return {k: rebuild(v, f"{prefix}{k}/") for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            t = [rebuild(v, f"{prefix}{i}/") for i, v in enumerate(node)]
            return type(node)(t)
        key = prefix.rstrip("/")
        if key not in flat:
            raise NotFound(f"leaf {key} missing from stored state")
        return flat[key]

    return rebuild(template, "")
