"""Seeded sparse changes to a state, by blocks of the file system.

``draw`` picks a fixed number of distinct blocks, uniformly over every
block of every leaf (a leaf's last block may be partial), and one element
in each. The count is the same for every seed, so each seed gives the
same amount of work in another place. ``bump`` is the one arithmetic by
which a changed element gets its new value, shared by the trainer and the
references that rebuild a version.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def draw(shapes: Dict[str, Sequence[int]], block_bytes: int, frac: float,
         rng: np.random.Generator, itemsize: int = 4) -> List[Tuple[str, int]]:
    """``(leaf, flat element index)`` of the changed elements, by name and
    index. ``shapes`` maps leaf names to shapes; every leaf's elements
    take ``itemsize`` bytes."""
    per_block = block_bytes // itemsize
    slots = [(n, b, int(np.prod(s))) for n, s in sorted(shapes.items())
             for b in range(-(-int(np.prod(s)) // per_block))]
    k = max(1, round(frac * len(slots)))
    out = []
    for i in sorted(rng.choice(len(slots), size=k, replace=False)):
        name, b, size = slots[i]
        lo = b * per_block
        out.append((name, lo + int(rng.integers(min(per_block, size - lo)))))
    return out


def blocks_of(elements: List[Tuple[str, int]], block_bytes: int,
              itemsize: int = 4) -> Dict[str, List[int]]:
    """The blocks holding ``elements``, per leaf, sorted."""
    out: Dict[str, set] = {}
    for name, i in elements:
        out.setdefault(name, set()).add(itemsize * i // block_bytes)
    return {n: sorted(b) for n, b in out.items()}


def bump(value, delta: float):
    """``value`` (a scalar of the leaf's type) plus ``delta``: added in
    float32, rounded back to the leaf's type."""
    return (np.float32(value) + np.float32(delta)).astype(value.dtype)
