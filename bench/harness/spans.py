"""The program's own spans, summed per operation of the window.

The program records its spans (``repro.core.obs.span``) into the ring
``obs.SPANS`` of this process while JAX's profiler captures, which in a
traced run is the window alone. ``per_op`` takes, for each operation that
``Run.done`` counts, the benchmark's own span of that operation
(``Run.spans``), sums the seconds of the named program spans that lie
inside it, and returns the mean over those operations.

Ring timestamps are ``CLOCK_MONOTONIC`` microseconds, the clock that
``time.perf_counter`` (``Run.spans``) and ``time.monotonic`` (an
operation's ``end``) read in CPython on Linux. A program that records no
such span (one older than the spans) gives ``None``, and so does a ring
that dropped spans the window needed.
"""
from __future__ import annotations

import sys
from typing import Callable, List, Optional, Tuple


def op_windows(r, op: str, bench: str,
               keep: Optional[Callable[[dict], bool]] = None) -> List[Tuple[float, float]]:
    """``(start, end)`` of the benchmark span ``bench`` of each operation
    ``op`` that ``r.done`` counts (and ``keep`` accepts): the last such
    span to close before the operation was recorded."""
    ends = sorted((t1, t0) for n, t0, t1 in r.spans if n == bench)
    out = []
    for o in r.done(op):
        if keep is not None and not keep(o):
            continue
        before = [(t1, t0) for t1, t0 in ends if t1 <= o["end"]]
        if before:
            t1, t0 = before[-1]
            out.append((t0, t1))
    return out


def per_op(r, name: str, op: str, bench: str,
           keep: Optional[Callable[[dict], bool]] = None) -> Optional[float]:
    """Mean over the counted operations of the seconds of program spans
    ``name`` inside each one's benchmark span ``bench``."""
    from repro.core import obs

    wins = op_windows(r, op, bench, keep)
    ring = obs.SPANS.spans()
    if not wins or not ring:
        return None
    first = min(t0 for t0, _ in wins) * 1e6
    # the ring keeps spans in the order they closed: when it has dropped
    # any, those closed before its oldest one; if that one closed after
    # the first operation began, the window may have lost spans
    dropped = getattr(obs.SPANS, "dropped", 0)
    if dropped and ring[0]["ts"] + ring[0]["du"] >= first:
        print(f"{name}: the span ring dropped {dropped} span(s), some in the "
              "window; the metric is left out", file=sys.stderr)
        return None
    mine = [(s["ts"], s["ts"] + s["du"]) for s in ring if s["n"] == name]
    if not mine:
        return None
    total = 0
    for t0, t1 in wins:
        # 1 us of slack: the ring floors its times to whole microseconds
        lo, hi = int(t0 * 1e6) - 1, int(t1 * 1e6) + 1
        total += sum(b - a for a, b in mine if lo <= a and b <= hi)
    return total / len(wins) / 1e6
