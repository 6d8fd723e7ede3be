"""The device trace of a run's window, and its reduction to numbers.

``capture`` records the window with JAX's profiler (no Python tracer: the
host side holds the benchmark's own ``bench.*`` annotations and JAX's
dispatch events). ``load_events`` turns the ``.xplane.pb`` into a plain
list of events, ``{"plane", "line", "name", "ts", "dur", "stats"}`` with
times in nanoseconds on one clock, and ``Trace`` reduces such a list:
device busy time, kernel time, a program's executions, the device
operations that took most
time, and the device's idle gaps named by what the host was doing.
``bench/tests/test_bench_trace.py`` checks this reduction.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterator, List, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KEEP_STATS = ("hlo_module", "hlo_op", "program_id")


@contextlib.contextmanager
def capture(log_dir: str) -> Iterator[None]:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def load_events(log_dir: str) -> List[dict]:
    """Device events, and the host's ``bench.*`` annotations."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return []
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                if not device and not ev.name.startswith("bench."):
                    continue
                name, stats = ev.name, {}
                if device:
                    # an op is named by its HLO text: keep the instruction's
                    # name, and the whole text only for custom calls
                    # (kernels), whose operand shapes the readers need
                    name = ev.name.split(" ", 1)[0].lstrip("%")
                    stats = {k: v for k, v in ev.stats
                             if k in KEEP_STATS and isinstance(v, (str, int, float))}
                    if "custom-call(" in ev.name:
                        stats["hlo"] = ev.name
                out.append({"plane": plane.name, "line": line.name, "name": name,
                            "ts": float(ev.start_ns), "dur": float(ev.duration_ns),
                            "stats": stats})
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


class Trace:
    def __init__(self, events: List[dict]):
        self.events = events
        host = [e for e in events if not e["plane"].startswith("/device:")]
        win = [e for e in host if e["name"] == WINDOW]
        if win:
            self.t0, self.t1 = win[0]["ts"], win[0]["ts"] + win[0]["dur"]
        elif events:
            self.t0 = min(e["ts"] for e in events)
            self.t1 = max(e["ts"] + e["dur"] for e in events)
        else:
            self.t0 = self.t1 = 0.0
        self.spans = [e for e in host if e["name"] != WINDOW]
        dev = [e for e in events if e["plane"].startswith("/device:")]
        with_ops = {e["plane"] for e in dev if e["line"] == OPS_LINE}
        self.ops = [e for e in dev if e["line"] == OPS_LINE
                    or e["plane"] not in with_ops]
        self.planes = sorted({e["plane"] for e in self.ops})

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def _clip(self, lo: float, hi: float) -> Tuple[float, float]:
        return max(lo, self.t0), min(hi, self.t1)

    def busy(self, plane: str) -> List[Tuple[float, float]]:
        iv = [self._clip(e["ts"], e["ts"] + e["dur"])
              for e in self.ops if e["plane"] == plane]
        return _union([(lo, hi) for lo, hi in iv if hi > lo])

    def busy_s(self) -> float:
        """Seconds in which an operation ran on a device, averaged over
        the devices that ran any."""
        if not self.planes:
            return 0.0
        return sum(hi - lo for p in self.planes
                   for lo, hi in self.busy(p)) / len(self.planes) / 1e9

    def program_runs(self, program: str) -> List[dict]:
        """Executions of the jitted program named ``program`` (its HLO
        module, ``jit_<function>``) on the first device, in order."""
        plane = self.planes[0] if self.planes else None
        return sorted((e for e in self.events
                       if e["plane"] == plane and e["line"] == MODULES_LINE
                       and e["name"].split("(", 1)[0] == program),
                      key=lambda e: e["ts"])

    def kernel_events(self, kernel: str) -> List[dict]:
        """Events of the kernel named ``kernel`` (instructions ``kernel``
        and ``kernel.<n>``)."""
        return [e for e in self.ops
                if e["name"] == kernel or e["name"].startswith(kernel + ".")]

    def top_ops(self, n: int = 10) -> List[List]:
        tot: Dict[str, float] = defaultdict(float)
        for e in self.ops:
            lo, hi = self._clip(e["ts"], e["ts"] + e["dur"])
            if hi > lo:
                mod = e["stats"].get("hlo_module")
                tot[f"{mod}/{e['name']}" if mod else e["name"]] += (hi - lo) / 1e9
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """Idle time of the first device, summed by the innermost
        benchmark span open at each gap's middle (``host`` where none)."""
        if not self.planes:
            return []
        busy = self.busy(self.planes[0])
        gaps, at = [], self.t0
        for lo, hi in busy + [(self.t1, self.t1)]:
            if lo > at:
                gaps.append((at, lo))
            at = max(at, hi)
        tot: Dict[str, float] = defaultdict(float)
        for lo, hi in gaps:
            mid = (lo + hi) / 2
            open_ = [s for s in self.spans if s["ts"] <= mid <= s["ts"] + s["dur"]]
            name = min(open_, key=lambda s: s["dur"])["name"] if open_ else "host"
            tot[name] += (hi - lo) / 1e9
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def operand_shapes(event: dict) -> List[Tuple[str, List[int]]]:
    """``(dtype, dims)`` of a kernel event's operands, from its HLO text."""
    text = event["stats"].get("hlo", "")
    args = text.split("custom-call(", 1)[1] if "custom-call(" in text else ""
    args = args.split("custom_call_target", 1)[0]
    return [(dt, [int(d) for d in dims.split(",") if d])
            for dt, dims in re.findall(r"\b([a-z]+[0-9]+)\[([0-9,]*)\]", args)]
