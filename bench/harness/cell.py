"""One run of one cell: set-up, the measured window, the check, the result.

The traffic mix names a loop (``serve`` or ``checkpoint``); the loop's
module provides ``setup(run)``, ``window(run, deadline)``,
``measure(run)`` and ``check(run)``. Everything else a cell needs is
data: the configuration file, the mix file, and one reader per per-layer
metric under ``bench/metrics``, all found by the names in
``BENCHMARK.json``.

Order of a run: the server process starts before JAX is imported (this
process alone holds the chip); set-up ends when the window opens, and
``setup_s`` counts from the start of the process. The window runs for
``--seconds``; what is still in flight when it closes is cut off and not
counted (``Run.done``). Then the device's memory peak is read, the loop
computes its end-to-end metrics (``measure``), and the reference checks
run (``check``).
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import sys
import threading
import time
import traceback
from pathlib import Path
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

from harness import procs

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoDevice(Exception):
    """JAX found no accelerator, or fewer chips than the cell needs."""


class Run:
    """What one run knows: its inputs, what it measured, and its checks."""

    def __init__(self, spec: dict, cell: dict, config: dict, mix: dict, *,
                 seed: int, seconds: float, trace: bool, control: bool = False):
        self.spec, self.cell, self.config, self.mix = spec, cell, config, mix
        self.seed, self.seconds, self.trace, self.control = seed, seconds, trace, control
        self.rundir: Optional[procs.RunDir] = None
        self.port = 0
        #: (name, start, end) of the benchmark's spans in the window
        self.spans: List[Tuple[str, float, float]] = []
        #: one record per timed operation
        self.ops: List[Dict[str, Any]] = []
        self.checks: Dict[str, Tuple[float, float]] = {}
        self.e2e: Dict[str, float] = {}
        self.extra: Dict[str, Any] = {}
        self.attempted = self.failed = 0
        self.compiles: List[float] = []
        self.window_open = False
        #: monotonic time the window closes, and whether work was cut there
        self.deadline = 0.0
        self.aborted = False
        self.trace_events: Optional[List[dict]] = None
        self.trace_view = None
        self.peaks: Optional[dict] = None
        self._conns: List[Any] = []
        self._admin = None

    # ------------------------------------------------------------------ #
    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        """A benchmark span, on the host clock and in the device trace;
        kept only when its work completes."""
        import jax

        with jax.profiler.TraceAnnotation("bench." + name):
            t0 = time.perf_counter()
            yield  # a span whose work raised is not recorded
            self.spans.append((name, t0, time.perf_counter()))

    def span_s(self, name: str) -> List[float]:
        return [t1 - t0 for n, t0, t1 in self.spans if n == name]

    def done(self, kind: str) -> List[Dict[str, Any]]:
        """The window's operations of ``kind`` that ended before it closed."""
        return [o for o in self.ops if o["op"] == kind and o["end"] <= self.deadline]

    def client(self):
        """A new client: its own connection and an empty LRU."""
        from repro.core.client import LocalServer
        from repro.core.remote import RemoteBackend

        rb = RemoteBackend("127.0.0.1", self.port)
        self._conns.append(rb)
        return LocalServer(rb, max_blocks=self.config["client_cache_blocks"])

    def counters(self) -> Optional[Dict[str, float]]:
        """The server's counters that per-layer metrics read, on a
        connection of their own; only in a traced run."""
        if not self.trace:
            return None
        if self._admin is None:
            from repro.core.remote import RemoteBackend

            self._admin = RemoteBackend("127.0.0.1", self.port)
        m = self._admin.metrics_snapshot()

        def hist_sum(fam: str, key: str) -> float:
            return float(m.get(fam, {}).get("values", {}).get(key, {}).get("sum", 0.0))

        return {
            "bytes_in": float(m.get("faasfs_server_bytes_in_total", {})
                              .get("values", {}).get("", 0.0)),
            "commit_exec_us": hist_sum("faasfs_server_exec_us", "op=commit"),
            "fsync_us": hist_sum("faasfs_wal_fsync_us", ""),
        }

    def counters_since(self, before: Optional[dict]) -> Optional[dict]:
        after = self.counters()
        return None if before is None else {k: after[k] - before[k] for k in before}

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks[name] = (float(value), float(limit))

    def close_conns(self) -> None:
        for rb in self._conns + ([self._admin] if self._admin else []):
            try:
                rb.close()
            except Exception:
                pass
        self._conns, self._admin = [], None


# ---------------------------------------------------------------------- #
def load_spec() -> dict:
    return json.loads((REPO / "BENCHMARK.json").read_text())


def cell_inputs(spec: dict, workload: str) -> Tuple[dict, dict, dict]:
    """The cell, its configuration file and its traffic mix, by name."""
    cell = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = json.loads((REPO / conf["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, config, mix


def applies(metric: dict, cell: dict) -> bool:
    return cell["name"] in metric.get("workloads", [cell["name"]])


def reader(name: str) -> Callable[[Run], Optional[float]]:
    """``read`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise NoDevice(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def loop_module(mix: dict):
    return importlib.import_module(f"harness.{mix['loop']}_loop")


# ---------------------------------------------------------------------- #
def _devices(chips: int, platform: Optional[str]):
    import jax

    devs = jax.devices()
    if platform is not None and (devs[0].platform != platform or len(devs) < chips):
        raise NoDevice(f"found {len(devs)} {devs[0].platform} device(s); "
                       f"the cell needs {chips} {platform}")
    return devs[:chips]


def _configure_jax(r: Run, persistent: bool) -> None:
    import os

    import jax

    if persistent and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    def on_compile(event: str, secs: float, **_: Any) -> None:
        if event == COMPILE_EVENT and r.window_open:
            r.compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_compile)


def _window(r: Run, loop) -> None:
    """The loop's window, in a thread of its own. At the deadline every
    client connection is closed: work still in flight fails, is not
    counted, and the loop stops."""
    r.deadline = time.monotonic() + r.seconds
    failure: List[str] = []

    def body() -> None:
        try:
            loop.window(r, r.deadline)
        except Exception:
            if not r.aborted:
                failure.append(traceback.format_exc())

    th = threading.Thread(target=body, name="bench-window")
    th.start()
    th.join(max(0.0, r.deadline - time.monotonic()))
    if th.is_alive():
        r.aborted = True
        r.close_conns()
        th.join(timeout=300)
    if th.is_alive():
        raise RuntimeError("the window's work did not stop after its connections closed")
    if failure:
        raise RuntimeError("the window failed:\n" + failure[0])


def execute(r: Run, t_start: float, *, platform: Optional[str] = "tpu") -> Dict[str, Any]:
    """Run ``r`` and return its result line's object; raises ``NoDevice``
    before any result exists. ``platform=None`` skips the look for a chip
    (tests on the CPU)."""
    from harness import trace as tr

    loop = loop_module(r.mix)
    r.rundir = procs.RunDir()
    error = None
    dev_info: Dict[str, Any] = {}
    try:
        _, r.port = r.rundir.start_server(
            shards=r.config["server_shards"], block_bytes=r.config["block_bytes"])
        devs = _devices(r.cell["chips"], platform)
        _configure_jax(r, persistent=platform is not None)
        r.peaks = peaks(devs[0].device_kind) if platform is not None else None
        dev_info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                    "count": len(devs)}
        try:
            loop.setup(r)
            r.e2e["setup_s"] = time.perf_counter() - t_start
            log_dir = str(Path(r.rundir.path) / "trace")
            ctx = tr.capture(log_dir) if r.trace else contextlib.nullcontext()
            r.window_open = True
            with ctx:
                _window(r, loop)
            r.window_open = False
            loop.measure(r)
            stats = [d.memory_stats() or {} for d in devs]
            dev_info["memory_peak_bytes"] = max(
                (s.get("peak_bytes_in_use", 0) for s in stats), default=0)
            if r.trace:
                r.trace_events = tr.load_events(log_dir)
            loop.check(r)
        except Exception:  # a run that broke is reported as not correct
            error = traceback.format_exc()
    finally:
        r.close_conns()
        r.rundir.close()
    return _result(r, dev_info, error)


def _result(r: Run, dev_info: Dict[str, Any], error: Optional[str]) -> Dict[str, Any]:
    from harness.trace import Trace

    metrics: Dict[str, Any] = {}
    kinds = r.spec["per_layer"] if r.trace else r.spec["end_to_end"]
    trace = Trace(r.trace_events or [])
    r.trace_view = trace
    for m in kinds:
        if not applies(m, r.cell):
            continue
        if r.trace:
            try:
                value = None if error else reader(m["name"])(r)
            except Exception:  # a reader's fault leaves its metric out
                print(f"reader of {m['name']} failed:\n{traceback.format_exc()}",
                      file=sys.stderr)
                value = None
        else:
            value = r.e2e.get(m["name"])
            if value is None and error is None:
                error = f"end-to-end metric {m['name']} was not measured"
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(dev_info)
    if r.trace:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
    ok = error is None and bool(r.checks) and all(
        v <= lim for v, lim in r.checks.values())
    if r.compiles:
        print(f"warning: {len(r.compiles)} compilation(s) inside the window, "
              f"{sum(r.compiles):.3f} s", file=sys.stderr)
    out: Dict[str, Any] = {"correct": ok, "attempted": r.attempted,
                           "failed": r.failed, "metrics": metrics, "device": device}
    if r.trace and trace.planes:
        out["breakdown"] = {"device_ops": trace.top_ops(), "idle_gaps": trace.idle_gaps()}
    if error:
        print(error, file=sys.stderr)
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in r.checks.items()}
    return out
