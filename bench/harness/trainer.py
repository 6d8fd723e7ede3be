"""The trainer of the ``serve`` loop: a client process that publishes.

    python trainer.py       # driven over its standard input

It never uses the chip (run it with ``JAX_PLATFORMS=cpu``). Its standard
input carries, in order:

  1. one JSON line: ``port``, ``block_bytes``, ``cache_blocks``,
     ``leaves`` (``[name, dtype, shape]`` in file-system order), ``delta``
     and ``schedule`` (per version ``k`` >= 1, the ``[leaf, element]``
     pairs that it changes by ``delta``);
  2. the raw bytes of every leaf, in that order.

It publishes them as version 0 (``launch.serve.publish``) and prints
``READY``. On a line ``GO <start> <deadline> <period>`` (monotonic clock)
it publishes version ``k`` at ``start + k * period`` for every such time
before the deadline, each as a delta of the previous version, and prints
one JSON line per publish: ``k``, ``due``, ``start``, ``end``, ``blocks``
and ``version`` (the version ``save_tree`` wrote).
"""
import json
import sys
import time
from pathlib import Path

import ml_dtypes  # noqa: F401  (numpy learns "bfloat16")
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness.perturb import bump  # noqa: E402
from harness.weights import nest  # noqa: E402


def main() -> None:
    from repro.core.client import LocalServer
    from repro.core.remote import RemoteBackend
    from repro.launch.serve import publish

    stdin = sys.stdin.buffer
    head = json.loads(stdin.readline())
    flat = {}
    for name, dtype, shape in head["leaves"]:
        a = np.empty(shape, dtype)
        view = memoryview(a.reshape(-1).view(np.uint8))
        got = 0
        while got < len(view):
            n = stdin.readinto(view[got:])
            if not n:
                raise SystemExit("trainer: input ended inside a leaf")
            got += n
        flat[name] = a
    rb = RemoteBackend("127.0.0.1", head["port"])
    local = LocalServer(rb, max_blocks=head["cache_blocks"])
    try:
        t0 = time.monotonic()
        st = publish(local, nest(flat)["params"])
        print(json.dumps({"ready": True, "seconds": time.monotonic() - t0,
                          "bytes": st["bytes_written"], "version": st["version"]}),
              flush=True)
        _, start, deadline, period = stdin.readline().split()
        start, deadline, period = float(start), float(deadline), float(period)
        for k, changes in enumerate(head["schedule"], 1):
            due = start + k * period
            if due >= deadline:
                break
            time.sleep(max(0.0, due - time.monotonic()))
            t1 = time.monotonic()
            new = dict(flat)
            for name in {n for n, _ in changes}:
                new[name] = flat[name].copy()
            for name, i in changes:
                a = new[name].reshape(-1)
                a[i] = bump(a[i], head["delta"])
            st = publish(local, nest(new)["params"], baseline=flat)
            flat = new
            print(json.dumps({"k": k, "due": due, "start": t1,
                              "end": time.monotonic(), "blocks": st["blocks_written"],
                              "version": st["version"]}), flush=True)
    finally:
        rb.close()


if __name__ == "__main__":
    main()
