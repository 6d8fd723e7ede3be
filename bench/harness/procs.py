"""The run's temporary directory and child processes, and their teardown.

A run owns one temporary directory, under ``$TMPDIR``, that holds the
server's WAL and the trace, and its children: the FaaSFS server and, in
some mixes, a trainer. ``RunDir.close`` stops them (SIGTERM, so the server
drains and flushes its WAL), waits for each and deletes the directory. A
reaper process (``reaper.py``) does the same if the run dies before it
can, so a run killed with SIGKILL leaves nothing behind either.
"""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path
from typing import List, Tuple

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
SRC = REPO / "src"


def child_env(**extra: str) -> dict:
    """This process's environment, with the repo's ``src`` on the path."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(SRC), env.get("PYTHONPATH", "")) if p)
    env.update(extra)
    return env


class RunDir:
    def __init__(self) -> None:
        self.path = tempfile.mkdtemp(prefix="faasfs-bench-")
        self.children: List[subprocess.Popen] = []
        self._reaper = subprocess.Popen(
            [sys.executable, str(HERE / "reaper.py")],
            stdin=subprocess.PIPE, text=True)
        self._tell(f"dir {self.path}")

    def _tell(self, line: str) -> None:
        self._reaper.stdin.write(line + "\n")
        self._reaper.stdin.flush()

    def spawn(self, argv: List[str], **kw) -> subprocess.Popen:
        proc = subprocess.Popen(argv, **kw)
        self.children.append(proc)
        self._tell(f"pid {proc.pid}")
        return proc

    def start_server(self, *, shards: int, block_bytes: int) -> Tuple[subprocess.Popen, int]:
        """A FaaSFS server process and its port. WAL compaction is off: its
        checkpoint packs the whole store into one record, which cannot
        hold a state of gigabytes."""
        proc = self.spawn(
            [sys.executable, "-m", "repro.core.server",
             "--wal", os.path.join(self.path, "wal"),
             "--shards", str(shards), "--block-size", str(block_bytes),
             "--checkpoint-bytes", "0", "--checkpoint-records", "0",
             "--log-level", "warn"],
            stdout=subprocess.PIPE, text=True, env=child_env(), cwd=str(REPO))
        line = proc.stdout.readline()
        if not line.startswith("LISTENING"):
            raise RuntimeError(f"server did not start: {line!r}")
        return proc, int(line.split()[1])

    def close(self) -> None:
        for proc in reversed(self.children):
            if proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=120)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
            for f in (proc.stdin, proc.stdout):
                if f is not None:
                    try:
                        f.close()
                    except OSError:
                        pass
        shutil.rmtree(self.path, ignore_errors=True)
        self._reaper.stdin.close()
        self._reaper.wait(timeout=60)
