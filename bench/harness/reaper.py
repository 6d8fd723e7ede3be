"""Clean up after a benchmark run however it ends.

    python reaper.py        # fed by the run over its standard input

The run writes ``pid <n>`` for every child it starts and ``dir <path>``
for its temporary directory. When the pipe closes, because the run
finished or because it was killed, the reaper kills every listed process
still alive, waits for each to end, and deletes the directories. It
ignores SIGTERM and SIGINT, so a signal sent to the whole process group
does not stop it before it has cleaned up.
"""
import os
import shutil
import signal
import sys
import time


def alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    try:  # a zombie has ended; its parent, gone, can no longer reap it
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def main() -> None:
    signal.signal(signal.SIGTERM, signal.SIG_IGN)
    signal.signal(signal.SIGINT, signal.SIG_IGN)
    pids, dirs = [], []
    for line in sys.stdin:
        kind, _, value = line.strip().partition(" ")
        if kind == "pid":
            pids.append(int(value))
        elif kind == "dir":
            dirs.append(value)
    for pid in pids:
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    deadline = time.monotonic() + 30
    for pid in pids:
        while alive(pid) and time.monotonic() < deadline:
            time.sleep(0.05)
    for d in dirs:
        shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    main()
