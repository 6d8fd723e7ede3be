"""Seconds a full save spent in its transactions' commits: the program's
``invoke.commit`` spans (client encode, send, server commit, WAL, reply)
summed per full save in the window. Moves ``ckpt_save_MBps``."""
from harness.spans import per_op


def read(r):
    return per_op(r, "invoke.commit", "save", "save", lambda o: o["kind"] == "full")
