"""Seconds a cold restore spent landing leaves in their arena buffers: the
program's ``tensor.land`` spans (open, read into the buffer) summed per
restore in the window. Moves ``restore_MBps``."""
from harness.spans import per_op


def read(r):
    return per_op(r, "tensor.land", "restore", "restore")
