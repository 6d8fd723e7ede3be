"""Seconds a replica refresh spent landing leaves in their arena buffers:
the program's ``tensor.land`` spans summed per refresh in the window.
Moves ``serve_tok_s``."""
from harness.spans import per_op


def read(r):
    return per_op(r, "tensor.land", "refresh", "refresh")
