"""Mean seconds a delta save spent finding its dirty blocks: the program's
``ckpt.detect`` span (both leaves blockified and sent to the device,
``block_delta``, the per-block count read back), per delta save in the
window. Moves ``ckpt_delta_s``."""
from harness.spans import per_op


def read(r):
    return per_op(r, "ckpt.detect", "save", "save", lambda o: o["kind"] == "delta")
