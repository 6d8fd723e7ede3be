"""Mean seconds a delta save spent committing its dirty blocks: the
program's ``ckpt.commit`` span (``save_tree``: resolve, plan, every write
transaction), per delta save in the window. Moves ``ckpt_delta_s``."""
from harness.spans import per_op


def read(r):
    return per_op(r, "ckpt.commit", "save", "save", lambda o: o["kind"] == "delta")
