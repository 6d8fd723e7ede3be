"""Mean seconds a request spent putting the served weights on the device:
the program's ``replica.upload`` span in ``decode_fn``, per request in the
window. Moves ``serve_tok_s``."""
from harness.spans import per_op


def read(r):
    return per_op(r, "replica.upload", "request", "serve")
