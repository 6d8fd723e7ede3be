"""Mean seconds a request spent decoding: the program's ``replica.decode``
span in ``decode_fn`` (the token loop), per request in the window. Moves
``serve_tok_s``."""
from harness.spans import per_op


def read(r):
    return per_op(r, "replica.decode", "request", "serve")
