"""Mean seconds a request spent prefilling its prompt into the decode
cache: the program's ``replica.prefill`` span in ``decode_fn`` (one call
of the jitted prefill and the first token back on the host), per request
in the window. A program that prefills nothing, or records no such span,
gives nothing. Moves ``serve_tok_s``."""
from harness.spans import per_op


def read(r):
    return per_op(r, "replica.prefill", "request", "serve")
