"""Mean seconds of one request (``SnapshotServer.serve``: the weights'
upload and the decode) in the window, from the benchmark's span around
the call. Moves ``serve_tok_s``."""


def read(r):
    s = r.span_s("serve")
    return sum(s) / len(s) if s else None
