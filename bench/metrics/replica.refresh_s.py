"""Mean seconds of a replica refresh (``SnapshotServer.refresh``) in the
window, from the benchmark's span around the call. Moves ``serve_tok_s``."""


def read(r):
    s = r.span_s("refresh")
    return sum(s) / len(s) if s else None
