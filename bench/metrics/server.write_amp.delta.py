"""Bytes the server received (``faasfs_server_bytes_in_total``) per byte
of state that a delta save changed blocks in: the counter's growth over
each delta save, summed, over the bytes of the dirty blocks. Moves
``ckpt_delta_s``."""


def read(r):
    ops = [o for o in r.done("save") if o["kind"] == "delta" and o.get("counters")]
    dirty = sum(o["bytes_written"] for o in ops)
    if not ops or not dirty:
        return None
    return sum(o["counters"]["bytes_in"] for o in ops) / dirty
