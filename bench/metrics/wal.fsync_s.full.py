"""Seconds of WAL fsync (``faasfs_wal_fsync_us``) per full save, from the
counter's growth over each full save. Moves ``ckpt_save_MBps``."""


def read(r):
    ops = [o for o in r.done("save") if o["kind"] == "full" and o.get("counters")]
    if not ops:
        return None
    return sum(o["counters"]["fsync_us"] for o in ops) / len(ops) / 1e6
