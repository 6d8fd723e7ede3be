"""Round trips a cold worker's connection made per GB it restored
(``RemoteBackend.connection_stats()["rpcs"]``). Moves ``restore_MBps``."""


def read(r):
    ops = r.done("restore")
    nbytes = sum(o["bytes"] for o in ops)
    if not ops or not nbytes:
        return None
    return sum(o["rpcs"] for o in ops) / (nbytes / 1e9)
