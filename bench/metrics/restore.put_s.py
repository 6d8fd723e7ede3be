"""Mean seconds of putting a restored state on the device
(``device_put`` until ready), from the benchmark's span. Moves
``restore_MBps``."""


def read(r):
    s = [o["put_s"] for o in r.done("restore")]
    return sum(s) / len(s) if s else None
