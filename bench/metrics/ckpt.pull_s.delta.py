"""Mean seconds of pulling the state off the device (``device_get``) before
a delta save, from the benchmark's span. Moves ``ckpt_delta_s``."""


def read(r):
    s = [o["pull_s"] for o in r.done("save") if o["kind"] == "delta"]
    return sum(s) / len(s) if s else None
