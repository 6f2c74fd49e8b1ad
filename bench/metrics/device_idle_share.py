"""Device: the share of the traced window in which no operation ran on
the chip, in % (1 - busy union / window, profiler trace)."""


def read(r):
    d = r.device
    return None if d is None else 100.0 * (1.0 - d["busy_s"] / d["window_s"])
