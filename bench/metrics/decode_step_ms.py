"""Step layer: mean device time of one execution of the decode step
program in the window (profiler trace)."""


def read(r):
    return None if r.device is None else r.device["step_ms"].get("decode")
