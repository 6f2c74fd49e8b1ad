"""Step and device: model FLOPs the window's tokens needed (``bench/work.py``:
valid prompt rows prefilled and tokens decoded, attention over live
context, the readout only where a token is emitted) over the window's
seconds, as a share of the chip's peak for the arithmetic the
configuration states (``mfu_peak`` in its file), in %."""


def read(r):
    if not r.work.model_flops:
        return None
    peak = r.peaks[r.conf["mfu_peak"]]
    return 100.0 * r.work.model_flops / r.window_s / peak
