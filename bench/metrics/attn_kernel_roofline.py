"""Kernel layer: the attention kernel's time against its roofline, in %.

The least time the chip could take for the kernel's needed work
(``bench/work.py``: live-context codes and scales read once, queries and
outputs, QK/PV FLOPs over live positions only) is the larger of FLOPs
over the bf16 peak and bytes over HBM bandwidth; the share is that over
the kernel's summed device time in the trace."""


def read(r):
    d, w = r.device, r.work
    if d is None or not d["kernel_s"] or not w.kernel_flops:
        return None
    least = max(w.kernel_flops / r.peaks["bf16_flops"],
                w.kernel_bytes / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / d["kernel_s"]
