"""Kernel layer: the attention kernel's time against its roofline, in %.

The least time the chip could take for the kernel's needed work (the
configuration's counter, ``work_counter`` of its ``bench/plain`` module:
for the decoder, live-context codes and scales read once, queries and
outputs, QK/PV FLOPs over live positions only) is the larger of FLOPs
over the bf16 peak and bytes over HBM bandwidth; the share is that over
the kernel's summed device time in the trace (``kernel_s``: the ops of
the configuration's ``attention_kernel``)."""

from bench.work import roofline_share


def read(r):
    d, need = r.device, r.work.kernels.get(r.conf.get("attention_kernel"))
    if d is None or not d["kernel_s"] or not need or not need[0]:
        return None
    return roofline_share(need[0], need[1], d["kernel_s"], r.peaks)
