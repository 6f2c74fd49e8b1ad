"""Kernel layer: the grouped expert kernel's time against its roofline,
in %.

The least time the chip could take for the kernel's needed work is the
larger of its FLOPs over the bf16 peak and its bytes over HBM bandwidth:
FLOPs and the bytes of the routed rows (in and out) from the
configuration's counter (``work_counter`` of its ``bench/plain`` module,
under the ``expert_kernel`` the configuration names), and the stored
codes and scales of the experts the step calls touched, read once per
layer (the program's ``experts_touched`` over the window's
``serve.prefill`` and ``serve.decode`` spans, times the module's
``expert_bytes``).  The share is that over the kernel's summed device time
in the trace (``op_s`` under the kernel's op name)."""

from bench.harness import architecture
from bench.spans import window
from bench.trace import op_seconds
from bench.work import roofline_share


def read(r):
    d, kind = r.device, r.conf.get("expert_kernel")
    need = r.work.kernels.get(kind) if kind else None
    if d is None or not need or not need[0]:
        return None
    seconds = op_seconds(d["op_s"], kind)
    spans = window(r)
    if not seconds or spans is None:
        return None
    touched = sum(s.counts.get("experts_touched", 0) for s in spans
                  if s.name in ("serve.prefill", "serve.decode"))
    if not touched:
        return None
    per_expert = architecture(r.conf["plain"]).expert_bytes(r.conf)
    return roofline_share(need[0], need[1] + touched * per_expert, seconds,
                          r.peaks)
