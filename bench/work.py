"""Operations and bytes a window's tokens need, and a kernel's roofline.

An architecture's counter (``work_counter`` of ``bench/plain/<name>.py``)
is a ``Need``: the harness tells it, step call by step call, which prompt
positions each row prefilled and which positions it decoded, and it adds
what the algorithm *needs* for them, counted from shapes: valid rows
only, and attention over each query's live context only.  It never counts
what the program happens to compute: the masked rows of a prefill call
that few slots use, the padded vocabulary, or the unmapped pages a paged
step gathers.  So a later change that stops computing waste raises a
share measured against this count, where a count of what the program
computes would only go stale.
"""

from __future__ import annotations


class Need:
    """Needed model FLOPs (``model_flops``) and, for each kernel the
    configuration names, that kernel's needed FLOPs and bytes
    (``kernels[name] = [flops, bytes]``, the name as the trace's op
    names give it, less any numeric suffix)."""

    def __init__(self, kernels=()):
        self.model_flops = 0
        self.kernels = {k: [0, 0] for k in kernels}

    def prefill(self, start: int, stop: int, emits: bool) -> None:
        """Prompt positions ``[start, stop)`` of one row in one call;
        ``emits``: the chunk ends the prompt, so its last row is read out."""
        raise NotImplementedError

    def decode(self, pos: int) -> None:
        """One decoded row: the token at ``pos`` attends to ``pos + 1``
        positions and its logits are read out."""
        raise NotImplementedError


def roofline_share(flops: int, nbytes: int, seconds: float,
                   peaks: dict) -> float:
    """The least time the chip could take for ``flops`` and ``nbytes`` (the
    larger of FLOPs over the bf16 peak and bytes over HBM bandwidth) as a
    share of ``seconds`` measured, in %."""
    least = max(flops / peaks["bf16_flops"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
