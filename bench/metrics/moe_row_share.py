"""Kernel layer: the share of the rows the grouped expert kernel computes
that carry a routed token, in %: 100 x the sum of ``expert_rows`` over
the sum of ``expert_rows_computed`` over the window's ``serve.prefill``
and ``serve.decode`` spans (the program's counts; the rest pads each
expert's rows to whole tiles)."""

from bench.spans import count_share


def read(r):
    return count_share(r, ("serve.prefill", "serve.decode"), "expert_rows",
                       "expert_rows_computed")
