"""Step layer: the share of the rows that prefill calls compute which
carry a prompt token, in %: 100 x the sum of ``rows_valid`` over the sum
of ``rows_computed`` over the window's ``serve.prefill`` spans (the
program's counts)."""

from bench.spans import count_share


def read(r):
    return count_share(r, ("serve.prefill",), "rows_valid", "rows_computed")
