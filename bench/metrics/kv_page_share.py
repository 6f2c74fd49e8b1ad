"""Kernel layer: the share of the KV pages the step calls gather that hold
a participating row's context, in %: 100 x the sum of ``pages_live`` over
the sum of ``pages_read`` over the window's ``serve.prefill`` and
``serve.decode`` spans (the program's counts)."""

from bench.spans import count_share


def read(r):
    return count_share(r, ("serve.prefill", "serve.decode"), "pages_live",
                       "pages_read")
