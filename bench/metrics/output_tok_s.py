"""Tokens served in the window over the window's seconds (host clock)."""

from bench.window import tokens_in


def read(r):
    return tokens_in(r.stamps, r.t0, r.t1) / r.window_s
