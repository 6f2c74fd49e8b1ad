"""95th percentile of every gap between consecutive tokens of a request
whose later token fell in the window, over all requests (host clock)."""

from bench.window import inter_token_gaps, percentile


def read(r):
    gaps = inter_token_gaps(r.stamps, r.t0, r.t1)
    return percentile(gaps, 0.95) * 1e3 if gaps else None
