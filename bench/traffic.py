"""One general generator for every traffic mix file in ``bench/traffic/``.

A mix file holds parameters only: the loop (``closed``, ``open`` or
``resident``), length distributions with their clips, an arrival rate and
the context the engine must hold (``max_len``).  Sizes come in blocks:
each block of ``block`` requests holds the distribution's mid-quantiles,
once each, in an order drawn from the mix's own ``schedule_seed``; an open
loop's gaps between arrivals are drawn the same way.  ``--seed`` chooses
the token ids only.  So every seed serves the same sizes at the same
times, and two seeds differ in content, not in the amount or order of
work: a window of about one block ranks its tail by the order in which
long prompts arrive, which moved the chat cell's first-token p90 by half
between seeds when the seed chose the order (TPU v5e runs).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Req:
    uid: int
    prompt: np.ndarray  # (P,) int32 token ids
    max_new: int
    due: float = 0.0  # open loop: seconds after the window opens


def quantile_grid(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles ``(i + 0.5) / n`` of ``dist``,
    clipped to ``[min, max]`` and rounded to whole tokens."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "loguniform":
        lo, hi = math.log(dist["min"]), math.log(dist["max"])
        v = np.exp(lo + u * (hi - lo))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def exp_gap_grid(rate: float, n: int) -> np.ndarray:
    """Mid-quantiles of Poisson inter-arrival gaps at ``rate`` per second."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


class Traffic:
    """Requests of one mix for one seed, in the order the window sends them.

    ``next_request()`` yields requests lazily (a closed loop takes as many
    as the engine completes); ``arrivals(seconds)`` gives the open loop's
    schedule; ``resident()`` the requests a resident loop holds.
    """

    def __init__(self, mix: dict, seed: int, vocab: int, n_slots: int):
        self.mix = mix
        self.rng = np.random.default_rng(seed)  # token ids
        self.order = np.random.default_rng(mix["schedule_seed"])
        self.vocab = vocab
        self.n_slots = n_slots
        self.max_len = mix["max_len"]
        self.block = mix.get("block", 64)
        self._uid = 0
        self._pending: list[tuple[int, int]] = []

    # ------------------------------------------------------------- sizes
    def _sizes_block(self) -> list[tuple[int, int]]:
        b = self.block
        prompts = self.order.permutation(quantile_grid(self.mix["prompt"], b))
        outs = self.order.permutation(quantile_grid(self.mix["output"], b))
        return [(int(p), int(o)) for p, o in zip(prompts, outs)]

    def _make(self, plen: int, max_new: int, due: float = 0.0) -> Req:
        if plen + max_new > self.max_len:
            raise ValueError(
                f"mix asks for {plen} + {max_new} tokens, over max_len "
                f"{self.max_len}")
        req = Req(uid=self._uid,
                  prompt=self.rng.integers(0, self.vocab, plen,
                                           dtype=np.int32),
                  max_new=max_new, due=due)
        self._uid += 1
        return req

    def next_request(self, due: float = 0.0) -> Req:
        if not self._pending:
            self._pending = self._sizes_block()
        plen, max_new = self._pending.pop(0)
        return self._make(plen, max_new, due)

    # ------------------------------------------------------------- loops
    def clients(self) -> int:
        return self.mix["clients_per_slot"] * self.n_slots

    def arrivals(self, seconds: float) -> list[Req]:
        """Open loop: every request due in ``[0, seconds)``."""
        rate, b = self.mix["rate_per_s"], self.block
        out, t = [], 0.0
        while True:
            for gap in self.order.permutation(exp_gap_grid(rate, b)):
                t += float(gap)
                if t >= seconds:
                    return out
                out.append(self.next_request(due=t))

    def resident(self) -> list[Req]:
        """Resident loop: one request per slot, each asking for every
        token its slot can hold, so none completes inside the window."""
        plens = self.order.permutation(
            quantile_grid(self.mix["prompt"], self.n_slots))
        return [self._make(int(p), self.max_len - int(p)) for p in plens]
