"""Decide ``correct``: the plain reference scores what the window served.

Once the window has closed and the engine is freed, a sample drawn from
the seed of the requests the window finished (with a resident mix, whose
requests outlast the window, also those in flight with their tokens so
far), the longest always among them, is run through the plain reference
of the configuration's architecture (``Reference`` of
``bench/plain/<name>.py``, judged by ``bench/reference.Judge``),
teacher-forced over each prompt and its served tokens.  At each served
position it reads how far the reference's logit of the served token lies
below the reference's best.  The cell's file (``bench/cells/<cell>.json``)
names the number compared, the widest such gap over the sample
(``max_gap``) or their mean (``mean_gap``), with its limit and the
readings the limit was set from.  The served tokens are greedy, so a
sound program serves the reference's best token up to the rounding the
configuration states.

Besides, every request finished in the window must have served exactly
the tokens it asked for (no end-of-sequence token is set): an exact count
with the limit 0.

``control`` (never used by the benchmark's own runs; a lower precision of
the plain reference, ``bench/reference.py``) puts the control in the
program's place: at each of those positions the token that the reference
at that precision puts first stands in for the served one, and the same
comparison reads its gap against the same limit, so the run has to come
out not correct.  That is how each limit's upper reading is taken; the
program's own readings are then reported beside it.
"""

from __future__ import annotations

import sys

import numpy as np

from bench import model as bmodel


def collect(ticker, engine, t0: float, t1: float, loop: str) -> dict:
    """What the window served: ``{"done": [(uid, prompt, tokens)],
    "short": n}``: every request finished in the window and, for a
    resident mix, every request still in a slot with its tokens so far."""
    from bench.harness import WARM_UID

    done, short = [], 0
    for c in engine.done:
        st = ticker.stamps.get(c.uid, [])
        if c.uid >= WARM_UID or not st or not t0 < st[-1] <= t1:
            continue
        req = ticker.reqs[c.uid]
        short += len(c.tokens) != req.max_new
        done.append((c.uid, np.asarray(req.prompt), list(c.tokens)))
    if loop == "resident":
        done += [(r.uid, np.asarray(r.prompt), list(engine.generated[s]))
                 for s, r in enumerate(engine.req) if r is not None]
    return {"done": done, "short": short}


def sample(done: list, seed: int, tokens: int) -> list:
    """The longest request, then others drawn from the seed, until the
    sample holds ``tokens`` served tokens."""
    if not done:
        return []
    done = sorted(done, key=lambda d: d[0])
    longest = max(range(len(done)),
                  key=lambda i: len(done[i][1]) + len(done[i][2]))
    rng = np.random.default_rng([seed, 3])
    order = [longest] + [int(i) for i in rng.permutation(len(done))
                         if i != longest]
    out, n = [], 0
    for i in order:
        if n >= tokens:
            break
        out.append(done[i])
        n += len(done[i][2])
    return out


def readings(gaps: list) -> dict:
    """The numbers a cell may compare, over every served position."""
    g = np.concatenate(gaps) if gaps else np.zeros(1)
    return {"max_gap": float(g.max()), "mean_gap": float(g.mean()),
            "flip_share": float((g > 0).mean())}


def check(cell, seed: int, served: dict, control: str | None = None
          ) -> tuple[dict, dict | None]:
    """The numbers compared, each ``{"value": v, "limit": l}``, and, with
    ``control``, the program's own readings (None without)."""
    lim = cell.check
    picked = sample(served["done"], seed, lim["sample_tokens"])
    plain = cell.plain
    ref = plain.Reference(cell.conf,
                          bmodel.make_weights(plain, cell.conf, seed))
    gaps, cgaps = [], []
    for _, prompt, tokens in picked:
        r = ref.judge(prompt, tokens, control=control)
        gaps.append(r["gap"])
        if control:
            cgaps.append(r["control_gap"])
    got = readings(gaps)
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    log(f"reference: {len(picked)} requests, "
        f"{sum(len(t) for _, _, t in picked)} served tokens compared; "
        f"program {got}")
    program = None
    if control:
        program, got = got, readings(cgaps)
        log(f"control {control} in the program's place: {got}")
    name = lim["number"]
    out = {"nothing_compared": {"value": int(not picked), "limit": 0},
           "short_answers": {"value": served["short"], "limit": 0},
           name: {"value": got[name], "limit": lim["limit"]}}
    for k, c in out.items():
        log(f"{k} {c['value']!r} limit {c['limit']!r}")
    return out, program
