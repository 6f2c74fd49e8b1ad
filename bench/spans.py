#!/usr/bin/env python3
"""The program's own spans: for metric readers, and a breakdown of a trace.

Readers of program spans and counts call ``window(r)``: the spans the
program's recorder (``repro.serve.tracing``) holds for the measured
window, or None where the program has no recorder or its ring lost part
of the window.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>

runs one traced cell as ``bench/run.py --trace 1`` does, prints its
result line, then one more JSON line with the whole of what the result
line's ``breakdown`` keeps ten of: every idle gap of the window summed by
the innermost host span that holds its midpoint, program spans
(``serve.*``) among them (``idle_s``), and the idle seconds split instant
by instant over those spans (``idle_split_s``); the device seconds of each
step kind's operations by the named-scope path of the operation
(``scope_s``, as ``bench/trace.py`` reduces it); and how far each step
program's start on the device lies after the start of the ``serve.step``
span that called it (``step_start_lag_ms``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import trace as btrace  # noqa: E402


def window(r):
    """The program's spans in the window of ``r`` (``harness.Facts``)."""
    try:
        from repro.serve import tracing
    except ImportError:  # a program without the recorder
        return None
    return tracing.window(r.t0, r.t1)


def count_share(r, phases: tuple, num: str, den: str):
    """100 x the sum of count ``num`` over the sum of ``den``, over the
    window's spans named in ``phases``."""
    spans = window(r)
    if spans is None:
        return None
    picked = [s.counts for s in spans if s.name in phases]
    total = sum(c.get(den, 0) for c in picked)
    return 100.0 * sum(c.get(num, 0) for c in picked) / total if total \
        else None


def idle_by_label(trace, calls, step_prefix, kernel) -> dict:
    """Idle seconds of every gap in the window, summed by the innermost
    host span holding the gap's midpoint (``bench/trace.py``'s labels)."""
    r = btrace.reduce(trace, calls=calls, step_prefix=step_prefix,
                      kernel=kernel, top=1 << 30)
    out: dict = {}
    for label, s in r["idle_gaps"]:
        out[label] = out.get(label, 0.0) + s
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def idle_split(trace) -> dict:
    """Idle seconds of the window, each instant of each gap given to the
    innermost host span open then ("between ticks" where none is), summed
    by span name and averaged over devices.  The device clock sits up to
    about a millisecond off the host's, so this places a gap's parts to
    within that."""
    win = next(e for e in trace.host if e.name == "bench.window")
    t0, t1 = win.start, win.end
    gaps = []
    for evs in trace.devices.values():
        busy = btrace._merged(
            c for c in (btrace._clip(e, t0, t1) for e in evs
                        if e.line == "XLA Ops") if c)
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges) - 1, 2)
                 if edges[i + 1] > edges[i]]
    segs, stack, at = [], [], t0

    def upto(t):  # the innermost span open from ``at`` to ``t``
        nonlocal at
        if t > at:
            segs.append((at, t, stack[-1].name if stack
                         else "between ticks"))
            at = t

    def close():  # the innermost open span ends
        upto(min(stack[-1].end, t1))
        stack.pop()

    for sp in sorted((e for e in trace.host if e is not win),
                     key=lambda e: (e.start, -e.end)):
        while stack and stack[-1].end <= sp.start:
            close()
        upto(min(max(sp.start, t0), t1))
        stack.append(sp)
    while stack:
        close()
    upto(t1)
    out: dict = {}
    i = 0
    for gs, ge in sorted(gaps):
        while i < len(segs) and segs[i][1] <= gs:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < ge:
            s, e, name = segs[j]
            part = (min(e, ge) - max(s, gs)) * 1e-9 / len(trace.devices)
            out[name] = out.get(name, 0.0) + part
            j += 1
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def step_start_lag_ms(trace, calls, step_prefix, max_shift: int = 8):
    """Quartiles and extremes of (device start of a step execution) -
    (start of the ``serve.step`` span that called it), in ms.  Spans and
    executions are paired in order, at the shift of up to ``max_shift``
    whose median lag lies nearest zero."""
    win = next(e for e in trace.host if e.name == "bench.window")
    steps = sorted(e.start for e in trace.host if e.name == "serve.step"
                   and win.start <= e.start <= win.end)
    per_device = list(btrace.executions(trace, calls, step_prefix)
                      .values())
    runs = [s for s, _, _ in per_device[0]] if per_device else []
    if not steps or not runs:
        return None
    best = None
    for shift in range(-max_shift, max_shift + 1):
        lags = [(runs[i + shift] - t) * 1e-6 for i, t in enumerate(steps)
                if 0 <= i + shift < len(runs)]
        if lags and (best is None
                     or abs(statistics.median(lags))
                     < abs(statistics.median(best))):
            best = lags
    q = statistics.quantiles(best, n=4) if len(best) > 1 else best * 3
    return {"n": len(best), "min": min(best), "q1": q[0], "median": q[1],
            "q3": q[2], "max": max(best)}


def breakdown(trace, *, calls, step_prefix, kernel) -> dict:
    if not trace.devices:  # a trace of no TPU
        return {}
    return {
        "idle_s": idle_by_label(trace, calls, step_prefix, kernel),
        "idle_split_s": idle_split(trace),
        "scope_s": btrace.scope_seconds(trace, calls, step_prefix),
        "step_start_lag_ms": step_start_lag_ms(trace, calls, step_prefix),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(args.workload)
    device, why = harness.find_device(cell.chips)
    if device is None:
        print(why, file=sys.stderr)
        return 3
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    extra: dict = {}
    plain = btrace.reduce

    def reduce(trace, **kw):
        btrace.reduce = plain  # which ``breakdown`` calls too
        extra.update(breakdown(trace, **kw))
        return plain(trace, **kw)

    btrace.reduce = reduce
    try:
        result = harness.run_cell(cell, args.seed, args.seconds, True,
                                  device=device, t_start=T_START)
    finally:
        btrace.reduce = plain
    print(json.dumps(result), flush=True)
    print(json.dumps(extra), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
