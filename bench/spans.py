#!/usr/bin/env python3
"""The program's own spans: for metric readers, and a breakdown of a trace.

Readers of program spans and counts call ``window(r)``: the spans the
program's recorder (``repro.serve.tracing``) holds for the measured
window, or None where the program has no recorder or its ring lost part
of the window.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>

runs one traced cell as ``bench/run.py --trace 1`` does, prints its
result line, then one more JSON line with what ``bench/trace.py`` does not
give: every idle gap of the window summed by the innermost host span that
holds its midpoint, program spans (``serve.*``) among them (``idle_s``),
and the idle seconds split instant by instant over those spans
(``idle_split_s``); the device seconds of each step kind's operations by
the named-scope path of the operation (``scope_s``, from each op's
``tf_op`` stat); and how far each step program's start on the device lies
after the start of the ``serve.step`` span that called it
(``step_start_lag_ms``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import glob  # noqa: E402
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

OP_SCOPE_STAT = "tf_op"  # an op event's named-scope path, on the TPU
# name-stack parts that are no scope of the program's: transformations,
# and the control flow that JAX lowers a scan or a branch to
TRANSFORMS = ("jit(", "pjit(", "vmap(", "jvp(", "transpose(")
CONTROL = {"while", "body", "cond", "scan", "closed_call", "checkpoint",
           "remat"}


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


def scope_path(op: str) -> str:
    """``jit(_step_fn)/while/body/closed_call/block/attn/q/dot_general:``
    -> ``block/attn/q``: the named scopes, without the jit and
    control-flow wrappers, einsum equations or the primitive's own
    name."""
    parts = [p for p in op.split("/")[:-1]
             if p and p not in CONTROL and not p.startswith(TRANSFORMS)
             and "->" not in p]
    return "/".join(parts)


def _xspace_class():
    """A message class for the parts of the profiler's ``XSpace`` proto
    (``tsl/profiler/protobuf/xplane.proto``) that hold each op's metadata
    stats, which ``jax.profiler.ProfileData`` does not expose; the rest of
    the file is skipped as unknown fields."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    kinds = {"i": F.TYPE_INT64, "s": F.TYPE_STRING, "u": F.TYPE_UINT64}

    def message(name, *fields):
        m = f.message_type.add(name=name)
        for fname, number, kind in fields:
            many = kind.startswith("*")
            kind = kind.lstrip("*")
            field = m.field.add(name=fname, number=number, label=(
                F.LABEL_REPEATED if many else F.LABEL_OPTIONAL))
            if kind in kinds:
                field.type = kinds[kind]
            else:
                field.type, field.type_name = F.TYPE_MESSAGE, kind

    message("XStat", ("metadata_id", 1, "i"), ("str_value", 5, "s"),
            ("ref_value", 7, "u"))
    message("XEventMetadata", ("id", 1, "i"), ("name", 2, "s"),
            ("stats", 5, "*.bench_xplane.XStat"))
    message("XStatMetadata", ("id", 1, "i"), ("name", 2, "s"))
    # map<int64, ...> fields, as their wire form: repeated (key, value)
    message("EventMetadataEntry", ("key", 1, "i"),
            ("value", 2, ".bench_xplane.XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, "i"),
            ("value", 2, ".bench_xplane.XStatMetadata"))
    message("XPlane", ("name", 2, "s"),
            ("event_metadata", 4, "*.bench_xplane.EventMetadataEntry"),
            ("stat_metadata", 5, "*.bench_xplane.StatMetadataEntry"))
    message("XSpace", ("planes", 1, "*.bench_xplane.XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def op_scopes(data: bytes) -> dict:
    """{device plane: {op event name: its ``tf_op`` stat}} of a serialized
    ``XSpace``.  An op event's name is its HLO instruction; where one
    instruction text appears in two programs, the first one's path is
    kept."""
    space = _xspace_class().FromString(data)
    out = {}
    for plane in space.planes:
        if not btrace.DEVICE_PLANE.match(plane.name):
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        paths = out.setdefault(plane.name, {})
        for entry in plane.event_metadata:
            for st in entry.value.stats:
                if names.get(st.metadata_id) == OP_SCOPE_STAT:
                    paths.setdefault(entry.value.name, st.str_value
                                     or names.get(st.ref_value, ""))
    return out


def load(log_dir: str):
    """The newest ``.xplane.pb`` under ``log_dir``, as ``bench/trace.py``
    reads it but with the program's ``serve.*`` spans among the host
    events; and each device's op events as (start, end, ``tf_op``)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    with open(files[-1], "rb") as fh:
        raw = fh.read()
    data = ProfileData.from_serialized_xspace(raw)
    scopes = op_scopes(raw)
    devices, ops, host = {}, {}, []
    for plane in data.planes:
        if btrace.DEVICE_PLANE.match(plane.name):
            paths = scopes.get(plane.name, {})
            evs = [btrace.Event(line.name, e.name, float(e.start_ns),
                                float(e.duration_ns))
                   for line in plane.lines
                   if line.name in ("XLA Modules", "XLA Ops")
                   for e in line.events]
            devices[plane.name] = evs
            ops[plane.name] = [(e.start, e.end, paths.get(e.name, ""))
                               for e in evs if e.line == "XLA Ops"]
        elif plane.name == "/host:CPU":
            host += [btrace.Event(line.name, e.name, float(e.start_ns),
                                  float(e.duration_ns))
                     for line in plane.lines for e in line.events
                     if e.name.startswith(("bench.", "serve."))]
    return btrace.Trace(devices=devices, host=host), ops


def _executions(trace, calls, step_prefix):
    """Per device: the step executions ``bench/trace.py`` times (wholly in
    the window, of a known kind), as (start, end, kind), in order."""
    win = next(e for e in trace.host if e.name == "bench.window")
    out = {}
    for plane, evs in trace.devices.items():
        mods = [e for e in evs if e.line == "XLA Modules"]
        kinds = btrace._step_kinds(mods, calls, step_prefix)
        out[plane] = sorted(
            (m.start, m.end, kinds[m.name]) for m in mods
            if m.name in kinds and win.start <= m.start
            and m.end <= win.end)
    return out


def scope_seconds(trace, ops, calls, step_prefix) -> dict:
    """{step kind: {scope path: device seconds}} over the executions
    ``bench/trace.py`` times, averaged over devices; ops of no scope (a
    layer scan's ``while`` among them) are left out."""
    out: dict = {}
    execs = _executions(trace, calls, step_prefix)
    for plane, runs in execs.items():
        starts = [s for s, _, _ in runs]
        for s, e, op in ops[plane]:
            path = scope_path(op)
            i = bisect.bisect_right(starts, s) - 1
            if not path or i < 0 or e > runs[i][1]:
                continue
            kind = out.setdefault(runs[i][2], {})
            kind[path] = kind.get(path, 0.0) + (e - s) * 1e-9 / len(execs)
    return out


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
    per_device = list(_executions(trace, calls, step_prefix).values())
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


def breakdown(log_dir: str, *, calls, step_prefix, kernel) -> dict:
    trace, ops = load(log_dir)
    if not trace.devices:  # a trace of no TPU
        return {}
    return {
        "idle_s": idle_by_label(trace, calls, step_prefix, kernel),
        "idle_split_s": idle_split(trace),
        "scope_s": scope_seconds(trace, ops, calls, step_prefix),
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

    def reduce(trace, **kw):  # the trace is read again before it goes
        btrace.reduce = plain  # which ``breakdown`` calls too
        extra.update(breakdown(str(harness.TRACE_DIR), **kw))
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
