"""Reduce a profiler trace of one window to the benchmark's device numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
On a TPU its device planes are named ``/device:TPU:<n>`` and carry an
``XLA Modules`` line (one event per program execution, named
``jit_<fn>(<fingerprint>)``) and an ``XLA Ops`` line (one event per HLO
operation; a Pallas kernel appears under its own name, e.g.
``%flash_attention_quant.1 = ...``), and each op's named-scope path in its
metadata's ``tf_op`` stat.  The host plane ``/host:CPU`` holds the
benchmark's ``jax.profiler.TraceAnnotation`` spans, named ``bench.*``, and
the program's own, named ``serve.*``.  ``load`` turns the file into plain
``Event`` lists, and ``reduce`` works on those lists only, so it can be
checked on a small synthetic trace.

The device clock is synchronised to the host's only to about a
millisecond, so device work is never matched to a host span by time
alone: step programs are matched to the step calls the harness recorded
by their order, and host spans only label idle gaps.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_SPANS = ("bench.", "serve.")  # the harness's and the program's
OP_SCOPE_STAT = "tf_op"  # an op event's named-scope path, on the TPU
# name-stack parts that are no scope of the program's: transformations,
# and the control flow that JAX lowers a scan or a branch to
TRANSFORMS = ("jit(", "pjit(", "vmap(", "jvp(", "transpose(")
CONTROL = {"while", "body", "cond", "scan", "closed_call", "checkpoint",
           "remat"}


@dataclasses.dataclass(frozen=True)
class Event:
    line: str
    name: str
    start: float  # ns, on the trace's clock
    dur: float  # ns
    scope: str = ""  # an op's ``tf_op`` stat: its named-scope path

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    devices: dict  # plane name -> list[Event] (XLA Modules + XLA Ops lines)
    host: list  # Event of every bench.* and serve.* span


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
        if not DEVICE_PLANE.match(plane.name):
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        paths = out.setdefault(plane.name, {})
        for entry in plane.event_metadata:
            for st in entry.value.stats:
                if names.get(st.metadata_id) == OP_SCOPE_STAT:
                    paths.setdefault(entry.value.name, st.str_value
                                     or names.get(st.ref_value, ""))
    return out


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    with open(files[-1], "rb") as fh:
        raw = fh.read()
    data = ProfileData.from_serialized_xspace(raw)
    scopes = op_scopes(raw)
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            paths = scopes.get(plane.name, {})
            devices[plane.name] = [
                Event(line.name, e.name, float(e.start_ns),
                      float(e.duration_ns),
                      paths.get(e.name, "") if line.name == "XLA Ops"
                      else "")
                for line in plane.lines
                if line.name in ("XLA Modules", "XLA Ops")
                for e in line.events]
        elif plane.name == "/host:CPU":
            host += [Event(line.name, e.name, float(e.start_ns),
                           float(e.duration_ns))
                     for line in plane.lines for e in line.events
                     if e.name.startswith(HOST_SPANS)]
    return Trace(devices=devices, host=host)


def _merged(intervals) -> list[tuple[float, float]]:
    """The union of ``(start, end)`` intervals, as disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(ev: Event, t0: float, t1: float):
    s, e = max(ev.start, t0), min(ev.end, t1)
    return (s, e) if e > s else None


def op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """``fusion.12`` -> ``fusion``: an op's name less its numeric suffix,
    which tells the instances of one kernel apart."""
    return re.sub(r"\.\d+$", "", name)


def scope_path(op: str) -> str:
    """``jit(_step_fn)/while/body/closed_call/block/attn/q/dot_general:``
    -> ``block/attn/q``: the named scopes, without the jit and
    control-flow wrappers, einsum equations or the primitive's own
    name."""
    parts = [p for p in op.split("/")[:-1]
             if p and p not in CONTROL and not p.startswith(TRANSFORMS)
             and "->" not in p]
    return "/".join(parts)


def _step_kinds(modules: list[Event], calls: list[str],
                prefix: str, max_shift: int = 8) -> dict:
    """Fingerprint of each step program -> the call kind it ran for.

    The k-th execution of a step program on the device is the k-th step
    call the host made in the window, give or take a few executions that
    the trace dropped or caught outside the window: of the pairings
    shifted by up to ``max_shift``, the one in which the fingerprints'
    votes agree best is taken, and each fingerprint takes the kind most of
    its executions were paired with.
    """
    runs = sorted((m for m in modules if m.name.startswith(prefix)),
                  key=lambda m: m.start)
    best, best_agree = {}, -1
    for shift in sorted(range(-max_shift, max_shift + 1), key=abs):
        votes: dict = {}
        for i, kind in enumerate(calls):
            if 0 <= i + shift < len(runs):
                v = votes.setdefault(runs[i + shift].name, {})
                v[kind] = v.get(kind, 0) + 1
        agree = sum(max(v.values()) for v in votes.values())
        if agree > best_agree:
            best_agree = agree
            best = {name: max(v, key=v.get) for name, v in votes.items()}
    return best


def executions(trace: Trace, calls: list[str], step_prefix: str) -> dict:
    """Per device: the step executions ``reduce`` times (wholly in the
    window, of a known kind), as (start, end, kind), in order."""
    win = next(e for e in trace.host if e.name == "bench.window")
    out = {}
    for plane, evs in trace.devices.items():
        mods = [e for e in evs if e.line == "XLA Modules"]
        kinds = _step_kinds(mods, calls, step_prefix)
        out[plane] = sorted(
            (m.start, m.end, kinds[m.name]) for m in mods
            if m.name in kinds and win.start <= m.start
            and m.end <= win.end)
    return out


def scope_seconds(trace: Trace, calls: list[str], step_prefix: str) -> dict:
    """{step kind: {scope path: device seconds}} of the ops inside the
    executions ``reduce`` times, averaged over devices; ops of no scope (a
    layer scan's ``while`` among them) are left out."""
    out: dict = {}
    execs = executions(trace, calls, step_prefix)
    for plane, runs in execs.items():
        starts = [s for s, _, _ in runs]
        for ev in trace.devices[plane]:
            if ev.line != "XLA Ops":
                continue
            path = scope_path(ev.scope)
            i = bisect.bisect_right(starts, ev.start) - 1
            if not path or i < 0 or ev.end > runs[i][1]:
                continue
            kind = out.setdefault(runs[i][2], {})
            kind[path] = kind.get(path, 0.0) + ev.dur * 1e-9 / len(execs)
    return out


def reduce(trace: Trace, *, calls: list[str], step_prefix: str,
           kernel: str, top: int = 10) -> dict | None:
    """Device numbers of the window the ``bench.window`` span covers.

    ``calls``: the step kinds the host called, in order; ``step_prefix``:
    the module-name prefix of the step program; ``kernel``: the op kind
    (``op_kind``) whose time ``kernel_s`` gives.  Returns None where the
    trace holds no device plane or no window span: the caller then
    reports nothing.

    Besides the busy and step times: ``op_s``, {step kind or "other":
    {op kind: device seconds in the window}} for every op, summed over
    the chips; ``scope_s``, {step kind: {scope path: device seconds}}
    over the timed executions, averaged over the chips
    (``scope_seconds``).
    """
    windows = [e for e in trace.host if e.name == "bench.window"]
    if not trace.devices or not windows:
        return None
    t0, t1 = windows[0].start, windows[0].end
    window_s = (t1 - t0) * 1e-9
    busy, step_ms, ops, op_s = [], {}, {}, {}
    gaps: list[tuple[float, float]] = []
    for evs in trace.devices.values():
        mods = [e for e in evs if e.line == "XLA Modules"]
        kinds = _step_kinds(mods, calls, step_prefix)
        spans = []
        for m in mods:
            c = _clip(m, t0, t1)
            if c is None:
                continue
            kind = kinds.get(m.name, "other")
            spans.append((m.start, m.end, kind))
            if kind != "other" and c == (m.start, m.end):
                step_ms.setdefault(kind, []).append(m.dur * 1e-6)
        spans.sort()
        starts = [s for s, _, _ in spans]
        op_iv = []
        for e in evs:
            if e.line != "XLA Ops":
                continue
            c = _clip(e, t0, t1)
            if c is None:
                continue
            op_iv.append(c)
            name = op_name(e.name)
            i = bisect.bisect_right(starts, e.start) - 1
            mod = spans[i][2] if i >= 0 and e.start < spans[i][1] \
                else "other"
            key = f"{mod}/{name}"
            ops[key] = ops.get(key, 0.0) + (c[1] - c[0]) * 1e-9
            by_kind = op_s.setdefault(mod, {})
            k = op_kind(name)
            by_kind[k] = by_kind.get(k, 0.0) + (c[1] - c[0]) * 1e-9
        merged = _merged(op_iv)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges) - 1, 2)
                 if edges[i + 1] > edges[i]]
    if not any(busy):
        return None
    spans = [e for e in trace.host if e.name != "bench.window"]

    def label(s: float, e: float) -> str:
        mid = (s + e) / 2
        inside = [h for h in spans if h.start <= mid < h.end]
        return min(inside, key=lambda h: h.dur).name if inside \
            else "between ticks"

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": window_s,
        "chips": len(busy),
        "step_ms": {k: sum(v) / len(v) for k, v in step_ms.items()},
        "step_calls": {k: len(v) for k, v in step_ms.items()},
        "kernel_s": op_seconds(op_s, kernel),
        "op_s": op_s,
        "scope_s": scope_seconds(trace, calls, step_prefix),
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[label(s, e), (e - s) * 1e-9] for s, e in gaps[:top]],
    }


def op_seconds(op_s: dict, kind: str) -> float:
    """Device seconds of op kind ``kind`` over every step kind of
    ``reduce``'s ``op_s``."""
    return sum(by_kind.get(kind, 0.0) for by_kind in op_s.values())
