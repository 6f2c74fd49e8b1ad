"""Reduce a profiler trace of one window to the benchmark's device numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
On a TPU its device planes are named ``/device:TPU:<n>`` and carry an
``XLA Modules`` line (one event per program execution, named
``jit_<fn>(<fingerprint>)``) and an ``XLA Ops`` line (one event per HLO
operation; a Pallas kernel appears under its own name, e.g.
``%flash_attention_quant.1 = ...``).  The host plane ``/host:CPU`` holds
the benchmark's ``jax.profiler.TraceAnnotation`` spans, all named
``bench.*``.  ``load`` turns the file into plain ``Event`` lists, and
``reduce`` works on those lists only, so it can be checked on a small
synthetic trace.

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


@dataclasses.dataclass(frozen=True)
class Event:
    line: str
    name: str
    start: float  # ns, on the trace's clock
    dur: float  # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    devices: dict  # plane name -> list[Event] (XLA Modules + XLA Ops lines)
    host: list  # Event of every bench.* span


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = [
                Event(line.name, e.name, float(e.start_ns),
                      float(e.duration_ns))
                for line in plane.lines
                if line.name in ("XLA Modules", "XLA Ops")
                for e in line.events]
        elif plane.name == "/host:CPU":
            host += [Event(line.name, e.name, float(e.start_ns),
                           float(e.duration_ns))
                     for line in plane.lines for e in line.events
                     if e.name.startswith("bench.")]
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


def reduce(trace: Trace, *, calls: list[str], step_prefix: str,
           kernel: str, top: int = 10) -> dict | None:
    """Device numbers of the window the ``bench.window`` span covers.

    ``calls``: the step kinds the host called, in order; ``step_prefix``:
    the module-name prefix of the step program; ``kernel``: the kernel
    whose op time is summed.  Returns None where the trace holds no
    device plane or no window span: the caller then reports nothing.
    """
    windows = [e for e in trace.host if e.name == "bench.window"]
    if not trace.devices or not windows:
        return None
    t0, t1 = windows[0].start, windows[0].end
    window_s = (t1 - t0) * 1e-9
    busy, step_ms, kernel_s, ops = [], {}, 0.0, {}
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
            if re.match(rf"{re.escape(kernel)}(\.\d+)?$", name):
                kernel_s += (c[1] - c[0]) * 1e-9
            i = bisect.bisect_right(starts, e.start) - 1
            mod = spans[i][2] if i >= 0 and e.start < spans[i][1] \
                else "other"
            key = f"{mod}/{name}"
            ops[key] = ops.get(key, 0.0) + (c[1] - c[0]) * 1e-9
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
        "step_ms": {k: sum(v) / len(v) for k, v in step_ms.items()},
        "step_calls": {k: len(v) for k, v in step_ms.items()},
        "kernel_s": kernel_s,
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[label(s, e), (e - s) * 1e-9] for s, e in gaps[:top]],
    }
