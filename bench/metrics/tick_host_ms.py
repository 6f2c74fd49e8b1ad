"""Engine layer: the host's own time in a tick, in ms: the mean over the
window's ``serve.tick`` spans of the tick's duration less the
``serve.step`` calls and ``serve.readback`` waits inside it (the
program's recorder, ``repro.serve.tracing``)."""

from bench.spans import window


def read(r):
    spans = window(r)
    if spans is None:
        return None
    from repro.serve.tracing import within

    ticks = [s for s in spans if s.name == "serve.tick"]
    if not ticks:
        return None
    host = [t.dur - sum(s.dur for s in within(spans, t)
                        if s.name in ("serve.step", "serve.readback"))
            for t in ticks]
    return sum(host) / len(host) * 1e-6
