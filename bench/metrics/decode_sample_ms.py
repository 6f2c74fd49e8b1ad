"""Step layer: device time under the ``sample`` named scope per execution
of the decode step program, in ms: the ops of the step's sampling (the
sort over the vocabulary among them), from each op's named-scope path in
the profiler trace (``scope_s``, averaged over the chips)."""


def read(r):
    d = r.device
    if d is None:
        return None
    runs = d["step_calls"].get("decode", 0) / d["chips"]
    s = sum(v for path, v in d["scope_s"].get("decode", {}).items()
            if path == "sample" or path.startswith("sample/"))
    return s / runs * 1e3 if runs and s else None
