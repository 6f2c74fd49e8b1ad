"""The reduction from a profiler trace to the benchmark's device numbers,
on a small synthetic trace with hand-counted answers."""

import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest  # noqa: E402

from bench.trace import (Event, Trace, _step_kinds, load,  # noqa: E402
                         op_kind, op_name, op_seconds, reduce)

MS = 1e6  # ns


def _trace():
    """A 100 ms window: prefill program 10-40 ms, decode programs 50-60 and
    70-80 ms, one helper program 85-86 ms; a kernel of 5 ms in the prefill
    and 2 ms in each decode; ops overlap in the prefill (10-30, 20-40)."""
    mod = "XLA Modules"
    ops = "XLA Ops"
    dev = [
        Event(mod, "jit__step_fn(111)", 10 * MS, 30 * MS),
        Event(mod, "jit__step_fn(222)", 50 * MS, 10 * MS),
        Event(mod, "jit__step_fn(222)", 70 * MS, 10 * MS),
        Event(mod, "jit_scatter(9)", 85 * MS, 1 * MS),
        Event(ops, "%fusion.1 = f32[8] fusion(x)", 10 * MS, 20 * MS),
        Event(ops, "%flash_attention_quant.3 = f32[8] custom-call(x)",
              20 * MS, 20 * MS),
        Event(ops, "%flash_attention_quant = f32[8] custom-call(x)",
              50 * MS, 2 * MS),
        Event(ops, "%fusion.1 = f32[8] fusion(y)", 52 * MS, 8 * MS),
        Event(ops, "%flash_attention_quant = f32[8] custom-call(x)",
              70 * MS, 2 * MS),
        Event(ops, "%fusion.1 = f32[8] fusion(y)", 72 * MS, 8 * MS),
        Event(ops, "%scatter.2 = s32[2] scatter(z)", 85 * MS, 1 * MS),
        # outside the window: never counted
        Event(ops, "%fusion.9 = f32[8] fusion(y)", 150 * MS, 30 * MS),
    ]
    host = [
        Event("python3", "bench.window", 0, 100 * MS),
        Event("python3", "bench.tick", 5 * MS, 40 * MS),
        Event("python3", "bench.prefill", 9 * MS, 32 * MS),
        Event("python3", "bench.tick", 45 * MS, 20 * MS),
        Event("python3", "bench.admit", 45 * MS, 4 * MS),
        Event("python3", "bench.decode", 49 * MS, 12 * MS),
        Event("python3", "bench.tick", 66 * MS, 20 * MS),
        Event("python3", "bench.decode", 69 * MS, 12 * MS),
    ]
    return Trace(devices={"/device:TPU:0": dev}, host=host)


def _reduced():
    return reduce(_trace(), calls=["prefill", "decode", "decode"],
                  step_prefix="jit__step_fn(", kernel="flash_attention_quant")


def test_busy_union_and_idle_share():
    r = _reduced()
    # busy: 10-40 (overlapping ops merged), 50-60, 70-80, 85-86 = 51 ms
    assert r["busy_s"] == pytest.approx(0.051)
    assert r["window_s"] == pytest.approx(0.100)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.49)


def test_step_time_per_kind_matched_by_call_order():
    r = _reduced()
    assert r["step_ms"] == pytest.approx({"prefill": 30.0, "decode": 10.0})
    assert r["step_calls"] == {"prefill": 1, "decode": 2}


def test_step_kinds_survive_a_dropped_execution():
    # the trace lost the window's first execution: paired in plain order,
    # every prefill program would vote for the decode call after it
    calls = ["prefill", "decode", "decode"] * 5
    fp = {"prefill": "jit__step_fn(111)", "decode": "jit__step_fn(222)"}
    runs = [Event("XLA Modules", fp[k], i * MS, MS)
            for i, k in enumerate(calls)]
    assert _step_kinds(runs[1:], calls, "jit__step_fn(") == {
        fp["prefill"]: "prefill", fp["decode"]: "decode"}


def test_kernel_time_sums_every_instance():
    assert _reduced()["kernel_s"] == pytest.approx(0.024)


def test_breakdown_ops_and_gaps_are_labelled():
    r = _reduced()
    ops = dict(r["device_ops"])
    assert ops["prefill/fusion.1"] == pytest.approx(0.020)
    assert ops["decode/fusion.1"] == pytest.approx(0.016)
    assert ops["other/scatter.2"] == pytest.approx(0.001)
    # idle 0-10, 40-50, 60-70, 80-85 and 86-100 ms, longest first, each
    # named by the narrowest host span holding its midpoint
    assert r["idle_gaps"] == [
        ["between ticks", pytest.approx(0.014)],
        ["bench.tick", pytest.approx(0.010)],
        ["bench.admit", pytest.approx(0.010)],
        ["between ticks", pytest.approx(0.010)],
        ["bench.tick", pytest.approx(0.005)],
    ]


def test_op_seconds_cover_every_op_by_step_kind():
    r = _reduced()
    want = {"prefill": {"fusion": 0.020, "flash_attention_quant": 0.020},
            "decode": {"flash_attention_quant": 0.004, "fusion": 0.016},
            "other": {"scatter": 0.001}}
    assert set(r["op_s"]) == set(want)
    for kind, ops in want.items():
        assert r["op_s"][kind] == pytest.approx(ops)
    assert r["kernel_s"] == op_seconds(r["op_s"], "flash_attention_quant")
    assert op_seconds(r["op_s"], "fusion") == pytest.approx(0.036)
    assert op_seconds(r["op_s"], "absent") == 0.0
    # no op of this trace carries a named scope
    assert r["scope_s"] == {} and r["chips"] == 1


def test_scope_seconds_follow_each_ops_scope_path():
    t = _trace()
    path = "jit(_step_fn)/while/body/closed_call/block/ffn/wi/dot_general:"
    dev = [dataclasses.replace(e, scope=path)
           if e.name.startswith("%fusion") else e
           for e in t.devices["/device:TPU:0"]]
    r = reduce(Trace(devices={"/device:TPU:0": dev}, host=t.host),
               calls=["prefill", "decode", "decode"],
               step_prefix="jit__step_fn(", kernel="flash_attention_quant")
    # fusion.9 lies outside the window and outside every timed execution
    assert r["scope_s"] == {"prefill": {"block/ffn/wi": pytest.approx(0.020)},
                            "decode": {"block/ffn/wi": pytest.approx(0.016)}}
    assert r["kernel_s"] == pytest.approx(0.024)


def test_load_keeps_the_harness_and_program_spans(tmp_path):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for name in ("bench.window", "serve.tick", "other.span"):
        with jax.profiler.TraceAnnotation(name):
            jax.block_until_ready(jax.numpy.ones(4) + 1)
    jax.profiler.stop_trace()
    names = {e.name for e in load(str(tmp_path)).host}
    assert names == {"bench.window", "serve.tick"}


def test_no_device_plane_reads_nothing():
    t = _trace()
    assert reduce(Trace(devices={}, host=t.host), calls=[],
                  step_prefix="x", kernel="k") is None


def test_op_name():
    assert op_name("%fusion.12 = f32[2] fusion(%a)") == "fusion.12"
    assert op_kind("fusion.12") == "fusion"
    assert op_kind("quant_matmul_codes.39") == "quant_matmul_codes"
    assert op_kind("flash_attention_quant") == "flash_attention_quant"
