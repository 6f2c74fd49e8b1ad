"""The program's spans in the benchmark: the breakdown of a synthetic trace
that holds ``serve.*`` spans and ops with named-scope stats, with
hand-counted answers, and the readers of program spans and counts on a
tiny CPU run."""

import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

from bench import harness, spans  # noqa: E402
from bench import trace as btrace  # noqa: E402
from bench.trace import Event, Trace, reduce  # noqa: E402

MS = 1e6  # ns
CALLS = ["prefill", "decode", "decode"]
PREFIX = "jit__step_fn("
NEW = ("tick_host_ms", "prefill_row_share", "kv_page_share")


def _host(program: bool):
    """Three ticks of a 100 ms window; with ``program``, the engine's own
    spans inside the harness's ``bench.tick``."""
    h = [("bench.window", 0, 100),
         ("bench.tick", 4, 44), ("bench.tick", 45, 65),
         ("bench.tick", 65.5, 86)]
    if program:
        h += [("serve.tick", 4.5, 43.5), ("serve.admit", 4.5, 6),
              ("serve.prefill", 6, 43), ("serve.prepare", 6, 9),
              ("serve.step", 9, 41), ("serve.readback", 41, 42),
              ("serve.update", 42, 43),
              ("serve.tick", 45.2, 64.8), ("serve.admit", 45.2, 45.5),
              ("serve.decode", 45.5, 64.5), ("serve.prepare", 45.5, 51),
              ("serve.step", 51, 61), ("serve.readback", 61, 62),
              ("serve.update", 62, 64.5),
              ("serve.tick", 65.7, 85.8), ("serve.admit", 65.7, 66.5),
              ("serve.decode", 66.5, 85.5), ("serve.prepare", 66.5, 71),
              ("serve.step", 71, 81), ("serve.readback", 81, 82),
              ("serve.update", 82, 85.5)]
    return [Event("python3", n, s * MS, (e - s) * MS) for n, s, e in h]


# device: a prefill program 10-40 ms and two decode programs 52-60 and
# 72-80 ms; each op as (start, end, its tf_op stat)
OPS = [(10, 30, "jit(_step_fn)/while/body/block/attn/q/dot_general"),
       (30, 40, "jit(_step_fn)/sample/sort"),
       (52, 57, "jit(_step_fn)/while"),
       (52, 54, "jit(_step_fn)/while/body/block/attn/gather/gather"),
       (57, 60, "jit(_step_fn)/sample/sort"),
       (72, 77, "jit(_step_fn)/while"),
       (72, 74, "jit(_step_fn)/while/body/block/attn/gather/gather"),
       (77, 80, "jit(_step_fn)/sample/argmax"),
       (150, 160, "jit(_step_fn)/sample/sort")]  # outside the window


def _trace(program=True):
    mods = [Event("XLA Modules", "jit__step_fn(111)", 10 * MS, 30 * MS),
            Event("XLA Modules", "jit__step_fn(222)", 52 * MS, 8 * MS),
            Event("XLA Modules", "jit__step_fn(222)", 72 * MS, 8 * MS)]
    ops = [Event("XLA Ops", f"%op.{i} = f32[8] op(x)", s * MS, (e - s) * MS,
                 scope)
           for i, (s, e, scope) in enumerate(OPS)]
    dev = "/device:TPU:0"
    return Trace(devices={dev: mods + ops}, host=_host(program))


def test_scope_path_drops_wrappers_and_the_primitive():
    assert btrace.scope_path(OPS[0][2]) == "block/attn/q"
    assert btrace.scope_path("jit(_step_fn)/sample/sort") == "sample"
    assert btrace.scope_path("jit(_step_fn)/while") == ""
    assert btrace.scope_path("") == ""


def test_scope_seconds_by_step_kind():
    got = btrace.scope_seconds(_trace(), CALLS, PREFIX)
    assert got == {
        "prefill": {"block/attn/q": pytest.approx(0.020),
                    "sample": pytest.approx(0.010)},
        "decode": {"block/attn/gather": pytest.approx(0.004),
                   "sample": pytest.approx(0.006)}}
    r = reduce(_trace(), calls=CALLS, step_prefix=PREFIX, kernel="op")
    assert r["scope_s"] == got


def test_decode_sample_ms_reads_the_sample_scope_per_decode_run():
    r = SimpleNamespace(device=reduce(_trace(), calls=CALLS,
                                      step_prefix=PREFIX, kernel="op"))
    # the two decode runs hold 57-60 ms under sample/sort and 77-80 ms
    # under sample/argmax: 6 ms over 2 runs
    assert harness.reader("decode_sample_ms")(r) == pytest.approx(3.0)
    r.device["scope_s"]["decode"].pop("sample")
    assert harness.reader("decode_sample_ms")(r) is None
    assert harness.reader("decode_sample_ms")(
        SimpleNamespace(device=None)) is None


def test_idle_gaps_take_the_innermost_program_span():
    trace = _trace()
    # idle 0-10 (mid 5: serve.admit), 40-52 (mid 46: serve.prepare),
    # 60-72 (mid 66: serve.admit), 80-100 (no tick)
    got = spans.idle_by_label(trace, CALLS, PREFIX, "k")
    assert list(got) == ["serve.admit", "between ticks", "serve.prepare"]
    assert got == {"serve.admit": pytest.approx(0.022),
                   "between ticks": pytest.approx(0.020),
                   "serve.prepare": pytest.approx(0.012)}


def test_idle_split_gives_each_instant_to_the_innermost_span():
    trace = _trace()
    # gaps 0-10, 40-52, 60-72, 80-100 ms walked through the spans of _host
    assert spans.idle_split(trace) == {
        k: pytest.approx(v * 1e-3) for k, v in {
            "between ticks": 19.5, "serve.prepare": 13.0,
            "serve.update": 7.0, "serve.step": 6.0, "serve.readback": 3.0,
            "serve.admit": 2.6, "bench.tick": 1.8, "serve.tick": 1.1}.items()}
    assert sum(spans.idle_split(_trace(program=False)).values()) == \
        pytest.approx(0.054)


def test_op_scopes_read_the_op_metadata_of_a_serialized_trace():
    cls = btrace._xspace_class()
    space = cls()
    plane = space.planes.add(name="/device:TPU:0")
    for i, name in ((1, "tf_op"), (2, "hlo_category"),
                    (3, "jit(_step_fn)/sample/jit(sort)/sort:")):
        plane.stat_metadata.add(key=i).value.name = name
    ops = plane.event_metadata
    md = ops.add(key=7).value
    md.name = "%sort.5 = f32[16] sort(x)"
    md.stats.add(metadata_id=2, str_value="sort")
    md.stats.add(metadata_id=1, ref_value=3)  # the path by reference
    md = ops.add(key=8).value
    md.name = "%fusion.1 = f32[8] fusion(y)"
    md.stats.add(metadata_id=1, str_value="jit(_step_fn)/while/body/"
                 "closed_call/block/ffn/wi/...gk,gkn->...gn/dot_general:")
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = "serve.tick"
    got = btrace.op_scopes(space.SerializeToString())
    assert list(got) == ["/device:TPU:0"]
    assert {k: btrace.scope_path(v)
            for k, v in got["/device:TPU:0"].items()} \
        == {"%sort.5 = f32[16] sort(x)": "sample",
            "%fusion.1 = f32[8] fusion(y)": "block/ffn/wi"}


def test_program_spans_leave_every_other_number_of_the_reduction():
    kw = dict(calls=CALLS, step_prefix=PREFIX, kernel="op")
    with_program = reduce(_trace(program=True), **kw)
    without = reduce(_trace(program=False), **kw)
    gaps = with_program.pop("idle_gaps"), without.pop("idle_gaps")
    assert with_program == without
    assert [s for _, s in gaps[0]] == [s for _, s in gaps[1]]
    assert [lbl for lbl, _ in gaps[1]] == [
        "between ticks", "bench.tick", "bench.tick", "bench.tick"]


def test_step_start_lag_pairs_calls_with_executions():
    trace = _trace()
    lag = spans.step_start_lag_ms(trace, CALLS, PREFIX)
    assert lag["n"] == 3
    assert lag["min"] == lag["median"] == lag["max"] == pytest.approx(1.0)


def test_readers_are_reported_by_a_tiny_traced_cpu_run():
    sys.path.insert(0, str(ROOT / "tests" / "bench"))
    from test_bench_run import _cell

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = _cell()
    cell.per_layer = [m for m in bench["per_layer"] if m["name"] in NEW]
    dev = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
    res = harness.run_cell(cell, 2**31 + 29, 1.5, True, device=dev,
                           t_start=time.perf_counter())
    assert res["correct"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) == set(NEW)
    assert got["tick_host_ms"] > 0
    assert 0 < got["prefill_row_share"] < 100
    assert 0 < got["kv_page_share"] < 100


def test_readers_on_a_hand_counted_engine_run():
    """Two slots, pages of 4, chunks of 8, max_len 64 (16 pages a row):
    prompts of 5 and 10 tokens with 3 and 2 new tokens take two prefill
    calls (5 + 8 rows, then 2) and two decode calls; the pages that hold
    the rows' context after each call are 2 + 2, 2, 3 and 2 + 3."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.policy import preset
    from repro.models import build_model
    from repro.nn.module import unbox
    from repro.serve.engine import PagedServeEngine, Request

    cfg = get_config("qwen2-7b").reduced()
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    eng = PagedServeEngine(model, params, n_slots=2, max_len=64,
                           policy=preset("fp32"), page_size=4,
                           prefill_chunk=8)
    eng.submit(Request(uid=0, prompt=np.arange(1, 6, dtype=np.int32),
                       max_new_tokens=3))
    eng.submit(Request(uid=1, prompt=np.arange(7, 17, dtype=np.int32),
                       max_new_tokens=2))
    r = SimpleNamespace(t0=time.perf_counter(), t1=None)
    eng.run_until_done()
    r.t1 = time.perf_counter()
    got = {name: harness.reader(name)(r) for name in NEW}
    assert got["prefill_row_share"] == pytest.approx(100 * 15 / 32)
    assert got["kv_page_share"] == pytest.approx(100 * 14 / 128)
    tick = (r.t1 - r.t0) * 1e3 / eng.ticks
    assert 0 < got["tick_host_ms"] < tick


def test_readers_read_nothing_without_the_program_recorder(monkeypatch):
    import repro.serve

    # as in a program that has no recorder: the import fails
    monkeypatch.delattr(repro.serve, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.serve.tracing", None)
    r = SimpleNamespace(t0=0.0, t1=time.perf_counter())
    for name in NEW:
        assert harness.reader(name)(r) is None


def test_breakdown_cli_runs_a_tiny_traced_cell(monkeypatch, capsys):
    """``bench/spans.py`` end to end on the CPU: its result line, then its
    breakdown line (empty here: a CPU trace holds no TPU plane)."""
    sys.path.insert(0, str(ROOT / "tests" / "bench"))
    from test_bench_run import _cell

    import bench.trace as btrace

    plain = btrace.reduce
    monkeypatch.setattr(harness, "load_cell", lambda name: _cell())
    monkeypatch.setattr(harness, "find_device", lambda chips: (
        {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}, None))
    assert spans.main(["--workload", "tiny", "--seed", "2147483659",
                       "--seconds", "1.5"]) == 0
    assert btrace.reduce is plain
    result, extra = capsys.readouterr().out.strip().splitlines()[-2:]
    assert json.loads(result)["correct"]
    assert json.loads(extra) == {}
