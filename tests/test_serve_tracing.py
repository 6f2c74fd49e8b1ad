"""The serving path's span recorder (``serve/tracing.py``) and the spans and
waste counts ``PagedServeEngine`` records with it."""

import itertools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.policy import preset
from repro.models import build_model
from repro.nn.module import unbox
from repro.serve import tracing
from repro.serve.engine import PagedServeEngine, Request
from repro.serve.tracing import Recorder, within


def _clock(step=1000):
    """A fake ``perf_counter_ns`` that advances ``step`` ns per reading."""
    ticks = itertools.count(step, step)
    return lambda: next(ticks)


def test_ring_overflow_makes_the_window_read_nothing():
    rec = Recorder(capacity=4, clock=_clock())
    for i in range(6):  # span i runs from (2i + 1) to (2i + 2) us
        with rec.span(f"s{i}"):
            pass
    # the ring overwrote s0 and s1: a window that holds s1's start is lost
    assert rec.window(0.0, 1.0) is None
    assert rec.window(3e-6, 1.0) is None
    kept = rec.window(3.5e-6, 1.0)
    assert [s.name for s in kept] == ["s2", "s3", "s4", "s5"]
    # totals never forget
    assert rec.totals()["s0"] == {"n": 1, "s": pytest.approx(1e-6)}


def test_parent_links_self_time_and_counts():
    rec = Recorder(clock=_clock())
    with rec.span("tick") as c:  # 1 .. 8 us
        with rec.span("prefill", rows=3):  # 2 .. 5
            with rec.span("step"):  # 3 .. 4
                pass
        with rec.span("decode") as d:  # 6 .. 7
            d["rows"] = 2
        c["admitted"] = 1
    spans = rec.window(0.0, 1.0)
    by = {s.name: s for s in spans}
    assert [s.name for s in spans] == ["tick", "prefill", "step", "decode"]
    assert by["tick"].parent == -1
    assert by["prefill"].parent == by["decode"].parent == by["tick"].seq
    assert by["step"].parent == by["prefill"].seq
    assert [s.name for s in within(spans, by["tick"])] == [
        "prefill", "step", "decode"]
    assert within(spans, by["step"]) == []
    # self time: the span less the part its children cover
    kids = [s for s in spans if s.parent == by["tick"].seq]
    assert by["tick"].dur - sum(s.dur for s in kids) == 7000 - 3000 - 1000
    assert by["tick"].counts == {"admitted": 1}
    rec2 = Recorder(clock=_clock())
    for rows in (3, 4):
        with rec2.span("prefill", rows=rows):
            pass
    assert rec2.totals()["prefill"]["rows"] == 7


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-7b").reduced()
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    return cfg, model, params


def _greedy(model, params, prompt, steps, policy):
    lg, st = model.prefill(params, {"tokens": jnp.asarray(prompt[None])},
                           policy, max_len=64)
    toks = [int(jnp.argmax(lg[0]))]
    for _ in range(steps - 1):
        lg, st = model.decode_step(
            params, jnp.asarray([[toks[-1]]], jnp.int32), st, policy)
        toks.append(int(jnp.argmax(lg[0])))
    return toks


def _run(eng, reqs):
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    done = {c.uid: c.tokens for c in eng.run_until_done()}
    spans = tracing.window(t0, time.perf_counter())
    return done, spans


def test_engine_counts_rows_and_pages_and_keeps_its_tokens(setup):
    cfg, model, params = setup
    pol = preset("fp32")
    rng = np.random.RandomState(5)
    lengths, max_new = (5, 11, 3, 17, 8, 2, 30), 4
    reqs = [Request(uid=i, prompt=rng.randint(0, cfg.vocab, n)
                    .astype(np.int32), max_new_tokens=max_new)
            for i, n in enumerate(lengths)]
    eng = PagedServeEngine(model, params, n_slots=3, max_len=64,
                           policy=pol, page_size=4, prefill_chunk=8)
    done, spans = _run(eng, reqs)
    for r in reqs:
        assert done[r.uid] == _greedy(model, params, r.prompt, max_new, pol)
    prefill = [s.counts for s in spans if s.name == "serve.prefill"]
    decode = [s.counts for s in spans if s.name == "serve.decode"]
    ticks = [s for s in spans if s.name == "serve.tick"]
    assert len(ticks) == eng.ticks
    assert sum(c["rows_valid"] for c in prefill) == sum(lengths)
    assert all(c["rows_computed"] == 3 * 8 for c in prefill)
    assert sum(c["rows_valid"] for c in decode) == len(reqs) * (max_new - 1)
    assert all(c["rows_computed"] == 3 for c in decode)
    assert all(c["pages_read"] == 3 * 16 for c in prefill + decode)
    admit = [s.counts for s in spans if s.name == "serve.admit"]
    assert sum(c["admitted"] for c in admit) == len(reqs)
    assert not any(c["blocked"] for c in admit)  # the pool fits 3 rows
    # every step call sits in a phase, with its preparation, read-back and
    # bookkeeping, and each phase in a tick
    for phase in (s for s in spans if s.name in ("serve.prefill",
                                                 "serve.decode")):
        kids = [s.name for s in spans if s.parent == phase.seq]
        assert kids == ["serve.prepare", "serve.step", "serve.readback",
                        "serve.update"]
        assert any(phase in within(spans, t) for t in ticks)


def test_pages_live_hand_counted_for_two_requests(setup):
    """Pages of 4, chunks of 8: prompts of 5 and 10 tokens with 3 and 2
    new tokens.  Tick 1 prefills 5 + 8 rows (context 5 and 8: 2 + 2
    pages) and decodes the first request (context 6: 2 pages); tick 2
    prefills the last 2 rows (context 10: 3 pages) and decodes both
    (context 7 and 11: 2 + 3 pages)."""
    cfg, model, params = setup
    eng = PagedServeEngine(model, params, n_slots=2, max_len=64,
                           policy=preset("fp32"), page_size=4,
                           prefill_chunk=8)
    _, spans = _run(eng, [
        Request(uid=0, prompt=np.arange(1, 6, dtype=np.int32),
                max_new_tokens=3),
        Request(uid=1, prompt=np.arange(7, 17, dtype=np.int32),
                max_new_tokens=2)])
    got = [(s.name, s.counts["rows_valid"], s.counts["pages_live"])
           for s in spans if s.name in ("serve.prefill", "serve.decode")]
    assert got == [("serve.prefill", 13, 4), ("serve.decode", 1, 2),
                   ("serve.prefill", 2, 3), ("serve.decode", 2, 5)]


@pytest.mark.parametrize("base,unit", [("fp32", 1), ("w4a8_abfp", 16)])
def test_pages_read_hand_counted_where_decode_reads_in_place(setup, base,
                                                            unit):
    """The requests of the test above on int8 pages with the compressed
    attention backend, whose decode reads each row's live pages in place.
    A prefill call still gathers the whole table, 2 x 16 pages.  A decode
    call reads each row's live pages in whole read units: one page where
    the probabilities are not quantized (contexts 6, then 7 and 11: 2,
    then 2 + 3 pages), 16 pages of 4 (one ABFP group of 64 positions)
    under w4a8_abfp (16, then 16 + 16)."""
    from repro.core.policy import with_attn_backend, with_kv_cache

    cfg, model, params = setup
    pol = with_attn_backend(with_kv_cache(
        preset(base, n_layers=cfg.n_layers), "int8"), "compressed")
    eng = PagedServeEngine(model, params, n_slots=2, max_len=64,
                           policy=pol, page_size=4, prefill_chunk=8,
                           kv="int8")
    _, spans = _run(eng, [
        Request(uid=0, prompt=np.arange(1, 6, dtype=np.int32),
                max_new_tokens=3),
        Request(uid=1, prompt=np.arange(7, 17, dtype=np.int32),
                max_new_tokens=2)])
    got = [(s.name, s.counts["pages_live"], s.counts["pages_read"])
           for s in spans if s.name in ("serve.prefill", "serve.decode")]
    by_rows = {1: [2], 2: [2, 3]}
    assert got == [
        ("serve.prefill", 4, 32),
        ("serve.decode", 2, sum(-(-p // unit) * unit for p in by_rows[1])),
        ("serve.prefill", 3, 32),
        ("serve.decode", 5, sum(-(-p // unit) * unit for p in by_rows[2]))]


def test_admission_blocked_on_pages_is_counted(setup):
    cfg, model, params = setup
    # 6 pages of 4: the first request reserves 5, so the second waits
    eng = PagedServeEngine(model, params, n_slots=2, max_len=24,
                           policy=preset("fp32"), page_size=4,
                           prefill_chunk=8, n_pages=6)
    _, spans = _run(eng, [
        Request(uid=i, prompt=np.arange(1, 13, dtype=np.int32),
                max_new_tokens=6) for i in range(2)])
    admit = [s.counts for s in spans if s.name == "serve.admit"]
    assert admit[0] == {"admitted": 1, "blocked": 1}
    assert sum(c["admitted"] for c in admit) == 2


def test_engine_counts_compressed_sites_by_contraction(setup):
    """Each step program's compressed matmul sites ride on its phase's
    span as ``qmm_kernel_sites`` / ``qmm_fallback_sites``: off the TPU the
    W4A8 sites (q, k, v, o, wi, wg, wo of the scanned block, and lm_head)
    take the einsum path; ``policy.fused`` puts all of them on the
    stored-codes kernel, which emits the same tokens."""
    cfg, model, params = setup
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab, n).astype(np.int32)
               for n in (5, 11, 3)]
    tokens, sites = {}, {}
    for fused in (False, True):
        pol = preset("w4a8_abfp")
        eng = PagedServeEngine(model, params, n_slots=2, max_len=64,
                               policy=pol.replace(fused=fused),
                               page_size=4, prefill_chunk=8, compress=True)
        done, spans = _run(eng, [Request(uid=i, prompt=p, max_new_tokens=3)
                                 for i, p in enumerate(prompts)])
        tokens[fused] = done
        sites[fused] = {(s.counts["qmm_kernel_sites"],
                         s.counts["qmm_fallback_sites"])
                        for s in spans
                        if s.name in ("serve.prefill", "serve.decode")}
    assert sites == {False: {(0, 8)}, True: {(8, 0)}}
    assert tokens[True] == tokens[False]
    # dense weights have no compressed sites
    eng = PagedServeEngine(model, params, n_slots=2, max_len=64,
                           policy=preset("fp32"), page_size=4,
                           prefill_chunk=8)
    _, spans = _run(eng, [Request(uid=0, prompt=prompts[0],
                                  max_new_tokens=2)])
    assert all(s.counts["qmm_kernel_sites"] == s.counts[
        "qmm_fallback_sites"] == 0 for s in spans if s.name == "serve.decode")


def test_serve_launcher_reports_span_totals(capsys):
    from repro.launch.serve import main

    # the totals count from the start of the process, this file's other
    # engines included
    before = tracing.totals()
    assert main(["--paged", "--n-slots", "2", "--max-len", "64",
                 "--n-requests", "3", "--max-new-tokens", "3"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "wall_s" not in out and "tokens_per_s" not in out
    spans = out["spans"]

    def added(name, key):
        return spans[name][key] - before.get(name, {}).get(key, 0)

    assert added("serve.tick", "n") == out["ticks"]
    assert added("serve.decode", "rows_valid") == 3 * 2
    assert added("serve.prefill", "rows_valid") > 0
    assert 0 < out["host_s"] < spans["serve.tick"]["s"]


@pytest.mark.parametrize("router", ["zero", "random"])
def test_engine_counts_routed_expert_rows(router):
    """A tiny Mellum2 (4 layers, 4 experts, top-2): each ``serve.prefill``
    and ``serve.decode`` span carries the step's routing summed over the
    layers.  With a zero router every expert ties and top-k takes experts
    0 and 1, so by hand: ``expert_rows`` = 2 x 4 x valid rows,
    ``experts_touched`` = 2 x 4, ``expert_rows_max`` = 4 x valid rows and
    ``expert_rows_computed`` = 4 x 2 tiles of 32 rows (a chunk holds at
    most 2 x 8 valid rows).  With a random router, every valid row still
    takes exactly 2 experts in each layer."""
    cfg = get_config("mellum2-12b-a2.5b").reduced()
    assert (cfg.n_layers, cfg.n_experts, cfg.top_k) == (4, 4, 2)
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(1)))
    if router == "zero":
        ffn = params["blocks"]["ffn"]
        params["blocks"]["ffn"] = dict(ffn, router=jnp.zeros_like(
            ffn["router"]))
    eng = PagedServeEngine(model, params, n_slots=2, max_len=64,
                           policy=preset("fp32"), page_size=4,
                           prefill_chunk=8)
    rng = np.random.RandomState(2)
    _, spans = _run(eng, [Request(uid=i, prompt=rng.randint(
        0, cfg.vocab, n).astype(np.int32), max_new_tokens=3)
        for i, n in enumerate((5, 13, 9))])
    steps = [s.counts for s in spans
             if s.name in ("serve.prefill", "serve.decode")]
    assert steps
    for c in steps:
        rows = c["rows_valid"]
        assert c["expert_rows"] == 2 * 4 * rows
        if router == "zero":
            assert c["experts_touched"] == 2 * 4
            assert c["expert_rows_max"] == 4 * rows
            assert c["expert_rows_computed"] == 4 * 2 * 32
        else:
            assert 2 * 4 <= c["experts_touched"] <= 4 * 4
            assert 4 * -(-2 * rows // 4) <= c["expert_rows_max"] <= 4 * rows
            assert c["expert_rows_computed"] % 32 == 0
            assert c["expert_rows_computed"] >= c["expert_rows"]
