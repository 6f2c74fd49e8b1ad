"""Continuous-batching engine: exactness vs straight decode, eviction,
slot reuse, quantized serving; paged-KV engine: identity vs fixed slots,
page accounting, admission under exhaustion, INT8-KV quality."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.policy import preset, with_kv_cache
from repro.models import build_model
from repro.nn.module import unbox
from repro.serve.engine import (Completion, PagedServeEngine, Request,
                                ServeEngine, TickBudgetExhausted)
from repro.serve.kv_pages import pages_for


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-7b").reduced()
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    return cfg, model, params


def _greedy_reference(model, params, prompt, steps, policy):
    lg, st = model.prefill(params, {"tokens": jnp.asarray(prompt[None])},
                           policy, max_len=64)
    toks = [int(jnp.argmax(lg[0]))]
    for _ in range(steps - 1):
        cur = jnp.asarray([[toks[-1]]], jnp.int32)
        lg, st = model.decode_step(params, cur, st, policy)
        toks.append(int(jnp.argmax(lg[0])))
    return toks


def test_engine_matches_straight_decode(setup):
    cfg, model, params = setup
    pol = preset("fp32")
    prompts = [
        np.array([5, 9, 3, 7], np.int32),
        np.array([1, 2, 3, 4, 5, 6], np.int32),
        np.array([100, 42], np.int32),
    ]
    refs = [_greedy_reference(model, params, p, 5, pol) for p in prompts]

    eng = ServeEngine(model, params, n_slots=2, max_len=64, policy=pol)
    for i, p in enumerate(prompts):  # 3 requests > 2 slots: queueing
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=5))
    done = eng.run_until_done()
    assert len(done) == 3
    by_uid = {c.uid: c.tokens for c in done}
    for i, ref in enumerate(refs):
        assert by_uid[i] == ref, f"request {i} diverged"


def test_engine_eos_eviction(setup):
    cfg, model, params = setup
    pol = preset("fp32")
    prompt = np.array([5, 9, 3, 7], np.int32)
    ref = _greedy_reference(model, params, prompt, 8, pol)
    eos = ref[2]  # make the 3rd generated token the EOS
    eng = ServeEngine(model, params, n_slots=1, max_len=64, policy=pol)
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=8, eos_id=eos))
    done = eng.run_until_done()
    assert done[0].finished_reason == "eos"
    assert done[0].tokens == ref[:3]


def test_engine_slot_reuse_and_utilization(setup):
    cfg, model, params = setup
    eng = ServeEngine(model, params, n_slots=2, max_len=64,
                      policy=preset("fp32"))
    for i in range(5):
        eng.submit(Request(uid=i, prompt=np.array([i + 1, i + 2], np.int32),
                           max_new_tokens=3))
    done = eng.run_until_done()
    assert len(done) == 5
    assert {c.uid for c in done} == set(range(5))
    assert all(len(c.tokens) == 3 for c in done)


def test_engine_quantized_policy_runs(setup):
    cfg, model, params = setup
    pol = preset("w4a8_abfp")
    prompt = np.array([3, 1, 4, 1, 5], np.int32)
    ref = _greedy_reference(model, params, prompt, 4, pol)
    eng = ServeEngine(model, params, n_slots=2, max_len=64, policy=pol)
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=4))
    done = eng.run_until_done()
    assert done[0].tokens == ref


def test_engine_rejects_oversized_request(setup):
    cfg, model, params = setup
    eng = ServeEngine(model, params, n_slots=1, max_len=16,
                      policy=preset("fp32"))
    with pytest.raises(ValueError, match="exceeds engine max_len"):
        eng.submit(Request(uid=0, prompt=np.zeros(12, np.int32),
                           max_new_tokens=8))


def test_engine_eos_evict_readmit_determinism(setup):
    """Slot eviction on EOS + re-admission must be invisible to results.

    A request stream where some sequences finish early by EOS — freeing
    slots that queued requests immediately re-use mid-flight — must produce
    exactly the completions of serving every request alone in a fresh
    single-slot engine.  This pins the continuous-batching bookkeeping
    (cache scatter, per-slot positions, cur_token handoff) as deterministic
    and isolation-safe.
    """
    cfg, model, params = setup
    pol = preset("fp32")
    prompts = [
        np.array([5, 9, 3, 7], np.int32),
        np.array([1, 2, 3, 4, 5, 6], np.int32),
        np.array([100, 42], np.int32),
        np.array([11, 13, 17], np.int32),
        np.array([2, 71, 82, 81, 8], np.int32),
    ]
    greedy = [_greedy_reference(model, params, p, 8, pol) for p in prompts]
    # EOS choices force mid-flight evictions: req0 stops at its 3rd
    # generated token, req3 at its very first (prefill-time eviction and
    # immediate slot reuse); the rest run to max length.
    eos_ids = [greedy[0][2], None, None, greedy[3][0], None]
    reqs = [
        Request(uid=i, prompt=p, max_new_tokens=8, eos_id=e)
        for i, (p, e) in enumerate(zip(prompts, eos_ids))
    ]

    # sequential single-request serving (fresh 1-slot engine per request)
    seq_done = {}
    for r in reqs:
        eng = ServeEngine(model, params, n_slots=1, max_len=64, policy=pol)
        eng.submit(Request(uid=r.uid, prompt=r.prompt,
                           max_new_tokens=r.max_new_tokens, eos_id=r.eos_id))
        seq_done[r.uid] = eng.run_until_done()[0]
    assert seq_done[0].finished_reason == "eos"
    assert seq_done[3].finished_reason == "eos"
    assert len(seq_done[3].tokens) == 1

    # continuous batching: 2 slots over 5 requests -> queueing + reuse
    eng = ServeEngine(model, params, n_slots=2, max_len=64, policy=pol)
    for r in reqs:
        eng.submit(r)
    batched = {c.uid: c for c in eng.run_until_done()}
    assert set(batched) == set(seq_done)
    for uid, ref in seq_done.items():
        got = batched[uid]
        assert got.tokens == ref.tokens, f"request {uid} diverged"
        assert got.finished_reason == ref.finished_reason, uid


def test_engine_interleaved_admission_isolation(setup):
    """A request admitted mid-flight must not perturb a running slot."""
    cfg, model, params = setup
    pol = preset("fp32")
    pa = np.array([5, 9, 3, 7], np.int32)
    pb = np.array([8, 8, 8], np.int32)
    ref_a = _greedy_reference(model, params, pa, 6, pol)

    eng = ServeEngine(model, params, n_slots=2, max_len=64, policy=pol)
    eng.submit(Request(uid=0, prompt=pa, max_new_tokens=6))
    eng.tick()  # A runs alone for 2 ticks
    eng.tick()
    eng.submit(Request(uid=1, prompt=pb, max_new_tokens=3))  # B joins late
    done = eng.run_until_done()
    a = next(c for c in done if c.uid == 0)
    assert a.tokens == ref_a


# ---------------------------------------------------------------------------
# bucketed prefill + tick budget (the PR-7 bugfixes)
# ---------------------------------------------------------------------------
def _mixed_trace(vocab, lengths=(5, 11, 3, 17, 8, 2), max_new=5, seed=3):
    rng = np.random.default_rng(seed)
    return [
        Request(uid=i, prompt=rng.integers(
            1, vocab - 1, size=int(n)).astype(np.int32),
            max_new_tokens=max_new)
        for i, n in enumerate(lengths)
    ]


def test_bucketed_prefill_bounds_compile_count(setup):
    """Mixed prompt lengths must reuse bucketed prefill programs: the
    compile-cache key count is bounded by the number of buckets spanned,
    not by the number of distinct lengths — and the padded prefill stays
    token-identical to straight decode."""
    cfg, model, params = setup
    pol = preset("fp32")
    reqs = _mixed_trace(cfg.vocab)  # 5 distinct lengths in (0, 24]
    refs = {r.uid: _greedy_reference(model, params, r.prompt,
                                     r.max_new_tokens, pol) for r in reqs}
    eng = ServeEngine(model, params, n_slots=3, max_len=64, policy=pol,
                      prefill_bucket=8)
    for r in reqs:
        eng.submit(r)
    done = {c.uid: c.tokens for c in eng.run_until_done()}
    # lengths 5,11,3,17,8,2 span buckets {8, 16, 24} -> exactly 3 programs
    assert eng.prefill_compiles <= 3, eng.prefill_compiles
    for uid, ref in refs.items():
        assert done[uid] == ref, f"request {uid} diverged under bucketing"


def test_run_until_done_budget_raises_with_partials(setup):
    """An exhausted tick budget must raise — carrying the partial
    completions and the unfinished uids — never silently return less work
    than was submitted."""
    cfg, model, params = setup
    eng = ServeEngine(model, params, n_slots=1, max_len=64,
                      policy=preset("fp32"))
    for r in _mixed_trace(cfg.vocab, lengths=(4, 6, 3), max_new=6):
        eng.submit(r)
    with pytest.raises(TickBudgetExhausted) as ei:
        eng.run_until_done(max_ticks=7)  # 3 requests x 5 decode ticks > 7
    exc = ei.value
    assert exc.max_ticks == 7
    done_uids = {c.uid for c in exc.completions}
    assert set(exc.unfinished) == {0, 1, 2} - done_uids
    assert exc.unfinished  # something genuinely unfinished
    # a sufficient budget still returns normally
    eng2 = ServeEngine(model, params, n_slots=1, max_len=64,
                       policy=preset("fp32"))
    for r in _mixed_trace(cfg.vocab, lengths=(4, 6, 3), max_new=6):
        eng2.submit(r)
    assert len(eng2.run_until_done(max_ticks=100)) == 3


def test_fixed_engine_rejects_fp8_kv(setup):
    cfg, model, params = setup
    with pytest.raises(ValueError, match="paged-only"):
        ServeEngine(model, params, n_slots=1, max_len=32,
                    policy=with_kv_cache(preset("w4a8_abfp"), "fp8"))


# ---------------------------------------------------------------------------
# paged-KV engine
# ---------------------------------------------------------------------------
def test_paged_engine_token_identical_to_fixed(setup):
    """Paged serving (block pool, chunked prefill interleaved with decode,
    mid-flight evictions and re-admissions) must emit exactly the fixed-
    slot engine's tokens on the same mixed-length trace."""
    cfg, model, params = setup
    for pol in (preset("fp32"), preset("w4a8_abfp")):
        reqs = _mixed_trace(cfg.vocab)
        fixed = ServeEngine(model, params, n_slots=3, max_len=64,
                            policy=pol, prefill_bucket=8)
        for r in reqs:
            fixed.submit(r)
        fdone = {c.uid: c.tokens for c in fixed.run_until_done()}

        paged = PagedServeEngine(model, params, n_slots=3, max_len=64,
                                 policy=pol, page_size=4, prefill_chunk=8)
        for r in _mixed_trace(cfg.vocab):
            paged.submit(r)
        pdone = {c.uid: c.tokens for c in paged.run_until_done()}
        assert pdone == fdone, pol.name


def test_paged_eos_eviction_frees_pages(setup):
    """EOS eviction mid-flight returns the slot's pages to the pool; the
    total alloc/free accounting balances to zero residency."""
    cfg, model, params = setup
    pol = preset("fp32")
    prompt = np.array([5, 9, 3, 7], np.int32)
    ref = _greedy_reference(model, params, prompt, 8, pol)
    eng = PagedServeEngine(model, params, n_slots=2, max_len=64,
                           policy=pol, page_size=4, prefill_chunk=8)
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=8,
                       eos_id=ref[2]))
    eng.submit(Request(uid=1, prompt=np.array([1, 2, 3], np.int32),
                       max_new_tokens=4))
    done = {c.uid: c for c in eng.run_until_done()}
    assert done[0].finished_reason == "eos"
    assert done[0].tokens == ref[:3]
    st = eng.page_stats()
    assert st["pages_in_use"] == 0
    assert st["page_allocs"] == st["page_frees"] > 0
    assert st["pages_peak"] > 0


def test_paged_admission_waits_for_pages(setup):
    """A pool too small for all requests at once forces queue waits; FCFS
    admission must still complete everything, stay token-identical, and
    never exceed the pool."""
    cfg, model, params = setup
    pol = preset("fp32")
    reqs = _mixed_trace(cfg.vocab)
    refs = {r.uid: _greedy_reference(model, params, r.prompt,
                                     r.max_new_tokens, pol) for r in reqs}
    # requests reserve pages_for(len + 5, 4) in {2..6} pages; an 8-page
    # pool fits only ~2 concurrently even though 3 slots are free
    # (max_len=24 keeps max_pages_per_seq=6 <= n_pages, so the geometry
    # gate still passes while the pool genuinely starves)
    eng = PagedServeEngine(model, params, n_slots=3, max_len=24,
                           policy=pol, page_size=4, prefill_chunk=8,
                           n_pages=8)
    for r in reqs:
        eng.submit(r)
    saw_wait = False
    spent = 0
    while eng._has_work():
        assert spent < 500
        # queue non-empty while a slot is free == admission blocked on pages
        free_slots = int((~(eng.active | eng.prefilling)).sum())
        if eng.queue and free_slots > 0:
            head = eng.queue[0]
            need = pages_for(len(head.prompt) + head.max_new_tokens, 4)
            if not eng.pool.can_alloc(need):
                saw_wait = True
        assert eng.page_stats()["pages_in_use"] <= 8
        eng.tick()
        spent += 1
    done = {c.uid: c.tokens for c in eng.done}
    for uid, ref in refs.items():
        assert done[uid] == ref, f"request {uid} diverged under paging"
    assert eng.page_stats()["pages_in_use"] == 0
    assert saw_wait, "pool never actually gated admission; grow the trace"


def test_paged_int8_kv_quality_close_to_fp(setup):
    """INT8 KV pages (monotone per-(page, head) requant) must track the
    fp-paged teacher-forced perplexity closely on the reduced model."""
    cfg, model, params = setup
    pol = preset("fp32")
    rng = np.random.default_rng(7)
    tokens = rng.integers(1, cfg.vocab - 1, size=24).astype(np.int32)

    def teacher_forced_ppl(kv):
        eng = PagedServeEngine(model, params, n_slots=1, max_len=32,
                               policy=pol, page_size=4, prefill_chunk=8,
                               kv=kv)
        state = eng.state
        table = np.full((1, eng.geometry.max_pages_per_seq), -1, np.int32)
        table[0, :8] = eng.pool.alloc(8)
        state = state._replace(pages=state.pages._replace(
            table=jnp.asarray(table)))
        logps = []
        for t in range(len(tokens) - 1):
            lg, state = model.paged_step(
                params, jnp.asarray(tokens[t][None, None]), state,
                n_valid=jnp.asarray([1]), policy=pol)
            lp = jax.nn.log_softmax(lg[0].astype(jnp.float32))
            logps.append(float(lp[tokens[t + 1]]))
        return float(np.exp(-np.mean(logps)))

    ppl_fp = teacher_forced_ppl("fp")
    ppl_i8 = teacher_forced_ppl("int8")
    assert abs(ppl_i8 - ppl_fp) / ppl_fp < 0.05, (ppl_fp, ppl_i8)


def test_paged_geometry_validation(setup):
    cfg, model, params = setup
    with pytest.raises(ValueError, match="not a multiple of the KV page"):
        PagedServeEngine(model, params, n_slots=1, max_len=32,
                         policy=preset("fp32"), page_size=4,
                         prefill_chunk=10)
    with pytest.raises(ValueError, match="cannot admit a maximal request"):
        PagedServeEngine(model, params, n_slots=1, max_len=32,
                         policy=preset("fp32"), page_size=4, n_pages=4)


# ------------------------------------- paged decode reading pages in place
def _compressed(cfg, base, kv):
    from repro.core.policy import with_attn_backend

    return with_attn_backend(
        with_kv_cache(preset(base, n_layers=cfg.n_layers), kv), "compressed")


def _mixed_requests(vocab, lengths, max_new, seed=11):
    rng = np.random.RandomState(seed)
    return [Request(uid=i, prompt=rng.randint(0, vocab, n).astype(np.int32),
                    max_new_tokens=m)
            for i, (n, m) in enumerate(zip(lengths, max_new))]


def _gather_decode(qh, k_pool, v_pool, k_scale, v_scale, table, n_ctx,
                   q_pos, layer, window=None, *, scale=None, probs_tq=None):
    """The paged decode kernel's gather path: the layer's pages in logical
    order into the quantized-KV kernel, as every step gathered before."""
    from repro.kernels import ops as kops

    B, NL = table.shape
    P, ps = k_pool.shape[1], k_pool.shape[2]
    KV, D = k_scale.shape[-1], qh.shape[-1]
    T = NL * ps
    phys = jnp.where(table >= 0, table, P - 1)
    idx = jnp.arange(T)[None]
    mapped = jnp.take_along_axis(
        table, jnp.broadcast_to(idx // ps, (B, T)), axis=1) >= 0
    kv_pos = jnp.where((idx < n_ctx[:, None]) & mapped, idx, -1)
    tok = lambda s: jnp.repeat(s[layer][phys], ps, axis=1)  # noqa: E731
    return kops.flash_attention_quant_gqa(
        qh, k_pool[layer][phys].reshape(B, T, KV, D),
        v_pool[layer][phys].reshape(B, T, KV, D), tok(k_scale),
        tok(v_scale), q_pos[:, None], kv_pos, window, scale=scale,
        probs_tq=probs_tq)


@pytest.mark.parametrize("base,kv", [("w4a8_abfp", "int8"), ("fp32", "fp8")])
def test_paged_decode_in_place_serves_the_gather_paths_tokens(
        setup, base, kv, monkeypatch):
    """Prompts of 3-45 tokens with 2-19 new tokens each, contexts ending
    on and off page and group boundaries: the engine whose decode reads
    pages in place serves the tokens of the same engine whose decode
    gathers every page into the quantized-KV kernel."""
    from repro.kernels import ops as kops

    cfg, model, params = setup
    pol = _compressed(cfg, base, kv)
    lengths, max_new = (3, 45, 16, 29, 8, 33, 5), (19, 2, 12, 7, 16, 4, 9)

    def serve():
        eng = PagedServeEngine(model, params, n_slots=3, max_len=64,
                               policy=pol, page_size=4, prefill_chunk=8,
                               kv=kv)
        for r in _mixed_requests(cfg.vocab, lengths, max_new):
            eng.submit(r)
        return {c.uid: c.tokens for c in eng.run_until_done()}

    in_place = serve()
    monkeypatch.setattr(kops, "paged_decode_attention_quant_gqa",
                        _gather_decode)
    assert in_place == serve()
    assert [len(in_place[i]) for i in range(len(lengths))] == list(max_new)


def test_paged_decode_compiles_nothing_after_the_first_decode_tick(setup):
    """Rows of very different live lengths (4 to 120 tokens of 128) decode
    across many ticks, through admissions and evictions, and no program
    compiles after the first tick that decodes: the live bound is a
    traced value, never a shape."""
    cfg, model, params = setup
    eng = PagedServeEngine(model, params, n_slots=3, max_len=128,
                           policy=_compressed(cfg, "w4a8_abfp", "int8"),
                           page_size=4, prefill_chunk=8, kv="int8")
    for r in _mixed_requests(cfg.vocab, (3, 90, 20, 64, 9, 110, 40),
                             (30, 30, 6, 25, 40, 10, 12), seed=4):
        eng.submit(r)
    while not eng.active.any():
        eng.tick()
    eng.tick()  # the first tick that decodes
    compiles, armed = [], [True]
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **kw: compiles.append(ev) if armed[0] and ev
        == "/jax/core/compile/backend_compile_duration" else None)
    try:
        done = eng.run_until_done()
    finally:
        armed[0] = False
    assert len(done) == 7 and compiles == []
