"""Expert-resident MoE serving parity: expert-store engines emit the
dense-resident engines' tokens (fp32 and flat W4A8), cache refresh never
changes tokens, the old MoE+compress mis-serve is pinned fixed, per-expert
policy rules resolve at runtime, and the QL5xx lint family fires with the
same message text as the runtime constructors."""

import jax
import numpy as np
import pytest

from repro.analysis.messages import (
    expert_cache_capacity_message,
    expert_cache_requires_compress_message,
    expert_non_moe_message,
)
from repro.analysis.qlint import lint
from repro.configs.base import ArchConfig
from repro.core.policy import QuantPolicy, preset
from repro.models.registry import build_model
from repro.nn.module import unbox
from repro.serve.engine import PagedServeEngine, Request, ServeEngine
from repro.serve.experts import expert_precision_map

E = 4


@pytest.fixture(scope="module")
def setup():
    cfg = ArchConfig(
        name="tiny-moe", family="moe", n_layers=2, d_model=32, n_heads=2,
        n_kv=2, head_dim=16, d_ff=32, vocab=97, n_experts=E, top_k=2,
        scan_layers=False,
        tied_embeddings=False,
    )
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    return cfg, model, params


PROMPTS = [np.array([3, 5, 7, 11, 13], np.int32),
           np.array([2, 4, 6], np.int32),
           np.array([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], np.int32)]


def _drive(engine_cls, model, params, policy, **kw):
    eng = engine_cls(model, params, n_slots=2, max_len=64, policy=policy,
                     **kw)
    for i, p in enumerate(PROMPTS):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=8))
    return {c.uid: c.tokens for c in eng.run_until_done()}, eng


# ------------------------------------------------------------ parity gate
@pytest.mark.parametrize("engine_cls", [ServeEngine, PagedServeEngine])
@pytest.mark.parametrize("pname", ["fp32", "w4a8_abfp"])
def test_expert_store_token_identical_to_dense(setup, engine_cls, pname):
    cfg, model, params = setup
    pol = QuantPolicy() if pname == "fp32" else preset(pname)
    dense, _ = _drive(engine_cls, model, params, pol)
    store, eng = _drive(engine_cls, model, params, pol, compress=True,
                        expert_cache=max(1, E // 4))
    assert store == dense
    stats = eng.expert_stats()
    assert stats is not None and stats["n_experts"] == E
    if pname != "fp32":
        # int4-packed backing store well under the dense footprint; the
        # resident total (store + E//4 dense cache) stays under it too
        # (the paper-level <= 0.5x claim runs on the phi3.5 proxy in
        # benchmarks moe_table — this fixture is scale-overhead-dominated)
        assert 0 < stats["store_bytes"] <= 0.5 * stats["dense_bytes"]
        assert stats["resident_bytes"] < stats["dense_bytes"]
        assert stats["misses"] > 0  # the routing probe actually ran


def test_refresh_experts_token_identical(setup):
    cfg, model, params = setup
    pol = preset("w4a8_abfp")
    ref, _ = _drive(ServeEngine, model, params, pol, compress=True)
    eng = ServeEngine(model, params, n_slots=2, max_len=64, policy=pol,
                      compress=True, expert_cache=2)
    for i, p in enumerate(PROMPTS):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=8))
    ticks = 0
    while eng._has_work():
        eng.tick()
        ticks += 1
        if ticks in (2, 5):  # refresh mid-flight, twice (idempotent swap)
            eng.refresh_experts()
    assert {c.uid: c.tokens for c in eng.done} == ref
    assert eng.expert_stats()["cached_experts"] > 0


def test_refresh_without_store_raises(setup):
    cfg, model, params = setup
    eng = ServeEngine(model, params, n_slots=1, max_len=64)
    with pytest.raises(ValueError, match="no expert store"):
        eng.refresh_experts()


# ------------------------------------------- regression: MoE + compress
def test_moe_compress_serves_like_qdq_sim(setup):
    """Pinned regression: compressed MoE serving used to leave the expert
    stacks dense while serving_policy dropped their weight quantizers, so
    experts silently served UNQUANTIZED — tokens drifted from the QDQ sim
    and the byte report had no expert rows.  Now the banks compress
    per-expert and serve token-identically."""
    cfg, model, params = setup
    pol = preset("w4a8_abfp")
    sim, _ = _drive(ServeEngine, model, params, pol)
    comp, eng = _drive(ServeEngine, model, params, pol, compress=True)
    assert comp == sim
    expert_rows = [r for r in eng.weight_bytes["sites"]
                   if "/experts." in r["site"]]
    assert len(expert_rows) == cfg.n_layers * E
    assert all(r["kind"] == "compressed" for r in expert_rows)


# -------------------------------------------------- per-expert runtime
def test_per_expert_rules_resolve_at_runtime(setup):
    cfg, model, params = setup
    tokens = np.arange(16, dtype=np.int32).reshape(1, 16) % cfg.vocab
    batch = {"tokens": tokens}
    base = preset("w4a8_abfp")
    # all experts assigned the base's own int4 => identical to flat QDQ
    flat_map = expert_precision_map(base, [], cold_fmt="int4")
    ref, _ = model.apply(params, batch, base)
    got, _ = model.apply(params, batch, flat_map)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # a genuinely mixed map changes the numerics (rules are not ignored)
    mixed = expert_precision_map(base, [0, 1], hot_fmt="int8")
    other, _ = model.apply(params, batch, mixed)
    assert not np.allclose(np.asarray(other), np.asarray(ref))


def test_expert_loads_probe_shape_and_conservation(setup):
    cfg, model, params = setup
    tokens = np.arange(16, dtype=np.int32).reshape(2, 8) % cfg.vocab
    loads = np.asarray(model.expert_loads(params, tokens))
    assert loads.shape == (cfg.n_layers, E)
    # dropless top-2 routing: every token lands on exactly 2 experts
    assert (loads.sum(axis=1) == 2 * tokens.size).all()
    assert (loads >= 0).all() and loads.sum() > 0


# --------------------------------------- QL5xx gate vs runtime guards
def test_engine_guard_messages_match_lint(setup):
    cfg, model, params = setup
    with pytest.raises(ValueError) as ei:
        ServeEngine(model, params, expert_cache=1)  # no compress
    assert str(ei.value) == expert_cache_requires_compress_message()

    dcfg = ArchConfig(name="tiny-dense", family="llama", n_layers=1,
                      d_model=32, n_heads=2, n_kv=2, head_dim=16, d_ff=32,
                      vocab=97, scan_layers=False, tied_embeddings=False)
    dmodel = build_model(dcfg)
    dparams = unbox(dmodel.init(jax.random.PRNGKey(1)))
    with pytest.raises(ValueError) as ei:
        ServeEngine(dmodel, dparams, policy=preset("w4a8_abfp"),
                    compress=True, expert_cache=1)
    want = expert_non_moe_message("an expert cache", dcfg.name)
    assert str(ei.value) == want
    # the QL502 gate carries the same message text
    r = lint(dcfg, preset("w4a8_abfp"), experts={"cache_capacity": 1})
    ql502 = [d for d in r.errors if d.code == "QL502"]
    assert ql502 and ql502[0].message == want


def test_fp32_compress_degenerates_gracefully(setup):
    """fp32 rules leave the expert stacks as plain dense arrays: no store
    is built and serving is plain dense-resident (trivially identical)."""
    cfg, model, params = setup
    eng = ServeEngine(model, params, n_slots=1, max_len=64,
                      policy=QuantPolicy(), compress=True, expert_cache=1)
    stats = eng.expert_stats()
    # the store collects the (dense) banks; nothing is compressed, so
    # resident == dense and the cache only adds copies
    assert stats is None or stats["store_bytes"] == stats["dense_bytes"]


def test_ql501_oversize_cache_warns():
    cfg = ArchConfig(
        name="tiny-moe-lint", family="moe", n_layers=2, d_model=32,
        n_heads=2, n_kv=2, head_dim=16, d_ff=32, vocab=97, n_experts=E,
        top_k=2,
        scan_layers=False, tied_embeddings=False,
    )
    r = lint(cfg, preset("w4a8_abfp"), experts={"cache_capacity": E})
    ql501 = [d for d in r.warnings if d.code == "QL501"]
    assert ql501 and r.ok
    assert ql501[0].message == expert_cache_capacity_message(E, E)
    r2 = lint(cfg, preset("w4a8_abfp"), experts={"cache_capacity": 1})
    assert not r2.has("QL501")
