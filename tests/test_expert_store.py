"""Expert store/cache unit behavior: LRU eviction order and hit
accounting, hit-rate monotonicity in capacity, store/cache byte totals vs
weight_bytes_report, routing counters, and the offline per-expert
precision assignment (serializable PolicyMap round-trip)."""

import jax
import numpy as np
import pytest

from repro.analysis.messages import expert_non_moe_message
from repro.configs.base import ArchConfig
from repro.core.policy import policy_from_dict, policy_to_dict, preset
from repro.models import serving_transforms as st
from repro.models.registry import build_model
from repro.nn.module import unbox
from repro.serve.experts import (
    ExpertCache,
    ExpertStore,
    assign_expert_precision,
    expert_precision_map,
    hot_experts,
    zipf_trace,
)

E = 4


@pytest.fixture(scope="module")
def moe_setup():
    cfg = ArchConfig(
        name="tiny-moe", family="moe", n_layers=2, d_model=32, n_heads=2,
        n_kv=2, head_dim=16, d_ff=32, vocab=97, n_experts=E, top_k=2,
        scan_layers=False,
        tied_embeddings=False,
    )
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    return cfg, model, params


# ------------------------------------------------------------------- LRU
def test_lru_eviction_order():
    cache = ExpertCache(2)
    for e in (0, 1, 2, 3):  # 0 and 1 evicted in insertion order
        assert not cache.access(e)
        cache.admit(e, f"v{e}")
    assert cache.keys() == [2, 3] and cache.evictions == 2
    assert cache.access(2)  # hit refreshes recency: 2 is now MRU
    assert cache.keys() == [3, 2]
    evicted = cache.admit(1, "v1")  # 3 is now LRU
    assert evicted == 3 and cache.keys() == [2, 1]
    assert cache.hits == 1 and cache.misses == 4


def test_lru_capacity_zero_disables():
    cache = ExpertCache(0)
    assert not cache.access(0)
    assert cache.admit(0, "v") is None
    assert len(cache) == 0 and cache.misses == 1


def _trace_hit_rate(alpha, capacity, n=16, steps=300):
    cache = ExpertCache(capacity)
    for row in zipf_trace(n, steps, alpha=alpha, top_k=2, seed=3):
        for e in np.nonzero(row)[0]:
            if not cache.access(int(e)):
                cache.admit(int(e), None)
    return cache.hit_rate


def test_lru_eviction_order_under_skew():
    # under heavy skew the hottest (lowest-index) experts stay resident:
    # the cache converges to the head of the popularity distribution
    cache = ExpertCache(4)
    for row in zipf_trace(16, 400, alpha=2.0, top_k=2, seed=5):
        for e in np.nonzero(row)[0]:
            if not cache.access(int(e)):
                cache.admit(int(e), None)
    assert 0 in cache and 1 in cache  # the two hottest Zipf ranks


def test_hit_rate_monotone_in_capacity():
    rates = [_trace_hit_rate(1.5, c) for c in (1, 2, 4, 8, 16)]
    assert all(a <= b + 1e-12 for a, b in zip(rates, rates[1:]))
    assert rates[-1] > rates[0]  # and the sweep is not degenerate


def test_skew_beats_uniform_at_fixed_capacity():
    assert _trace_hit_rate(1.5, 4) > _trace_hit_rate(0.0, 4)


# ----------------------------------------------------------------- store
def test_store_bytes_match_weight_bytes_report(moe_setup):
    cfg, model, params = moe_setup
    pol = preset("w4a8_abfp")
    served = st.compress_weights(params, pol)
    rep = st.weight_bytes_report(params, served)
    store = ExpertStore(served, capacity=0, model_name=cfg.name)
    expert_rows = [r for r in rep["sites"] if "/experts." in r["site"]]
    assert len(expert_rows) == cfg.n_layers * E
    assert store.stats()["store_bytes"] == sum(
        r["resident_bytes"] for r in expert_rows)
    assert store.stats()["dense_bytes"] == sum(
        r["dense_bytes"] for r in expert_rows)


def test_store_cache_bytes_and_counters(moe_setup):
    cfg, model, params = moe_setup
    served = st.compress_weights(params, preset("w4a8_abfp"))
    store = ExpertStore(served, capacity=1, model_name=cfg.name)
    assert store.n_experts == E and len(store.sites) == cfg.n_layers

    loads = np.zeros((cfg.n_layers, E))
    loads[:, 1] = 10.0
    loads[:, 3] = 4.0
    store.observe(loads)
    stats = store.stats()
    # heaviest expert (1) ends most-recently-used => sole cache resident
    for site in store.sites:
        assert store.caches[site].keys() == [1]
        assert stats["sites"][site]["counts"][1] == 10.0
    # cached copy bytes = dense f32 bytes of one expert's wi/wg/wo
    per_expert_dense = stats["dense_bytes"] // (cfg.n_layers * E)
    assert stats["cache_bytes"] == cfg.n_layers * per_expert_dense
    assert stats["resident_bytes"] == (stats["store_bytes"]
                                       + stats["cache_bytes"])
    # hot/cold split covers the store exactly
    assert stats["hot_bytes"] + stats["cold_bytes"] == \
        stats["resident_bytes"]


def test_store_cached_copy_matches_backing_entry(moe_setup):
    cfg, model, params = moe_setup
    served = st.compress_weights(params, preset("w4a8_abfp"))
    store = ExpertStore(served, capacity=2, model_name=cfg.name)
    store.warm([2])
    site = store.sites[0]
    for kind in store.banks[site]:
        cached = store.caches[site].get(2)[kind]
        # one integer rule for every expert: the bank is one stacked kernel
        bank = store.banks[site][kind]
        assert isinstance(bank, st.CompressedKernel)
        backing = st.decompress_kernel(st.expert_entries(bank)[2])
        np.testing.assert_array_equal(np.asarray(cached),
                                      np.asarray(backing))


def test_store_rejects_dense_model():
    cfg = ArchConfig(name="tiny-dense", family="llama", n_layers=1,
                     d_model=32, n_heads=2, n_kv=2, head_dim=16, d_ff=32,
                     vocab=97, scan_layers=False, tied_embeddings=False)
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(1)))
    served = st.compress_weights(params, preset("w4a8_abfp"))
    with pytest.raises(ValueError) as ei:
        ExpertStore(served, capacity=1, model_name=cfg.name)
    # constructor error shares the QL502 formatter's message text
    assert str(ei.value) == expert_non_moe_message("an expert store",
                                                   cfg.name)


# ------------------------------------------------ precision assignment
def test_hot_experts_ordering():
    loads = np.array([[1.0, 5.0, 3.0, 5.0]])
    assert hot_experts(loads, 2) == [1, 3]  # ties break low-index
    assert hot_experts(loads, 0) == []
    assert hot_experts(loads, 99) == [1, 3, 2, 0]


def test_assignment_map_round_trips():
    loads = np.array([7.0, 1.0, 2.0, 9.0])
    pm = assign_expert_precision(loads, preset("w4a8_abfp"), n_hot=2)
    # hottest 2 experts carry int8 rules ahead of the int4 catch-all
    hot_pats = {r.pattern for r in pm.rules
                if r.policy.weight.fmt_name == "int8"}
    assert hot_pats == {"*/experts.0", "*/experts.3"}
    assert pm.resolve("block/ffn/experts.3").weight.fmt_name == "int8"
    assert pm.resolve("block/ffn/experts.1").weight.fmt_name == "int4"
    rt = policy_from_dict(policy_to_dict(pm))
    assert rt == pm


def test_assignment_requires_weight_rule():
    with pytest.raises(ValueError, match="enabled weight rule"):
        expert_precision_map(preset("fp32"), [0])
