"""Mamba2 SSD scan + MoE routing unit tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import QuantPolicy, preset
from repro.nn.module import unbox
from repro.nn.moe import MoE
from repro.nn.ssm import Mamba2

POL = QuantPolicy()


def mk_mamba(**kw):
    base = dict(d_model=32, d_state=16, d_conv=4, expand=2, head_dim=16,
                n_groups=1, chunk=8)
    base.update(kw)
    return Mamba2(**base)


def test_mamba_shapes_finite():
    m = mk_mamba()
    params = unbox(m.init(jax.random.PRNGKey(0)))
    x = jnp.asarray(np.random.RandomState(0).randn(2, 16, 32), jnp.float32)
    y = m.apply(params, x, POL)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()


def test_mamba_chunked_scan_chunk_invariance():
    """The SSD chunked algorithm must give identical results for any chunk
    size (it's an exact reformulation, not an approximation)."""
    params = unbox(mk_mamba().init(jax.random.PRNGKey(1)))
    x = jnp.asarray(np.random.RandomState(1).randn(1, 32, 32), jnp.float32)
    y8 = mk_mamba(chunk=8).apply(params, x, POL)
    y16 = mk_mamba(chunk=16).apply(params, x, POL)
    y32 = mk_mamba(chunk=32).apply(params, x, POL)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y16),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y32),
                               rtol=1e-4, atol=1e-5)


def test_mamba_decode_matches_scan():
    """Stepwise decode through the conv+SSM caches == full-sequence scan."""
    m = mk_mamba()
    params = unbox(m.init(jax.random.PRNGKey(2)))
    S = 12
    x = jnp.asarray(np.random.RandomState(2).randn(1, S, 32), jnp.float32)
    full = m.apply(params, x, POL)

    cache = m.init_cache(1, dtype=jnp.float32)
    outs = []
    for t in range(S):
        y, cache = m.decode_step(params, x[:, t:t + 1], cache, policy=POL)
        outs.append(y)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(dec),
                               rtol=2e-3, atol=2e-4)


def test_mamba_prefill_cache_continues_decode():
    m = mk_mamba()
    params = unbox(m.init(jax.random.PRNGKey(3)))
    S = 16
    x = jnp.asarray(np.random.RandomState(3).randn(1, S, 32), jnp.float32)
    full = m.apply(params, x, POL)
    # prefill the first half, then decode the rest
    half = S // 2
    _, cache = m.apply(params, x[:, :half], POL, return_cache=True)
    outs = []
    for t in range(half, S):
        y, cache = m.decode_step(params, x[:, t:t + 1], cache, policy=POL)
        outs.append(y)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full[:, half:]), np.asarray(dec),
                               rtol=2e-3, atol=2e-4)


def test_mamba_quantized_close_to_fp():
    m = mk_mamba()
    params = unbox(m.init(jax.random.PRNGKey(4)))
    x = jnp.asarray(np.random.RandomState(4).randn(1, 16, 32), jnp.float32)
    y_fp = m.apply(params, x, POL)
    y_q = m.apply(params, x, preset("w4a8_abfp"))
    c = np.corrcoef(np.asarray(y_fp).ravel(), np.asarray(y_q).ravel())[0, 1]
    assert c > 0.98


# ----------------------------------------------------------------------- MoE
def mk_moe(**kw):
    base = dict(d_model=32, d_ff=64, n_experts=4, top_k=2)
    base.update(kw)
    return MoE(**base)


def test_moe_shapes_and_aux():
    m = mk_moe()
    params = unbox(m.init(jax.random.PRNGKey(0)))
    x = jnp.asarray(np.random.RandomState(0).randn(2, 16, 32), jnp.float32)
    y, metrics = m.apply(params, x, POL)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert float(metrics["moe_aux_loss"]) > 0


def test_moe_matches_dense_expert_computation():
    """With top_k == n_experts, the MoE output equals the gate-weighted sum
    of every expert's MLP — validated against an explicit dense loop."""
    m = mk_moe(n_experts=2, top_k=2)
    params = unbox(m.init(jax.random.PRNGKey(1)))
    x = jnp.asarray(np.random.RandomState(1).randn(1, 8, 32), jnp.float32)
    y, _ = m.apply(params, x, POL)

    # dense reference
    router = np.asarray(params["router"])  # (d, E)
    logits = np.asarray(x) @ router
    probs = jax.nn.softmax(jnp.asarray(logits), axis=-1)
    want = np.zeros_like(np.asarray(x))
    for e in range(2):
        wi = np.asarray(params["wi"])[e]
        wg = np.asarray(params["wg"])[e] if "wg" in params else None
        wo = np.asarray(params["wo"])[e]
        h = np.asarray(x) @ wi
        if wg is not None:
            g = np.asarray(x) @ wg
            h = (g * (1 / (1 + np.exp(-g)))) * h  # silu gate
        out_e = h @ wo
        want += np.asarray(probs[..., e])[..., None] * out_e
    np.testing.assert_allclose(np.asarray(y), want, rtol=5e-2, atol=5e-3)


def test_moe_is_dropless_under_a_collapsed_router():
    """Routing has no capacity: with every token sent to one expert, each
    token still takes its top_k experts, and the output equals the dense
    gate-weighted sum over the chosen experts."""
    m = mk_moe(n_experts=4, top_k=2)
    params = unbox(m.init(jax.random.PRNGKey(2)))
    r = np.zeros_like(np.asarray(params["router"]))
    r[:, 0] = 10.0
    params = dict(params, router=jnp.asarray(r))
    x = jnp.asarray(np.abs(np.random.RandomState(2).randn(1, 32, 32)),
                    jnp.float32)
    y, metrics = m.apply(params, x, POL)
    assert float(metrics["expert_load"].sum()) == 2 * 32
    assert float(metrics["expert_load"][0]) == 32
    assert int(metrics["counts"]["expert_rows"]) == 2 * 32
    assert int(metrics["counts"]["expert_rows_max"]) == 32
    xs = np.asarray(x)[0]
    probs = np.asarray(jax.nn.softmax(jnp.asarray(xs @ r), axis=-1))
    want = np.zeros_like(xs)
    for t in range(32):
        for e in np.argsort(-probs[t], kind="stable")[:2]:
            wi, wg = np.asarray(params["wi"])[e], np.asarray(params["wg"])[e]
            g = xs[t] @ wg
            want[t] += probs[t, e] * (((g / (1 + np.exp(-g))) * (xs[t] @ wi))
                                      @ np.asarray(params["wo"])[e])
    np.testing.assert_allclose(np.asarray(y)[0], want, rtol=5e-2, atol=5e-3)


def test_moe_invalid_rows_route_nowhere():
    """Rows marked invalid take no expert row and give a zero output; the
    valid rows' outputs do not depend on them."""
    m = mk_moe()
    params = unbox(m.init(jax.random.PRNGKey(5)))
    x = jnp.asarray(np.random.RandomState(5).randn(2, 8, 32), jnp.float32)
    valid = jnp.arange(8)[None] < jnp.asarray([[3], [8]])
    y, metrics = m.apply(params, x, POL, valid=valid)
    assert int(metrics["counts"]["expert_rows"]) == 2 * 11
    np.testing.assert_array_equal(np.asarray(y)[0, 3:], 0.0)
    y_all, _ = m.apply(params, x, POL)
    np.testing.assert_allclose(np.asarray(y)[valid],
                               np.asarray(y_all)[valid], rtol=1e-6,
                               atol=1e-6)


def test_moe_aux_loss_balanced_vs_collapsed():
    """Aux loss is ~1x E for a balanced router and larger when collapsed."""
    m = mk_moe(n_experts=4, top_k=1)
    params = unbox(m.init(jax.random.PRNGKey(3)))
    x = jnp.asarray(np.random.RandomState(3).randn(1, 64, 32), jnp.float32)
    _, metrics = m.apply(params, x, POL)
    balanced = float(metrics["moe_aux_loss"])
    # collapse the router to expert 0
    p2 = dict(params)
    r = np.zeros_like(np.asarray(params["router"]))
    r[:, 0] = 10.0
    p2["router"] = jnp.asarray(r)
    _, m2 = m.apply(p2, x, POL)
    assert float(m2["moe_aux_loss"]) > balanced
