"""Compressed-domain flash attention: interpret-mode kernel parity vs the
dequantize-then-reference oracles, the trash-page property, per-site
backend dispatch (QL601 runtime contract), engine token identity across
fixed-slot/paged x fp32/w4a8, and the QL6xx lint family."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import abfp as abfp_mod
from repro.core.policy import (preset, with_attn_backend, with_kv_cache)
from repro.kernels import ops as kops
from repro.kernels.ref import flash_attention_ref


def _inputs(B, S, T, H, KV, D, *, code_dtype="int8", q_offset=0, seed=0):
    """Query + cache-style codes/scales/positions for the GQA wrapper."""
    rng = np.random.RandomState(seed)
    qh = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    codes = rng.randint(-127, 128, (2, B, T, KV, D)).astype(np.float32)
    if code_dtype == "int8":
        kc, vc = (jnp.asarray(c, jnp.int8) for c in codes)
    else:  # fp8: arbitrary e4m3-representable values
        kc, vc = (jnp.asarray(c / 16.0, jnp.float8_e4m3fn) for c in codes)
    ks = jnp.asarray(rng.rand(B, T, KV).astype(np.float32) * 0.05 + 1e-3)
    vs = jnp.asarray(rng.rand(B, T, KV).astype(np.float32) * 0.05 + 1e-3)
    q_pos = jnp.asarray(q_offset + np.arange(S, dtype=np.int32))[None, :]
    q_pos = jnp.broadcast_to(q_pos, (B, S))
    kv_pos = jnp.broadcast_to(
        jnp.asarray(np.arange(T, dtype=np.int32))[None, :], (B, T))
    return qh, kc, vc, ks, vs, q_pos, kv_pos


def _dequant_flash_ref(qh, kc, vc, ks, vs, *, causal, q_offset):
    """The ISSUE's oracle: dequantize the codes, then the dense jnp
    reference kernel (kernels.ref.flash_attention_ref) with GQA repeat."""
    B, S, H, D = qh.shape
    T, KV = kc.shape[1], kc.shape[2]
    G = H // KV
    k = kc.astype(jnp.float32) * ks[..., None]
    v = vc.astype(jnp.float32) * vs[..., None]
    q = qh.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, T, D)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, T, D)
    o = flash_attention_ref(q, kf, vf, causal=causal, q_offset=q_offset)
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _oracle(qh, kc, vc, ks, vs, q_pos, kv_pos, *, causal, tq=None,
            window=None):
    """nn.attention._reference's math over dequantized codes (masking via
    kv_pos, optional ABFP probs QDQ) — the QDQ-sim decode path."""
    B, S, H, D = qh.shape
    T, KV = kc.shape[1], kc.shape[2]
    G = H // KV
    kh = kc.astype(jnp.float32) * ks[..., None]
    vh = vc.astype(jnp.float32) * vs[..., None]
    qg = qh.reshape(B, S, KV, G, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, kh,
                   preferred_element_type=jnp.float32) * D**-0.5
    qp = q_pos[:, :, None]
    kp = kv_pos[:, None, :]
    m = kp >= 0
    if causal:
        m = m & (kp <= qp)
    if window is not None:
        m = m & (kp > qp - window)
    s = jnp.where(m[:, None, None], s, -1e9)
    p = jax.nn.softmax(s, axis=-1)
    if tq is not None:
        p = abfp_mod.abfp_qdq(p, tq.fmt, axis=-1, n=tq.group)
    out = jnp.einsum("bkgst,btkd->bskgd", p, vh,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, H, D)


def _kernel(qh, kc, vc, ks, vs, q_pos, kv_pos, **kw):
    return kops.flash_attention_quant_gqa(qh, kc, vc, ks, vs, q_pos,
                                          kv_pos, interpret=True, **kw)


# ------------------------------------------------------- kernel parity
@pytest.mark.parametrize("code_dtype", ["int8", "fp8"])
def test_parity_square_causal_gqa(code_dtype):
    args = _inputs(2, 32, 32, 4, 2, 16, code_dtype=code_dtype)
    got = _kernel(*args, causal=True)
    want = _dequant_flash_ref(*args[:5], causal=True, q_offset=0)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("code_dtype", ["int8", "fp8"])
def test_parity_decode_suffix_q_offset(code_dtype):
    # S != T: queries are the trailing suffix of the KV timeline (decode /
    # chunked prefill); q_pos carries the absolute offset the dense kernel
    # needs passed explicitly
    args = _inputs(2, 8, 48, 4, 2, 16, code_dtype=code_dtype, q_offset=40)
    got = _kernel(*args, causal=True)
    want = _dequant_flash_ref(*args[:5], causal=True, q_offset=40)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_parity_noncausal():
    args = _inputs(1, 16, 40, 4, 4, 8)
    got = _kernel(*args, causal=False)
    want = _dequant_flash_ref(*args[:5], causal=False, q_offset=0)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_parity_non_aligned_tiling_multi_block():
    # T=96 with single_block_max=32 forces the online multi-block body and
    # fit_block back-off (96 % 64 != 0 -> bk=32); S=24 is no power of two
    args = _inputs(1, 24, 96, 2, 1, 16, q_offset=72)
    got = _kernel(*args, causal=True, block_k=64, single_block_max=32)
    want = _dequant_flash_ref(*args[:5], causal=True, q_offset=72)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_parity_masked_slots_and_window():
    # ring-cache style: some slots unwritten (kv_pos = -1), sliding window
    qh, kc, vc, ks, vs, q_pos, kv_pos = _inputs(2, 4, 32, 4, 2, 16,
                                                q_offset=20)
    kv_pos = jnp.where(jnp.arange(32)[None] < 24, kv_pos, -1)
    win = jnp.asarray(9, jnp.int32)
    got = _kernel(qh, kc, vc, ks, vs, q_pos, kv_pos, window=win,
                  causal=True)
    want = _oracle(qh, kc, vc, ks, vs, q_pos, kv_pos, causal=True,
                   window=win)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_parity_probs_qdq_exact_body():
    # in-kernel ABFP probs QDQ (int8 groups of 64, BF16 scales) against
    # core.abfp.abfp_qdq on the reference probs; T=64 -> single block
    tq = preset("w4a8_abfp").input
    args = _inputs(2, 8, 64, 4, 2, 16, q_offset=56)
    got = _kernel(*args, causal=True, probs_tq=tq)
    want = _oracle(*args, causal=True, tq=tq)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_parity_probs_qdq_padded_groups():
    # T=40 zero-pads to the 64-group; padded slots carry kv_pos=-1 and
    # probability exactly 0 — matching core.abfp's zero-padded groups
    tq = preset("w4a8_abfp").input
    args = _inputs(1, 4, 40, 2, 2, 16, q_offset=36)
    got = _kernel(*args, causal=True, probs_tq=tq)
    want = _oracle(*args, causal=True, tq=tq)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_parity_probs_qdq_phased_multi_block():
    # multi-block + probs QDQ runs the two-pass (phased) body; a quant
    # code sitting exactly on a round boundary may flip one step under
    # XLA's reciprocal-multiply rewrite, so the assertion is boundary-
    # tolerant: tiny overall deviation, near-all elements tight
    tq = preset("w4a8_abfp").input
    args = _inputs(1, 8, 128, 2, 1, 16, q_offset=120)
    got = np.asarray(_kernel(*args, causal=True, probs_tq=tq,
                             block_k=64, single_block_max=32))
    want = np.asarray(_oracle(*args, causal=True, tq=tq))
    diff = np.abs(got - want)
    assert diff.max() < 5e-3, diff.max()
    assert (diff <= 1e-5).mean() > 0.9


def test_trash_slots_never_reach_output():
    # flipping garbage behind kv_pos = -1 leaves the output BIT-identical:
    # the paged gather can route trash-page codes into the kernel freely
    qh, kc, vc, ks, vs, q_pos, kv_pos = _inputs(1, 4, 32, 2, 2, 16,
                                                q_offset=28)
    kv_pos = jnp.where(jnp.arange(32)[None] < 20, kv_pos, -1)
    out1 = np.asarray(_kernel(qh, kc, vc, ks, vs, q_pos, kv_pos,
                              causal=True))
    trash = jnp.arange(32)[None, :, None, None] >= 20
    kc2 = jnp.where(trash, (kc.astype(jnp.int32) * -3 + 17).astype(jnp.int8)
                    if kc.dtype == jnp.int8 else kc, kc)
    ks2 = jnp.where(trash[..., 0], ks * 1e6 + 42.0, ks)
    out2 = np.asarray(_kernel(qh, kc2, vc, ks2, vs, q_pos, kv_pos,
                              causal=True))
    assert np.array_equal(out1, out2)


# ------------------------------------- paged decode, pages read in place
def _pool(L, P, ps, KV, D, *, code_dtype="int8", seed=0):
    """A layer-stacked page pool whose layers hold different codes and
    scales."""
    rng = np.random.RandomState(seed)
    codes = rng.randint(-127, 128, (2, L, P, ps, KV * D)).astype(np.float32)
    if code_dtype == "int8":
        kc, vc = (jnp.asarray(c, jnp.int8) for c in codes)
    else:
        kc, vc = (jnp.asarray(c / 16.0, jnp.float8_e4m3fn) for c in codes)
    ks, vs = (jnp.asarray(rng.rand(L, P, KV).astype(np.float32) * 0.05
                          + 1e-3) for _ in range(2))
    return kc, vc, ks, vs


def _paged_rows(NL, P, seed=0):
    """Four rows of a decode step, as (table, n_ctx): no live page; a
    context ending on a page boundary; one token past a 64-group; and
    scrambled physical pages with the trash page mapped among them."""
    rng = np.random.RandomState(seed)
    table = np.full((4, NL), -1, np.int32)
    perm = rng.permutation(P - 1)
    table[1, :32] = perm[:32]                 # 128 tokens in pages of 4
    table[2, :17] = perm[32:49]               # 65 tokens
    table[3, :38] = perm[49:87]               # 150 tokens, scrambled
    table[3, 5] = P - 1                       # the trash page, mapped
    n_ctx = np.asarray([37, 128, 65, 150], np.int32)
    return jnp.asarray(table), jnp.asarray(n_ctx)


def _gather_then_kernel(qh, kc, vc, ks, vs, table, n_ctx, layer, *,
                        window=None, tq=None):
    """The gather path: layer ``layer``'s pages gathered in logical order,
    page scales broadcast over their tokens, the multi-block kernel at
    blocks of 64 positions.  Each KV head's G query heads, padded to 8,
    are its query rows, as the paged kernel's query tile holds them, so
    the two contract the same tiles."""
    B, NL = table.shape
    P, ps = kc.shape[1], kc.shape[2]
    KV, D = ks.shape[-1], qh.shape[-1]
    G = qh.shape[2] // KV
    Gp = -(-G // 8) * 8
    T = NL * ps
    phys = jnp.where(table >= 0, table, P - 1)
    idx = jnp.arange(T)[None]
    mapped = jnp.take_along_axis(
        table, jnp.broadcast_to(idx // ps, (B, T)), axis=1) >= 0
    kv_pos = jnp.where((idx < n_ctx[:, None]) & mapped, idx, -1)

    def heads(c):  # (L, P, ps, KV*D) -> (B*KV, T, 1, D)
        c = c[layer][phys].reshape(B, T, KV, D)
        return c.transpose(0, 2, 1, 3).reshape(B * KV, T, 1, D)

    def tok(s):  # (L, P, KV) -> (B*KV, T, 1): each page's scale per token
        s = jnp.repeat(s[layer][phys], ps, axis=1)
        return s.transpose(0, 2, 1).reshape(B * KV, T, 1)

    q = jnp.pad(qh.reshape(B, KV, G, D), ((0, 0), (0, 0), (0, Gp - G),
                                         (0, 0))).reshape(B * KV, Gp, 1, D)
    q_pos = jnp.repeat(jnp.broadcast_to((n_ctx - 1)[:, None], (B, Gp)), KV,
                       axis=0)
    o = kops.flash_attention_quant_gqa(
        q, heads(kc), heads(vc), tok(ks), tok(vs), q_pos,
        jnp.repeat(kv_pos, KV, axis=0), window, probs_tq=tq, block_k=64,
        single_block_max=0, interpret=True)
    return o.reshape(B, KV, Gp, D)[:, :, :G].reshape(B, 1, KV * G, D)


@pytest.mark.parametrize("G,probs,window", [
    (7, False, None), (7, True, None), (8, False, None), (8, True, None),
    (8, True, 40)])
def test_paged_decode_matches_gather_then_kernel(G, probs, window):
    """Layer 1 of a three-layer pool, read in place, against the gather of
    that layer's pages and the multi-block kernel at the same block of 64
    positions and the same query tile: bit for bit, since the blocks past
    a row's live context that only the gather visits are exact no-ops.
    The row with no live page returns zeros."""
    KV, D, ps, NL = 2, 16, 4, 48
    P = 4 * NL + 1
    kc, vc, ks, vs = _pool(3, P, ps, KV, D, seed=G)
    table, n_ctx = _paged_rows(NL, P, seed=G)
    qh = jnp.asarray(np.random.RandomState(G).randn(4, 1, KV * G, D)
                     .astype(np.float32))
    tq = preset("w4a8_abfp").input if probs else None
    win = None if window is None else jnp.asarray(window, jnp.int32)
    got = np.asarray(kops.paged_decode_attention_quant_gqa(
        qh, kc, vc, ks, vs, table, n_ctx, n_ctx - 1, 1, win, probs_tq=tq,
        block_k=64, interpret=True))
    want = np.asarray(_gather_then_kernel(qh, kc, vc, ks, vs, table, n_ctx,
                                          1, window=win, tq=tq))
    assert np.array_equal(got[0], np.zeros_like(got[0]))
    assert np.array_equal(got[1:], want[1:])
    # the same two paths at another layer read other codes
    other = np.asarray(kops.paged_decode_attention_quant_gqa(
        qh, kc, vc, ks, vs, table, n_ctx, n_ctx - 1, 2, win, probs_tq=tq,
        block_k=64, interpret=True))
    assert np.abs(other[1:] - got[1:]).max() > 1e-3


def test_paged_decode_reads_fp8_pages():
    KV, D, ps, NL = 2, 16, 4, 48
    P = 4 * NL + 1
    kc, vc, ks, vs = _pool(2, P, ps, KV, D, code_dtype="fp8", seed=5)
    table, n_ctx = _paged_rows(NL, P, seed=5)
    qh = jnp.asarray(np.random.RandomState(5).randn(4, 1, 8, D)
                     .astype(np.float32))
    got = np.asarray(kops.paged_decode_attention_quant_gqa(
        qh, kc, vc, ks, vs, table, n_ctx, n_ctx - 1, 1, block_k=64,
        interpret=True))
    want = np.asarray(_gather_then_kernel(qh, kc, vc, ks, vs, table, n_ctx,
                                          1))
    assert np.isfinite(got).all() and not got[0].any()
    assert np.array_equal(got[1:], want[1:])


# -------------------------------------------------- dispatch + registry
def test_backend_registry():
    from repro.core.simulate import attn_backends, attention_backend

    b = attn_backends()
    assert set(b) >= {"auto", "ref", "fused", "compressed"}
    assert b["compressed"].kv_repr == "codes"
    pol = with_attn_backend(preset("w4a8_abfp"), "compressed")
    assert attention_backend(pol).name == "compressed"
    with pytest.raises(ValueError, match="unknown attention backend"):
        with_attn_backend(pol, "bogus")


def test_attn_backend_mode_requires_agreement():
    from repro.core.policy import attn_backend_mode

    pol = preset("w4ffn_fp8attn")  # a PolicyMap
    assert attn_backend_mode(pol) == "auto"
    assert attn_backend_mode(with_attn_backend(pol, "ref")) == "ref"


def test_policy_roundtrips_attn_backend():
    from repro.core.policy import policy_from_dict, policy_to_dict

    pol = with_attn_backend(preset("w4a8_abfp"), "compressed")
    back = policy_from_dict(policy_to_dict(pol))
    assert back.attn_backend == "compressed"


# ------------------------------------------------------- engine parity
@pytest.fixture(scope="module")
def setup():
    from repro.configs import get_config
    from repro.models import build_model
    from repro.nn.module import unbox

    cfg = get_config("qwen2-7b").reduced()
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    return cfg, model, params


def _serve(cfg, model, params, policy, *, paged, kv="auto"):
    from repro.serve.engine import PagedServeEngine, Request, ServeEngine

    if paged:
        eng = PagedServeEngine(model, params, n_slots=2, max_len=48,
                               policy=policy, page_size=8, kv=kv)
    else:
        eng = ServeEngine(model, params, n_slots=2, max_len=48,
                          policy=policy)
    rng = np.random.RandomState(7)
    for uid in range(3):
        plen = int(rng.randint(3, 10))
        eng.submit(Request(
            uid=uid, prompt=rng.randint(0, cfg.vocab, plen).astype(np.int32),
            max_new_tokens=4))
    return {c.uid: c.tokens for c in eng.run_until_done()}


@pytest.mark.parametrize("base", ["fp32", "w4a8_abfp"])
def test_fixed_slot_token_identity(setup, base):
    # fp32-over-int8-storage is the parity leg where the kernel's only
    # approximation is the storage itself — no probs QDQ in the way
    cfg, model, params = setup
    pol = with_kv_cache(preset(base, n_layers=cfg.n_layers), "int8")
    ref = _serve(cfg, model, params, pol, paged=False)
    got = _serve(cfg, model, params, with_attn_backend(pol, "compressed"),
                 paged=False)
    assert got == ref


@pytest.mark.parametrize("base,kv", [("fp32", "fp8"), ("w4a8_abfp", "int8")])
def test_paged_token_identity(setup, base, kv):
    cfg, model, params = setup
    pol = with_kv_cache(preset(base, n_layers=cfg.n_layers), kv)
    ref = _serve(cfg, model, params, pol, paged=True, kv=kv)
    got = _serve(cfg, model, params, with_attn_backend(pol, "compressed"),
                 paged=True, kv=kv)
    assert got == ref


def test_compressed_requires_quantized_storage(setup):
    # the QL601 runtime contract: engines reject at construction
    from repro.serve.engine import PagedServeEngine, ServeEngine

    cfg, model, params = setup
    pol = with_attn_backend(preset("w4a8_abfp", n_layers=cfg.n_layers),
                            "compressed")
    with pytest.raises(ValueError, match="needs quantized KV storage"):
        ServeEngine(model, params, n_slots=1, max_len=32, policy=pol)
    with pytest.raises(ValueError, match="needs quantized KV storage"):
        PagedServeEngine(model, params, n_slots=1, max_len=32, policy=pol,
                         page_size=8, kv="fp")


# --------------------------------------------------------------- lint
def _lint(cfg, policy, attn=None):
    from repro.analysis.qlint import lint

    return lint(cfg, policy, attn=attn)


def test_ql601_compressed_over_fp_storage():
    from repro.configs import get_config

    cfg = get_config("qwen2-7b").reduced()
    pol = with_attn_backend(preset("w4a8_abfp", n_layers=cfg.n_layers),
                            "compressed")
    rep = _lint(cfg, pol, attn={"engine": "fixed"})
    assert rep.has("QL601") and not rep.ok
    # quantized storage clears it
    rep = _lint(cfg, with_kv_cache(pol, "int8"),
                attn={"engine": "paged", "kv": "int8"})
    assert not rep.has("QL601")


def test_ql602_softcap_fallback_is_warning():
    from repro.configs import get_config

    cfg = get_config("gemma2-9b").reduced()  # attn softcap
    pol = with_attn_backend(
        with_kv_cache(preset("w4a8_abfp", n_layers=cfg.n_layers), "int8"),
        "compressed")
    rep = _lint(cfg, pol, attn={"engine": "paged", "kv": "int8"})
    msgs = [d.message for d in rep if d.code == "QL602"]
    assert any("softcap" in m for m in msgs)
    assert rep.ok  # warnings never block


def test_ql603_fp8_on_fixed_slot_engine():
    from repro.analysis.messages import fp8_fixed_slot_message
    from repro.configs import get_config

    cfg = get_config("qwen2-7b").reduced()
    pol = with_kv_cache(preset("w4a8_abfp", n_layers=cfg.n_layers), "fp8")
    rep = _lint(cfg, pol, attn={"engine": "fixed"})
    assert rep.has("QL603") and not rep.ok
    # same words as the ServeEngine constructor raise
    d = [d for d in rep if d.code == "QL603"][0]
    assert d.message == fp8_fixed_slot_message()
    # the paged engine serves it fine
    rep = _lint(cfg, pol, attn={"engine": "paged", "kv": "fp8"})
    assert not rep.has("QL603")
