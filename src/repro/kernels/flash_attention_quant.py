"""Pallas TPU kernel: flash attention over quantized KV codes.

The serving engines store KV as int8 / fp8-e4m3 codes with per-(token, head)
or per-(page, head) f32 unit scales (``nn.attention.KVCache`` /
``PagedKVCache``) — but the QDQ-sim serving path still dequantizes every
cache read to dense fp before the QK^T/PV contractions, the last dense-fp
island in the serving stack (§Perf).  This kernel consumes the codes
directly: each (bk, D) code tile and its (bk, 1) scale column are
dequantized in VMEM registers, so HBM sees the CODE bytes (1 byte/element)
plus metadata-sized scales — never a dense fp copy of the cache.

Parity contract (the PR 5 bar): compressed-attention serving must be
token-identical to the dequantize-then-reference engine.  Two consequences:

  * masking uses the reference's finite ``NEG_INF`` (-1e9) and probabilities
    are computed as ``exp(s - max) / sum`` — op-for-op ``jax.nn.softmax`` on
    the same masked scores, so masked positions carry *exact* zeros (no NaN
    guards needed: ``exp(-1e9 - m)`` underflows to 0 for any row with a
    valid key);
  * the contraction dequantizes codes in VMEM and multiplies in the query's
    dtype with f32 accumulation — the same per-element products as
    ``_kv_dequantize`` + einsum, identical up to dot accumulation order
    (greedy tokens are asserted identical; EXPERIMENTS.md §Compressed
    attention documents why the int-domain contraction was traded away).

Three bodies, picked by the wrapper (``kernels.ops.flash_attention_quant_gqa``):

  exact   — single KV block (T fits VMEM — every serving call in practice):
            full-row softmax + optional in-kernel ABFP probs QDQ; reads K
            and V exactly once.
  online  — multi-block, no probs QDQ: the dense flash recurrence
            (``flash_attention._kernel``) with in-VMEM dequant.
  phased  — multi-block + probs QDQ: pass 1 accumulates the exact row
            max/denominator, pass 2 rebuilds ``exp(s - m) / l`` per block
            and applies the group QDQ (bk % n == 0 keeps groups inside one
            block).  Reads K/V twice — documented in the bytes accounting.

Masking is data-driven — absolute q/kv position planes plus a traced window
scalar — and reproduces ``Attention._mask`` exactly: ``kv_pos < 0`` marks
padded / unwritten / trash entries, so gathered garbage (including the
paged trash page) lands on probability zero, never in the output.  A row
with *no* valid key degenerates to the uniform mean the reference softmax
also produces (its zero-masked V makes the reference output 0 instead;
rows are independent, and the engines ignore dead-row outputs).

GQA never repeats KV in HBM: the block index maps broadcast KV row
``(b // H) * KV + (b % H) // G`` to its G query heads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.messages import abfp_group_message, attention_block_message
from repro.kernels.abfp_qdq import group_scale

NEG_INF = -1e9  # mask value — matches nn.attention.NEG_INF (finite in bf16)
M_INIT = -1e30  # running-max init; exp(M_INIT - m_new) underflows to exact 0


def _dequant(c_ref, s_ref, dtype):
    """(1, bk, D) codes + (1, bk, 1) scales -> (bk, D) values in ``dtype``."""
    return (c_ref[0].astype(jnp.float32) * s_ref[0]).astype(dtype)


def _tile_mask(qp_ref, kp_ref, win_ref, causal: bool):
    """(bq, bk) validity mask — ``Attention._mask`` on one tile."""
    qp = qp_ref[0]  # (bq, 1) absolute query positions
    kp = kp_ref[0]  # (1, bk) absolute kv positions; -1 = invalid/padded
    m = kp >= 0
    if causal:
        m &= kp <= qp
    m &= kp > qp - win_ref[0, 0]  # traced window; >= seq len means global
    return m


def _probs_qdq(p, *, n: int, qmax: float, qmin: float):
    """ABFP QDQ of a (bq, bk) probability tile, groups of n along kv.

    Mirrors ``core.abfp.abfp_qdq`` (int formats, BF16 scales) bit-for-bit —
    the same ops as ``kernels.abfp_qdq._qdq_tile``; the wrapper zero-pads T
    to a multiple of n so groups here line up with the reference's
    zero-padded groups.
    """
    scale = group_scale(p, n, -1, qmax)  # paper: scales live in BF16
    return jnp.clip(jnp.round(p / scale), qmin, qmax) * scale


def _scores(q, kc_ref, ks_ref, qp_ref, kp_ref, win_ref, *, scale: float,
            causal: bool):
    """Masked (bq, bk) score tile from a query tile + code/scale tiles."""
    k = _dequant(kc_ref, ks_ref, q.dtype)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    return jnp.where(_tile_mask(qp_ref, kp_ref, win_ref, causal), s, NEG_INF)


def _kernel_exact(q_ref, kc_ref, vc_ref, ks_ref, vs_ref, qp_ref, kp_ref,
                  win_ref, o_ref, *, scale: float, causal: bool, n: int,
                  qmax: float, qmin: float):
    """Single KV block: full-row softmax, op-for-op the reference path."""
    q = q_ref[0]  # (bq, D)
    s = _scores(q, kc_ref, ks_ref, qp_ref, kp_ref, win_ref,
                scale=scale, causal=causal)
    m = s.max(axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / e.sum(axis=-1, keepdims=True)  # == jax.nn.softmax(s)
    if n:
        p = _probs_qdq(p, n=n, qmax=qmax, qmin=qmin)
    v = _dequant(vc_ref, vs_ref, q.dtype)
    o_ref[0] = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def _kernel_online(q_ref, kc_ref, vc_ref, ks_ref, vs_ref, qp_ref, kp_ref,
                   win_ref, o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                   causal: bool, k_steps: int):
    """Multi-block online-softmax recurrence (no probs QDQ).

    The finite -1e9 mask needs no NaN guards: a fully-masked leading block
    sets m to -1e9 and contributes uniform junk that the first valid
    block's correction factor ``exp(-1e9 - m_new)`` flushes to exact 0.
    """
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, M_INIT)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]
    s = _scores(q, kc_ref, ks_ref, qp_ref, kp_ref, win_ref,
                scale=scale, causal=causal)
    m_prev = m_ref[...]  # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    v = _dequant(vc_ref, vs_ref, q.dtype)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new

    @pl.when(ki == k_steps - 1)
    def _done():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _kernel_phased(q_ref, kc_ref, vc_ref, ks_ref, vs_ref, qp_ref, kp_ref,
                   win_ref, o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                   causal: bool, n: int, qmax: float, qmin: float,
                   k_steps: int):
    """Multi-block + probs QDQ: two sweeps over the KV blocks.

    The group QDQ needs the *final* softmax values (the reference quantizes
    ``softmax(s)``, not the running unnormalized p), so pass 1 finds the
    exact row max/denominator and pass 2 rebuilds ``exp(s - m) / l`` per
    block and quantizes it — K/V are read twice (documented deviation in
    the bytes accounting; the single-block exact body is the serving path).
    """
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, M_INIT)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]
    s = _scores(q, kc_ref, ks_ref, qp_ref, kp_ref, win_ref,
                scale=scale, causal=causal)

    @pl.when(j < k_steps)
    def _pass1():
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = (l_ref[...] * corr
                      + jnp.exp(s - m_new).sum(axis=-1, keepdims=True))
        m_ref[...] = m_new

    @pl.when(j == k_steps)
    def _acc0():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j >= k_steps)
    def _pass2():
        p = jnp.exp(s - m_ref[...]) / l_ref[...]
        p = _probs_qdq(p, n=n, qmax=qmax, qmin=qmin)
        v = _dequant(vc_ref, vs_ref, q.dtype)
        acc_ref[...] += jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == 2 * k_steps - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "h", "kv", "probs_n", "probs_qmax",
                     "probs_qmin", "block_q", "block_k", "interpret"),
)
def flash_attention_quant(
    q: jnp.ndarray,        # (B*H, S, D) queries (caller applies any q QDQ)
    k_codes: jnp.ndarray,  # (B*KV, T, D) int8 / fp8-e4m3 codes
    v_codes: jnp.ndarray,  # (B*KV, T, D)
    k_scale: jnp.ndarray,  # (B*KV, T, 1) f32 per-token unit scales
    v_scale: jnp.ndarray,  # (B*KV, T, 1) f32
    q_pos: jnp.ndarray,    # (B, S, 1) int32 absolute query positions
    kv_pos: jnp.ndarray,   # (B, 1, T) int32 absolute kv positions; -1 invalid
    window: jnp.ndarray,   # (1, 1) int32 traced window (>= seq len: global)
    *,
    scale: float,
    causal: bool = True,
    h: int = 1,            # query heads folded into q's leading dim
    kv: int = 1,           # KV heads folded into k/v's leading dim
    probs_n: int = 0,      # ABFP probs-QDQ group length; 0 disables
    probs_qmax: float = 0.0,
    probs_qmin: float = 0.0,
    block_q: int = 256,
    block_k: int = 0,      # 0: single KV block (bk = T)
    interpret: bool = False,
) -> jnp.ndarray:
    """Flash attention over quantized KV codes; returns (B*H, S, D).

    ``kernels.ops.flash_attention_quant_gqa`` is the (B, S, H, D) front-end
    that owns layout, padding and block selection; this entry enforces the
    tiling contract and picks the kernel body.
    """
    BH, S, D = q.shape
    BKV, T, _ = k_codes.shape
    g = h // kv
    bq = min(block_q, S)
    bk = T if block_k in (0, T) else block_k
    if S % bq or T % bk:
        raise ValueError(attention_block_message(S, T, bq, bk))
    if probs_n and bk % probs_n:
        raise ValueError(abfp_group_message(bk, probs_n, where="attn probs"))
    k_steps = T // bk
    kvrow = lambda b: (b // h) * kv + (b % h) // g

    if k_steps == 1:
        grid = (BH, S // bq)
        qm = lambda b, i: (b, i, 0)
        km = lambda b, i: (kvrow(b), 0, 0)
        qpm = lambda b, i: (b // h, i, 0)
        kpm = lambda b, i: (b // h, 0, 0)
        wm = lambda b, i: (0, 0)
        kernel = functools.partial(
            _kernel_exact, scale=scale, causal=causal, n=probs_n,
            qmax=probs_qmax, qmin=probs_qmin)
        scratch = []
    else:
        steps = 2 * k_steps if probs_n else k_steps
        col = (lambda j: j % k_steps) if probs_n else (lambda j: j)
        grid = (BH, S // bq, steps)
        qm = lambda b, i, j: (b, i, 0)
        km = lambda b, i, j: (kvrow(b), col(j), 0)
        qpm = lambda b, i, j: (b // h, i, 0)
        kpm = lambda b, i, j: (b // h, 0, col(j))
        wm = lambda b, i, j: (0, 0)
        if probs_n:
            kernel = functools.partial(
                _kernel_phased, scale=scale, causal=causal, n=probs_n,
                qmax=probs_qmax, qmin=probs_qmin, k_steps=k_steps)
        else:
            kernel = functools.partial(
                _kernel_online, scale=scale, causal=causal, k_steps=k_steps)
        scratch = [
            pltpu.VMEM((bq, 1), jnp.float32),  # running max
            pltpu.VMEM((bq, 1), jnp.float32),  # running denominator
            pltpu.VMEM((bq, D), jnp.float32),  # output accumulator
        ]

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), qm),
            pl.BlockSpec((1, bk, D), km),
            pl.BlockSpec((1, bk, D), km),
            pl.BlockSpec((1, bk, 1), km),
            pl.BlockSpec((1, bk, 1), km),
            pl.BlockSpec((1, bq, 1), qpm),
            pl.BlockSpec((1, 1, bk), kpm),
            pl.BlockSpec((1, 1), wm),
        ],
        out_specs=pl.BlockSpec((1, bq, D), qm),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k_codes, v_codes, k_scale, v_scale, q_pos, kv_pos, window)
