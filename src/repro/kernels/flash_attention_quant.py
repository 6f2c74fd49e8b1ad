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

Paged decode (``paged_decode_attention_quant``; front-end
``kernels.ops.paged_decode_attention_quant_gqa``): at S == 1 the kernel
reads the codes where they live, in the layer-stacked page pool
``(L, P, page_size, KV*D)``.  The grid is one step per row; the page
table, each row's live context, the layer and the window come in as
scalar prefetch, and the row's per-(page, head) scales as an SMEM block.
A ``fori_loop`` walks the row's live page blocks, DMAs each page of a
block, every KV head in one copy, from ``pool[layer, page]`` while the
block before computes, and stops at the row's live context (in whole
read units), so nothing past it is fetched.  Inside a block a
``fori_loop`` over KV heads contracts each head's lanes of the pages
with its G query heads, folded into one query tile: each code is read
from HBM once.
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


# --------------------------------------------------------------------------
# Paged decode: one query token per row, KV codes read in place from the
# layer-stacked page pool.
# --------------------------------------------------------------------------
def _paged_decode_kernel(tab_ref, live_ref, qpos_ref, meta_ref, q_ref,
                         k_hbm, v_hbm, ks_ref, vs_ref, o_ref, kbuf, vbuf,
                         sems, deq, m_scr, l_scr, acc_scr, *s_scr,
                         scale: float, n: int, qmax: float, qmin: float,
                         pages: int, unit: int, n_logical: int):
    """One row: its live pages, ``pages`` at a time, every KV head.

    Each block's pages (all heads of a page in one DMA) come from
    ``pool[layer, page]`` into a VMEM buffer while the block before
    computes; blocks past the row's live context are never visited.  Per
    block and KV head the arithmetic is the multi-block bodies'
    (``_kernel_online`` without probs QDQ, ``_kernel_phased`` with it,
    whose pass 2 reads the scores pass 1 kept instead of recomputing
    them), and the blocks past the live context are exact no-ops there.
    """
    b = pl.program_id(0)
    layer, window = meta_ref[0], meta_ref[1]
    live, qp = live_ref[b], qpos_ref[b]
    ps = kbuf.shape[2]
    KV, D = q_ref.shape[1], q_ref.shape[3]
    bk = pages * ps
    # whole read units (probability groups) of the live context
    n_read = (live + unit * ps - 1) // (unit * ps) * unit
    n_blk = (n_read + pages - 1) // pages

    def dma(pool, buf, which, i, j, page):
        slot = i % 2
        return pltpu.make_async_copy(pool.at[layer, page], buf.at[slot, j],
                                     sems.at[which, slot])

    def pages_of(i, op):
        """``op(j)`` for each page j of block i the row reads."""
        def body(j, c):
            op(j)
            return c
        jax.lax.fori_loop(0, jnp.minimum(pages, n_read - i * pages), body, 0)

    def fetch(pool, buf, which, i):
        pages_of(i, lambda j: dma(
            pool, buf, which, i, j,
            tab_ref[b * n_logical + i * pages + j]).start())

    def wait(pool, buf, which, i):
        pages_of(i, lambda j: dma(pool, buf, which, i, j, 0).wait())

    def dequant(buf, sc_ref, i, h):
        """(bk, D) values of head h in block i: each page's codes x its
        scale, and zeros for pages the row does not read (their buffer
        is stale)."""
        slot = i % 2
        col = pl.ds(pl.multiple_of(h * D, D), D)

        def body(j, c):
            page = i * pages + j
            deq[pl.ds(pl.multiple_of(j * ps, ps), ps), :] = jnp.where(
                page < n_read,
                buf[slot, j, :, col].astype(jnp.float32)
                * sc_ref[0, h, page], 0.0)
            return c
        jax.lax.fori_loop(0, pages, body, 0)
        return deq[...].astype(q_ref.dtype)

    def scores(i, h):
        """Masked (G, bk) scores of head h's query tile in block i."""
        s = jax.lax.dot_general(
            q_ref[0, h], dequant(kbuf, ks_ref, i, h),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        ) * scale
        kp = i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        ok = (kp < live) & (kp <= qp) & (kp > qp - window)
        return jnp.where(ok, s, NEG_INF)

    def pv(p, i, h):
        v = dequant(vbuf, vs_ref, i, h)
        return jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def prefetch(i, with_v):
        @pl.when(i < n_blk)
        def _():
            fetch(k_hbm, kbuf, 0, i)
            if with_v:
                fetch(v_hbm, vbuf, 1, i)

    def heads(op):
        def body(h, c):
            op(h)
            return c
        jax.lax.fori_loop(0, KV, body, 0)

    m_scr[...] = jnp.full_like(m_scr, M_INIT)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    prefetch(0, True)

    if not n:
        def online(h, i):
            s = scores(i, h)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + p.sum(axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + pv(p, i, h)
            m_scr[h] = m_new

        def block(i, c):
            prefetch(i + 1, True)
            wait(k_hbm, kbuf, 0, i)
            wait(v_hbm, vbuf, 1, i)
            heads(lambda h: online(h, i))
            return c

        jax.lax.fori_loop(0, n_blk, block, 0)
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)
        return

    (s_keep,) = s_scr

    def pass1(h, i):
        s = scores(i, h)
        s_keep[i, h] = s
        m_prev = m_scr[h]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        l_scr[h] = l_scr[h] * corr + jnp.exp(s - m_new).sum(axis=-1,
                                                            keepdims=True)
        m_scr[h] = m_new

    def pass2(h, i):
        p = jnp.exp(s_keep[i, h] - m_scr[h]) / l_scr[h]
        p = _probs_qdq(p, n=n, qmax=qmax, qmin=qmin)
        acc_scr[h] = acc_scr[h] + pv(p, i, h)

    def k_block(i, c):
        prefetch(i + 1, False)
        wait(k_hbm, kbuf, 0, i)
        heads(lambda h: pass1(h, i))
        return c

    def v_block(i, c):
        @pl.when(i + 1 < n_blk)
        def _():
            fetch(v_hbm, vbuf, 1, i + 1)
        wait(v_hbm, vbuf, 1, i)
        heads(lambda h: pass2(h, i))
        return c

    jax.lax.fori_loop(0, n_blk, k_block, 0)
    jax.lax.fori_loop(0, n_blk, v_block, 0)
    o_ref[0] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "probs_n", "probs_qmax", "probs_qmin",
                     "pages", "unit", "interpret"),
)
def paged_decode_attention_quant(
    q: jnp.ndarray,        # (B, KV, G, D) one query token's heads, by KV head
    k_pool: jnp.ndarray,   # (L, P, ps, KV*D) int8 / fp8-e4m3 codes
    v_pool: jnp.ndarray,   # (L, P, ps, KV*D)
    k_scale: jnp.ndarray,  # (B, KV, NL) f32 scale of each row's pages
    v_scale: jnp.ndarray,  # (B, KV, NL) f32
    table: jnp.ndarray,    # (B*NL,) int32 physical page of each logical page
    live: jnp.ndarray,     # (B,) int32 live context tokens of each row
    q_pos: jnp.ndarray,    # (B,) int32 absolute query positions
    meta: jnp.ndarray,     # (2,) int32: pool layer, attention window
    *,
    scale: float,
    probs_n: int = 0,
    probs_qmax: float = 0.0,
    probs_qmin: float = 0.0,
    pages: int = 32,       # pages per block (bk = pages * ps positions)
    unit: int = 1,         # pages per read unit: a row reads whole units
    interpret: bool = False,
) -> jnp.ndarray:
    """Decode attention over each row's live pages, read in place; returns
    (B, KV, G, D).  ``kernels.ops.paged_decode_attention_quant_gqa`` is
    the front-end that builds the tables from the page table."""
    B, KV, G, D = q.shape
    _, _, ps, F = k_pool.shape
    NL = k_scale.shape[2]
    bk = pages * ps
    if NL % pages:
        raise ValueError(attention_block_message(1, NL * ps, 1, bk))
    if probs_n and bk % probs_n:
        raise ValueError(abfp_group_message(bk, probs_n, where="attn probs"))
    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, n=probs_n, qmax=probs_qmax,
        qmin=probs_qmin, pages=pages, unit=unit, n_logical=NL)
    row = lambda b, *_: (b, 0, 0, 0)
    sc = pl.BlockSpec((1, KV, NL), lambda b, *_: (b, 0, 0),
                      memory_space=pltpu.SMEM)
    scratch = [
        pltpu.VMEM((2, pages, ps, F), k_pool.dtype),  # K pages, two slots
        pltpu.VMEM((2, pages, ps, F), v_pool.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM((bk, D), jnp.float32),             # one head dequantized
        pltpu.VMEM((KV, G, 1), jnp.float32),          # running max
        pltpu.VMEM((KV, G, 1), jnp.float32),          # running denominator
        pltpu.VMEM((KV, G, D), jnp.float32),          # output accumulator
    ]
    if probs_n:  # pass 1's scores, kept for pass 2
        scratch.append(pltpu.VMEM((NL // pages, KV, G, bk), jnp.float32))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, KV, G, D), row),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                sc, sc,
            ],
            out_specs=pl.BlockSpec((1, KV, G, D), row),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        name="flash_attention_quant",
        interpret=interpret,
    )(table, live, q_pos, meta, q, k_pool, v_pool, k_scale, v_scale)
