"""Pure-jnp oracles for the Pallas kernels (the ground truth in tests).

These intentionally re-derive the math independently of core/abfp.py's
helpers where practical, so kernel bugs and library bugs can't cancel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.analysis.messages import abfp_group_message
from repro.core.formats import Format, IntFormat


def _group_scales(x: jnp.ndarray, axis: int, n: int,
                  scale_dtype=jnp.bfloat16) -> jnp.ndarray:
    """Per-group max(|x|) scales along ``axis`` with bf16 round-up."""
    xm = jnp.moveaxis(x, axis, -1)
    g = xm.shape[-1] // n
    xg = xm.reshape(*xm.shape[:-1], g, n)
    alpha = jnp.max(jnp.abs(xg), axis=-1)
    a16 = alpha.astype(scale_dtype)
    return jnp.maximum(a16.astype(jnp.float32), 1e-12)


def abfp_qdq_ref(x: jnp.ndarray, fmt: Format, n: int = 64,
                 axis: int = -1) -> jnp.ndarray:
    """Reference ABFP quantize-dequantize along ``axis``."""
    axis = axis % x.ndim
    xm = jnp.moveaxis(x, axis, -1)
    if xm.shape[-1] % n:
        raise ValueError(abfp_group_message(xm.shape[-1], n,
                                            where="abfp_qdq_ref"))
    g = xm.shape[-1] // n
    xg = xm.reshape(*xm.shape[:-1], g, n).astype(jnp.float32)
    alpha = _group_scales(x, axis, n)[..., None]
    scale = alpha / fmt.qmax_pos
    yg = fmt.qdq_unit(xg / scale) * scale
    ym = yg.reshape(xm.shape)
    return jnp.moveaxis(ym, -1, axis).astype(x.dtype)


def abfp_matmul_ref(x: jnp.ndarray, w: jnp.ndarray, fmt_x: Format,
                    fmt_w: Format, n: int = 64) -> jnp.ndarray:
    """Reference fused ABFP matmul: QDQ both operands along K, fp32 dot."""
    xq = abfp_qdq_ref(x, fmt_x, n, axis=-1)
    wq = abfp_qdq_ref(w, fmt_w, n, axis=0)
    return jnp.dot(xq.astype(jnp.float32), wq.astype(jnp.float32))


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        scale: float | None = None,
                        causal: bool = True,
                        q_offset: int | None = None) -> jnp.ndarray:
    """Reference attention: materialized softmax(QK^T·scale)V, causal.

    ``q_offset`` is the absolute position of query row 0; under causal it
    defaults to ``T - S`` (queries are the trailing suffix of the KV
    timeline — the decode/chunked-prefill convention).  The Pallas kernel
    refuses to guess and requires it explicitly when S != T.
    """
    BH, S, D = q.shape
    T = k.shape[1]
    scale = D**-0.5 if scale is None else scale
    s = jnp.einsum("bsd,btd->bst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        if q_offset is None:
            q_offset = T - S
        mask = (jnp.arange(T)[None, :]
                <= jnp.arange(S)[:, None] + q_offset)
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bst,btd->bsd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def int8_matmul_ref(x: jnp.ndarray, w: jnp.ndarray, fmt_x: Format,
                    fmt_w: Format, n: int = 64) -> jnp.ndarray:
    """Reference native-int path: per-group int codes, int32 accum,
    per-group rescale."""
    if not (isinstance(fmt_x, IntFormat) and isinstance(fmt_w, IntFormat)):
        raise TypeError(
            "int8_matmul_ref accumulates integer codes: both formats must "
            f"be IntFormat, got fmt_x={fmt_x!r} fmt_w={fmt_w!r}")
    M, K = x.shape
    K2, N = w.shape
    if K != K2:
        raise ValueError(
            f"contraction mismatch: x has K={K} but w has K={K2}")
    if K % n:
        raise ValueError(abfp_group_message(K, n, where="int8_matmul_ref"))
    g = K // n
    sx = _group_scales(x, -1, n) / fmt_x.qmax_pos  # (M, g)
    sw = _group_scales(w, 0, n) / fmt_w.qmax_pos  # (N, g)
    xg = x.astype(jnp.float32).reshape(M, g, n)
    wg = jnp.moveaxis(w.astype(jnp.float32), 0, -1).reshape(N, g, n)
    xc = jnp.clip(jnp.round(xg / sx[..., None]), fmt_x.qmin, fmt_x.qmax_pos)
    wc = jnp.clip(jnp.round(wg / sw[..., None]), fmt_w.qmin, fmt_w.qmax_pos)
    partial = jnp.einsum("mgk,ngk->mgn", xc, wc)  # int-valued f32
    # the rescale is f32 arithmetic, not an MXU product: keep it exact
    return jnp.einsum("mgn,mg,ng->mn", partial, sx, jnp.moveaxis(sw, 0, 0),
                      precision=jax.lax.Precision.HIGHEST)


def quant_matmul_ref(x: jnp.ndarray, w_codes: jnp.ndarray,
                     w_scales: jnp.ndarray, fmt_x: IntFormat,
                     n: int = 64) -> jnp.ndarray:
    """Reference stored-codes matmul: x quantized to per-group int codes,
    contracted against ``w_codes`` group by group (code rows
    ``[g*n, (g+1)*n)``), each group's integer sum rescaled by ``x``'s step
    and ``w_scales`` (G, N).  ``w_codes`` is (K, N) int8, or (K/2, N)
    uint8 packed INT4: group g's n/2 byte rows hold its first n/2 code
    rows in the low nibbles and the rest in the high ones.  Groups are
    summed one at a time, so no (M, G, N) partial is held."""
    M, K = x.shape
    rows, N = w_codes.shape
    G = K // n
    packed = w_codes.dtype == jnp.uint8
    if rows * (2 if packed else 1) != K or G * n != K:
        raise ValueError(
            f"codes {w_codes.shape} do not cover x's K={K} in groups of "
            f"n={n}")
    wc = w_codes.astype(jnp.int32).reshape(G, rows // G, N)
    if packed:  # two's complement of each 4-bit field
        wc = jnp.concatenate([((wc & 15) ^ 8) - 8, ((wc >> 4) ^ 8) - 8],
                             axis=1)
    sx = _group_scales(x, -1, n) / fmt_x.qmax_pos  # (M, G)
    xg = x.astype(jnp.float32).reshape(M, G, n)
    xc = jnp.clip(jnp.round(xg / sx[..., None]), fmt_x.qmin, fmt_x.qmax_pos)

    def group(acc, g):
        xc_g, wc_g, sx_g, sw_g = g
        # integer-valued operands under 2^8: exact at any MXU precision
        part = jnp.dot(xc_g, wc_g.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
        return acc + part * sx_g[:, None] * sw_g[None, :], None

    y, _ = jax.lax.scan(group, jnp.zeros((M, N), jnp.float32),
                        (jnp.moveaxis(xc, 1, 0), wc, sx.T,
                         w_scales.astype(jnp.float32)))
    return y


def quant_matmul_grouped_ref(x: jnp.ndarray, tile_group: jnp.ndarray,
                             tiles_used, w_codes: jnp.ndarray,
                             w_scales: jnp.ndarray, fmt_x: IntFormat,
                             n: int = 64) -> jnp.ndarray:
    """Reference grouped stored-codes matmul: ``x`` (M, K) in tiles of
    ``M / len(tile_group)`` rows, tile t against expert ``tile_group[t]``'s
    codes (``w_codes`` (E, K or K/2, N), ``w_scales`` (E, G, N)) as
    ``quant_matmul_ref`` contracts one kernel; rows of tiles past
    ``tiles_used`` are zero."""
    tiles = tile_group.shape[0]
    M, K = x.shape
    bm = M // tiles

    def one(args):
        t, g, xt = args
        y = quant_matmul_ref(xt, w_codes[g], w_scales[g], fmt_x, n)
        return jnp.where(t < tiles_used, y, 0.0)

    y = jax.lax.map(one, (jnp.arange(tiles), tile_group,
                          x.reshape(tiles, bm, K)))
    return y.reshape(M, -1)


def flash_attention_quant_ref(qh, k_codes, v_codes, k_scale, v_scale,
                              q_pos, kv_pos, *, causal: bool = True,
                              window=None, probs_fmt: Format | None = None,
                              probs_n: int = 0) -> jnp.ndarray:
    """Reference attention over quantized KV: dequantize the codes, mask by
    absolute positions (``kv_pos < 0`` = invalid), softmax with the finite
    -1e9 mask, optional ABFP QDQ of the probabilities, then P·V.

    Shapes as ``kernels.ops.flash_attention_quant_gqa``: ``qh`` (B, S, H,
    D), codes (B, T, KV, D), scales (B, T, KV), ``q_pos`` (B, S),
    ``kv_pos`` (B, T).
    """
    B, S, H, D = qh.shape
    KV = k_codes.shape[2]
    kh = k_codes.astype(jnp.float32) * k_scale[..., None]
    vh = v_codes.astype(jnp.float32) * v_scale[..., None]
    qg = qh.astype(jnp.float32).reshape(B, S, KV, H // KV, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, kh) * D**-0.5
    qp = q_pos[:, :, None]
    kp = kv_pos[:, None, :]
    m = kp >= 0
    if causal:
        m = m & (kp <= qp)
    if window is not None:
        m = m & (kp > qp - window)
    s = jnp.where(m[:, None, None], s, -1e9)
    p = jax.nn.softmax(s, axis=-1)
    if probs_n:
        T = p.shape[-1]
        pad = -T % probs_n
        pp = jnp.pad(p, [(0, 0)] * 4 + [(0, pad)])
        p = abfp_qdq_ref(pp, probs_fmt, probs_n)[..., :T]
    out = jnp.einsum("bkgst,btkd->bskgd", p, vh)
    return out.reshape(B, S, H, D).astype(qh.dtype)


def paged_decode_attention_quant_ref(qh, k_pool, v_pool, k_scale, v_scale,
                                     table, n_ctx, q_pos, layer, *,
                                     window=None,
                                     probs_fmt: Format | None = None,
                                     probs_n: int = 0) -> jnp.ndarray:
    """Reference for ``kernels.ops.paged_decode_attention_quant_gqa``:
    gather layer ``layer``'s pages of every row in logical order, then
    ``flash_attention_quant_ref`` over positions below ``n_ctx`` on the
    row's leading mapped pages; a row with none returns zeros."""
    B, _, H, D = qh.shape
    _, P, ps, F = k_pool.shape
    KV, NL = F // D, table.shape[1]
    T = NL * ps
    phys = jnp.where(table >= 0, table, P - 1)
    mapped = jnp.cumprod(table >= 0, axis=1).sum(axis=1)
    live = jnp.minimum(n_ctx, mapped * ps)
    idx = jnp.arange(T)[None]
    kv_pos = jnp.where(idx < live[:, None], idx, -1)

    def tokens(s):  # (L, P, KV) -> (B, T, KV): each page's scale per token
        return jnp.repeat(s[layer][phys], ps, axis=1)

    out = flash_attention_quant_ref(
        qh, k_pool[layer][phys].reshape(B, T, KV, D),
        v_pool[layer][phys].reshape(B, T, KV, D), tokens(k_scale),
        tokens(v_scale), q_pos[:, None], kv_pos, window=window,
        probs_fmt=probs_fmt, probs_n=probs_n)
    return jnp.where((live > 0)[:, None, None, None], out, 0.0)
