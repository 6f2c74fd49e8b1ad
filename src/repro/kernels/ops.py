"""Jit'd wrappers over the Pallas kernels with CPU interpret fallback.

``should_interpret()`` — True when no TPU is present, so tests and the
policy.fused path run the kernel bodies through the Pallas interpreter
(bit-accurate, slow) on CPU.

``fit_block()`` — the one copy of the block-size back-off every wrapper
uses: the kernels require each dim to divide its block, so the wrappers
halve the preferred block until it does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.policy import QuantPolicy
from repro.kernels import abfp_qdq as _qdq_mod
from repro.kernels import quant_matmul as _mm_mod


@functools.lru_cache(maxsize=1)
def should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def fit_block(dim: int, start: int = 256, multiple: int = 1) -> int:
    """Largest block <= ``start`` that divides ``dim``.

    Halves ``start`` until it divides ``dim`` (bottoming out at
    ``multiple``); ``multiple`` > 1 keeps the result a multiple of the
    group length (blocks are counted in units of ``multiple``).
    """
    if multiple > 1:
        if dim % multiple:
            raise ValueError(
                f"dim={dim} is not a multiple of the group unit "
                f"{multiple}; cannot pick a block size"
            )
        return fit_block(dim // multiple, max(start // multiple, 1)) * multiple
    b = start
    while dim % b and b > 1:
        b //= 2
    return b


def abfp_qdq(x, fmt, n: int = 64, interpret: bool | None = None):
    """Fused QDQ over the last dim; leading dims are flattened to rows."""
    interpret = should_interpret() if interpret is None else interpret
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    bm = fit_block(x2.shape[0])
    y = _qdq_mod.abfp_qdq(x2, fmt, n=n, block_m=bm, interpret=interpret)
    return y.reshape(shape)


def flash_attention_gqa(qh, kh, vh, scale: float | None = None,
                        causal: bool = True,
                        q_offset: int | None = None,
                        block_q: int = 128,
                        block_k: int = 128,
                        interpret: bool | None = None):
    """(B, S, H, D) GQA front-end for the fused flash kernel.

    KV heads are broadcast to the query-head count and heads fold into the
    batch dim; no softcap/window support (callers keep the jnp paths for
    those variants).
    """
    from repro.kernels.flash_attention import flash_attention

    interpret = should_interpret() if interpret is None else interpret
    B, S, H, D = qh.shape
    T, KV = kh.shape[1], kh.shape[2]
    G = H // KV
    q = qh.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    k = jnp.repeat(kh.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, T, D)
    v = jnp.repeat(vh.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, T, D)
    o = flash_attention(q, k, v, scale=scale, causal=causal,
                        q_offset=q_offset, block_q=block_q, block_k=block_k,
                        interpret=interpret)
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def flash_attention_quant_gqa(qh, k_codes, v_codes, k_scale, v_scale,
                              q_pos, kv_pos, window=None,
                              scale: float | None = None,
                              causal: bool = True,
                              probs_tq=None,
                              block_q: int = 256,
                              block_k: int = 512,
                              single_block_max: int = 2048,
                              interpret: bool | None = None):
    """(B, S, H, D) GQA front-end for the quantized-KV flash kernel.

    ``k_codes``/``v_codes``: (B, T, KV, D) int8/fp8 codes straight from the
    cache (ring reshape or paged gather — never dequantized);
    ``k_scale``/``v_scale``: (B, T, KV) f32 per-token unit scales (page
    scales broadcast over their tokens by the caller); ``q_pos`` (B, S) /
    ``kv_pos`` (B, T) absolute positions with -1 marking invalid KV slots;
    ``window`` a traced sliding-window scalar (None = global).

    ``probs_tq``: the policy's input TensorQuant when attention-probability
    QDQ is active — must be an int-format ABFP quantizer (the in-kernel QDQ
    mirrors ``core.abfp``); T is zero-padded to a multiple of its group so
    groups tile exactly (padded positions carry ``kv_pos = -1`` and land on
    probability 0, matching the reference's zero-padded groups bit-for-bit).

    KV heads are never repeated in HBM — the kernel's index maps broadcast
    each KV row to its G query heads.
    """
    from repro.kernels import flash_attention_quant as _faq_mod

    interpret = should_interpret() if interpret is None else interpret
    B, S, H, D = qh.shape
    T, KV = k_codes.shape[1], k_codes.shape[2]
    n = 0
    qmax = qmin = 0.0
    if probs_tq is not None:
        fmt = probs_tq.fmt
        n = int(probs_tq.group)
        qmax, qmin = float(fmt.qmax_pos), float(fmt.qmin)
    scale = D**-0.5 if scale is None else scale
    if n:
        T_pad = -(-T // n) * n
    elif T > single_block_max:
        T_pad = -(-T // 128) * 128  # keep fit_block away from tiny tilings
    else:
        T_pad = T
    kv_pos = kv_pos.astype(jnp.int32)
    if T_pad > T:
        p = T_pad - T
        k_codes = jnp.pad(k_codes, ((0, 0), (0, p), (0, 0), (0, 0)))
        v_codes = jnp.pad(v_codes, ((0, 0), (0, p), (0, 0), (0, 0)))
        k_scale = jnp.pad(k_scale, ((0, 0), (0, p), (0, 0)))
        v_scale = jnp.pad(v_scale, ((0, 0), (0, p), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, p)), constant_values=-1)
    bq = fit_block(S, start=block_q)
    if T_pad <= single_block_max:
        bk = 0  # single KV block: the exact (serving) body, K/V read once
    else:
        bk = fit_block(T_pad, start=block_k, multiple=n if n else 1)
    if window is None:
        window = T + S + 1  # > any position delta: global attention
    win = jnp.asarray(window, jnp.int32).reshape(1, 1)
    q = qh.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kc = k_codes.transpose(0, 2, 1, 3).reshape(B * KV, T_pad, D)
    vc = v_codes.transpose(0, 2, 1, 3).reshape(B * KV, T_pad, D)
    ks = k_scale.transpose(0, 2, 1).reshape(B * KV, T_pad, 1)
    vs = v_scale.transpose(0, 2, 1).reshape(B * KV, T_pad, 1)
    o = _faq_mod.flash_attention_quant(
        q, kc, vc, ks.astype(jnp.float32), vs.astype(jnp.float32),
        q_pos.astype(jnp.int32)[:, :, None], kv_pos[:, None, :], win,
        scale=scale, causal=causal, h=H, kv=KV, probs_n=n,
        probs_qmax=qmax, probs_qmin=qmin, block_q=bq, block_k=bk,
        interpret=interpret,
    )
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def abfp_matmul_fused(x, w, policy: QuantPolicy,
                      interpret: bool | None = None):
    """Dispatch the fused kernel for a (…, K) x (K, N) quantized matmul."""
    interpret = should_interpret() if interpret is None else interpret
    tq_x, tq_w = policy.input, policy.weight
    if tq_x is None or tq_w is None:
        raise ValueError(
            f"fused path needs both x and w quantizers; policy "
            f"{policy.name!r} has input={tq_x} weight={tq_w}"
        )
    n = tq_x.group
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    bm = fit_block(x2.shape[0])
    bn = fit_block(w.shape[1])
    kw = dict(n=n, block_m=bm, block_n=bn, interpret=interpret)
    if policy.compute == "int8":
        y = _mm_mod.abfp_matmul_int8(x2, w, tq_x.fmt, tq_w.fmt, **kw)
    else:
        y = _mm_mod.abfp_matmul(x2, w, tq_x.fmt, tq_w.fmt, **kw)
    return y.reshape(*shape[:-1], w.shape[1])


def quant_matmul_fused(x, wk, tq_x, interpret: bool | None = None):
    """Compressed-domain Pallas dispatch: (…, K) x stored codes + scales.

    ``wk`` is a ``CompressedKernel``, whose ``(Kp, N)`` int8 or packed
    ``(Kp/2, N)`` INT4 codes and ``(G, N)`` scales the kernel reads as
    stored.  x is zero-padded to the stored (padded) contraction length
    and quantized against ``tq_x`` as the jnp compressed path does, so
    both contract the same codes.
    """
    interpret = should_interpret() if interpret is None else interpret
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if wk.pad:
        x2 = jnp.pad(x2, ((0, 0), (0, wk.pad)))
    y = _mm_mod.quant_matmul(
        x2, wk.codes, wk.scale, tq_x.fmt, n=wk.group,
        scale_dtype=jnp.dtype(tq_x.scale_dtype), interpret=interpret,
    )
    return y.reshape(*shape[:-1], y.shape[-1])


def quant_matmul_grouped_fused(xc, xs, tile_group, tiles_used, wk,
                               interpret: bool | None = None):
    """Grouped compressed-domain Pallas dispatch: rows laid out in tiles
    by expert, as ABFP codes ``(M, G, n)`` and steps ``(M, G)``, against a
    stacked ``CompressedKernel`` ``(E, Kp, N)`` read as stored
    (``quant_matmul.quant_matmul_grouped``)."""
    interpret = should_interpret() if interpret is None else interpret
    M = xc.shape[0]
    return _mm_mod.quant_matmul_grouped(
        xc.reshape(M, -1), xs, tile_group, jnp.reshape(tiles_used, (1,)),
        wk.codes, wk.scale, interpret=interpret)
