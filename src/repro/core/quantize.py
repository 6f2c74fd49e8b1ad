"""Quantize / de-quantize primitives (paper eqns (1)-(3), (6)-(9)).

All QDQ functions take ``alpha`` — the clipping range — and map it onto the
format's largest magnitude: ``scale = alpha / fmt.qmax_pos`` so that
``x = alpha`` lands exactly on the top code.  This matches the paper's
``s = qmax / alpha`` with ``x_q = clip(round(s*x))`` and ``x_hat = x_q / s``.

``qdq_ste`` is the QAT forward/backward: identical forward, with the
piecewise-linear estimator of eqn (5): ``dQ/dx = 1{|x| <= alpha}``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.formats import Format

_EPS = 1e-12


def _unit_scale(alpha: jnp.ndarray, fmt: Format) -> jnp.ndarray:
    """Step/scale mapping clip-range ``alpha`` to the top code of ``fmt``."""
    return jnp.maximum(jnp.abs(alpha), _EPS) / fmt.qmax_pos


def qdq(x: jnp.ndarray, alpha: jnp.ndarray, fmt: Format) -> jnp.ndarray:
    """Simulated quantization: DQ(Q(x; alpha, fmt)).

    ``alpha`` broadcasts against ``x`` (per-tensor scalar, per-channel, or
    per-group after reshaping — see ``repro.core.abfp``).
    """
    scale = _unit_scale(alpha, fmt).astype(jnp.float32)
    xs = x.astype(jnp.float32) / scale
    return (fmt.qdq_unit(xs) * scale).astype(x.dtype)


def quantize(x: jnp.ndarray, alpha: jnp.ndarray, fmt: Format, dtype=jnp.int8):
    """Real quantization to integer codes (storage / native-int8 compute).

    Returns ``(codes, scale)`` with ``x ≈ codes * scale``.
    Only defined for integer formats.
    """
    scale = _unit_scale(alpha, fmt).astype(jnp.float32)
    codes = fmt.quantize_unit(x.astype(jnp.float32) / scale, dtype=dtype)
    return codes, scale


def dequantize(codes: jnp.ndarray, scale: jnp.ndarray, dtype=jnp.float32):
    return codes.astype(jnp.float32) * scale.astype(jnp.float32)


# ---------------------------------------------------------------------------
# 4-bit code packing (compressed weight storage)
# ---------------------------------------------------------------------------
def pack_int4_codes(codes: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Pack signed 4-bit codes two-per-byte along ``axis`` (even length).

    The first half of ``axis`` lands in the low nibbles, the second half in
    the high nibbles; each nibble is the code's 4-bit two's complement.
    Unpacking is then elementwise plus one concatenation, with no
    interleave for the compiler to relayout.  Inverse of
    ``unpack_int4_codes``.
    """
    if codes.shape[axis] % 2:
        name = "last dim" if axis % codes.ndim == codes.ndim - 1 else \
            f"dim {axis}"
        raise ValueError(
            f"pack_int4_codes needs an even {name}, got {codes.shape}"
        )
    lo, hi = jnp.split(codes.astype(jnp.int32), 2, axis=axis)
    return ((lo & 0xF) | ((hi & 0xF) << 4)).astype(jnp.uint8)


def int4_nibbles(packed: jnp.ndarray):
    """uint8 nibble pairs -> ``(low, high)`` int8 codes, elementwise."""
    b = jax.lax.bitcast_convert_type(packed, jnp.int8)
    # arithmetic shifts sign-extend each nibble's two's complement
    return (b << 4) >> 4, b >> 4


def unpack_int4_codes(packed: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """uint8 nibble pairs -> int8 codes; dim ``axis`` doubles."""
    return jnp.concatenate(int4_nibbles(packed), axis=axis)


# ---------------------------------------------------------------------------
# QAT: piecewise-linear straight-through estimator (paper eqn (5)).
# ---------------------------------------------------------------------------
import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def qdq_ste(x: jnp.ndarray, alpha: jnp.ndarray, fmt: Format) -> jnp.ndarray:
    return qdq(x, alpha, fmt)


def _qdq_ste_fwd(x, alpha, fmt):
    return qdq(x, alpha, fmt), (x, jnp.abs(alpha))


def _qdq_ste_bwd(fmt, res, g):
    x, a = res
    mask = (jnp.abs(x) <= a).astype(g.dtype)
    # Scales are dynamic (ABFP max) or static (calibrated): not learned, so
    # they receive no gradient (paper eqn (5) differentiates w.r.t. x only).
    return (g * mask, jnp.zeros(jnp.shape(a), g.dtype))


qdq_ste.defvjp(_qdq_ste_fwd, _qdq_ste_bwd)


def maybe_ste(x, alpha, fmt, ste: bool):
    """Dispatch between plain QDQ (PTQ / eval) and STE QDQ (QAT)."""
    if ste:
        return qdq_ste(x, jnp.asarray(alpha), fmt)
    return qdq(x, alpha, fmt)
