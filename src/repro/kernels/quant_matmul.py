"""Pallas TPU kernel: fused ABFP-quantized matmul.

Computes ``y = DQ(Q(x)) @ DQ(Q(w))`` (paper eqns (6)-(8)) in one kernel:
every (BM, BK) x-tile and (BK, BN) w-tile is quantize-dequantized against
its per-vector (n along K) BF16 max *in VMEM*, then fed to the MXU with an
fp32 accumulator scratch.  HBM sees each operand exactly once — the
simulator's QDQ becomes free of extra memory traffic.

Variants:
  * ``abfp_matmul``      — fp path (paper-faithful numerics).
  * ``abfp_matmul_int8`` — beyond-paper: per-group int8 codes contracted
    with int32 accumulation (2x MXU throughput on TPU), rescaled per group.
  * ``quant_matmul``     — compressed-domain serving: the weight arrives as
    PRE-QUANTIZED int8 codes (K, N) + per-group unit scales (G, N); only
    x is quantized in-kernel.  HBM reads the codes, never a dequantized
    kernel — the ``compressed`` execution backend's fast path.

Grid = (M/BM, N/BN, K/BK), K innermost so the accumulator lives in VMEM
scratch across K steps (canonical Pallas matmul schedule).  BM/BN/BK are
128-multiples for MXU alignment; BK is a multiple of the group length n.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import Format, IntFormat
from repro.kernels.abfp_qdq import _qdq_tile, group_scale


def _grouped_int_dot(xc, sx, wc, sw_row, *, n: int):
    """``sum_g (xc_g . wc_g) * sx_g * sw_g`` with an int8 x int8 -> int32
    contraction per ABFP group, rescaled in f32 and summed in group order.

    ``xc``: (bm, bk) integer-valued f32 x codes; ``sx``: (bm, bk) their
    per-element step sizes (uniform inside a group); ``wc``: (bk, bn) int8
    weight codes; ``sw_row(g)``: the (1, bn) weight step sizes of group g.
    Mosaic cannot batch a dot over a middle group axis, so each group's
    lanes of x are contracted with its rows of ``wc`` — exact in int32.
    """
    xi = xc.astype(jnp.int8)
    total = None
    for g in range(xc.shape[1] // n):
        cols = slice(g * n, (g + 1) * n)
        p = jax.lax.dot_general(xi[:, cols], wc[cols, :],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
        part = p.astype(jnp.float32) * sx[:, g * n:g * n + 1] * sw_row(g)
        total = part if total is None else total + part
    return total


def _int_codes(v, scale, fmt):
    return jnp.clip(jnp.round(v / scale), fmt.qmin, fmt.qmax_pos)


def _fp_kernel(x_ref, w_ref, o_ref, acc_ref, *, n, fmt_x, fmt_w, k_steps):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)  # (bm, bk)
    w = w_ref[...].astype(jnp.float32)  # (bk, bn)
    xq = _qdq_tile(x, fmt_x, jnp.bfloat16, n, axis=-1)
    wq = _qdq_tile(w, fmt_w, jnp.bfloat16, n, axis=0)
    acc_ref[...] += jax.lax.dot_general(
        xq, wq, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _int8_kernel(x_ref, w_ref, o_ref, acc_ref, *, n, fmt_x, fmt_w, k_steps):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)  # (bm, bk)
    w = w_ref[...].astype(jnp.float32)  # (bk, bn)
    sx = group_scale(x, n, -1, fmt_x.qmax_pos)  # (bm, bk)
    sw = group_scale(w, n, 0, fmt_w.qmax_pos)  # (bk, bn)
    wc = _int_codes(w, sw, fmt_w).astype(jnp.int8)
    acc_ref[...] += _grouped_int_dot(
        _int_codes(x, sx, fmt_x), sx, wc,
        lambda g: sw[g * n:g * n + 1, :], n=n)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _check_blocking(M, N, K, bm, bn, bk, n):
    """Validate grid divisibility with dims/blocks named in the error."""
    if K % n:
        raise ValueError(
            f"contraction dim K={K} is not a multiple of the ABFP group "
            f"length n={n}"
        )
    if M % bm or N % bn or K % bk:
        raise ValueError(
            f"matmul dims (M={M}, N={N}, K={K}) do not tile by blocks "
            f"(block_m={bm}, block_n={bn}, block_k={bk}); every dim must "
            "divide its block (see kernels.ops.fit_block)"
        )


def _call(kernel, x, w, fmt_x, fmt_w, n, bm, bn, bk, interpret, out_dtype):
    M, K = x.shape
    K2, N = w.shape
    if K != K2:
        raise ValueError(
            f"contraction mismatch: x has K={K} but w has K={K2} "
            f"(x.shape={x.shape}, w.shape={w.shape})"
        )
    bm = min(bm, M)
    bn = min(bn, N)
    bk = min(bk, K)
    bk -= bk % n
    bk = max(bk, min(n, K))  # block_k < n: fall back to one group per step
    _check_blocking(M, N, K, bm, bn, bk, n)
    k_steps = K // bk
    grid = (M // bm, N // bn, k_steps)
    return pl.pallas_call(
        functools.partial(kernel, n=n, fmt_x=fmt_x, fmt_w=fmt_w,
                          k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w)


@functools.partial(
    jax.jit,
    static_argnames=("fmt_x", "fmt_w", "n", "block_m", "block_n", "block_k",
                     "interpret"),
)
def abfp_matmul(
    x: jnp.ndarray, w: jnp.ndarray, fmt_x: Format, fmt_w: Format,
    n: int = 64, block_m: int = 256, block_n: int = 256, block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused fp-path ABFP matmul (paper-faithful numerics)."""
    return _call(_fp_kernel, x, w, fmt_x, fmt_w, n, block_m, block_n,
                 block_k, interpret, jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("fmt_x", "fmt_w", "n", "block_m", "block_n", "block_k",
                     "interpret"),
)
def abfp_matmul_int8(
    x: jnp.ndarray, w: jnp.ndarray, fmt_x: IntFormat = None,
    fmt_w: IntFormat = None, n: int = 64, block_m: int = 256,
    block_n: int = 256, block_k: int = 512, interpret: bool = False,
) -> jnp.ndarray:
    """Fused native-int8 ABFP matmul (beyond-paper fast path)."""
    from repro.core.formats import INT8

    fmt_x = fmt_x or INT8
    fmt_w = fmt_w or INT8
    return _call(_int8_kernel, x, w, fmt_x, fmt_w, n, block_m, block_n,
                 block_k, interpret, jnp.float32)


# ---------------------------------------------------------------------------
# Compressed-domain serving: contract PRE-QUANTIZED weight codes
# ---------------------------------------------------------------------------
def _stored_codes_kernel(x_ref, wc_ref, ws_ref, o_ref, acc_ref, *,
                         n, fmt_x, k_steps):
    """x is quantized in-VMEM; the weight arrives as codes + unit scales."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)   # (bm, bk)
    wc = wc_ref[...]                      # (bk, bn) int8 codes
    gk = x.shape[1] // n
    sx = group_scale(x, n, -1, fmt_x.qmax_pos)  # (bm, bk)
    # ws_ref holds every group's (1, bn) scale row of this N-block
    acc_ref[...] += _grouped_int_dot(
        _int_codes(x, sx, fmt_x), sx, wc,
        lambda g: ws_ref[pl.ds(k * gk + g, 1), :].astype(jnp.float32), n=n)

    @pl.when(k == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("fmt_x", "n", "block_m", "block_n", "block_k",
                     "interpret"),
)
def quant_matmul(
    x: jnp.ndarray, w_codes: jnp.ndarray, w_scales: jnp.ndarray,
    fmt_x: Format, n: int = 64, block_m: int = 256, block_n: int = 256,
    block_k: int = 512, interpret: bool = False,
) -> jnp.ndarray:
    """Compressed-domain matmul: ``x (M, K)`` vs stored weight codes.

    ``w_codes``: (K, N) int8 pre-quantized codes, group g in rows
    ``[g*n, (g+1)*n)``; ``w_scales``: (G, N) f32 unit scales, G*n == K —
    ``CompressedKernel``'s stored layout, read as is.  Only x is
    quantized (in VMEM, against ``fmt_x``); the contraction is int8 x int8
    with int32 accumulation and per-group rescale, so the dense kernel is
    never materialized anywhere — HBM traffic for weights is the codes.
    """
    M, K = x.shape
    if w_codes.ndim != 2:
        raise ValueError(
            f"w_codes must be (K, N) codes, got {w_codes.shape}"
        )
    K2, N = w_codes.shape
    if K2 != K:
        raise ValueError(f"w_codes cover K={K2} but x has K={K}")
    bm = min(block_m, M)
    bn = min(block_n, N)
    bk = min(block_k, K)
    bk -= bk % n
    bk = max(bk, min(n, K))
    _check_blocking(M, N, K, bm, bn, bk, n)
    G = K // n
    if w_scales.shape != (G, N):
        raise ValueError(
            f"w_scales shape {w_scales.shape} != (G, N)=({G}, {N})"
        )
    k_steps = K // bk
    grid = (M // bm, N // bn, k_steps)
    # the scales enter whole along G: the Mosaic lowering only accepts
    # blocks whose last two dims are (8, 128)-aligned or whole
    return pl.pallas_call(
        functools.partial(_stored_codes_kernel, n=n, fmt_x=fmt_x,
                          k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((G, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w_codes, w_scales)
