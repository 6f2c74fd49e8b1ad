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
    its stored codes — int8 (K, N) or packed INT4 (K/2, N) — + per-group
    unit scales (G, N); x arrives as its ABFP codes and steps.  HBM reads
    the codes as stored, never a dequantized or unpacked kernel — the
    ``compressed`` execution backend's contraction on the TPU.

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

from repro.core.abfp import abfp_quantize
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
# Compressed-domain serving: contract the stored weight codes
# ---------------------------------------------------------------------------
_BM_MAX = 256  # x rows per block; longer x pads to a multiple of it
_CHUNK = 8  # ABFP groups one loop iteration contracts
_X_BLOCK = 16 * 2**20  # bytes of an x block: codes and lane-padded steps
_W_BLOCK = 8 * 2**20  # bytes of a block of stored weight codes
# v5e has 128 MiB of VMEM and scopes 16 MiB to a kernel by default; the
# blocks above, double-buffered, need more than that
_VMEM_LIMIT = 64 * 2**20


def _lane_block(total: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``total`` and is at most
    ``cap``, or ``total`` itself (one block) if it is no multiple of 128."""
    if total % 128:
        return total
    units = total // 128
    return 128 * max(d for d in range(1, max(cap // 128, 1) + 1)
                     if units % d == 0)


def codes_blocks(m: int, kp: int, n_out: int, n: int,
                 packed: bool) -> tuple[int, int, int, int]:
    """``(bm, bn, bk, c)`` of ``quant_matmul_codes`` for ``m`` rows of x,
    the stored contraction ``kp`` (in codes), ``n_out`` outputs, groups of
    ``n`` and packed or plain codes: one code path whose blocks follow the
    shape.

    x takes blocks of up to 256 rows; one block at decode, four for a
    1024-row prefill chunk.  A K step is the whole contraction unless its
    x block would pass 16 MiB.  Weight blocks hold up to 8 MiB of codes
    and are up to 2048 wide at decode rows, 512 at prefill rows, which
    keeps the grid short and the f32 accumulator small.  The kernel loops
    over chunks of ``c`` groups, ``c * n`` a multiple of 128 lanes (whole
    groups in one chunk where no such ``c`` divides them).
    """
    bm = min(m, _BM_MAX)
    g = kp // n
    c = max((d for d in range(1, _CHUNK + 1)
             if g % d == 0 and d * n % 128 == 0), default=g)
    # x block bytes per code: the code, and its chunk's steps, whose c
    # lanes pad to 128 f32 in VMEM
    per_code = bm * (1 + 512 / (c * n))
    bk = max((d * c * n for d in range(1, g // c + 1)
              if g // c % d == 0 and d * c * n * per_code <= _X_BLOCK),
             default=c * n)
    w_rows = bk // 2 if packed else bk
    bn = _lane_block(n_out, min(2048 if bm <= 128 else 512,
                                max(_W_BLOCK // w_rows, 128)))
    return bm, bn, bk, c


def _codes_kernel(xc_ref, xs_ref, wc_ref, ws_ref, o_ref, acc_ref, *,
                  n, packed, k_steps):
    """One (bm, bn) output tile: each ABFP group's int8 x int8 product in
    int32, rescaled in f32 by its x step and weight step, summed in VMEM.

    A loop over the K step's chunks of ``c`` groups keeps the kernel's
    code the size of one chunk.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    chunks, _, c = xs_ref.shape  # x steps of this K step: (chunk, row, c)
    rows = n // 2 if packed else n  # stored code rows of one group

    def chunk(i, carry):
        xc = xc_ref[:, pl.ds(pl.multiple_of(i * c * n, c * n), c * n)]
        wc = wc_ref[pl.ds(pl.multiple_of(i * c * rows, c * rows),
                          c * rows), :]
        sx = xs_ref[i]  # (bm, c)
        total = None
        for q in range(c):
            b = wc[q * rows:(q + 1) * rows]
            if packed:
                # the group's first n/2 code rows in the low nibbles, the
                # rest in the high ones (sign-extended shifts)
                b = b.astype(jnp.int32)
                b = jnp.concatenate([(b << 28) >> 28, (b << 24) >> 28])
                b = b.astype(jnp.int8)
            p = jax.lax.dot_general(xc[:, q * n:(q + 1) * n], b,
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.int32)
            g = (k * chunks + i) * c + q
            part = (p.astype(jnp.float32) * sx[:, q:q + 1]
                    * ws_ref[pl.ds(g, 1), :])
            total = part if total is None else total + part
        acc_ref[...] += total
        return carry

    jax.lax.fori_loop(0, chunks, chunk, 0)

    @pl.when(k == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_matmul_codes(xc: jnp.ndarray, xs: jnp.ndarray,
                       w_codes: jnp.ndarray, w_scales: jnp.ndarray,
                       interpret: bool = False) -> jnp.ndarray:
    """``(M, N)`` f32 ``sum_g xs[:, g] * w_scales[g] * (xc_g . w_g)``.

    ``xc``: (M, Kp) int8 x codes and ``xs``: (M, G) their steps, as
    ``core.abfp.abfp_quantize`` gives them; ``w_codes`` / ``w_scales``:
    ``CompressedKernel``'s stored layout, read as is — (Kp, N) int8 codes,
    or (Kp/2, N) uint8 packed INT4 (group g's byte rows hold its first
    half of code rows in the low nibbles, the second in the high ones),
    and (G, N) f32 steps.  Packed codes are unpacked in VMEM; each group's
    int32 product and its rescale stay there, and only the output is
    written.  Blocks follow the shape (``codes_blocks``).
    """
    M, Kp = xc.shape
    G, N = w_scales.shape
    n = Kp // G
    packed = w_codes.dtype == jnp.uint8
    bm, bn, bk, c = codes_blocks(M, Kp, N, n, packed)
    k_steps = Kp // bk
    Mp = -(-M // bm) * bm
    if Mp > M:
        xc = jnp.pad(xc, ((0, Mp - M), (0, 0)))
        xs = jnp.pad(xs, ((0, Mp - M), (0, 0)))
    # x steps as (chunk, row, group of the chunk): the kernel indexes a
    # chunk on the leading axis and its groups statically
    xs = xs.astype(jnp.float32).reshape(Mp, G // c, c).transpose(1, 0, 2)
    ck = bk // (c * n)  # chunks per K step
    wb = bk // 2 if packed else bk
    y = pl.pallas_call(
        functools.partial(_codes_kernel, n=n, packed=packed,
                          k_steps=k_steps),
        grid=(Mp // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((ck, bm, c), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((wb, bn), lambda i, j, k: (k, j)),
            # whole along G: a (gk, bn) block would break the (8, 128) rule
            pl.BlockSpec((G, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(xc, xs, w_codes, w_scales.astype(jnp.float32))
    return y[:M]


@functools.partial(
    jax.jit, static_argnames=("fmt_x", "n", "scale_dtype", "interpret"))
def quant_matmul(
    x: jnp.ndarray, w_codes: jnp.ndarray, w_scales: jnp.ndarray,
    fmt_x: IntFormat, n: int = 64, scale_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jnp.ndarray:
    """Compressed-domain matmul: ``x (M, K)`` vs stored weight codes.

    ``w_codes``: (K, N) int8 codes, or (K/2, N) uint8 packed INT4, group g
    in code rows ``[g*n, (g+1)*n)``; ``w_scales``: (G, N) f32 unit
    scales, G*n == K.  x is quantized once, by ``abfp_quantize`` (the
    codes and steps the jnp compressed path contracts), and the kernel
    contracts int8 x int8 with int32 accumulation and per-group rescale,
    so no dense or unpacked weight is materialized anywhere.
    """
    M, K = x.shape
    if w_codes.ndim != 2:
        raise ValueError(
            f"w_codes must be (K, N) codes, got {w_codes.shape}"
        )
    rows, N = w_codes.shape
    K2 = rows * 2 if w_codes.dtype == jnp.uint8 else rows
    if K2 != K:
        raise ValueError(f"w_codes cover K={K2} but x has K={K}")
    if K % n:
        raise ValueError(
            f"contraction dim K={K} is not a multiple of the ABFP group "
            f"length n={n}"
        )
    G = K // n
    if w_scales.shape != (G, N):
        raise ValueError(
            f"w_scales shape {w_scales.shape} != (G, N)=({G}, {N})"
        )
    xc, xs, _ = abfp_quantize(x, fmt_x, axis=-1, n=n,
                              scale_dtype=scale_dtype)
    return quant_matmul_codes(xc.reshape(M, K), xs, w_codes, w_scales,
                              interpret=interpret)
