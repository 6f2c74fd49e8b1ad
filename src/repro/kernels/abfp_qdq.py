"""Pallas TPU kernel: fused ABFP quantize-dequantize (paper eqn (4) + (2,3)).

The paper's simulator applies QDQ as separate tensor ops around each matmul
— on TPU that is 3 extra HBM round-trips per operand.  This kernel fuses the
per-vector (n=64/128) max, quantize and dequantize into one VMEM-resident
pass: each (BM, BK) tile is loaded once, grouped along K, scaled against its
BF16 group max, rounded/clipped in-register, rescaled and written once.

Block shapes are MXU/VPU aligned: BK a multiple of the group length n (so
groups never straddle tiles) and lanes of 128; BM a multiple of 8 (f32
sublane) — see the taxonomy's quantized-kernel guidance (B.12).

Target is TPU (pl.pallas_call + BlockSpec); on CPU we run interpret=True.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import Format, IntFormat


def group_amax(a: jnp.ndarray, n: int, axis: int = -1) -> jnp.ndarray:
    """Max of ``|a|`` over each aligned run of ``n`` elements along ``axis``,
    broadcast back to every element of the run (2-D tiles).

    Mosaic refuses to split the lane dim into ``(groups, n)`` (an
    unsupported shape cast), so the group max is a doubling scan of
    ``pltpu.roll`` shifts instead: after the steps with shifts up to s,
    every element holds the max over its group members within distance
    2s - 1, and ceil(log2 n) steps cover the group.  ``pltpu.roll`` moves
    element i to i + shift, like ``jnp.roll``, so an element's neighbour
    under a shift of s sits in its group when its offset in the group is at
    least s, and under L - s (that is, -s) when the offset is below n - s.
    A max is exact, so the result equals a reshape-and-reduce bit for bit.
    """
    axis = axis % a.ndim
    L = a.shape[axis]
    a = jnp.abs(a)
    pos = jax.lax.broadcasted_iota(jnp.int32, a.shape, axis) % n
    s = 1
    while s < n:
        for shift, same in ((s, pos >= s), (L - s, pos < n - s)):
            a = jnp.maximum(a, jnp.where(same, pltpu.roll(a, shift, axis), a))
        s *= 2
    return a


def group_scale(v: jnp.ndarray, n: int, axis: int, qmax: float,
                scale_dtype=jnp.bfloat16) -> jnp.ndarray:
    """Per-element ABFP step size: the group max rounded to ``scale_dtype``
    (round-to-nearest, as in core/abfp and the ref oracles) over ``qmax``."""
    alpha = group_amax(v, n, axis).astype(scale_dtype).astype(jnp.float32)
    return jnp.maximum(alpha, 1e-12) / qmax


def _qdq_tile(x: jnp.ndarray, fmt: Format, scale_dtype, n: int,
              axis: int = -1) -> jnp.ndarray:
    """QDQ a 2-D f32 tile in groups of ``n`` along ``axis``."""
    scale = group_scale(x, n, axis, fmt.qmax_pos, scale_dtype)
    if isinstance(fmt, IntFormat):
        q = jnp.clip(jnp.round(x / scale), fmt.qmin, fmt.qmax_pos)
        return q * scale
    return fmt.qdq_unit(x / scale) * scale


def _kernel(x_ref, o_ref, *, n: int, fmt: Format, scale_dtype):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = _qdq_tile(x, fmt, scale_dtype, n).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "n", "block_m", "block_k", "interpret"),
)
def abfp_qdq(
    x: jnp.ndarray,
    fmt: Format,
    n: int = 64,
    block_m: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused ABFP QDQ along the last dim of a 2-D array (M, K)."""
    M, K = x.shape
    if K % n:
        raise ValueError(
            f"last dim K={K} is not a multiple of the ABFP group length "
            f"n={n}"
        )
    bk = min(block_k, K)
    bk -= bk % n
    bk = max(bk, min(n, K))  # block_k < n: one group per tile
    bm = min(block_m, M)
    if K % bk or M % bm:
        raise ValueError(
            f"QDQ dims (M={M}, K={K}) do not tile by blocks "
            f"(block_m={bm}, block_k={bk}); every dim must divide its "
            "block (see kernels.ops.fit_block)"
        )
    grid = (M // bm, K // bk)
    return pl.pallas_call(
        functools.partial(_kernel, n=n, fmt=fmt, scale_dtype=jnp.bfloat16),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, K), x.dtype),
        interpret=interpret,
    )(x)
