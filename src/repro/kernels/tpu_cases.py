"""Every Pallas kernel body at ``qwen2-7b`` widths, compiled for the TPU.

One table, two readers: ``tests/test_tpu_compile.py`` compiles each case
for a described v5e (shapes only, via ``jax.eval_shape`` of ``make``), and
``chip_smoke.py`` runs each case on the chip against its ``kernels/ref.py``
oracle.  Every kernel is called with ``interpret=False``.

Widths (arXiv:2407.10671): d_model 3584, d_ff 18944, 28 query / 4 KV
heads of 128, vocabulary 152064.  Serving shapes follow the chip smoke's
engine: 4 slots, pages gathered to T = max_len = 512, prefill chunks of
64; the multi-block attention bodies run at T = 4096, past the
single-block cut-off (2048).  The stored-codes matmul also runs at the
benchmark engine's 16 decode rows and 16 x 64 prefill rows.

The paged decode attention kernel reads a layer-stacked int8 pool in
place at the benchmark engines' geometry: 16 rows, pages of 16, contexts
spread over 2048 and 32768 positions, 28/4 heads (qwen2-7b) and 32/4
(Mellum2-12B-A2.5B).

The grouped stored-codes matmul runs at Mellum2-12B-A2.5B's expert widths
(d_model 2304, expert width 896, 64 experts, top-8) on its benchmark
engine's rows: 32 slots decoding (256 routed rows in tiles of 32) and a
32 x 64-row prefill call (16384 routed rows in tiles of 128), with the
tiles laid out over the experts and some left unused.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import FP8_E4M3, INT4, INT8
from repro.core.policy import TensorQuant
from repro.kernels import ref
from repro.kernels.abfp_qdq import abfp_qdq
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ops import (flash_attention_quant_gqa,
                               paged_decode_attention_quant_gqa)
from repro.core.abfp import abfp_quantize
from repro.kernels.quant_matmul import (abfp_matmul, abfp_matmul_int8,
                                        grouped_tile_rows, quant_matmul,
                                        quant_matmul_grouped)
from repro.models.serving_transforms import compress_kernel

D_MODEL, D_FF, HEADS, KV_HEADS, HEAD_DIM = 3584, 18944, 28, 4, 128
VOCAB = 152064
N_GROUP = 64  # w4a8_abfp's ABFP group
SLOTS, MAX_LEN, CHUNK = 4, 512, 64
# Mellum2-12B-A2.5B's experts and its benchmark engine's slots
EXPERT_D, EXPERT_F, EXPERTS, TOP_K, MOE_SLOTS = 2304, 896, 64, 8, 32
# the benchmark engines' paged decode: 16 rows, pages of 16, two layers
PAGED_ROWS, PAGE, PAGED_LAYERS = 16, 16, 2

# Kernels and oracles both run at the TPU's default matmul precision (one
# bf16 MXU pass over f32 operands, f32 accumulation), so the tolerances
# cover accumulation order and rounding boundaries, not precision.
_MATMUL_WHY = ("both sides round the same operands to bf16 for the MXU or "
               "contract exact int codes; f32 accumulation order and a code "
               "one step off at a rounding boundary stay under 1e-3")
_ATTN_WHY = ("both sides take the same bf16 MXU pass; accumulation order "
             "and the online softmax's rescaling stay under 1e-3")
_ONLINE_WHY = ("the online body rounds unnormalized probabilities to bf16 "
               "for the MXU, the oracle normalized ones: each within 2^-9, "
               "so within 2^-8 of max|v|, under 2x max|ref| on these inputs")
_PROBS_WHY = ("as without probs QDQ, plus a probability code that rounds one "
              "int8 step apart: max|v|/127, under 1% of the output range")


@dataclasses.dataclass(frozen=True)
class KernelCase:
    name: str
    kernel: Callable  # arrays -> output (the Pallas kernel, compiled)
    ref: Callable     # arrays -> the kernels/ref.py oracle's output
    make: Callable    # PRNG key -> tuple of input arrays
    tol: float        # max |kernel - ref| allowed, as a share of max |ref|
    why: str          # one line: where the allowed error comes from


def _normal(key, shape, scale=1.0):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _qdq_case(fmt, tol, why):
    return KernelCase(
        f"abfp_qdq[{fmt.name}]",
        lambda x: abfp_qdq(x, fmt, n=N_GROUP, interpret=False),
        lambda x: ref.abfp_qdq_ref(x, fmt, N_GROUP),
        lambda key: (_normal(key, (256, D_MODEL), 2.0),),
        tol, why)


def _dense_w(key, m):
    kx, kw = jax.random.split(key)
    return (_normal(kx, (m, D_MODEL)),
            _normal(kw, (D_MODEL, D_FF), D_MODEL ** -0.5))


def _codes_w(key, m, k, n):
    """x and w4a8_abfp's stored INT4 weight, packed as served."""
    kx, kw = jax.random.split(key)
    ck = compress_kernel(_normal(kw, (k, n), k ** -0.5),
                         TensorQuant("int4", scaler="abfp", group=N_GROUP))
    return _normal(kx, (m, k)), ck.codes, ck.scale


def _quant_matmul_case(m, label, k=D_MODEL, n=D_FF):
    """The stored-codes kernel as the compressed backend calls it: packed
    codes, the blocks ``codes_blocks`` picks for the shape."""
    return KernelCase(
        f"quant_matmul[{label} M={m}]",
        lambda x, c, s: quant_matmul(x, c, s, INT8, n=N_GROUP,
                                     interpret=False),
        lambda x, c, s: ref.quant_matmul_ref(x, c, s, INT8, N_GROUP),
        lambda key: _codes_w(key, m, k, n), 1e-3, _MATMUL_WHY)


def _grouped_w(key, routed, k, n):
    """Rows laid out by expert as the MoE layer lays them out for
    ``routed`` rows: a tile per expert past its rows, some tiles unused;
    and the experts' stacked INT4 codes, packed as served."""
    kx, kw = jax.random.split(key)
    bm = grouped_tile_rows(routed, EXPERTS)
    tiles = -(-routed // bm) + EXPERTS
    used = tiles - 3
    # the experts in order, each over one or more tiles
    group = jnp.minimum(jnp.arange(tiles) * EXPERTS // used, EXPERTS - 1)
    ck = compress_kernel(_normal(kw, (EXPERTS, k, n), k ** -0.5),
                         TensorQuant("int4", scaler="abfp", group=N_GROUP))
    return (_normal(kx, (tiles * bm, k)), group.astype(jnp.int32),
            jnp.full((1,), used, jnp.int32), ck.codes, ck.scale)


def _grouped(x, group, used, codes, scales):
    xc, xs, _ = abfp_quantize(x, INT8, axis=-1, n=N_GROUP)
    y = quant_matmul_grouped(xc.reshape(x.shape[0], -1), xs, group, used,
                             codes, scales, interpret=False)
    bm = x.shape[0] // group.shape[0]
    return jnp.where(jnp.arange(x.shape[0])[:, None] < used[0] * bm, y, 0.0)


def _grouped_case(routed, label, k, n):
    return KernelCase(
        f"quant_matmul_grouped[{label} rows={routed}]",
        _grouped,
        lambda x, g, u, c, s: ref.quant_matmul_grouped_ref(
            x, g, u[0], c, s, INT8, N_GROUP),
        lambda key: _grouped_w(key, routed, k, n), 1e-3, _MATMUL_WHY)


def _attn_inputs(key, s, t, code_dtype):
    """Quantized-KV attention inputs shaped like a paged gather: rows hold
    different context lengths, unwritten positions carry kv_pos = -1."""
    kq, kk, kv, ks, kvs = jax.random.split(key, 5)
    q = _normal(kq, (SLOTS, s, HEADS, HEAD_DIM))
    shape = (SLOTS, t, KV_HEADS, HEAD_DIM)
    kc, vc = (jax.random.randint(k, shape, -127, 128).astype(jnp.float32)
              for k in (kk, kv))
    if code_dtype == "int8":
        kc, vc = kc.astype(jnp.int8), vc.astype(jnp.int8)
    else:  # e4m3-representable values
        kc, vc = ((c / 16.0).astype(jnp.float8_e4m3fn) for c in (kc, vc))
    k_scale = jax.random.uniform(ks, shape[:3]) * 0.05 + 1e-3
    v_scale = jax.random.uniform(kvs, shape[:3]) * 0.05 + 1e-3
    ctx = jnp.asarray([t, (t * 5) // 8, t // 4 + 1, s + 1], jnp.int32)
    q_pos = ctx[:, None] - s + jnp.arange(s, dtype=jnp.int32)[None]
    idx = jnp.arange(t, dtype=jnp.int32)[None]
    kv_pos = jnp.where(idx < ctx[:, None], idx, -1)
    return q, kc, vc, k_scale, v_scale, q_pos, kv_pos


def _flash_quant_case(body, s, t, code_dtype, probs):
    tq = None
    if probs:
        from repro.core.policy import preset

        tq = preset("w4a8_abfp").input  # int8 ABFP, n=64, BF16 scales
    return KernelCase(
        f"flash_attention_quant[{body} S={s} T={t} {code_dtype}"
        f"{' probs-n64' if probs else ''}]",
        lambda *a: flash_attention_quant_gqa(*a, probs_tq=tq,
                                             interpret=False),
        lambda *a: ref.flash_attention_quant_ref(
            *a, probs_fmt=None if tq is None else tq.fmt,
            probs_n=0 if tq is None else tq.group),
        lambda key: _attn_inputs(key, s, t, code_dtype),
        *((1e-2, _PROBS_WHY) if probs else (2.0 ** -7, _ONLINE_WHY)
          if body == "online" else (1e-3, _ATTN_WHY)))


def _paged_inputs(key, t, heads):
    """A layer-stacked int8 pool holding 16 rows' pages in scrambled
    physical order, the rows' contexts spread from 1 token to nearly
    ``t``; the kernel reads layer 1."""
    kq, kk, kv, ks, kvs, kp = jax.random.split(key, 6)
    nl = t // PAGE
    r = np.arange(PAGED_ROWS)
    ctx = np.maximum(1, (r + 1) * t // PAGED_ROWS - 7 * r).astype(np.int32)
    need = -(-ctx // PAGE)
    n_pages = int(need.sum())
    first = np.cumsum(need) - need
    perm = jax.random.permutation(kp, n_pages).astype(jnp.int32)
    j = jnp.arange(nl, dtype=jnp.int32)[None]
    table = jnp.where(j < need[:, None],
                      perm[jnp.minimum(first[:, None] + j, n_pages - 1)], -1)
    shape = (PAGED_LAYERS, n_pages + 1, PAGE, KV_HEADS * HEAD_DIM)
    kc, vc = (jax.random.randint(k, shape, -127, 128).astype(jnp.int8)
              for k in (kk, kv))
    scales = (PAGED_LAYERS, n_pages + 1, KV_HEADS)
    k_scale = jax.random.uniform(ks, scales) * 0.05 + 1e-3
    v_scale = jax.random.uniform(kvs, scales) * 0.05 + 1e-3
    q = _normal(kq, (PAGED_ROWS, 1, heads, HEAD_DIM))
    return q, kc, vc, k_scale, v_scale, table, ctx, ctx - 1


def _paged_case(t, heads, probs, label):
    tq = None
    if probs:
        from repro.core.policy import preset

        tq = preset("w4a8_abfp").input  # int8 ABFP, n=64, BF16 scales
    return KernelCase(
        f"flash_attention_quant[paged decode {label} {heads}/{KV_HEADS} "
        f"T={t}{' probs-n64' if probs else ''}]",
        lambda *a: paged_decode_attention_quant_gqa(
            *a, 1, probs_tq=tq, interpret=False),
        lambda *a: ref.paged_decode_attention_quant_ref(
            *a, 1, probs_fmt=None if tq is None else tq.fmt,
            probs_n=0 if tq is None else tq.group),
        lambda key: _paged_inputs(key, t, heads),
        *((1e-2, _PROBS_WHY) if probs else (2.0 ** -7, _ONLINE_WHY)))


def kernel_cases() -> list[KernelCase]:
    cases = [
        _qdq_case(INT8, 1 / 127,
                  "codes match the oracle's; a value on a rounding boundary "
                  "may land one step (1/127 of its group max) away"),
        _qdq_case(FP8_E4M3, 2.0 ** -3,
                  "one e4m3 step is 2^-3 of its binade, if a value rounds "
                  "the other way at a boundary"),
        KernelCase(
            "abfp_matmul[int8 x int4]",
            lambda x, w: abfp_matmul(x, w, INT8, INT4, n=N_GROUP,
                                     interpret=False),
            lambda x, w: ref.abfp_matmul_ref(x, w, INT8, INT4, N_GROUP),
            lambda key: _dense_w(key, 256), 1e-3, _MATMUL_WHY),
        KernelCase(
            "abfp_matmul_int8[int8 x int4]",
            lambda x, w: abfp_matmul_int8(x, w, INT8, INT4, n=N_GROUP,
                                          interpret=False),
            lambda x, w: ref.int8_matmul_ref(x, w, INT8, INT4, N_GROUP),
            lambda key: _dense_w(key, 256), 1e-3, _MATMUL_WHY),
        _quant_matmul_case(SLOTS, "decode"),
        _quant_matmul_case(SLOTS * CHUNK, "prefill"),
    ]
    # the benchmark's qwen2-7b engine: 16 decode rows, 16 x 64 prefill rows
    for m in (16, 16 * CHUNK):
        cases += [
            _quant_matmul_case(m, "wi", D_MODEL, D_FF),
            _quant_matmul_case(m, "wo", D_FF, D_MODEL),
            _quant_matmul_case(m, "lm_head", D_MODEL, VOCAB),
        ]
    # Mellum2's experts: wi/wg (d_model -> 896) and wo (896 -> d_model)
    for routed, label in ((MOE_SLOTS * TOP_K, "decode"),
                          (MOE_SLOTS * CHUNK * TOP_K, "prefill")):
        cases += [
            _grouped_case(routed, f"{label} wi", EXPERT_D, EXPERT_F),
            _grouped_case(routed, f"{label} wo", EXPERT_F, EXPERT_D),
        ]
    cases += [
        KernelCase(
            "flash_attention[S=T=512]",
            lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            interpret=False),
            lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=True),
            lambda key: tuple(_normal(k, (HEADS, MAX_LEN, HEAD_DIM))
                              for k in jax.random.split(key, 3)),
            2.0 ** -7, _ONLINE_WHY),
    ]
    for code_dtype in ("int8", "fp8"):
        cases += [
            _flash_quant_case("exact", 1, MAX_LEN, code_dtype, False),
            _flash_quant_case("exact", 1, MAX_LEN, code_dtype, True),
            _flash_quant_case("exact", CHUNK, MAX_LEN, code_dtype, True),
            _flash_quant_case("online", CHUNK, 4096, code_dtype, False),
            _flash_quant_case("phased", CHUNK, 4096, code_dtype, True),
        ]
    # paged decode at the benchmark cells' widths: qwen2-7b (28/4 heads)
    # at 2048 and 32768 positions, Mellum2-12B-A2.5B (32/4) at 2048
    cases += [
        _paged_case(2048, HEADS, True, "qwen2"),
        _paged_case(2048, HEADS, False, "qwen2"),
        _paged_case(32768, HEADS, True, "qwen2"),
        _paged_case(2048, 32, True, "mellum2"),
    ]
    return cases
