"""The decoder's operation and byte counts against hand counts for a tiny
model, and the roofline share."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest  # noqa: E402

from bench.harness import architecture  # noqa: E402
from bench.work import roofline_share  # noqa: E402

dec = architecture("decoder")
Shape, WorkCounter = dec.Shape, dec.WorkCounter
attention_flops, kernel_bytes = dec.attention_flops, dec.kernel_bytes
linear_flops, readout_flops = dec.linear_flops, dec.readout_flops
KERNEL = "flash_attention_quant"

# 2 layers, d 8, 2 query heads and 1 KV head of 4, d_ff 16, vocab 10
S = Shape(n_layers=2, d_model=8, n_heads=2, n_kv=1, head_dim=4, d_ff=16,
          vocab=10, page_size=4)


def test_linear_flops_by_hand():
    # q 8x8, k 8x4, v 8x4, o 8x8: 192; gate, up, down 8x16 each: 384
    per_layer = 8 * 8 + 8 * 4 + 8 * 4 + 8 * 8 + 3 * 8 * 16
    assert linear_flops(S) == 2 * 2 * per_layer == 2304


def test_attention_and_readout_flops_by_hand():
    # one query over 5 positions: QK 2 heads x 4 x 5, PV the same, x2, x2 L
    assert attention_flops(S, 5) == 2 * 2 * (2 * 4 * 5) * 2 == 320
    assert readout_flops(S) == 2 * 8 * 10


def test_kernel_bytes_by_hand():
    # 5 live positions: 2 pages; K and V codes 1 B x 4 x 5, scales 2 x 4 B,
    # per layer; one query and one output row of 2 heads x 4 f32
    kv = 2 * 2 * (4 * 5 + 2 * 4)
    qo = 2 * 2 * 1 * 2 * 4 * 4
    assert kernel_bytes(S, 1, 5) == kv + qo == 240


def test_counter_counts_need_not_padding():
    w = WorkCounter(S, KERNEL)
    w.prefill(0, 3, emits=False)  # rows at contexts 1, 2, 3
    w.prefill(3, 5, emits=True)  # contexts 4, 5; the last row is read out
    w.decode(5)  # context 6, read out
    attn = sum(attention_flops(S, c) for c in range(1, 7))
    assert w.model_flops == 6 * linear_flops(S) + attn + 2 * readout_flops(S)
    assert w.kernels == {KERNEL: [attn, kernel_bytes(S, 3, 3)
                                  + kernel_bytes(S, 2, 5)
                                  + kernel_bytes(S, 1, 6)]}


def test_counter_without_a_kernel_counts_the_model_only():
    w, named = WorkCounter(S), WorkCounter(S, KERNEL)
    for c in (w, named):
        c.prefill(0, 4, emits=True)
        c.decode(4)
    assert w.kernels == {} and w.model_flops == named.model_flops > 0


def test_roofline_share_takes_the_larger_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    # 200 FLOPs take 2 s at the peak, 10 bytes 1 s: 2 s of 4 s measured
    assert roofline_share(200, 10, 4.0, peaks) == pytest.approx(50.0)
    assert roofline_share(100, 40, 8.0, peaks) == pytest.approx(50.0)
