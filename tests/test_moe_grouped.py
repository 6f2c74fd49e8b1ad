"""The grouped stored-codes contraction of the experts: the Pallas kernel
in interpret mode against a jnp grouped contraction, on layouts with
empty experts, one expert taking every row and rows marked invalid; and
the MoE layer on the kernel against the layer on the jnp path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import abfp
from repro.core.policy import preset, resolve_policy
from repro.core.simulate import codes_contraction
from repro.kernels.quant_matmul import quant_matmul_grouped
from repro.models import serving_transforms as st
from repro.nn.module import unbox
from repro.nn.moe import MoE, _take_rows, expert_layout

E, K, N, T, TOP = 4, 256, 128, 24, 2
POL = preset("w4a8_abfp")


def _routing(kind, rng):
    idx = np.stack([rng.permutation(E)[:TOP] for _ in range(T)])
    valid = np.ones(T, bool)
    if kind == "empty experts":  # experts 1 and 3 take no row
        idx = np.stack([rng.permutation([0, 2])[:TOP] for _ in range(T)])
    elif kind == "one expert takes every row":
        idx = np.zeros((T, 1), np.int64)
    elif kind == "rows marked invalid":
        valid = rng.random(T) > 0.4
    return jnp.asarray(idx), jnp.asarray(valid)


@pytest.mark.parametrize("bm", [32, 128])
@pytest.mark.parametrize("kind", ["mixed", "empty experts",
                                  "one expert takes every row",
                                  "rows marked invalid"])
def test_grouped_kernel_equals_jnp_grouped_contraction(kind, bm):
    rng = np.random.default_rng(1)
    idx, valid = _routing(kind, rng)
    lay = expert_layout(idx, valid, E, bm)
    w = jnp.asarray(rng.standard_normal((E, K, N)) * K ** -0.5, jnp.float32)
    wk = st.compress_kernel(w, POL.weight)
    x = jnp.asarray(rng.standard_normal((T, K)), jnp.float32)
    xc, xs, _ = abfp.abfp_quantize(x, POL.input.fmt, axis=-1, n=64)
    xc, xs = _take_rows(xc, lay.src), _take_rows(xs, lay.src)
    got = np.asarray(quant_matmul_grouped(
        xc.reshape(xc.shape[0], -1), xs, lay.tile_group,
        lay.tiles_used.reshape(1), wk.codes, wk.scale, interpret=True))
    group = np.asarray(lay.row_group())
    used = group >= 0
    want = np.stack([np.asarray(codes_contraction(xc[r], xs[r],
                                                  wk.index(max(g, 0))))
                     for r, g in enumerate(group)])
    # the same int32 group products, rescaled and summed in f32 in
    # another order
    np.testing.assert_allclose(got[used], want[used], rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())
    rows = np.asarray(lay.group_rows)
    assert rows.sum() == TOP * int(valid.sum()) if kind != \
        "one expert takes every row" else rows[0] == T
    assert int(lay.tiles_used) == int((-(-rows // bm)).sum())
    # the kernel parks unused tiles' output on the last tile
    assert int(lay.tiles_used) < lay.tile_group.shape[0]


def test_moe_layer_on_the_kernel_equals_the_jnp_path():
    moe = MoE(d_model=K, d_ff=N, n_experts=E, top_k=TOP)
    params = unbox(moe.init(jax.random.PRNGKey(3)))
    served = st.compress_weights({"blocks": {"ffn": params}}, POL)
    ffn = served["blocks"]["ffn"]
    assert isinstance(ffn["wi"], st.CompressedKernel)
    assert ffn["wi"].codes.shape[0] == E  # one stacked kernel
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 12, K)),
                    jnp.float32)
    valid = jnp.arange(12)[None] < jnp.asarray([[12], [5]])
    pol = resolve_policy(st.serving_policy(POL), moe.name)
    y_jnp, m = moe.apply(ffn, x, pol, valid=valid)
    y_kernel, _ = moe.apply(ffn, x, pol.replace(fused=True), valid=valid)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_jnp),
                               rtol=1e-5, atol=1e-6)
    assert int(m["counts"]["expert_rows"]) == TOP * 17
