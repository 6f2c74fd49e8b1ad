"""Compressed-domain execution backend: codes-consuming kernels vs the
QDQ-then-matmul reference, backend dispatch, and the ServeEngine token
regression (compressed serving == decompress-then-QDQ serving)."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import simulate as sim
from repro.core.formats import INT4, INT8
from repro.core.policy import (
    NONE,
    PolicyMap,
    PolicyRule,
    QuantPolicy,
    TensorQuant,
    preset,
)
from repro.core.quantize import pack_int4_codes, unpack_int4_codes
from repro.kernels import ops as kops
from repro.kernels.quant_matmul import quant_matmul
from repro.models import build_model
from repro.models import serving_transforms as st
from repro.nn.module import unbox


def _seed(*parts) -> int:
    """Deterministic RNG seed (hash() varies per process under PYTHONHASHSEED)."""
    return zlib.crc32(repr(parts).encode()) % 2**31


def _abfp_policy(fmt: str, n: int) -> QuantPolicy:
    return QuantPolicy(
        name=f"w{fmt}a{fmt}_n{n}",
        input=TensorQuant(fmt, scaler="abfp", group=n),
        weight=TensorQuant(fmt, scaler="abfp", group=n),
    )


# ------------------------------------------------------------ dispatch table
def test_backend_registry_declares_weight_reprs():
    be = sim.backends()
    assert set(be) >= {"ref", "int8", "fused", "compressed"}
    assert be["compressed"].weight_repr == "compressed"
    for name in ("ref", "int8", "fused"):
        assert be[name].weight_repr == "dense"


def test_backend_selection():
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(128, 64), jnp.float32)
    ck = st.compress_kernel(w, TensorQuant("int8", scaler="abfp", group=64))

    assert sim.execution_backend(NONE, w).name == "ref"
    assert sim.execution_backend(preset("w4a8_abfp"), w).name == "ref"
    assert sim.execution_backend(preset("w8a8_int8_native"), w).name == "int8"
    fused = preset("w4a8_abfp").replace(fused=True)
    assert sim.execution_backend(fused, w).name == "fused"
    # the weight representation wins: compressed storage always executes
    # in the compressed domain, whatever the policy says
    for pol in (NONE, preset("w4a8_abfp"), preset("w4a16"), fused):
        assert sim.execution_backend(pol, ck).name == "compressed"
    # a float-format abfp pair is NOT int8-native eligible (falls to ref)
    e4 = preset("w8a8_e4m3").replace(compute="int8", attn_bmm=False)
    assert sim.execution_backend(e4, w).name == "ref"


# ------------------------------------------- jnp compressed backend parity
@pytest.mark.parametrize("fmt", ["int4", "int8"])
@pytest.mark.parametrize("n", [32, 64])
@pytest.mark.parametrize("mkn", [(8, 96, 40), (16, 128, 56), (3, 200, 24)])
def test_compressed_matmul_matches_qdq_reference(fmt, n, mkn):
    """codes-consuming path == QDQ-then-matmul across bit-widths, group
    sizes and non-square M/N/K (incl. K % n != 0, the padded case)."""
    M, K, N = mkn
    rng = np.random.RandomState(_seed(fmt, n, mkn))
    x = jnp.asarray(rng.randn(M, K), jnp.float32)
    w = jnp.asarray(rng.randn(K, N), jnp.float32)
    pol = _abfp_policy(fmt, n)
    y_ref = sim.qmatmul(x, w, pol)
    ck = st.compress_kernel(w, pol.weight)
    y_c = sim.qmatmul(x, ck, st.serving_policy(pol))
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_compressed_matmul_bit_exact_with_int8_native():
    """Same codes, same contraction: the compressed backend must equal the
    int8-native backend bit-for-bit (only the storage moved offline)."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(6, 192), jnp.float32)
    w = jnp.asarray(rng.randn(192, 48), jnp.float32)
    pol = preset("w8a8_int8_native")
    y_native = sim.qmatmul(x, w, pol)
    ck = st.compress_kernel(w, pol.weight)
    y_comp = sim.qmatmul(x, ck, st.serving_policy(pol))
    assert np.array_equal(np.asarray(y_native), np.asarray(y_comp))


def test_compressed_matmul_channel_max_static():
    """channel_max-compressed weights (static-MSE presets) track the
    runtime QDQ path; storage is bit-exact with the runtime weight grid."""
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(4, 96), jnp.float32)
    w = jnp.asarray(rng.randn(96, 40), jnp.float32)
    tq = TensorQuant("int4", scaler="channel_max")
    ck = st.compress_kernel(w, tq)
    assert np.array_equal(np.asarray(st.decompress_kernel(ck)),
                          np.asarray(sim.qdq_weight(w, tq, contract_axis=0)))
    pol = QuantPolicy(name="w4a8_mse_t",
                      input=TensorQuant("int8", scaler="static"), weight=tq)
    y_ref = sim.qmatmul(x, w, pol)
    y_c = sim.qmatmul(x, ck, st.serving_policy(pol))
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_compressed_matmul_weight_only():
    """w4a16 (no input quantizer): codes contract against fp activations."""
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(5, 128), jnp.float32)
    w = jnp.asarray(rng.randn(128, 24), jnp.float32)
    pol = preset("w4a16")
    ck = st.compress_kernel(w, pol.weight)
    y_ref = sim.qmatmul(x, w, pol)
    y_c = sim.qmatmul(x, ck, st.serving_policy(pol))
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_int4_pack_roundtrip():
    rng = np.random.RandomState(8)
    c = jnp.asarray(rng.randint(-7, 8, (5, 3, 64)), jnp.int8)
    assert (unpack_int4_codes(pack_int4_codes(c)) == c).all()
    # first half in the low nibbles, second half in the high ones
    q = pack_int4_codes(jnp.asarray([1, -2, -8, 7], jnp.int8))
    assert q.tolist() == [0x81, 0x7E]
    assert unpack_int4_codes(q).tolist() == [1, -2, -8, 7]
    # stored kernels pack the two halves of each group's contraction rows
    rows = c[:, :2]
    packed = pack_int4_codes(rows, axis=-2)
    assert packed.shape == (5, 1, 64) and packed.dtype == jnp.uint8
    assert (unpack_int4_codes(packed, axis=-2) == rows).all()
    with pytest.raises(ValueError, match="even last dim"):
        pack_int4_codes(jnp.zeros((2, 3), jnp.int8))


# ------------------------------------------------ Pallas stored-codes kernel
@pytest.mark.parametrize("fmt", [INT4, INT8], ids=lambda f: f.name)
@pytest.mark.parametrize("n", [32, 64])
@pytest.mark.parametrize("mkn", [(16, 128, 48), (32, 192, 96), (8, 256, 24)])
def test_quant_matmul_kernel_vs_qdq_reference(fmt, n, mkn):
    """The Pallas codes-consuming kernel vs the QDQ-then-matmul reference
    across bit-widths, group sizes and non-square M/N/K."""
    M, K, N = mkn
    rng = np.random.RandomState(_seed(fmt.name, n, mkn))
    x = jnp.asarray(rng.randn(M, K), jnp.float32)
    w = jnp.asarray(rng.randn(K, N), jnp.float32)
    tq = TensorQuant(fmt.name, scaler="abfp", group=n)
    pol = QuantPolicy(name="t", input=tq, weight=tq)
    # the stored layout as is: INT4 codes stay packed
    ck = st.compress_kernel(w, tq)
    got = quant_matmul(x, ck.codes, ck.scale, fmt, n=n, interpret=True)
    want = sim.qmatmul(x, w, pol)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_quant_matmul_fused_wrapper_padded():
    """The ops wrapper pads x to the stored (padded) contraction length."""
    rng = np.random.RandomState(9)
    x = jnp.asarray(rng.randn(2, 5, 200), jnp.float32)  # K=200, n=64 -> pad
    w = jnp.asarray(rng.randn(200, 32), jnp.float32)
    tq = TensorQuant("int8", scaler="abfp", group=64)
    ck = st.compress_kernel(w, tq)
    got = kops.quant_matmul_fused(x, ck, tq, interpret=True)
    want = sim.qmatmul(x.reshape(-1, 200), w,
                       QuantPolicy(name="t", input=tq, weight=tq))
    np.testing.assert_allclose(np.asarray(got).reshape(-1, 32),
                               np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("fmt", ["int4", "int8"])
def test_fused_policy_routes_compressed_kernel(fmt):
    """policy.fused + compressed weights: the compressed backend hands the
    aligned int path to the Pallas stored-codes kernel (packed INT4 codes
    are unpacked inside it)."""
    rng = np.random.RandomState(10)
    x = jnp.asarray(rng.randn(8, 128), jnp.float32)
    w = jnp.asarray(rng.randn(128, 64), jnp.float32)
    tq = TensorQuant(fmt, scaler="abfp", group=64)
    pol = QuantPolicy(name="t", input=tq, weight=tq, fused=True)
    ck = st.compress_kernel(w, tq)
    assert ck.packed == (fmt == "int4")
    assert ck.group == 64
    got = sim.qmatmul(x, ck, st.serving_policy(pol))
    want = sim.qmatmul(x, w, pol.replace(fused=False))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ------------------------------- stored-codes kernel vs the einsum path
@pytest.mark.parametrize("n_out", [128, 384])
@pytest.mark.parametrize("m", [8, 16, 64])
@pytest.mark.parametrize("k", [256, 200], ids=["K256", "K200-padded"])
@pytest.mark.parametrize("wfmt", ["int4", "int8"])
def test_codes_kernel_matches_einsum_path(wfmt, k, m, n_out):
    """The kernel reads the codes as stored (packed INT4 or int8, padded K
    too) and, given the einsum path's own x codes and steps, agrees with
    ``_compressed_group_matmul`` within f32 summation order."""
    from repro.core.abfp import abfp_quantize
    from repro.kernels.quant_matmul import quant_matmul_codes

    rng = np.random.RandomState(_seed(wfmt, k, m, n_out))
    x = jnp.asarray(rng.randn(m, k), jnp.float32)
    w = jnp.asarray(rng.randn(k, n_out), jnp.float32)
    tq_x = TensorQuant("int8", scaler="abfp", group=64)
    pol = QuantPolicy(name="t", input=tq_x,
                      weight=TensorQuant(wfmt, scaler="abfp", group=64))
    wk = st.compress_kernel(w, pol.weight)
    assert wk.packed == (wfmt == "int4") and (wk.pad > 0) == (k == 200)
    want = np.asarray(sim._compressed_group_matmul(x, wk, pol, site="",
                                                   in_alpha=None))
    # the x codes and steps the kernel is given are the einsum path's
    sd = jnp.dtype(tq_x.scale_dtype)
    xc, xs, _ = abfp_quantize(jnp.pad(x, ((0, 0), (0, wk.pad))), tq_x.fmt,
                              n=64, scale_dtype=sd)
    xc_e, xs_e, _ = abfp_quantize(x, tq_x.fmt, n=64, scale_dtype=sd)
    assert np.array_equal(np.asarray(xc), np.asarray(xc_e))
    assert np.array_equal(np.asarray(xs), np.asarray(xs_e))
    tol = dict(rtol=1e-5, atol=1e-6 * float(np.abs(want).max()))
    got = quant_matmul_codes(xc.reshape(m, -1), xs, wk.codes, wk.scale,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, **tol)
    # the ops wrapper quantizes x itself, the same way
    got = kops.quant_matmul_fused(x, wk, tq_x, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, **tol)


def test_codes_blocks_follow_the_shape():
    """Decode rows take one row block and wide weight blocks; prefill rows
    take 256-row blocks; weight blocks hold up to 8 MiB of codes; the
    kernel loops over chunks of 8 groups (qwen2-7b widths: wi, wo,
    lm_head), or of all groups where no chunk of whole lanes divides
    them."""
    from repro.kernels.quant_matmul import codes_blocks

    assert codes_blocks(16, 3584, 18944, 64, True) == (16, 512, 3584, 8)
    assert codes_blocks(16, 18944, 3584, 64, True) == (16, 512, 18944, 8)
    assert codes_blocks(16, 3584, 152064, 64, True) == (16, 1536, 3584, 8)
    assert codes_blocks(1024, 3584, 18944, 64, True) == (256, 512, 3584, 8)
    assert codes_blocks(1024, 18944, 3584, 64, True) == (256, 512, 18944,
                                                         8)
    # an N off the lane grid is one block (interpret mode only)
    assert codes_blocks(8, 128, 48, 64, True) == (8, 48, 128, 2)
    assert codes_blocks(32, 192, 96, 64, False) == (32, 96, 192, 3)


def test_codes_kernel_pads_rows_past_one_block():
    """More rows than one block pad to a multiple of it, and the padded
    rows are cut from the output."""
    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.randn(300, 128), jnp.float32)
    w = jnp.asarray(rng.randn(128, 128), jnp.float32)
    tq = TensorQuant("int4", scaler="abfp", group=64)
    pol = QuantPolicy(name="t", input=TensorQuant("int8", scaler="abfp",
                                                  group=64), weight=tq)
    wk = st.compress_kernel(w, tq)
    want = np.asarray(sim._compressed_group_matmul(x, wk, pol, site="",
                                                   in_alpha=None))
    got = kops.quant_matmul_fused(x, wk, pol.input, interpret=True)
    assert got.shape == (300, 128)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                               atol=1e-6 * float(np.abs(want).max()))


def _as_tpu(monkeypatch):
    """Make the dispatcher see a TPU backend (its kernels are then only
    traced, never run, here)."""
    monkeypatch.setattr(kops, "should_interpret", lambda: False)


@pytest.mark.parametrize("case,kernel", [
    ("tpu", True),
    ("cpu", False),
    ("cpu-fused", True),
    ("tpu-group-mismatch", False),
    ("tpu-fp8-input", False),
    ("tpu-static-input", False),
    ("tpu-weight-only", False),
    ("tpu-n-off-lanes", False),
    ("tpu-n32-packed", False),
])
def test_codes_kernel_dispatch_rule(case, kernel, monkeypatch):
    """The kernel takes a compressed site on a TPU when the input is int
    ABFP at the stored group and the shapes tile; ``policy.fused`` forces
    it anywhere; every other case keeps the einsum path."""
    if case.startswith("tpu"):
        _as_tpu(monkeypatch)
    n_out = 96 if case == "tpu-n-off-lanes" else 256
    n = 32 if case == "tpu-n32-packed" else 64
    w = jnp.asarray(np.random.RandomState(12).randn(128, n_out), jnp.float32)
    wk = st.compress_kernel(w, TensorQuant("int4", scaler="abfp", group=n))
    tq_in = {
        "tpu-group-mismatch": TensorQuant("int8", scaler="abfp", group=32),
        "tpu-fp8-input": TensorQuant("e4m3", scaler="abfp", group=64),
        "tpu-static-input": TensorQuant("int8", scaler="static"),
        "tpu-weight-only": None,
    }.get(case, TensorQuant("int8", scaler="abfp", group=n))
    pol = QuantPolicy(name="t", input=tq_in,
                      weight=TensorQuant("int4", scaler="abfp", group=n),
                      fused=case == "cpu-fused")
    assert sim.codes_kernel_takes(wk, pol) == kernel


def test_site_tally_counts_kernel_and_fallback_sites(monkeypatch):
    """Traced on a TPU backend, a tiling int-ABFP site counts as a kernel
    site and one off the lane grid as a fallback; the tally is of traced
    sites and closes with its block."""
    _as_tpu(monkeypatch)
    rng = np.random.RandomState(13)
    tq = TensorQuant("int4", scaler="abfp", group=64)
    pol = QuantPolicy(name="t", input=TensorQuant("int8", scaler="abfp",
                                                  group=64), weight=tq)
    tiles = st.compress_kernel(jnp.asarray(rng.randn(128, 256)), tq)
    ragged = st.compress_kernel(jnp.asarray(rng.randn(128, 96)), tq)
    x = jax.ShapeDtypeStruct((16, 128), jnp.float32)
    with sim.compressed_site_tally() as outer:
        with sim.compressed_site_tally() as inner:
            y = jax.eval_shape(lambda x: sim.qmatmul(x, tiles, pol), x)
        jax.eval_shape(lambda x: sim.qmatmul(x, ragged, pol), x)
    assert y.shape == (16, 256)
    assert inner == {"qmm_kernel_sites": 1, "qmm_fallback_sites": 0}
    assert outer == {"qmm_kernel_sites": 1, "qmm_fallback_sites": 1}
    assert sim._SITE_TALLIES == []


# -------------------------------------------------- named-shape ValueErrors
def test_kernel_shape_errors_name_offenders():
    x = jnp.zeros((8, 100), jnp.float32)
    w = jnp.zeros((96, 16), jnp.float32)
    from repro.kernels.abfp_qdq import abfp_qdq as pallas_qdq
    from repro.kernels.quant_matmul import abfp_matmul

    with pytest.raises(ValueError, match="K=100"):
        abfp_matmul(x, jnp.zeros((100, 16), jnp.float32), INT8, INT8, n=64,
                    interpret=True)
    with pytest.raises(ValueError, match="K=100 but w has K=96"):
        abfp_matmul(x, w, INT8, INT8, n=4, interpret=True)
    with pytest.raises(ValueError, match="block_m=6"):
        abfp_matmul(jnp.zeros((8, 64), jnp.float32),
                    jnp.zeros((64, 16), jnp.float32), INT8, INT8, n=64,
                    block_m=6, interpret=True)
    with pytest.raises(ValueError, match="n=64"):
        pallas_qdq(x, INT8, n=64, interpret=True)
    with pytest.raises(ValueError, match="block_m=5"):
        pallas_qdq(jnp.zeros((8, 64), jnp.float32), INT8, n=64, block_m=5,
                   interpret=True)
    with pytest.raises(ValueError, match="w_codes"):
        quant_matmul(jnp.zeros((8, 64), jnp.float32),
                     jnp.zeros((16, 1, 64), jnp.int8),
                     jnp.zeros((1, 16), jnp.float32), INT8, n=64,
                     interpret=True)
    with pytest.raises(ValueError, match="cover K=128"):
        quant_matmul(jnp.zeros((8, 64), jnp.float32),
                     jnp.zeros((128, 16), jnp.int8),
                     jnp.zeros((2, 16), jnp.float32), INT8, n=64,
                     interpret=True)
    with pytest.raises(ValueError, match=r"w_scales shape \(16, 1\)"):
        quant_matmul(jnp.zeros((8, 64), jnp.float32),
                     jnp.zeros((64, 16), jnp.int8),
                     jnp.zeros((16, 1), jnp.float32), INT8, n=64,
                     interpret=True)


def test_fit_block_shared_helper():
    assert kops.fit_block(1024) == 256
    assert kops.fit_block(24) == 8
    assert kops.fit_block(7) == 1
    # group-unit blocks: counted in multiples of n
    assert kops.fit_block(320, start=512, multiple=64) == 64
    assert kops.fit_block(512, start=512, multiple=64) == 512
    with pytest.raises(ValueError, match="group unit"):
        kops.fit_block(100, start=512, multiple=64)


# ----------------------------------------------- model-level per-site serve
@pytest.fixture(scope="module")
def opt_setup():
    cfg = get_config("opt-tiny").replace(
        n_layers=2, d_model=48, n_heads=4, n_kv=4, head_dim=12, d_ff=96,
        vocab=131)
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(2)))
    return cfg, model, params


def test_per_site_compression_mixed_map(opt_setup):
    """w4ffn_fp8attn-style map: FP8-rule attention stays dense
    (prequantized), INT4-rule FFN compresses, fp32-rule sites untouched —
    and the forward matches the QDQ simulation."""
    cfg, model, params = opt_setup
    pm = PolicyMap(
        name="mix",
        rules=(PolicyRule("*attn*", preset("w8a8_e4m3")),
               PolicyRule("blocks.0/ffn/*", NONE)),
        default=preset("w4a4_abfp"),
    )
    comp = st.compress_weights(params, pm)
    # fp32 rule: untouched object
    assert (comp["blocks"][0]["ffn"]["wi"]["kernel"]
            is params["blocks"][0]["ffn"]["wi"]["kernel"])
    # FP8 rule: dense but prequantized
    aq = comp["blocks"][1]["attn"]["q"]["kernel"]
    assert hasattr(aq, "ndim") and not st.is_compressed(aq)
    assert not np.array_equal(
        np.asarray(aq), np.asarray(params["blocks"][1]["attn"]["q"]["kernel"]))
    # INT4 rule: compressed + packed
    k = comp["blocks"][1]["ffn"]["wi"]["kernel"]
    assert st.is_compressed(k) and k.packed and k.fmt_name == "int4"

    batch = {"tokens": np.random.RandomState(3).randint(
        0, 131, (2, 16)).astype(np.int32)}
    a, _ = model.apply(params, batch, pm)
    b, _ = model.apply(comp, batch, st.serving_policy(pm))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)

    rep = st.weight_bytes_report(params, comp)
    assert rep["compressed_sites"] == 2  # blocks.1 ffn wi + wo (relu MLP)
    assert rep["resident_kernel_bytes"] < rep["dense_kernel_bytes"]


def test_per_site_compression_w4ffn_fp8attn_mse(opt_setup):
    """The acceptance map: static-MSE FP8 attention stays dense
    (prequantized E4M3), channel-max INT4 FFN/readout kernels compress —
    and serving matches the QDQ simulation."""
    cfg, model, params = opt_setup
    pm = preset("w4ffn_fp8attn_mse")
    comp = st.compress_weights(params, pm)
    aq = comp["blocks"][0]["attn"]["q"]["kernel"]
    assert not st.is_compressed(aq)  # FP8 rule: dense (prequantized)
    k = comp["blocks"][0]["ffn"]["wi"]["kernel"]
    assert st.is_compressed(k) and k.fmt_name == "int4"
    assert k.scale.shape[-2:] == (1, cfg.d_ff)  # channel_max: one group
    batch = {"tokens": np.random.RandomState(4).randint(
        0, 131, (2, 16)).astype(np.int32)}
    # no q tree: both sides fall back to dynamic-max inputs identically
    a, _ = model.apply(params, batch, pm)
    b, _ = model.apply(comp, batch, st.serving_policy(pm))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)


def test_serve_engine_compressed_matches_qdq_sim(opt_setup):
    """Regression: compressed serving emits the same tokens as
    decompress-then-QDQ serving on the OPT proxy (2+ decode steps)."""
    cfg, model, params = opt_setup
    pol = preset("w4ffn_fp8attn")
    from repro.serve.engine import Request, ServeEngine

    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 131, int(rng.randint(3, 8))).astype(np.int32)
               for _ in range(3)]

    def run(**kw):
        eng = ServeEngine(model, params, n_slots=2, max_len=64, policy=pol,
                          **kw)
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=4))
        return eng, {c.uid: c.tokens for c in eng.run_until_done()}

    _, sim_tokens = run()
    eng_c, comp_tokens = run(compress=True)
    assert comp_tokens == sim_tokens
    wb = eng_c.weight_bytes
    assert wb["compressed_sites"] > 0
    assert wb["ratio"] < 1.0
    # decompress-then-QDQ serving (dense backends over the same storage):
    # force-densify the compressed params and serve with the same policy
    def densify(node):
        if isinstance(node, dict):
            return {k: densify(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)) and not hasattr(node, "ndim"):
            return type(node)(densify(v) for v in node)
        if st.is_compressed(node):
            return st.decompress_kernel(node)
        return node
    dd = densify(eng_c.params)
    eng_d = ServeEngine(model, dd, n_slots=2, max_len=64,
                        policy=eng_c.policy)
    for i, p in enumerate(prompts):
        eng_d.submit(Request(uid=i, prompt=p, max_new_tokens=4))
    dec_tokens = {c.uid: c.tokens for c in eng_d.run_until_done()}
    assert dec_tokens == comp_tokens


def test_site_rule_maps_rejected_on_non_contract_trees():
    """hybrid/encdec param paths don't match their runtime site addresses
    (e.g. 'shared/attn/q' path vs 'shared/q' site): site-rule maps must be
    rejected instead of silently mis-resolving; flat policies still work."""
    cfg = get_config("zamba2-7b").reduced()
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    pm = PolicyMap(name="m", rules=(PolicyRule("*attn*", NONE),),
                   default=preset("w4a8_abfp"))
    with pytest.raises(NotImplementedError, match="site addresses"):
        st.compress_weights(params, pm)
    with pytest.raises(NotImplementedError, match="site addresses"):
        st.prequantize_weights(params, pm)
    # flat policy: site-independent resolution, still supported
    comp = st.compress_weights(params, preset("w4a8_abfp"))
    assert any(st.is_compressed(leaf) for leaf in
               jax.tree_util.tree_leaves(
                   comp, is_leaf=st.is_compressed)
               if st.is_compressed(leaf))


def test_compress_axes_mixed_tree(opt_setup):
    """compress_axes mirrors per-site compression: compressed kernels get
    codes/scale axes; dense kernels keep their original axes tuples."""
    cfg, model, params = opt_setup
    from repro.nn.module import axes_of

    boxes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    sds, axes = unbox(boxes), axes_of(boxes)
    pm = PolicyMap(name="mix",
                   rules=(PolicyRule("*attn*", preset("w8a8_e4m3")),),
                   default=preset("w4a4_abfp"))
    csds = jax.eval_shape(lambda p: st.compress_weights(p, pm), sds)
    caxes = st.compress_axes(axes, csds)
    ffn_ax = caxes["blocks"][0]["ffn"]["wi"]["kernel"]
    assert st.is_compressed(ffn_ax)
    assert ffn_ax.codes == (None, "mlp")
    assert ffn_ax.scale == (None, "mlp")
    attn_ax = caxes["blocks"][0]["attn"]["q"]["kernel"]
    assert not st.is_compressed(attn_ax)
    assert attn_ax == ("embed", "qkv")
