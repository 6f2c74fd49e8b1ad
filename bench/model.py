"""A configuration file -> the served model: its weights and its engine.

The weights are the benchmark's, not the program's: one jitted call makes
them on the device from ``--seed``, in a plain layout (``make_weights``),
and ``program_params`` renames them into the tree the program's model
takes.  The plain reference (``bench/reference.py``) reads the same plain
layout, so it shares nothing with the program but the seed.

Every value is exact in f32 whatever the compiler fuses: an integer drawn
from the seed times a power of two.  A configuration served in bfloat16
(``serving.dtype``) gets every leaf rounded to bfloat16 where it is made,
so the program and the reference read the very same values.  ``uniform_grid`` weights are uniform
on a 2^23-step grid (finer than bf16 everywhere, so one bf16 MXU pass
rounds nearly every weight); ``int4_abfp_grid`` weights are INT4 codes
times a power-of-two scale per group of ``group`` rows along the
contraction and per output column, with a code of +-7 in every group, so
ABFP INT4 compression (scale = group max / 7, kept in bf16) stores them
without loss: a served INT4 checkpoint, as GPTQ and its kin produce.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.work import Shape

INT4_CODE_RMS = math.sqrt(sum(k * k for k in range(-7, 8)) / 15)


def seed_key(seed: int):
    """A PRNG key for any whole ``seed``, 64-bit ones included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file's sizes."""
    from repro.configs import get_config

    m = conf["model"]
    if m["rms_norm_eps"] != 1e-6:
        raise ValueError("the program's RMSNorm has eps 1e-6 only")
    return get_config(conf["arch"]).replace(
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv=m["num_key_value_heads"],
        head_dim=m["head_dim"], d_ff=m["intermediate_size"],
        vocab=m["vocab_size"], tied_embeddings=m["tie_word_embeddings"],
        qkv_bias=m["attention_bias"], rope_theta=m["rope_theta"],
        act="swiglu", norm="rms", scan_layers=True, remat="none",
        dtype=served_dtype(conf), param_dtype=served_dtype(conf))


def served_dtype(conf: dict) -> str:
    """The dtype of the served weights, activations and fp pages."""
    return conf["serving"].get("dtype", "float32")


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


def shape(conf: dict) -> Shape:
    m, s = conf["model"], conf["serving"]
    return Shape(n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                 n_heads=m["num_attention_heads"],
                 n_kv=m["num_key_value_heads"], head_dim=m["head_dim"],
                 d_ff=m["intermediate_size"], vocab=m["vocab_size"],
                 page_size=s["page_size"],
                 code_bytes=1 if s["kv"] in ("int8", "fp8") else 4)


def _pow2(e):
    """2.0 ** e for int32 ``e``, built from its exponent bits (exact)."""
    return jax.lax.bitcast_convert_type(
        ((e + 127) << 23).astype(jnp.int32), jnp.float32)


def _bits(key, shp):
    return jax.random.bits(key, shp, jnp.uint32)


def _uniform_grid(key, shp, std: float):
    """Uniform with standard deviation ~``std``, on a power-of-two grid."""
    step = 2.0 ** round(math.log2(math.sqrt(3) * std / 2**22))
    k = (_bits(key, shp) >> 9).astype(jnp.int32) - 2**22
    return k.astype(jnp.float32) * step


def _int4_grid(key, shp, group: int):
    """INT4 codes x a power-of-two scale per (group of rows, column); the
    first row of every group holds +-7, so each group's max is 7 x scale."""
    *lead, K, N = shp
    if K % group:
        raise ValueError(f"contraction {K} is no multiple of group {group}")
    kc, ke = jax.random.split(key)
    bits = _bits(kc, (*lead, K // group, group, N))
    codes = ((bits >> 8) % 15).astype(jnp.int32) - 7
    top = 7 * (2 * (bits & 1).astype(jnp.int32) - 1)
    row = jax.lax.broadcasted_iota(jnp.int32, bits.shape, bits.ndim - 2)
    codes = jnp.where(row == 0, top, codes)
    e0 = round(math.log2(1 / (math.sqrt(K) * INT4_CODE_RMS)))
    e = e0 - 1 + (_bits(ke, (*lead, K // group, 1, N)) % 3).astype(jnp.int32)
    return (codes.astype(jnp.float32) * _pow2(e)).reshape(shp)


def make_weights(conf: dict, seed: int) -> dict:
    """The plain weight tree of ``weights_fn``, made in one jitted call."""
    return jax.jit(weights_fn(conf))(seed_key(seed))


def weights_fn(conf: dict):
    """PRNG key -> the plain weight tree, stacked over layers.

    Keys: ``embed`` (Vp, D), ``final_norm`` (D,), ``lm_head`` (D, Vp) when
    untied, and per layer ``ln1``, ``ln2`` (L, D), ``wq`` (L, D, H*hd),
    ``wk``, ``wv`` (L, D, KV*hd), their biases ``bq``, ``bk``, ``bv``,
    ``wo`` (L, H*hd, D), ``w_gate``, ``w_up`` (L, D, F), ``w_down``
    (L, F, D).  Rows and columns past the published vocabulary are zero.
    Layers are made one at a time (``lax.map``), so the call needs about
    one layer of scratch memory beside its outputs.
    """
    m = conf["model"]
    L, D, F = m["num_hidden_layers"], m["hidden_size"], m["intermediate_size"]
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    V, Vp = m["vocab_size"], padded_vocab(m["vocab_size"])
    int4 = conf["weights"] == "int4_abfp_grid"
    dt = jnp.dtype(served_dtype(conf))

    def kernel(key, shp):
        return (_int4_grid(key, shp, conf["group"]) if int4
                else _uniform_grid(key, shp, shp[-2] ** -0.5))

    def norm(key, shp):
        k = (_bits(key, shp) >> 25).astype(jnp.int32) - 64
        return 1.0 + k.astype(jnp.float32) * 2.0 ** -9

    def layer(key):
        k = iter(jax.random.split(key, 16))
        w = {"ln1": norm(next(k), (D,)), "ln2": norm(next(k), (D,)),
             "wq": kernel(next(k), (D, H * hd)),
             "wk": kernel(next(k), (D, KV * hd)),
             "wv": kernel(next(k), (D, KV * hd)),
             "wo": kernel(next(k), (H * hd, D)),
             "w_gate": kernel(next(k), (D, F)),
             "w_up": kernel(next(k), (D, F)),
             "w_down": kernel(next(k), (F, D))}
        if m["attention_bias"]:
            for b, n in (("bq", H * hd), ("bk", KV * hd), ("bv", KV * hd)):
                w[b] = _uniform_grid(next(k), (n,), 0.02)
        return {n: v.astype(dt) for n, v in w.items()}

    def make(key):
        k_embed, k_norm, k_head, k_layers = jax.random.split(key, 4)
        w = jax.lax.map(layer, jax.random.split(k_layers, L))
        w["embed"] = jnp.where(jnp.arange(Vp)[:, None] < V,
                               _uniform_grid(k_embed, (Vp, D), 0.02),
                               0.0).astype(dt)
        w["final_norm"] = norm(k_norm, (D,)).astype(dt)
        if not m["tie_word_embeddings"]:
            w["lm_head"] = jnp.where(jnp.arange(Vp)[None] < V,
                                     kernel(k_head, (D, Vp)), 0.0).astype(dt)
        return w

    return make


def program_params(w: dict) -> dict:
    """The plain tree under the names the program's ``TransformerLM`` uses
    (``unbox(model.init(key))`` with layers stacked)."""
    def dense(k, b=None):
        return {"kernel": w[k], **({"bias": w[b]} if b in w else {})}

    p = {
        "embed": {"table": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "blocks": {
            "ln1": {"scale": w["ln1"]}, "ln2": {"scale": w["ln2"]},
            "attn": {"q": dense("wq", "bq"), "k": dense("wk", "bk"),
                     "v": dense("wv", "bv"), "o": dense("wo")},
            "ffn": {"wi": dense("w_up"), "wg": dense("w_gate"),
                    "wo": dense("w_down")},
        },
    }
    if "lm_head" in w:
        p["lm_head"] = dense("lm_head")
    return p


def build_engine(conf: dict, cfg, params: dict, n_slots: int,
                 max_len: int):
    """The program's paged engine over ``params``, as the configuration
    states it: policy, compression, page storage, attention backend."""
    from repro.core.policy import preset, with_attn_backend
    from repro.models import build_model
    from repro.nn.module import unbox
    from repro.serve.engine import PagedServeEngine

    s = conf["serving"]
    model = build_model(cfg)
    want = jax.tree.structure(unbox(jax.eval_shape(
        model.init, jax.random.PRNGKey(0))))
    if jax.tree.structure(params) != want:
        raise ValueError(f"weight tree {jax.tree.structure(params)} is not "
                         f"the program's {want}")
    policy = preset(s["policy"], n_layers=cfg.n_layers)
    if s["attn_backend"] != "auto":
        policy = with_attn_backend(policy, s["attn_backend"])
    return PagedServeEngine(model, params, n_slots=n_slots, max_len=max_len,
                            policy=policy, compress=s["compress"],
                            page_size=s["page_size"], kv=s["kv"])
