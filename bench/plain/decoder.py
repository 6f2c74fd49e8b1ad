"""A dense decoder: Qwen2 / Granite-3 / Llama style.

The architecture module of every configuration that names
``"plain": "decoder"``; ``bench/harness.py`` loads it by path.  It gives:

  ``arch_config(conf)``     the program's ``ArchConfig`` at the file's sizes;
  ``weights_fn(conf)``      PRNG key -> the plain weight tree;
  ``program_params(w)``     that tree under the program's names;
  ``Reference(conf, w)``    the plain forward pass, judged by
                            ``bench.reference.Judge``;
  ``work_counter(conf)``    the needed work of the window's rows.

The reference follows the published description of the decoder: RMSNorm
before attention and before the MLP, q/k/v projections (with biases where
the configuration has them), rotary embedding over the two halves of each
head (the ``rotate_half`` convention of the published code), causal
grouped-query attention with query head ``h`` reading KV head ``h // (H /
KV)`` and scale ``head_dim ** -0.5``, a SwiGLU MLP ``down(silu(gate(x)) *
up(x))``, a final RMSNorm and the readout (the embedding's transpose where
tied).  It runs one request at a time, layer by layer, over the prompt and
the served tokens, with the sequence padded to a power of two of at least
``block`` rows and attention taken ``block`` query rows at a time, so that
ten thousand positions fit beside the weights.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import (_int4_grid, _norm_grid, _uniform_grid,
                         padded_vocab, served_dtype)
from bench.reference import HIGHEST, NEG, Judge, _mm, _rms, _rope
from bench.work import Need


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


# -- weights ---------------------------------------------------------------

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

    def layer(key):
        k = iter(jax.random.split(key, 16))
        w = {"ln1": _norm_grid(next(k), (D,)),
             "ln2": _norm_grid(next(k), (D,)),
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
        w["final_norm"] = _norm_grid(k_norm, (D,)).astype(dt)
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


# -- the plain reference ---------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "precision", "block"))
def _layer(x, lw, *, m: tuple, precision: str, block: int):
    """One decoder layer over ``x`` (S, D), S a multiple of ``block``."""
    H, KV, hd, theta, bias = m
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, lw["ln1"], precision)
    q = _mm(h, lw["wq"], precision)
    k = _mm(h, lw["wk"], precision)
    v = _mm(h, lw["wv"], precision)
    if bias:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    dt = jnp.bfloat16 if precision == "bf16" else jnp.float32
    q = _rope(q.reshape(S, H, hd), pos, theta).astype(dt)
    k = _rope(k.reshape(S, KV, hd), pos, theta).astype(dt)
    v = v.reshape(S, KV, hd).astype(dt)
    g = H // KV
    mmp = None if precision == "bf16" else HIGHEST

    def attend(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        qb = qb.reshape(block, KV, g, hd)
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=mmp,
                       preferred_element_type=jnp.float32) * hd ** -0.5
        qpos = i * block + jnp.arange(block)
        s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                      s, NEG)
        p = jax.nn.softmax(s, axis=-1).astype(dt)
        o = jnp.einsum("kgqt,tkd->qkgd", p, v, precision=mmp,
                       preferred_element_type=jnp.float32)
        return o.reshape(block, H * hd).astype(dt)

    att = jax.lax.map(attend, jnp.arange(S // block)).reshape(S, H * hd)
    x = x + _mm(att, lw["wo"], precision).astype(x.dtype)
    h = _rms(x, lw["ln2"], precision)
    gate = _mm(h, lw["w_gate"], precision).astype(jnp.float32)
    up = _mm(h, lw["w_up"], precision).astype(jnp.float32)
    f = (jax.nn.silu(gate) * up).astype(dt)
    return x + _mm(f, lw["w_down"], precision).astype(x.dtype)


class Reference(Judge):
    """Teacher-forced logits of the plain decoder over served requests."""

    def __init__(self, conf: dict, weights: dict, block: int = 512):
        m = conf["model"]
        self.m = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"], float(m["rope_theta"]),
                  bool(m["attention_bias"]))
        self.vocab = m["vocab_size"]
        self.L = m["num_hidden_layers"]
        self.w = weights
        self.final_norm = weights["final_norm"]
        self.head = (weights["lm_head"] if "lm_head" in weights
                     else weights["embed"].T)
        self.block = block

    def _hidden(self, ids: np.ndarray, precision: str):
        S = len(ids)
        # a power of two of rows: few shapes to compile over any lengths
        Sp = max(self.block, 1 << (S - 1).bit_length())
        dt = jnp.bfloat16 if precision == "bf16" else jnp.float32
        x = self.w["embed"][jnp.asarray(np.pad(ids, (0, Sp - S)))].astype(dt)
        for i in range(self.L):
            lw = {k: v[i].astype(dt) for k, v in self.w.items()
                  if k not in ("embed", "final_norm", "lm_head")}
            x = _layer(x, lw, m=self.m, precision=precision,
                       block=self.block)
        return x


# -- needed work -----------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Shape:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int  # the published vocabulary, not the padded one
    page_size: int
    code_bytes: int = 1  # bytes per stored KV element (int8 codes: 1)
    q_bytes: int = 4  # bytes per query / output element (f32)


def shape(conf: dict) -> Shape:
    m, s = conf["model"], conf["serving"]
    return Shape(n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                 n_heads=m["num_attention_heads"],
                 n_kv=m["num_key_value_heads"], head_dim=m["head_dim"],
                 d_ff=m["intermediate_size"], vocab=m["vocab_size"],
                 page_size=s["page_size"],
                 code_bytes=1 if s["kv"] in ("int8", "fp8") else 4)


def linear_flops(s: Shape) -> int:
    """Multiply-adds x 2 of every projection one token passes through:
    q, k, v, o and the three SwiGLU matrices, in every layer."""
    attn = s.d_model * s.head_dim * (s.n_heads + 2 * s.n_kv) \
        + s.n_heads * s.head_dim * s.d_model
    return 2 * s.n_layers * (attn + 3 * s.d_model * s.d_ff)


def attention_flops(s: Shape, ctx: int) -> int:
    """QK^T and PV of one query over ``ctx`` live positions, all layers."""
    return 4 * s.n_layers * s.n_heads * s.head_dim * ctx


def readout_flops(s: Shape) -> int:
    """The readout of one token that emits: d_model x vocab."""
    return 2 * s.d_model * s.vocab


def kernel_bytes(s: Shape, n_queries: int, ctx: int) -> int:
    """Bytes the attention kernel needs for one row of one step: the K and
    V codes and per-(page, head) scales of the row's live context, read
    once, and its ``n_queries`` queries and outputs."""
    pages = math.ceil(ctx / s.page_size)
    kv = 2 * s.n_layers * s.n_kv * (s.head_dim * ctx * s.code_bytes
                                    + pages * 4)
    return kv + 2 * s.n_layers * n_queries * s.n_heads * s.head_dim \
        * s.q_bytes


class WorkCounter(Need):
    """Needed model FLOPs, and the attention kernel's FLOPs and bytes, of
    the rows a window's step calls served.  ``attention``: the kernel's
    op name, or None where attention runs as plain ops (then only the
    model FLOPs are counted)."""

    def __init__(self, shape: Shape, attention: str | None = None):
        super().__init__([attention] if attention else [])
        self.s = shape
        self.attention = attention

    def _kernel(self, flops: int, nbytes: int) -> None:
        if self.attention:
            k = self.kernels[self.attention]
            k[0] += flops
            k[1] += nbytes

    def prefill(self, start: int, stop: int, emits: bool) -> None:
        s, n = self.s, stop - start
        # sum of contexts start+1 .. stop, one per query row
        ctx_sum = (start + 1 + stop) * n // 2
        attn = 4 * s.n_layers * s.n_heads * s.head_dim * ctx_sum
        self.model_flops += n * linear_flops(s) + attn \
            + (readout_flops(s) if emits else 0)
        self._kernel(attn, kernel_bytes(s, n, stop))

    def decode(self, pos: int) -> None:
        s = self.s
        attn = attention_flops(s, pos + 1)
        self.model_flops += linear_flops(s) + attn + readout_flops(s)
        self._kernel(attn, kernel_bytes(s, 1, pos + 1))


def work_counter(conf: dict) -> WorkCounter:
    return WorkCounter(shape(conf), conf.get("attention_kernel"))
