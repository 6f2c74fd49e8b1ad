"""The plain reference: a decoder forward pass in ``jax.numpy``.

It imports nothing of the program and reads only the benchmark's plain
weight tree (``bench/model.py``).  It follows the published description of
a Qwen2 / Granite-3 style decoder: RMSNorm before attention and before the
MLP, q/k/v projections (with biases where the configuration has them),
rotary embedding over the two halves of each head (the ``rotate_half``
convention of the published code), causal grouped-query attention with
query head ``h`` reading KV head ``h // (H / KV)`` and scale
``head_dim ** -0.5``, a SwiGLU MLP ``down(silu(gate(x)) * up(x))``, a
final RMSNorm and the readout (the embedding's transpose where tied).

``precision`` selects how the matmuls and the stream are computed:

  ``f32``  float32 throughout, matmuls at ``HIGHEST``: the reference.
  ``bf16`` the stream, weights and matmul outputs in bfloat16: the
           control of a float32 configuration.
  ``a4``   float32 at ``HIGHEST``, with the input of every projection
           rounded to INT4 codes in groups of 64 along the contraction
           (scale = group max / 7): the control of an A8 configuration.
  ``fp8``  float32 at ``HIGHEST``, with both operands of every projection
           rounded to float8 e4m3, each row of the input and each column
           of the weight scaled to the format's largest value first: the
           next precision below a bf16 matmul pass.

Weights are read in float32 (bfloat16 weights widen exactly), or in
bfloat16 for the ``bf16`` control.  It runs one request at a time, layer by layer, over the prompt and the
served tokens, with the sequence padded to a power of two of at least
``block`` rows and attention taken ``block`` query rows at a time, so that
ten thousand positions fit beside the weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-6
NEG = -1e30


def _qdq4(x, group: int = 64):
    """Round ``x`` to INT4 codes per group of ``group`` along the last
    axis (scale = group max / 7) and back."""
    *lead, k = x.shape
    xg = x.reshape(*lead, k // group, group)
    scale = jnp.max(jnp.abs(xg), axis=-1, keepdims=True) / 7
    scale = jnp.where(scale > 0, scale, 1.0)
    return (jnp.clip(jnp.round(xg / scale), -7, 7) * scale).reshape(x.shape)


def _fp8(x, axis: int):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``
    (that slice's max maps to e4m3's largest value, 448) and back."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, precision: str):
    if precision == "fp8":
        return jnp.dot(_fp8(x, -1), _fp8(w, 0), precision=HIGHEST)
    if precision == "bf16":
        return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32
                       ).astype(jnp.bfloat16)
    if precision == "a4":
        x = _qdq4(x)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, scale, precision: str):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + EPS) \
        * scale
    return y.astype(jnp.bfloat16) if precision == "bf16" else y


def _rope(x, pos, theta: float):
    """x: (S, heads, hd); rotate the two halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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


@functools.partial(jax.jit, static_argnames=("vocab", "precision"))
def _gaps(x, final_norm, head, tokens, *, vocab: int, precision: str):
    """Per row: (reference best logit - logit of ``tokens``, argmax)."""
    h = _rms(x, final_norm, precision)
    logits = _mm(h, head[:, :vocab], precision).astype(jnp.float32)
    picked = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return logits.max(-1) - picked, logits.argmax(-1)


class Reference:
    """Teacher-forced logits of the plain decoder over served requests."""

    def __init__(self, conf: dict, weights: dict, block: int = 512):
        m = conf["model"]
        self.m = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"], float(m["rope_theta"]),
                  bool(m["attention_bias"]))
        self.vocab = m["vocab_size"]
        self.L = m["num_hidden_layers"]
        self.w = weights
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

    def _read(self, x, at: np.ndarray, tokens: np.ndarray,
              precision: str, rows: int = 256):
        """Gaps of ``tokens`` and argmaxes at rows ``at`` of ``x``."""
        head = self.head.astype(jnp.bfloat16 if precision == "bf16"
                                else jnp.float32)
        gaps, picks = [], []
        for i in range(0, len(at), rows):
            n = len(at[i:i + rows])
            idx = np.pad(at[i:i + rows], (0, rows - n))
            tk = np.pad(tokens[i:i + rows], (0, rows - n))
            g, a = _gaps(x[jnp.asarray(idx)],
                         self.w["final_norm"].astype(jnp.float32), head,
                         jnp.asarray(tk, jnp.int32), vocab=self.vocab,
                         precision=precision)
            gaps.append(np.asarray(g)[:n])
            picks.append(np.asarray(a)[:n])
        return np.concatenate(gaps), np.concatenate(picks)

    def judge(self, prompt: np.ndarray, served: list[int],
              control: str | None = None) -> dict:
        """Gaps at each served position of one request, teacher-forced.

        ``gap``: how far the f32 reference's logit of each served token
        lies below its best.  With ``control``, ``control_gap`` is the same
        for the token the reference at that lower precision puts first.
        """
        ids = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        at = np.arange(len(prompt) - 1, len(ids))
        served = np.asarray(served, np.int32)
        x = self._hidden(ids, "f32")
        out = {"gap": self._read(x, at, served, "f32")[0]}
        if control:
            xc = self._hidden(ids, control)
            _, picks = self._read(xc, at, served, control)
            out["control_gap"] = self._read(x, at, picks, "f32")[0]
        return out
