"""What every plain reference shares: the numerics and the judge.

A plain reference (``Reference`` of ``bench/plain/<name>.py``) imports
nothing of the program and reads only the benchmark's plain weight tree.
It subclasses ``Judge``, which teacher-forces it over a served request
and reads the gaps; the subclass gives the hidden rows before the final
norm (``_hidden``) and the final norm's scale and the readout it reads
them through.

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
bfloat16 for the ``bf16`` control.
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


@functools.partial(jax.jit, static_argnames=("vocab", "precision"))
def _gaps(x, final_norm, head, tokens, *, vocab: int, precision: str):
    """Per row: (reference best logit - logit of ``tokens``, argmax)."""
    h = _rms(x, final_norm, precision)
    logits = _mm(h, head[:, :vocab], precision).astype(jnp.float32)
    picked = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return logits.max(-1) - picked, logits.argmax(-1)


class Judge:
    """Teacher-forced logits of a plain reference over served requests.

    A subclass sets ``vocab`` (the published vocabulary), ``final_norm``
    (D,) and ``head`` (D, padded vocabulary), and gives ``_hidden(ids,
    precision)``: the rows before the final norm at each position of
    ``ids`` (more rows may follow them)."""

    vocab: int
    final_norm: jax.Array
    head: jax.Array

    def _hidden(self, ids: np.ndarray, precision: str):
        raise NotImplementedError

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
                         self.final_norm.astype(jnp.float32), head,
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
