"""Attention: GQA, sliding-window, logit softcap, blockwise (flash-style),
KV cache (fp or quantized), cross-attention — with INT-FP-QSim BMM hooks.

Three compute paths:
  * reference  — materializes scores; used for small seqs / benchmark-exact
                 quantization of attention probabilities.
  * blockwise  — running-softmax scan over KV blocks (Rabe-Staats /
                 FlashAttention recurrence in pure jnp): 32k prefill never
                 materializes S^2.  Quantizes q/k/v per block; probs are
                 quantized per-block (documented deviation, scale-equivalent).
  * decode     — one-token query against the cache; GSPMD's partial-softmax
                 over a seq-sharded cache reproduces flash-decoding.

The *window* is a traced per-layer scalar so scan-over-layers can alternate
local/global (gemma2) without unrolling: window >= S means global.

Serving note: the q/k/v/o projection kernels may arrive as
``CompressedKernel`` codes + scales (per-site compressed storage) — they
flow through ``Dense.apply`` into qmatmul's execution-backend dispatch
untouched, so compressed mixed-precision maps (e.g. dense FP8 attention
projections next to compressed INT4 FFNs) need no special handling here.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.analysis.messages import (attention_block_message,
                                     compressed_attn_storage_message)
from repro.core.policy import Policy, resolve_policy
from repro.core.simulate import attention_backend, attn_backends, \
    qdq_activation
from repro.dist import sharding as shd
from repro.nn.linear import Dense
from repro.nn.module import Box
from repro.nn.rotary import apply_rope

NEG_INF = -1e9  # mask value (safe in bf16/f32)


class KVCache(NamedTuple):
    """Decode cache. k/v: (B, S_max, n_kv * head_dim) flat (even sharding).

    int8 storage mode (policy.kv_cache == 'int8'): k/v hold int8 codes and
    k_scale/v_scale hold per-(slot, kv_head) f32 unit scales — halves cache
    HBM capacity AND read traffic per decode step (§Perf)."""

    k: jnp.ndarray
    v: jnp.ndarray
    # int32 scalar per batch-constant position (all requests aligned per step)
    length: jnp.ndarray
    k_scale: jnp.ndarray | None = None  # (B, S_max, n_kv) f32, int8 mode
    v_scale: jnp.ndarray | None = None


class PagedKVCache(NamedTuple):
    """One layer's paged KV store: a shared pool of fixed-size pages.

    k/v: (n_pages + 1, page_size, n_kv * head_dim) — physical pages shared
    by every slot of the serving batch; which pages belong to which
    sequence lives in the engine's per-slot page table (threaded through
    ``DecodeState.pages``), not here.  The LAST physical page is the trash
    page: masked/padded writes are routed to it so the jitted scatter
    stays fixed-shape (it is never gathered unmasked).

    Quantized storage (policy.kv_cache 'int8' / 'fp8'): k/v hold codes and
    k_scale/v_scale hold per-(page, kv_head) f32 unit scales — one scale
    amortized over the whole page (coarser than the ring buffer's
    per-token scales; the capacity win is the point).  Decode writes into
    a partially-filled page monotonically raise its scale and requantize
    the resident codes (documented drift, bounded by the page's dynamic
    range ratio)."""

    k: jnp.ndarray
    v: jnp.ndarray
    k_scale: jnp.ndarray | None = None  # (n_pages + 1, n_kv) f32
    v_scale: jnp.ndarray | None = None


FP8_KV_MAX = 448.0  # float8_e4m3fn finite max (the paper's serving format)
_KV_EPS = 1e-12


def paged_kv_mode(cache: PagedKVCache) -> str:
    """Storage mode from the store itself: 'fp' | 'int8' | 'fp8'."""
    if cache.k_scale is None:
        return "fp"
    return "int8" if cache.k.dtype == jnp.int8 else "fp8"


def _page_encode(x4: jnp.ndarray, scale: jnp.ndarray, mode: str):
    """Values (..., n_kv, D) + per-(..., n_kv) unit scales -> stored codes."""
    y = x4.astype(jnp.float32) / scale[..., None]
    if mode == "int8":
        return jnp.clip(jnp.round(y), -127, 127).astype(jnp.int8)
    return y.astype(jnp.float8_e4m3fn)


def _page_unit_scale(alpha: jnp.ndarray, mode: str) -> jnp.ndarray:
    qmax = 127.0 if mode == "int8" else FP8_KV_MAX
    return jnp.maximum(alpha.astype(jnp.float32), _KV_EPS) / qmax


def _kv_quantize(x4: jnp.ndarray):
    """(…, n_kv, D) -> int8 codes (flat) + per-(…, head) unit scales."""
    alpha = jnp.max(jnp.abs(x4), axis=-1)  # (..., n_kv)
    scale = jnp.maximum(alpha.astype(jnp.float32), 1e-12) / 127.0
    codes = jnp.clip(
        jnp.round(x4.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return codes, scale


def _kv_dequantize(codes_flat, scale, n_kv: int, head_dim: int, dtype):
    """int8 flat codes + (…, n_kv) scales -> (…, n_kv, D) values."""
    c4 = codes_flat.reshape(*codes_flat.shape[:-1], n_kv, head_dim)
    return (c4.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _softcap(x: jnp.ndarray, cap: float | None) -> jnp.ndarray:
    if cap is None or cap <= 0:
        return x
    return cap * jnp.tanh(x / cap)


@dataclasses.dataclass(frozen=True)
class Attention:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    qkv_bias: bool = False
    causal: bool = True
    rope_theta: float = 10000.0
    use_rope: bool = True
    softcap: float | None = None
    query_scale: float | None = None  # default 1/sqrt(head_dim)
    param_dtype: str = "float32"
    dtype: str = "float32"
    q_block: int = 512
    kv_block: int = 512
    blockwise_min_seq: int = 1024  # use blockwise above this length
    use_flash_kernel: bool = False  # fused Pallas path (TPU; no softcap/SWA)
    name: str = "attn"

    # ---------------------------------------------------------------- init
    def init(self, key) -> dict:
        kq, kk, kv, ko = jax.random.split(key, 4)
        mk = lambda i, o, ax_o, k, name: Dense(
            i, o, use_bias=self.qkv_bias, in_axis="embed", out_axis=ax_o,
            param_dtype=self.param_dtype, dtype=self.dtype, name=name,
        ).init(k)
        p = {
            "q": mk(self.d_model, self.n_heads * self.head_dim, "qkv", kq, "q"),
            "k": mk(self.d_model, self.n_kv * self.head_dim, "qkv", kk, "k"),
            "v": mk(self.d_model, self.n_kv * self.head_dim, "qkv", kv, "v"),
        }
        o = Dense(
            self.n_heads * self.head_dim, self.d_model, use_bias=False,
            in_axis="qkv", out_axis="embed",
            param_dtype=self.param_dtype, dtype=self.dtype, name="o",
        )
        p["o"] = o.init(ko)
        return p

    # ------------------------------------------------------------- helpers
    def _dense(self, which: str, out_dim: int, in_dim: int | None = None):
        return Dense(
            in_dim or self.d_model, out_dim, use_bias=self.qkv_bias
            if which in ("q", "k", "v") else False,
            in_axis="embed" if which != "o" else "qkv",
            out_axis="qkv" if which != "o" else "embed",
            param_dtype=self.param_dtype, dtype=self.dtype,
            name=f"{self.name}/{which}",
        )

    def _project_qkv(self, params, x, positions, policy, q=None,
                     rope=None):
        """q, k, v heads; ``rope``: the layer's ``(freqs, scale)``
        (``nn.rotary.apply_rope``), or None for default RoPE at
        ``rope_theta``."""
        B, S, _ = x.shape
        qh = self._dense("q", self.n_heads * self.head_dim).apply(
            params["q"], x, policy, q=None if q is None else q.get("q")
        )
        kh = self._dense("k", self.n_kv * self.head_dim).apply(
            params["k"], x, policy, q=None if q is None else q.get("k")
        )
        vh = self._dense("v", self.n_kv * self.head_dim).apply(
            params["v"], x, policy, q=None if q is None else q.get("v")
        )
        qh = qh.reshape(B, S, self.n_heads, self.head_dim)
        kh = kh.reshape(B, S, self.n_kv, self.head_dim)
        vh = vh.reshape(B, S, self.n_kv, self.head_dim)
        if self.use_rope:
            freqs, scale = (None, None) if rope is None else rope
            qh = apply_rope(qh, positions, self.rope_theta, freqs=freqs,
                            scale=scale)
            kh = apply_rope(kh, positions, self.rope_theta, freqs=freqs,
                            scale=scale)
        qh = shd.constrain(qh, ("batch", "seq", "heads", "head_dim"))
        kh = shd.constrain(kh, ("batch", "seq", "kv_heads", "head_dim"))
        vh = shd.constrain(vh, ("batch", "seq", "kv_heads", "head_dim"))
        return qh, kh, vh

    def _scale(self) -> float:
        return (
            self.query_scale
            if self.query_scale is not None
            else self.head_dim**-0.5
        )

    def _maybe_quant_qkv(self, policy: Policy, qh, kh, vh,
                         q: dict | None = None, skip_kv: bool = False):
        """QDQ attention-BMM operands along their contraction dims:
        q,k along head_dim (QK^T); v along its seq axis (probs@V).
        ``q``: optional static alphas {'bmm_q': {'in_alpha': ...}, ...}.
        ``skip_kv``: cache entries were quantized at write time (policy
        kv_cache='on_write') — only q needs QDQ here.
        BMM operands resolve the policy at the block site (``self.name``)."""
        policy = resolve_policy(policy, self.name)
        if not (policy.enabled and policy.attn_bmm and policy.input):
            return qh, kh, vh
        tq = policy.input
        geta = (lambda k: None) if q is None else (
            lambda k: (q.get(k) or {}).get("in_alpha"))
        qh = qdq_activation(qh, tq, axis=-1, site=self.name + "/bmm_q",
                            alpha=geta("bmm_q"))
        if not skip_kv:
            kh = qdq_activation(kh, tq, axis=-1, site=self.name + "/bmm_k",
                                alpha=geta("bmm_k"))
            vh = qdq_activation(vh, tq, axis=1, site=self.name + "/bmm_v",
                                alpha=geta("bmm_v"))
        return qh, kh, vh

    # ------------------------------------------- attention-backend dispatch
    def _attn_probs_tq(self, pol):
        """The probs/q quantizer when attention-BMM QDQ is active."""
        if pol.enabled and pol.attn_bmm and pol.input is not None:
            return pol.input
        return None

    def _compressed_eligible(self, pol) -> bool:
        """Can the quantized-KV kernel reproduce the QDQ-sim path here?

        Softcap has no kernel body, and the in-kernel probs QDQ mirrors
        int-format ABFP with BF16 scales only — anything else silently
        falls back to the dequantize-then-reference path (the QL602 lint
        is the signal for that degradation).
        """
        if self.softcap is not None:
            return False
        tq = self._attn_probs_tq(pol)
        if tq is None:
            return True
        from repro.core.formats import IntFormat

        return (tq.scaler == "abfp" and bool(tq.group)
                and isinstance(tq.fmt, IntFormat)
                and jnp.dtype(tq.scale_dtype) == jnp.bfloat16)

    def _quant_q(self, pol, qh, q):
        """The q-operand half of ``_maybe_quant_qkv`` (kernel callers QDQ
        q outside the kernel; K/V arrive pre-quantized as cache codes)."""
        tq = self._attn_probs_tq(pol)
        if tq is None:
            return qh
        alpha = None if q is None else (q.get("bmm_q") or {}).get("in_alpha")
        return qdq_activation(qh, tq, axis=-1, site=self.name + "/bmm_q",
                              alpha=alpha)

    def _use_compressed(self, pol, *, mode: str, where: str) -> bool:
        """Decode-path dispatch: contract cache codes in-kernel?

        ``mode`` is the cache's actual storage format ('fp'/'int8'/'fp8').
        Raises on compressed-over-fp-storage (the QL601 contract — there
        are no codes to contract); returns False for the silent-fallback
        cases QL602 flags (softcap / unsupported probs quantizer).
        """
        if attention_backend(pol).name != "compressed":
            return False
        if mode not in ("int8", "fp8"):
            raise ValueError(compressed_attn_storage_message(mode, where))
        return self._compressed_eligible(pol)

    # -------------------------------------------------- reference attention
    def _reference(self, qh, kh, vh, q_pos, kv_pos, window, policy,
                   q=None, kv_prequant: bool = False):
        policy = resolve_policy(policy, self.name)
        G = self.n_heads // self.n_kv
        B, S, H, D = qh.shape
        T = kh.shape[1]
        qh, kh, vh = self._maybe_quant_qkv(policy, qh, kh, vh, q,
                                           skip_kv=kv_prequant)
        qg = qh.reshape(B, S, self.n_kv, G, D)
        # Native-dtype operands + f32 accumulation (MXU semantics): avoids
        # materializing f32 copies of the (huge) K cache — see §Perf it.1.
        scores = jnp.einsum(
            "bskgd,btkd->bkgst", qg, kh,
            preferred_element_type=jnp.float32,
        ) * self._scale()
        scores = _softcap(scores, self.softcap)
        mask = self._mask(q_pos, kv_pos, window)  # (B?, S, T)
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        if policy.enabled and policy.attn_bmm and policy.input is not None:
            palpha = None if q is None else (
                (q.get("probs") or {}).get("in_alpha"))
            probs = qdq_activation(
                probs, policy.input, axis=-1, site=self.name + "/probs",
                alpha=palpha,
            )
        out = jnp.einsum(
            "bkgst,btkd->bskgd", probs.astype(vh.dtype), vh,
            preferred_element_type=jnp.float32,
        )
        return out.reshape(B, S, H, D).astype(jnp.dtype(self.dtype))

    def _mask(self, q_pos, kv_pos, window):
        """(B, S, T) boolean validity mask given absolute positions."""
        qp = q_pos[:, :, None]
        kp = kv_pos[:, None, :]
        m = kp >= 0  # padded/unwritten slots carry position -1
        if self.causal:
            m &= kp <= qp
        # window is a traced scalar; window >= S means global.
        m &= kp > qp - window
        return m

    # -------------------------------------------------- blockwise attention
    def _blockwise(self, qh, kh, vh, q_pos, kv_pos, window, policy,
                   q=None):
        policy = resolve_policy(policy, self.name)
        B, S, H, D = qh.shape
        T = kh.shape[1]
        qb, kb = min(self.q_block, S), min(self.kv_block, T)
        nq, nk = S // qb, T // kb
        if S % qb or T % kb:
            raise ValueError(attention_block_message(S, T, qb, kb))
        G = self.n_heads // self.n_kv
        scale = self._scale()
        qh, kh, vh = self._maybe_quant_qkv(policy, qh, kh, vh, q)
        tq = policy.input if (policy.enabled and policy.attn_bmm) else None
        _palpha = None if q is None else (
            (q.get("probs") or {}).get("in_alpha"))

        qs = qh.reshape(B, nq, qb, self.n_kv, G, D)
        qp = q_pos.reshape(B, nq, qb)
        ks = kh.reshape(B, nk, kb, self.n_kv, D)
        vs = vh.reshape(B, nk, kb, self.n_kv, D)
        kp = kv_pos.reshape(B, nk, kb)

        def q_chunk(args):
            qc, qpc = args  # (B, qb, KV, G, D), (B, qb)

            def kv_step(carry, kv):
                m_run, l_run, acc = carry
                kc, vc, kpc = kv  # (B, kb, KV, D), (B, kb)
                s = jnp.einsum(
                    "bskgd,btkd->bkgst", qc, kc,
                    preferred_element_type=jnp.float32,
                ) * scale
                s = _softcap(s, self.softcap)
                mask = self._mask(qpc, kpc, window)  # (B, qb, kb)
                s = jnp.where(mask[:, None, None], s, NEG_INF)
                m_new = jnp.maximum(m_run, s.max(axis=-1))
                p = jnp.exp(s - m_new[..., None])
                if tq is not None:
                    p = qdq_activation(p, tq, axis=-1,
                                       site=self.name + "/probs",
                                       alpha=_palpha)
                corr = jnp.exp(m_run - m_new)
                l_new = l_run * corr + p.sum(axis=-1)
                pv = jnp.einsum("bkgst,btkd->bkgsd", p.astype(vc.dtype),
                                vc, preferred_element_type=jnp.float32)
                acc = acc * corr[..., None] + pv
                return (m_new, l_new, acc), None

            m0 = jnp.full((B, self.n_kv, G, qb), NEG_INF, jnp.float32)
            l0 = jnp.zeros((B, self.n_kv, G, qb), jnp.float32)
            a0 = jnp.zeros((B, self.n_kv, G, qb, D), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(
                kv_step, (m0, l0, a0),
                (ks.swapaxes(0, 1), vs.swapaxes(0, 1), kp.swapaxes(0, 1)),
            )
            out = acc / jnp.maximum(l, 1e-20)[..., None]  # (B,KV,G,qb,D)
            return out.transpose(0, 3, 1, 2, 4)  # (B, qb, KV, G, D)

        outs = jax.lax.map(q_chunk, (qs.swapaxes(0, 1), qp.swapaxes(0, 1)))
        out = outs.swapaxes(0, 1).reshape(B, S, H, D)
        return out.astype(jnp.dtype(self.dtype))

    # --------------------------------------------------------- public apply
    def apply(
        self,
        params: dict,
        x: jnp.ndarray,
        *,
        positions: jnp.ndarray,
        policy: Policy,
        window=None,
        q: dict | None = None,
        kv_override: tuple | None = None,  # (k, v, kv_positions) for cross
        return_kv: bool = False,
        n_valid: jnp.ndarray | None = None,  # (B,) valid prefix lengths
        rope=None,  # the layer's (freqs, scale); None: default RoPE
    ) -> jnp.ndarray:
        """Full-sequence attention (training / prefill).

        ``policy`` may be a PolicyMap: block-level decisions (BMM quant,
        flash eligibility, KV handling) resolve at ``self.name`` while the
        q/k/v/o projections resolve at their own sub-sites inside qmatmul.

        ``n_valid``: bucketed prefill pads prompts to the bucket length;
        K/V rows at or past each row's valid length are zeroed so (a) the
        returned ``return_kv`` tensors fill the cache exactly as an
        exact-length prefill would, and (b) requant/on_write QDQ group
        maxima over the seq axis see zeros — not pad-token projections —
        keeping padded prefill token-identical to unpadded (ABFP zero-pads
        partial groups the same way).  Causality already hides the pad
        rows from valid queries; this hides them from the quantizers.
        """
        pol = resolve_policy(policy, self.name)
        B, S, _ = x.shape
        qh, kh, vh = self._project_qkv(params, x, positions, policy, q,
                                       rope)
        if n_valid is not None:
            keep = (jnp.arange(S, dtype=jnp.int32)[None, :]
                    < n_valid[:, None])[..., None, None]
            kh = kh * keep.astype(kh.dtype)
            vh = vh * keep.astype(vh.dtype)
        kv_pos = positions
        if kv_override is not None:
            kh, vh, kv_pos = kv_override
        T = kh.shape[1]
        if window is None:
            window = jnp.asarray(max(T, S) + 1, jnp.int32)
        use_block = (
            max(S, T) >= self.blockwise_min_seq
            and S % min(self.q_block, S) == 0
            and T % min(self.kv_block, T) == 0
        )
        # Per-site backend (registry-validated): 'auto' keeps the module's
        # opt-in flag; 'fused'/'compressed' request the flash kernel
        # ('compressed' has no stored codes at prefill — dense flash is its
        # eligible prefill form); 'ref' pins the jnp paths.
        backend = attention_backend(pol).name
        flash_want = (self.use_flash_kernel if backend == "auto"
                      else backend in ("fused", "compressed"))
        flash_ok = (
            flash_want
            and self.softcap is None
            and kv_override is None
            and S == T  # self-attention, standard causal layout
            and not (pol.enabled and pol.attn_bmm
                     and pol.input is not None)
        )
        if flash_ok:
            out = attn_backends()["fused"].fn(
                qh, kh, vh, scale=self._scale(), causal=self.causal,
                block_q=min(self.q_block, S), block_k=min(self.kv_block, T),
                q_offset=0,  # full-sequence self-attention: q starts at 0
            )
        else:
            fn = self._blockwise if use_block else self._reference
            out = fn(qh, kh, vh, positions, kv_pos, window, policy, q=q)
        out = shd.constrain(out, ("batch", "seq", "heads", "head_dim"))
        o_dense = Dense(
            self.n_heads * self.head_dim, self.d_model,
            in_axis="qkv", out_axis="embed",
            param_dtype=self.param_dtype, dtype=self.dtype,
            name=f"{self.name}/o",
        )
        y = o_dense.apply(
            params["o"], out.reshape(B, S, -1), policy,
            q=None if q is None else q.get("o"),
        )
        y = shd.constrain(y, ("batch", "seq_res", "embed"))
        if return_kv:
            return y, (kh.reshape(B, T, -1), vh.reshape(B, T, -1))
        return y

    def fill_cache(self, kh_flat, vh_flat, size: int,
                   policy: Policy | None = None) -> KVCache:
        """Build a ring-buffer cache from prefill K/V (B, S, flat).

        With ``policy.kv_cache == 'on_write'`` the entries are quantized
        here (K per head_dim group — exact; V along seq — exact at prefill
        because the full sequence is present)."""
        if policy is not None:
            policy = resolve_policy(policy, self.name)
        B, S, F = kh_flat.shape
        if (policy is not None and policy.enabled and policy.attn_bmm
                and policy.input is not None
                and policy.kv_cache == "on_write"):
            kh4 = kh_flat.reshape(B, S, self.n_kv, self.head_dim)
            vh4 = vh_flat.reshape(B, S, self.n_kv, self.head_dim)
            kh4 = qdq_activation(kh4, policy.input, axis=-1,
                                 site=self.name + "/bmm_k")
            vh4 = qdq_activation(vh4, policy.input, axis=1,
                                 site=self.name + "/bmm_v")
            kh_flat = kh4.reshape(B, S, F)
            vh_flat = vh4.reshape(B, S, F)
        take = min(S, size)
        idx = (jnp.arange(S - take, S) % size).astype(jnp.int32)
        if policy is not None and policy.kv_cache == "int8":
            kc, ks = _kv_quantize(
                kh_flat.reshape(B, S, self.n_kv, self.head_dim))
            vc, vs = _kv_quantize(
                vh_flat.reshape(B, S, self.n_kv, self.head_dim))
            kc = kc.reshape(B, S, F)
            vc = vc.reshape(B, S, F)
            k = jnp.zeros((B, size, F), jnp.int8).at[:, idx].set(
                kc[:, -take:])
            v = jnp.zeros((B, size, F), jnp.int8).at[:, idx].set(
                vc[:, -take:])
            k_scale = jnp.zeros((B, size, self.n_kv), jnp.float32).at[
                :, idx].set(ks[:, -take:])
            v_scale = jnp.zeros((B, size, self.n_kv), jnp.float32).at[
                :, idx].set(vs[:, -take:])
            k = shd.constrain(k, ("batch", "kv_seq", "qkv"))
            v = shd.constrain(v, ("batch", "kv_seq", "qkv"))
            return KVCache(k=k, v=v, length=jnp.asarray(S, jnp.int32),
                           k_scale=k_scale, v_scale=v_scale)
        k = jnp.zeros((B, size, F), kh_flat.dtype).at[:, idx].set(
            kh_flat[:, -take:]
        )
        v = jnp.zeros((B, size, F), vh_flat.dtype).at[:, idx].set(
            vh_flat[:, -take:]
        )
        k = shd.constrain(k, ("batch", "kv_seq", "qkv"))
        v = shd.constrain(v, ("batch", "kv_seq", "qkv"))
        return KVCache(k=k, v=v, length=jnp.asarray(S, jnp.int32))

    # ------------------------------------------------------------ decoding
    def init_cache(
        self, batch: int, max_len: int, dtype=None, window: int | None = None,
        quantized: bool = False,
    ) -> KVCache:
        """Ring-buffer cache of size min(max_len, window) (SWA truncates).

        ``quantized``: int8 codes + per-(slot, head) f32 scales (§Perf)."""
        size = max_len if window is None else min(max_len, window)
        dt = jnp.dtype(dtype or self.dtype)
        flat = self.n_kv * self.head_dim
        if quantized:
            return KVCache(
                k=jnp.zeros((batch, size, flat), jnp.int8),
                v=jnp.zeros((batch, size, flat), jnp.int8),
                length=jnp.zeros((), jnp.int32),
                k_scale=jnp.zeros((batch, size, self.n_kv), jnp.float32),
                v_scale=jnp.zeros((batch, size, self.n_kv), jnp.float32),
            )
        return KVCache(
            k=jnp.zeros((batch, size, flat), dt),
            v=jnp.zeros((batch, size, flat), dt),
            length=jnp.zeros((), jnp.int32),
        )

    def decode_step(
        self,
        params: dict,
        x: jnp.ndarray,  # (B, 1, d_model)
        cache: KVCache,
        *,
        position: jnp.ndarray,  # int32 scalar (aligned) or (B,) per-slot
        policy: Policy,
        window=None,
        q: dict | None = None,
        rope=None,
    ) -> tuple[jnp.ndarray, KVCache]:
        pol = resolve_policy(policy, self.name)
        B = x.shape[0]
        position = jnp.asarray(position, jnp.int32)
        aligned = position.ndim == 0  # all rows at the same position
        pos_vec = jnp.broadcast_to(jnp.atleast_1d(position), (B,))
        pos_b = pos_vec[:, None]  # (B, 1) query positions
        qh, kh, vh = self._project_qkv(params, x, pos_b, policy, q, rope)
        int8_cache = cache.k_scale is not None
        kv_on_write = (pol.enabled and pol.attn_bmm
                       and pol.input is not None
                       and pol.kv_cache == "on_write")
        if kv_on_write:
            # quantize ONCE at write time; reads skip the re-QDQ (exact for
            # K's head_dim groups; per-token for V — documented deviation)
            kh = qdq_activation(kh, pol.input, axis=-1,
                                site=self.name + "/bmm_k")
            vh = qdq_activation(vh, pol.input, axis=-1,
                                site=self.name + "/bmm_v")
        size = cache.k.shape[1]
        new_ks = new_vs = None
        if int8_cache:
            # int8 storage: the quantization IS the write (per token, head)
            kc, ks = _kv_quantize(kh)  # kh: (B, 1, n_kv, D)
            vc, vs = _kv_quantize(vh)
            k_flat = kc.reshape(B, 1, -1)
            v_flat = vc.reshape(B, 1, -1)
        else:
            k_flat = kh.reshape(B, 1, -1).astype(cache.k.dtype)
            v_flat = vh.reshape(B, 1, -1).astype(cache.v.dtype)
        if aligned:
            # fast path: one dynamic_update_slice for the whole batch
            slot = position % size
            new_k = jax.lax.dynamic_update_slice_in_dim(
                cache.k, k_flat, slot, 1)
            new_v = jax.lax.dynamic_update_slice_in_dim(
                cache.v, v_flat, slot, 1)
            if int8_cache:
                new_ks = jax.lax.dynamic_update_slice_in_dim(
                    cache.k_scale, ks, slot, 1)
                new_vs = jax.lax.dynamic_update_slice_in_dim(
                    cache.v_scale, vs, slot, 1)
        else:
            # per-slot positions (continuous batching): batched scatter
            slot_b = pos_vec % size
            rows = jnp.arange(B)
            new_k = cache.k.at[rows, slot_b].set(k_flat[:, 0])
            new_v = cache.v.at[rows, slot_b].set(v_flat[:, 0])
            if int8_cache:
                new_ks = cache.k_scale.at[rows, slot_b].set(ks[:, 0])
                new_vs = cache.v_scale.at[rows, slot_b].set(vs[:, 0])
        new_k = shd.constrain(new_k, ("batch", "kv_seq", "qkv"))
        new_v = shd.constrain(new_v, ("batch", "kv_seq", "qkv"))
        # length stays a scalar high-water mark even for vector positions
        cache = KVCache(new_k, new_v, jnp.max(position) + 1,
                        k_scale=new_ks, v_scale=new_vs)

        # Absolute positions stored in each slot of the ring buffer.
        idx = jnp.arange(size, dtype=jnp.int32)[None]  # (1, size)
        slot_b = (pos_vec % size)[:, None]
        ring_rounds = (pos_vec // size)[:, None] * size
        slot_pos = idx + jnp.where(idx <= slot_b, ring_rounds,
                                   ring_rounds - size)
        slot_pos = jnp.where(slot_pos > pos_vec[:, None], -1, slot_pos)
        slot_pos = jnp.where(slot_pos < 0, -1, slot_pos)  # unwritten

        dt = jnp.dtype(self.dtype)
        if window is None:
            window = jnp.asarray(size + 1, jnp.int32)
        qp = pos_vec[:, None]
        kp = slot_pos
        if self._use_compressed(pol, mode="int8" if int8_cache else "fp",
                                where="the ring-buffer cache"):
            # codes go straight to the kernel: HBM reads stay 1 byte/elem
            out = attn_backends()["compressed"].fn(
                self._quant_q(pol, qh, q),
                cache.k.reshape(B, size, self.n_kv, self.head_dim),
                cache.v.reshape(B, size, self.n_kv, self.head_dim),
                cache.k_scale, cache.v_scale, qp, kp, window,
                scale=self._scale(), causal=self.causal,
                probs_tq=self._attn_probs_tq(pol),
            ).astype(dt)
        else:
            if int8_cache:
                kv = _kv_dequantize(cache.k, cache.k_scale, self.n_kv,
                                    self.head_dim, dt)
                vv = _kv_dequantize(cache.v, cache.v_scale, self.n_kv,
                                    self.head_dim, dt)
            else:
                kv = cache.k.reshape(B, size, self.n_kv, self.head_dim)
                vv = cache.v.reshape(B, size, self.n_kv, self.head_dim)
            out = self._reference(qh, kv, vv, qp, kp, window, policy, q=q,
                                  kv_prequant=kv_on_write or int8_cache)
        o_dense = Dense(
            self.n_heads * self.head_dim, self.d_model,
            in_axis="qkv", out_axis="embed",
            param_dtype=self.param_dtype, dtype=self.dtype,
            name=f"{self.name}/o",
        )
        y = o_dense.apply(params["o"], out.reshape(B, 1, -1), policy,
                          q=None if q is None else q.get("o"))
        return shd.constrain(y, ("batch", "seq_res", "embed")), cache

    def chunk_step(
        self,
        params: dict,
        x: jnp.ndarray,  # (B, S, d_model): an S-token verify/score chunk
        cache: KVCache,
        *,
        position: jnp.ndarray,  # (B,) absolute position of x[:, 0]
        n_valid: jnp.ndarray,  # (B,) valid tokens in x (0 masks the row)
        policy: Policy,
        window=None,
        q: dict | None = None,
        rope=None,
    ) -> tuple[jnp.ndarray, KVCache]:
        """Write-then-attend over an S-token chunk against the ring buffer.

        The speculative verify pass: score S drafted tokens in ONE call —
        each chunk token attends to the whole cache plus the chunk's own
        earlier tokens (strictly causal), exactly as S sequential
        ``decode_step`` calls would, and the returned activations cover
        every chunk position (the caller needs all S logits, not just the
        last).  Tokens past a row's ``n_valid`` leave the cache untouched
        and produce garbage outputs the caller ignores (dead slots in a
        serving batch use ``n_valid = 0``).  Rolling back after a
        rejection is the
        caller rewinding ``position``: stale entries past the new position
        are masked by the ring validity mask and overwritten by the next
        write, the same convention the paged engine pins.
        """
        pol = resolve_policy(policy, self.name)
        B, S, _ = x.shape
        size = cache.k.shape[1]
        if S > size:
            raise ValueError(
                f"chunk of {S} tokens exceeds the ring-buffer cache size "
                f"{size}; a chunk must not wrap over itself")
        position = jnp.asarray(position, jnp.int32)
        pos_vec = jnp.broadcast_to(jnp.atleast_1d(position), (B,))
        n_valid = jnp.asarray(n_valid, jnp.int32)
        positions = pos_vec[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
        qh, kh, vh = self._project_qkv(params, x, positions, policy, q,
                                       rope)
        int8_cache = cache.k_scale is not None
        kv_on_write = (pol.enabled and pol.attn_bmm
                       and pol.input is not None
                       and pol.kv_cache == "on_write")
        if kv_on_write:
            kh = qdq_activation(kh, pol.input, axis=-1,
                                site=self.name + "/bmm_k")
            vh = qdq_activation(vh, pol.input, axis=-1,
                                site=self.name + "/bmm_v")
        rows = jnp.arange(B)[:, None]
        slot = positions % size  # (B, S)
        # invalid tail tokens (>= n_valid) must leave their target slots
        # untouched: a wrapped slot can still hold a live older position
        keep = (jnp.arange(S, dtype=jnp.int32)[None] < n_valid[:, None])
        kf = keep[..., None]  # (B, S, 1) over the flat kv axis
        new_ks = new_vs = None
        if int8_cache:
            kc, ks = _kv_quantize(kh)  # per (token, head) — rollback-exact
            vc, vs = _kv_quantize(vh)
            new_k = cache.k.at[rows, slot].set(
                jnp.where(kf, kc.reshape(B, S, -1), cache.k[rows, slot]))
            new_v = cache.v.at[rows, slot].set(
                jnp.where(kf, vc.reshape(B, S, -1), cache.v[rows, slot]))
            new_ks = cache.k_scale.at[rows, slot].set(
                jnp.where(kf, ks, cache.k_scale[rows, slot]))
            new_vs = cache.v_scale.at[rows, slot].set(
                jnp.where(kf, vs, cache.v_scale[rows, slot]))
        else:
            new_k = cache.k.at[rows, slot].set(jnp.where(
                kf, kh.reshape(B, S, -1).astype(cache.k.dtype),
                cache.k[rows, slot]))
            new_v = cache.v.at[rows, slot].set(jnp.where(
                kf, vh.reshape(B, S, -1).astype(cache.v.dtype),
                cache.v[rows, slot]))
        new_k = shd.constrain(new_k, ("batch", "kv_seq", "qkv"))
        new_v = shd.constrain(new_v, ("batch", "kv_seq", "qkv"))
        last = pos_vec + jnp.maximum(n_valid, 1) - 1  # last written position
        cache = KVCache(new_k, new_v, jnp.max(last) + 1,
                        k_scale=new_ks, v_scale=new_vs)

        # absolute position per ring slot (decode_step's formula at the
        # chunk's high-water mark)
        idx = jnp.arange(size, dtype=jnp.int32)[None]  # (1, size)
        slot_b = (last % size)[:, None]
        ring_rounds = (last // size)[:, None] * size
        slot_pos = idx + jnp.where(idx <= slot_b, ring_rounds,
                                   ring_rounds - size)
        slot_pos = jnp.where(slot_pos > last[:, None], -1, slot_pos)
        slot_pos = jnp.where(slot_pos < 0, -1, slot_pos)

        dt = jnp.dtype(self.dtype)
        if window is None:
            window = jnp.asarray(size + 1, jnp.int32)
        if self._use_compressed(pol, mode="int8" if int8_cache else "fp",
                                where="the ring-buffer cache"):
            out = attn_backends()["compressed"].fn(
                self._quant_q(pol, qh, q),
                cache.k.reshape(B, size, self.n_kv, self.head_dim),
                cache.v.reshape(B, size, self.n_kv, self.head_dim),
                cache.k_scale, cache.v_scale, positions, slot_pos, window,
                scale=self._scale(), causal=self.causal,
                probs_tq=self._attn_probs_tq(pol),
            ).astype(dt)
        else:
            if int8_cache:
                kv = _kv_dequantize(cache.k, cache.k_scale, self.n_kv,
                                    self.head_dim, dt)
                vv = _kv_dequantize(cache.v, cache.v_scale, self.n_kv,
                                    self.head_dim, dt)
            else:
                kv = cache.k.reshape(B, size, self.n_kv, self.head_dim)
                vv = cache.v.reshape(B, size, self.n_kv, self.head_dim)
            out = self._reference(qh, kv, vv, positions, slot_pos, window,
                                  policy, q=q,
                                  kv_prequant=kv_on_write or int8_cache)
        o_dense = Dense(
            self.n_heads * self.head_dim, self.d_model,
            in_axis="qkv", out_axis="embed",
            param_dtype=self.param_dtype, dtype=self.dtype,
            name=f"{self.name}/o",
        )
        y = o_dense.apply(params["o"], out.reshape(B, S, -1), policy,
                          q=None if q is None else q.get("o"))
        return shd.constrain(y, ("batch", "seq_res", "embed")), cache

    # ------------------------------------------------------- paged decoding
    def init_paged_cache(self, n_pages: int, page_size: int, dtype=None,
                         kv: str = "fp") -> PagedKVCache:
        """One layer's shared page pool (+1 trash page for masked writes).

        ``kv``: 'fp' (native dtype), 'int8', or 'fp8' (e4m3 codes); the
        quantized modes add per-(page, head) f32 scales."""
        flat = self.n_kv * self.head_dim
        P = n_pages + 1  # physical pages incl. the trash page
        if kv in ("int8", "fp8"):
            ct = jnp.int8 if kv == "int8" else jnp.float8_e4m3fn
            # zero scales: an unwritten page dequantizes to exactly 0
            return PagedKVCache(
                k=jnp.zeros((P, page_size, flat), ct),
                v=jnp.zeros((P, page_size, flat), ct),
                k_scale=jnp.zeros((P, self.n_kv), jnp.float32),
                v_scale=jnp.zeros((P, self.n_kv), jnp.float32),
            )
        if kv != "fp":
            raise ValueError(f"unknown paged KV storage mode {kv!r} "
                             "(expected 'fp', 'int8' or 'fp8')")
        dt = jnp.dtype(dtype or self.dtype)
        return PagedKVCache(
            k=jnp.zeros((P, page_size, flat), dt),
            v=jnp.zeros((P, page_size, flat), dt),
        )

    def _page_write(self, cache: PagedKVCache, kh, vh, phys_tok, positions,
                    mode: str) -> PagedKVCache:
        """Scatter S new tokens per row into the page pool.

        kh/vh: (B, S, n_kv, D) with invalid rows already zeroed.
        ``phys_tok``: (B, S) physical page per token (masked writes
        already routed to the trash page).  Two static shapes:

          * S == 1 (decode): single-token write; quantized modes gather the
            resident page, monotonically raise its per-(page, head) scale
            and requantize the old codes against it (drift bounded by the
            scale ratio — the documented paged-KV deviation).
          * S == m * page_size with page-aligned positions (prefill
            chunks): whole-page writes; the page scale is the exact max
            over the page's (masked) tokens, so prefilled pages carry no
            requantization drift at all.
        """
        B, S, KV, D = kh.shape
        ps = cache.k.shape[1]
        F = KV * D
        if mode == "fp":
            slot = positions % ps
            new_k = cache.k.at[phys_tok, slot].set(
                kh.reshape(B, S, F).astype(cache.k.dtype))
            new_v = cache.v.at[phys_tok, slot].set(
                vh.reshape(B, S, F).astype(cache.v.dtype))
            return PagedKVCache(k=new_k, v=new_v)
        if S == 1:
            phys = phys_tok[:, 0]  # (B,)
            slot = (positions[:, 0] % ps)  # (B,)
            rows = jnp.arange(B)

            def upd(store, scale, x4):
                old = store[phys].reshape(B, ps, KV, D)  # codes
                s_old = scale[phys]  # (B, n_kv)
                alpha = jnp.max(jnp.abs(x4[:, 0]), axis=-1)  # (B, n_kv)
                s_new = jnp.maximum(s_old, _page_unit_scale(alpha, mode))
                ratio = s_old / s_new  # <= 1; 0 for untouched pages
                old_f = old.astype(jnp.float32) * ratio[:, None, :, None]
                if mode == "int8":
                    old_rq = jnp.clip(jnp.round(old_f), -127, 127)
                else:
                    old_rq = old_f
                page = old_rq.at[rows, slot].set(
                    x4[:, 0].astype(jnp.float32) / s_new[..., None])
                if mode == "int8":
                    page = jnp.clip(jnp.round(page), -127, 127)
                page = page.astype(store.dtype).reshape(B, ps, F)
                return store.at[phys].set(page), scale.at[phys].set(s_new)

            new_k, new_ks = upd(cache.k, cache.k_scale, kh)
            new_v, new_vs = upd(cache.v, cache.v_scale, vh)
            return PagedKVCache(k=new_k, v=new_v, k_scale=new_ks,
                                v_scale=new_vs)
        if S % ps:
            from repro.analysis.messages import page_chunk_message

            raise ValueError(page_chunk_message(S, ps))
        m = S // ps
        phys_pg = phys_tok.reshape(B, m, ps)[:, :, 0]  # (B, m)

        def enc(x4):
            xg = x4.reshape(B, m, ps, KV, D)
            alpha = jnp.max(jnp.abs(xg), axis=(2, 4))  # (B, m, n_kv)
            s = _page_unit_scale(alpha, mode)
            codes = _page_encode(xg, s[:, :, None], mode)
            return codes.reshape(B, m, ps, F), s

        kc, ks = enc(kh)
        vc, vs = enc(vh)
        return PagedKVCache(
            k=cache.k.at[phys_pg].set(kc),
            v=cache.v.at[phys_pg].set(vc),
            k_scale=cache.k_scale.at[phys_pg].set(ks),
            v_scale=cache.v_scale.at[phys_pg].set(vs),
        )

    def paged_step(
        self,
        params: dict,
        x: jnp.ndarray,  # (B, S, d_model): S=1 decode, S=chunk prefill
        cache: PagedKVCache,
        *,
        page_table: jnp.ndarray,  # (B, n_logical) physical indices, -1 free
        position: jnp.ndarray,  # (B,) absolute position of x[:, 0]
        n_valid: jnp.ndarray,  # (B,) valid tokens in x (0 masks the row)
        policy: Policy,
        window=None,
        q: dict | None = None,
        rope=None,
    ) -> tuple[jnp.ndarray, PagedKVCache]:
        """Unified paged write-then-attend over a token chunk.

        Projects S tokens, writes their K/V into the row's pages (invalid
        tokens — pad rows past ``n_valid`` or rows with no page mapped —
        go to the trash page), then gathers the row's full page list,
        rescales quantized pages, zero-masks unwritten positions and runs
        the reference attention with absolute positions.  Exactness notes:
        gathered-length T = n_logical * page_size differs from the fixed
        engine's max_len, but masked positions are exact zeros and ABFP
        seq-axis groups align from index 0, so requant QDQ over the gather
        matches the ring-buffer path bit-for-bit (the token-identity claim
        ``serving_table`` makes).
        """
        pol = resolve_policy(policy, self.name)
        mode = paged_kv_mode(cache)
        B, S, _ = x.shape
        NL = page_table.shape[1]
        ps = cache.k.shape[1]
        trash = cache.k.shape[0] - 1
        position = jnp.asarray(position, jnp.int32)
        n_valid = jnp.asarray(n_valid, jnp.int32)
        positions = position[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
        qh, kh, vh = self._project_qkv(params, x, positions, policy, q,
                                       rope)
        keep = (jnp.arange(S, dtype=jnp.int32)[None] < n_valid[:, None])
        kh = kh * keep[..., None, None].astype(kh.dtype)
        vh = vh * keep[..., None, None].astype(vh.dtype)
        kv_on_write = (mode == "fp" and pol.enabled and pol.attn_bmm
                       and pol.input is not None
                       and pol.kv_cache == "on_write")
        if kv_on_write:
            # quantize ONCE at write time (per-token, as decode_step does)
            kh = qdq_activation(kh, pol.input, axis=-1,
                                site=self.name + "/bmm_k")
            vh = qdq_activation(vh, pol.input, axis=-1,
                                site=self.name + "/bmm_v")

        # physical page per token; every masked write routes to the trash
        lp = jnp.clip(positions // ps, 0, NL - 1)  # (B, S) logical pages
        phys_tok = jnp.take_along_axis(page_table, lp, axis=1)
        ok = keep & (phys_tok >= 0) & (positions // ps < NL)
        phys_tok = jnp.where(ok, phys_tok, trash)
        cache = self._page_write(cache, kh, vh, phys_tok, positions, mode)

        # gather the row's pages in logical order -> contiguous (B, T, ...)
        T = NL * ps
        phys_tab = jnp.where(page_table >= 0, page_table, trash)  # (B, NL)
        idx = jnp.arange(T, dtype=jnp.int32)[None]  # (1, T) absolute pos
        mapped = jnp.take_along_axis(
            page_table, jnp.broadcast_to(idx // ps, (B, T)), axis=1) >= 0
        n_ctx = position + n_valid  # tokens visible after this write
        valid = (idx < n_ctx[:, None]) & mapped
        kv_pos = jnp.where(valid, idx, -1)
        if window is None:
            window = jnp.asarray(T + 1, jnp.int32)
        if self._use_compressed(pol, mode=mode, where="the paged KV pool"):
            # gather CODES only — no dequantized dense copy, no zero-mask:
            # invalid/trash positions carry kv_pos = -1, which the kernel
            # turns into probability-exactly-0 (trash never reaches the
            # output), and the page scales broadcast over their tokens.
            with jax.named_scope("gather"):
                gk = cache.k[phys_tab].reshape(B, T, self.n_kv,
                                               self.head_dim)
                gv = cache.v[phys_tab].reshape(B, T, self.n_kv,
                                               self.head_dim)
                sk = jnp.broadcast_to(
                    cache.k_scale[phys_tab][:, :, None, :],
                    (B, NL, ps, self.n_kv)).reshape(B, T, self.n_kv)
                sv = jnp.broadcast_to(
                    cache.v_scale[phys_tab][:, :, None, :],
                    (B, NL, ps, self.n_kv)).reshape(B, T, self.n_kv)
            out = attn_backends()["compressed"].fn(
                self._quant_q(pol, qh, q), gk, gv, sk, sv,
                positions, kv_pos, window,
                scale=self._scale(), causal=self.causal,
                probs_tq=self._attn_probs_tq(pol),
            ).astype(jnp.dtype(self.dtype))
        else:
            with jax.named_scope("gather"):
                gk = cache.k[phys_tab]  # (B, NL, ps, F)
                gv = cache.v[phys_tab]
                if mode != "fp":
                    sk = cache.k_scale[phys_tab][:, :, None, :, None]
                    sv = cache.v_scale[phys_tab][:, :, None, :, None]
                    gk = gk.reshape(B, NL, ps, self.n_kv, self.head_dim)
                    gv = gv.reshape(B, NL, ps, self.n_kv, self.head_dim)
                    gk = (gk.astype(jnp.float32) * sk).astype(
                        jnp.dtype(self.dtype))
                    gv = (gv.astype(jnp.float32) * sv).astype(
                        jnp.dtype(self.dtype))
                gk = gk.reshape(B, T, self.n_kv, self.head_dim)
                gv = gv.reshape(B, T, self.n_kv, self.head_dim)
                # zero-mask: requant group maxima must see zeros, never
                # trash
                gk = gk * valid[..., None, None].astype(gk.dtype)
                gv = gv * valid[..., None, None].astype(gv.dtype)
            out = self._reference(qh, gk, gv, positions, kv_pos, window,
                                  policy, q=q,
                                  kv_prequant=kv_on_write or mode != "fp")
        o_dense = Dense(
            self.n_heads * self.head_dim, self.d_model,
            in_axis="qkv", out_axis="embed",
            param_dtype=self.param_dtype, dtype=self.dtype,
            name=f"{self.name}/o",
        )
        y = o_dense.apply(params["o"], out.reshape(B, S, -1), policy,
                          q=None if q is None else q.get("o"))
        return shd.constrain(y, ("batch", "seq_res", "embed")), cache
