"""Decoder-only transformer LM (covers dense / GQA / SWA / softcap / MoE /
pure-SSM families) with scan-over-layers, KV-cache decode, and the
INT-FP-QSim policy threaded through every matmul.

Calibration note: PTQ calibration (Calibrator observers) requires eager
per-layer execution — run with ``cfg.scan_layers=False`` (unrolled) and no
jit so observation sites fire per layer.  Scan mode is for training/serving
at scale where calibration state is already solved.

Policy note: ``policy`` may be a flat QuantPolicy or a site-addressed
PolicyMap.  Layer-indexed rules (``blocks.3/...``) need the same unrolled
execution as calibration — all three entry points (apply / prefill /
decode_step) thread ``blocks.{i}`` site names when ``scan_layers=False``
and raise on layer-indexed rules under scan.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.policy import (
    Policy,
    QuantPolicy,
    check_scan_compatible,
    kv_cache_mode,
)
from repro.dist import sharding as shd
from repro.nn.attention import Attention, KVCache
from repro.nn.ffn import MLP
from repro.nn.linear import Dense, Embed
from repro.nn.moe import COUNT_KEYS, MoE
from repro.nn.module import Box, stack_init, truncated_normal
from repro.nn.norms import LayerNorm, RMSNorm
from repro.nn.rotary import yarn_attention_factor, yarn_freqs
from repro.nn.ssm import Mamba2, SSMCache

GLOBAL_WINDOW = 1 << 30
NEG_INF = -1e9


class PagedState(NamedTuple):
    """Paged KV serving state: the shared page pool + the page table.

    ``cache``: PagedKVCache leaves stacked with a leading L dim — one
    physical pool per layer, indexed by the SAME page table (a page index
    addresses the same slot in every layer's store).
    ``table``: (B, max_pages_per_seq) int32 physical page per logical
    page, -1 where unmapped; owned/updated host-side by the engine's
    admission control, read by every jitted paged step.
    """

    cache: Any  # PagedKVCache with leading L dim
    table: jnp.ndarray  # (B, n_logical) int32


class DecodeState(NamedTuple):
    """Stacked per-layer caches + absolute position.

    Exactly one of kv / ssm / pages is populated: the fixed-slot ring
    buffer, the SSM state, or the paged KV pool (continuous batching).
    ``stats``: on paged states, the routing counts of the step that made
    the state, summed over the MoE layers (``nn.moe.Layout.counts``; {}
    without experts), so the engine reads them with the step's tokens.
    """

    kv: Any  # KVCache with leading L dim, or None
    ssm: Any  # SSMCache with leading L dim, or None
    position: jnp.ndarray  # scalar int32 (aligned) or (B,) per-slot
    pages: Any = None  # PagedState, or None
    stats: Any = None  # dict of int32 scalars, paged states only


def _norm(cfg: ArchConfig):
    if cfg.norm == "ln":
        return LayerNorm(cfg.d_model, param_dtype=cfg.param_dtype,
                         dtype=cfg.dtype)
    return RMSNorm(cfg.d_model, plus_one=cfg.norm_plus_one,
                   param_dtype=cfg.param_dtype, dtype=cfg.dtype)


@dataclasses.dataclass(frozen=True)
class TransformerLM:
    cfg: ArchConfig

    # ------------------------------------------------------------ builders
    def _attention(self, name: str = "attn") -> Attention:
        c = self.cfg
        return Attention(
            d_model=c.d_model, n_heads=c.n_heads, n_kv=c.n_kv,
            head_dim=c.head_dim_, qkv_bias=c.qkv_bias,
            rope_theta=c.rope_theta, use_rope=(c.pos == "rope"),
            softcap=c.attn_softcap, param_dtype=c.param_dtype, dtype=c.dtype,
            q_block=c.q_block, kv_block=c.kv_block, name=name,
        )

    def _mlp(self, name: str = "ffn") -> MLP:
        c = self.cfg
        return MLP(c.d_model, c.d_ff, act=c.act, param_dtype=c.param_dtype,
                   dtype=c.dtype, name=name)

    def _moe(self, name: str = "ffn") -> MoE:
        c = self.cfg
        return MoE(
            c.d_model, c.d_ff, n_experts=c.n_experts, top_k=c.top_k,
            norm_topk_prob=c.norm_topk_prob, act=c.act,
            param_dtype=c.param_dtype, dtype=c.dtype, name=name,
        )

    def _mamba(self, name: str = "mamba") -> Mamba2:
        c = self.cfg
        return Mamba2(
            d_model=c.d_model, d_state=c.ssm_state, d_conv=c.ssm_conv,
            expand=c.ssm_expand, head_dim=c.ssm_head_dim,
            n_groups=c.ssm_groups, chunk=c.ssm_chunk,
            param_dtype=c.param_dtype, dtype=c.dtype, name=name,
        )

    @property
    def is_ssm(self) -> bool:
        return self.cfg.ssm_state > 0

    @property
    def is_moe(self) -> bool:
        return self.cfg.family == "moe" and self.cfg.n_experts > 0

    # ----------------------------------------------------------------- init
    def _block_init(self, key) -> dict:
        c = self.cfg
        if self.is_ssm:
            k1, k2 = jax.random.split(key)
            return {"ln": _norm(c).init(k1), "mamba": self._mamba().init(k2)}
        keys = jax.random.split(key, 6)
        p = {
            "ln1": _norm(c).init(keys[0]),
            "attn": self._attention().init(keys[1]),
            "ln2": _norm(c).init(keys[2]),
        }
        p["ffn"] = (self._moe() if self.is_moe else self._mlp()).init(keys[3])
        if c.post_norms:
            p["ln1_post"] = _norm(c).init(keys[4])
            p["ln2_post"] = _norm(c).init(keys[5])
        return p

    def init(self, key) -> dict:
        c = self.cfg
        kE, kB, kN, kH, kP = jax.random.split(key, 5)
        params: dict = {
            "embed": Embed(c.vocab_padded, c.d_model,
                           param_dtype=c.param_dtype, dtype=c.dtype).init(kE),
            "final_norm": _norm(c).init(kN),
        }
        if c.scan_layers:
            params["blocks"] = stack_init(self._block_init, kB, c.n_layers)
        else:
            bkeys = jax.random.split(kB, c.n_layers)
            params["blocks"] = [self._block_init(k) for k in bkeys]
        if not c.tied_embeddings:
            params["lm_head"] = Dense(
                c.d_model, c.vocab_padded, in_axis="embed", out_axis="vocab",
                param_dtype=c.param_dtype, dtype=c.dtype, name="lm_head",
            ).init(kH)
        if c.pos == "learned":
            params["pos_embed"] = Box(
                truncated_normal(
                    kP, (c.max_position, c.d_model),
                    jnp.dtype(c.param_dtype), 0.02,
                ),
                ("seq", "embed"),
            )
        return params

    # ---------------------------------------------------------- layer kinds
    def layer_params(self) -> dict:
        """Each layer's attention settings from its kind
        (``ArchConfig.layer_kinds``), stacked over layers so a scan
        carries them: ``window`` (L,) int32 (``GLOBAL_WINDOW`` on full
        layers) and the layer's RoPE, ``freqs`` (L, head_dim/2) and
        ``scale`` (L,), which multiplies cos and sin (YaRN's attention
        factor; 1 for default RoPE)."""
        c = self.cfg
        hd = c.head_dim_
        default = (1.0 / c.rope_theta ** (
            np.arange(0, hd, 2, dtype=np.float32) / hd)).astype(np.float32)
        windows, freqs, scales = [], [], []
        for kind in c.layer_kinds():
            yarn = c.yarn if kind == "full" else None
            windows.append(c.window if kind == "sliding" else GLOBAL_WINDOW)
            if yarn is None:
                freqs.append(default)
                scales.append(1.0)
            else:
                freqs.append(yarn_freqs(hd, c.rope_theta, yarn.factor,
                                        yarn.original_max_position,
                                        yarn.beta_fast, yarn.beta_slow))
                scales.append(yarn.attention_factor
                              or yarn_attention_factor(yarn.factor))
        return {"window": jnp.asarray(windows, jnp.int32),
                "freqs": jnp.asarray(np.stack(freqs)),
                "scale": jnp.asarray(scales, jnp.float32)}

    @staticmethod
    def _layer(lp: dict, i: int) -> dict:
        return jax.tree_util.tree_map(lambda a: a[i], lp)

    def _attend(self, attn, method, bp, h, lp, **kw):
        """``attn.<method>`` with the layer's window and RoPE."""
        return getattr(attn, method)(bp, h, window=lp["window"],
                                     rope=(lp["freqs"], lp["scale"]), **kw)

    def _ffn(self, bp, h, policy, name, q=None, valid=None):
        """The block's MLP or MoE: (output, MoE metrics or None)."""
        if self.is_moe:
            return self._moe(f"{name}/ffn").apply(bp, h, policy, q=q,
                                                  valid=valid)
        return self._mlp(f"{name}/ffn").apply(bp, h, policy, q=q), None

    # --------------------------------------------------------------- blocks
    def _block_apply(self, bparams, x, positions, lp, policy,
                     q=None, name="block", collect_load=False):
        c = self.cfg
        aux = jnp.zeros((), jnp.float32)
        load = None
        getq = (lambda k: None) if q is None else q.get
        if self.is_ssm:
            h = _norm(c).apply(bparams["ln"], x)
            x = x + self._mamba(f"{name}/mamba").apply(
                bparams["mamba"], h, policy, q=getq("mamba"))
            return (x, aux, load) if collect_load else (x, aux)
        h = _norm(c).apply(bparams["ln1"], x)
        h = self._attend(self._attention(f"{name}/attn"), "apply",
                         bparams["attn"], h, lp, positions=positions,
                         policy=policy, q=getq("attn"))
        if c.post_norms:
            h = _norm(c).apply(bparams["ln1_post"], h)
        x = x + h
        h = _norm(c).apply(bparams["ln2"], x)
        h, metrics = self._ffn(bparams["ffn"], h, policy, name,
                               q=getq("ffn"))
        if metrics is not None:
            aux = aux + metrics["moe_aux_loss"]
            load = metrics["expert_load"]
        if c.post_norms:
            h = _norm(c).apply(bparams["ln2_post"], h)
        return (x + h, aux, load) if collect_load else (x + h, aux)

    def _remat(self, fn):
        c = self.cfg
        if c.remat == "none":
            return fn
        if c.remat == "dots":
            pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
            return jax.checkpoint(fn, policy=pol)
        return jax.checkpoint(fn)

    def _run_blocks(self, params, x, positions, policy, q=None):
        c = self.cfg
        check_scan_compatible(policy, c.scan_layers, c.name)
        lps = self.layer_params()
        aux0 = jnp.zeros((), jnp.float32)
        if c.scan_layers:
            def body(carry, xs):
                xc, aux = carry
                if q is None:
                    bp, lp = xs
                    qs = None
                else:
                    bp, lp, qs = xs
                xc, a = self._block_apply(bp, xc, positions, lp, policy, qs)
                return (xc, aux + a), None

            body = self._remat(body)
            xs = (params["blocks"], lps)
            if q is not None:
                xs = xs + (q["blocks"],)
            (x, aux), _ = jax.lax.scan(body, (x, aux0), xs)
            return x, aux
        aux = aux0
        block_fn_w = None
        if c.remat != "none":
            pol = (jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
                   if c.remat == "dots" else None)
            # name is static (site addressing must survive remat — a
            # layer-indexed PolicyMap resolves per block here too)
            block_fn_w = jax.checkpoint(
                lambda name, bp, xc, lp, qi: self._block_apply(
                    bp, xc, positions, lp, policy, qi, name=name),
                policy=pol, static_argnums=(0,))
        for i, bp in enumerate(params["blocks"]):
            qi = None if q is None else q["blocks"][i]
            lp = self._layer(lps, i)
            if c.remat != "none":
                x, a = block_fn_w(f"blocks.{i}", bp, x, lp, qi)
            else:
                x, a = self._block_apply(bp, x, positions, lp, policy, qi,
                                         name=f"blocks.{i}")
            aux = aux + a
        return x, aux

    # -------------------------------------------------------- routing probe
    def expert_loads(self, params, tokens, *,
                     policy: Policy = QuantPolicy()) -> jnp.ndarray:
        """Routed-token counts per expert: ``(n_layers, n_experts)`` f32.

        A routing-frequency probe for the serve-side expert store
        (``repro.serve.experts``): runs the block stack forward and
        collects each MoE block's ``expert_load`` (routing is dropless,
        so each layer's loads sum to ``top_k`` x the tokens).  Works under
        scan (loads stack as scan ys) and unrolled; ``tokens`` is
        ``(B, S)`` and loads sum over the whole batch.
        """
        c = self.cfg
        if not self.is_moe:
            raise TypeError(
                f"expert_loads: {c.name!r} is not an MoE config")
        check_scan_compatible(policy, c.scan_layers, c.name)
        x, positions = self._embed_in(params, tokens)
        lps = self.layer_params()
        if c.scan_layers:
            def body(xc, xs):
                bp, lp = xs
                xn, _, load = self._block_apply(bp, xc, positions, lp,
                                                policy, collect_load=True)
                return xn, load

            _, loads = jax.lax.scan(body, x, (params["blocks"], lps))
            return loads
        loads = []
        for i, bp in enumerate(params["blocks"]):
            x, _, load = self._block_apply(bp, x, positions,
                                           self._layer(lps, i), policy,
                                           name=f"blocks.{i}",
                                           collect_load=True)
            loads.append(load)
        return jnp.stack(loads, axis=0)

    # ------------------------------------------------------------- embed in
    def _embed_in(self, params, tokens, prefix_embeds=None, pos_offset=0):
        c = self.cfg
        x = Embed(c.vocab_padded, c.d_model, param_dtype=c.param_dtype,
                  dtype=c.dtype).apply(params["embed"], tokens)
        if c.norm_plus_one:  # gemma convention: scale embeddings by sqrt(d)
            x = x * jnp.asarray(c.d_model**0.5, x.dtype)
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        B, S = x.shape[0], x.shape[1]
        po = jnp.asarray(pos_offset, jnp.int32)
        if po.ndim == 1:  # per-row offsets (continuous-batching decode)
            po = po[:, None]
        positions = po + jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None], (B, S)
        )
        if c.pos == "learned":
            pe = jnp.take(params["pos_embed"], positions, axis=0)  # (B,S,d)
            x = x + pe.astype(x.dtype)
        elif c.pos == "sinusoidal":
            x = x + _sinusoid_at(positions, c.d_model).astype(x.dtype)
        return shd.constrain(x, ("batch", "seq_res", "embed")), positions

    # ----------------------------------------------------------------- head
    def head_logits(self, params, x, policy):
        c = self.cfg
        if c.tied_embeddings:
            logits = Embed(c.vocab_padded, c.d_model,
                           param_dtype=c.param_dtype, dtype=c.dtype).attend(
                params["embed"], x, policy)
        else:
            logits = Dense(
                c.d_model, c.vocab_padded, in_axis="embed", out_axis="vocab",
                param_dtype=c.param_dtype, dtype=c.dtype, name="lm_head",
            ).apply(params["lm_head"], x, policy)
        if c.final_softcap:
            logits = c.final_softcap * jnp.tanh(logits / c.final_softcap)
        if c.vocab_padded != c.vocab:
            pad_mask = jnp.arange(c.vocab_padded) >= c.vocab
            logits = jnp.where(pad_mask, NEG_INF, logits)
        return logits

    # ---------------------------------------------------------------- apply
    def apply(self, params, tokens, *, policy=QuantPolicy(), q=None,
              prefix_embeds=None, return_hidden: bool = False):
        x, positions = self._embed_in(params, tokens, prefix_embeds)
        x, aux = self._run_blocks(params, x, positions, policy, q)
        x = _norm(self.cfg).apply(params["final_norm"], x)
        if return_hidden:
            return x, aux
        logits = self.head_logits(params, x, policy)
        return logits, aux

    # -------------------------------------------------------------- prefill
    def prefill(self, params, tokens, *, policy=QuantPolicy(),
                max_len: int | None = None, prefix_embeds=None,
                n_valid=None):
        """Forward pass that also builds decode caches.

        Returns (last-position logits (B, vocab_padded), DecodeState).

        ``n_valid`` ((B,) int32) supports bucketed prefill: ``tokens`` is
        right-padded to a bucket length, K/V cache rows past each row's
        valid length are zeroed (see ``Attention.apply``) and the logits
        are taken at position ``n_valid - 1`` instead of the last column —
        token-identical to an exact-length prefill, at a bounded number of
        compile shapes.  Attention-family models only: SSM state is
        recurrent over the padded tail, so bucketing would corrupt it.
        """
        c = self.cfg
        check_scan_compatible(policy, c.scan_layers, c.name)
        kv_cache_mode(policy)  # cache storage is engine-global: reject
        # maps whose rules disagree on it with a clear error here, not a
        # pytree-mismatch crash when the per-layer caches get stacked
        if n_valid is not None:
            if self.is_ssm:
                raise ValueError(
                    "bucketed prefill (n_valid) is attention-family only: "
                    "SSM recurrence integrates the padded tail into the "
                    "state; prefill SSM models at exact length")
            n_valid = jnp.asarray(n_valid, jnp.int32)
        x, positions = self._embed_in(params, tokens, prefix_embeds)
        B, S = x.shape[0], x.shape[1]
        max_len = max_len or S
        lps = self.layer_params()
        eff_window = c.uniform_window
        cache_size = max_len if eff_window is None \
            else min(max_len, eff_window)
        valid = None if n_valid is None else (
            jnp.arange(S, dtype=jnp.int32)[None] < n_valid[:, None])

        if self.is_ssm:
            def body(carry, xs, name="block"):
                xc = carry
                bp = xs
                h = _norm(c).apply(bp["ln"], xc)
                h, cache = self._mamba(f"{name}/mamba").apply(
                    bp["mamba"], h, policy, return_cache=True)
                return xc + h, cache

            if c.scan_layers:
                x, ssm = jax.lax.scan(body, x, params["blocks"])
            else:
                caches = []
                for i, bp in enumerate(params["blocks"]):
                    x, cc = body(x, bp, name=f"blocks.{i}")
                    caches.append(cc)
                ssm = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *caches)
            state = DecodeState(kv=None, ssm=ssm,
                                position=jnp.asarray(S, jnp.int32))
        else:
            def body(carry, xs, name="block"):
                xc = carry
                bp, lp = xs
                attn_l = self._attention(f"{name}/attn")
                h = _norm(c).apply(bp["ln1"], xc)
                h, (kf, vf) = self._attend(
                    attn_l, "apply", bp["attn"], h, lp, positions=positions,
                    policy=policy, return_kv=True, n_valid=n_valid,
                )
                cache = attn_l.fill_cache(kf, vf, cache_size, policy=policy)
                if c.post_norms:
                    h = _norm(c).apply(bp["ln1_post"], h)
                xc = xc + h
                h = _norm(c).apply(bp["ln2"], xc)
                h, _ = self._ffn(bp["ffn"], h, policy, name, valid=valid)
                if c.post_norms:
                    h = _norm(c).apply(bp["ln2_post"], h)
                return xc + h, cache

            if c.scan_layers:
                x, kv = jax.lax.scan(body, x, (params["blocks"], lps))
            else:
                caches = []
                for i, bp in enumerate(params["blocks"]):
                    x, cc = body(x, (bp, self._layer(lps, i)),
                                 name=f"blocks.{i}")
                    caches.append(cc)
                kv = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *caches)
            pos = jnp.asarray(S, jnp.int32) if n_valid is None else n_valid
            state = DecodeState(kv=kv, ssm=None, position=pos)

        if n_valid is None:
            x = x[:, -1:, :]
        else:  # last VALID position per row, not the padded column
            sel = jnp.maximum(n_valid - 1, 0)[:, None, None]
            x = jnp.take_along_axis(x, jnp.broadcast_to(
                sel, (B, 1, x.shape[-1])), axis=1)
        x = _norm(c).apply(params["final_norm"], x)
        logits = self.head_logits(params, x, policy)
        return logits[:, 0], state

    # --------------------------------------------------------------- decode
    def init_decode_state(self, batch: int, max_len: int,
                          kv_quant: bool = False) -> DecodeState:
        c = self.cfg
        L = c.n_layers
        kv = ssm = None
        if self.is_ssm:
            one = self._mamba().init_cache(batch, dtype=c.dtype)
            ssm = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), one
            )
        else:
            attn = self._attention()
            # all layers share the ring-buffer size policy: SWA truncates
            eff_window = c.uniform_window
            one = attn.init_cache(batch, max_len, dtype=c.dtype,
                                  window=eff_window, quantized=kv_quant)
            kv = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), one
            )
        return DecodeState(kv=kv, ssm=ssm,
                           position=jnp.zeros((), jnp.int32))

    def decode_step(self, params, token, state: DecodeState, *,
                    policy=QuantPolicy(), q=None):
        """token: (B, 1) -> (logits (B, vocab_padded), new state)."""
        c = self.cfg
        check_scan_compatible(policy, c.scan_layers, c.name)
        x, _ = self._embed_in(params, token, pos_offset=state.position)
        pos = state.position
        lps = self.layer_params()

        if self.is_ssm:
            def body(xc, xs, name="block"):
                bp, cache = xs
                h = _norm(c).apply(bp["ln"], xc)
                h, cache = self._mamba(f"{name}/mamba").decode_step(
                    bp["mamba"], h, cache, policy=policy)
                return xc + h, cache

            if c.scan_layers:
                x, new_ssm = jax.lax.scan(body, x, (params["blocks"],
                                                    state.ssm))
            else:
                caches = []
                for i, bp in enumerate(params["blocks"]):
                    ci = jax.tree_util.tree_map(lambda a: a[i], state.ssm)
                    x, cnew = body(x, (bp, ci), name=f"blocks.{i}")
                    caches.append(cnew)
                new_ssm = jax.tree_util.tree_map(
                    lambda *a: jnp.stack(a), *caches)
            new_state = DecodeState(kv=None, ssm=new_ssm, position=pos + 1)
        else:
            def body(xc, xs, name="block"):
                bp, cache, lp = xs
                h = _norm(c).apply(bp["ln1"], xc)
                attn = self._attention(f"{name}/attn")
                h, cache = self._attend(
                    attn, "decode_step", bp["attn"], h, lp, cache=cache,
                    position=pos, policy=policy,
                )
                if c.post_norms:
                    h = _norm(c).apply(bp["ln1_post"], h)
                xc = xc + h
                h = _norm(c).apply(bp["ln2"], xc)
                h, _ = self._ffn(bp["ffn"], h, policy, name)
                if c.post_norms:
                    h = _norm(c).apply(bp["ln2_post"], h)
                return xc + h, cache

            if c.scan_layers:
                def scan_body(xc, xs):
                    return body(xc, xs)
                x, new_kv = jax.lax.scan(
                    scan_body, x, (params["blocks"], state.kv, lps))
            else:
                caches = []
                for i, bp in enumerate(params["blocks"]):
                    ci = jax.tree_util.tree_map(lambda a: a[i], state.kv)
                    ci = KVCache(*ci)
                    x, cnew = body(x, (bp, ci, self._layer(lps, i)),
                                   name=f"blocks.{i}")
                    caches.append(cnew)
                new_kv = jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                                *caches)
            new_state = DecodeState(kv=new_kv, ssm=None, position=pos + 1)

        x = _norm(c).apply(params["final_norm"], x)
        logits = self.head_logits(params, x, policy)
        return logits[:, 0], new_state

    def chunk_step(self, params, tokens, state: DecodeState, *,
                   n_valid, policy=QuantPolicy(), q=None):
        """Score a (B, S) token chunk against the fixed-slot KV cache.

        The speculative verify pass: equivalent to S sequential
        ``decode_step`` calls under teacher forcing, but ONE jit shape and
        one pass, returning logits at EVERY chunk position (B, S, vocab).
        Rows score their first ``n_valid`` tokens; ``n_valid = 0`` masks a
        row entirely.  ``position`` advances by ``n_valid`` per row — the
        caller rolls back a rejected suffix by resetting positions, which
        the ring-buffer validity mask honors without any cache surgery.
        Attention-family models only: SSM recurrent state cannot rewind.
        """
        c = self.cfg
        check_scan_compatible(policy, c.scan_layers, c.name)
        if self.is_ssm:
            raise TypeError(
                "chunk_step is attention-family only; SSM recurrent state "
                f"cannot roll back a rejected draft suffix ({c.name})")
        n_valid = jnp.asarray(n_valid, jnp.int32)
        pos = jnp.asarray(state.position, jnp.int32)
        x, _ = self._embed_in(params, tokens, pos_offset=pos)
        lps = self.layer_params()
        valid = (jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
                 < n_valid[:, None])

        def body(xc, xs, name="block"):
            bp, cache, lp = xs
            h = _norm(c).apply(bp["ln1"], xc)
            attn = self._attention(f"{name}/attn")
            h, cache = self._attend(
                attn, "chunk_step", bp["attn"], h, lp, cache=cache,
                position=pos, n_valid=n_valid, policy=policy,
            )
            if c.post_norms:
                h = _norm(c).apply(bp["ln1_post"], h)
            xc = xc + h
            h = _norm(c).apply(bp["ln2"], xc)
            h, _ = self._ffn(bp["ffn"], h, policy, name, valid=valid)
            if c.post_norms:
                h = _norm(c).apply(bp["ln2_post"], h)
            return xc + h, cache

        if c.scan_layers:
            x, new_kv = jax.lax.scan(
                body, x, (params["blocks"], state.kv, lps))
        else:
            caches = []
            for i, bp in enumerate(params["blocks"]):
                ci = jax.tree_util.tree_map(lambda a: a[i], state.kv)
                ci = KVCache(*ci)
                x, cnew = body(x, (bp, ci, self._layer(lps, i)),
                               name=f"blocks.{i}")
                caches.append(cnew)
            new_kv = jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                            *caches)
        new_state = DecodeState(kv=new_kv, ssm=None, position=pos + n_valid)
        x = _norm(c).apply(params["final_norm"], x)
        logits = self.head_logits(params, x, policy)  # (B, S, vocab_padded)
        return logits, new_state

    # ---------------------------------------------------------- paged decode
    def init_paged_state(self, batch: int, *, page_size: int, n_pages: int,
                         max_pages_per_seq: int,
                         kv: str = "fp") -> DecodeState:
        """Paged serving state: one physical page pool per layer plus the
        per-slot page table (all -1 = nothing mapped), per-row positions.

        ``kv``: page storage — 'fp' (native dtype), 'int8' or 'fp8' codes
        with per-(page, head) scales.  Attention-family models only.
        """
        c = self.cfg
        if self.is_ssm:
            raise TypeError(
                "paged KV serving is attention-family only; SSM state is "
                f"O(1) per sequence and needs no pages ({c.name})")
        L = c.n_layers
        one = self._attention().init_paged_cache(n_pages, page_size,
                                                 dtype=c.dtype, kv=kv)
        cache = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), one
        )
        table = jnp.full((batch, max_pages_per_seq), -1, jnp.int32)
        stats = ({k: jnp.zeros((), jnp.int32) for k in COUNT_KEYS}
                 if self.is_moe else {})
        return DecodeState(
            kv=None, ssm=None,
            position=jnp.zeros((batch,), jnp.int32),
            pages=PagedState(cache=cache, table=table), stats=stats,
        )

    def paged_read_unit(self, policy, kv: str, page_size: int):
        """Pages a decode step reads at a time from each row's live
        context where it reads them in place (``Attention.paged_step``);
        None where it gathers the whole page table."""
        name = "block/attn" if self.cfg.scan_layers else "blocks.0/attn"
        return self._attention(name).paged_read_unit(policy, kv, page_size)

    def paged_step(self, params, tokens, state: DecodeState, *,
                   n_valid, policy=QuantPolicy(), q=None,
                   all_logits: bool = False):
        """One paged serving step over a (B, S) token chunk.

        S = 1 is a decode tick over every slot; S = chunk is one chunked-
        prefill tile for a prefilling slot (other rows masked with
        ``n_valid = 0``).  Writes the chunk's K/V into the pages mapped by
        ``state.pages.table``, attends over each row's gathered pages and
        returns (logits at each row's last valid token, new state) with
        ``position`` advanced by ``n_valid``, and the step's routing
        counts as its ``stats``.  Tokens past a row's ``n_valid`` route to
        no expert.

        ``all_logits``: return logits at EVERY chunk position (B, S,
        vocab) instead of the last valid one — the speculative verify
        pass scores all k+1 draft positions from one call.
        """
        c = self.cfg
        check_scan_compatible(policy, c.scan_layers, c.name)
        if state.pages is None:
            raise TypeError("paged_step needs a DecodeState from "
                            "init_paged_state (state.pages is None)")
        n_valid = jnp.asarray(n_valid, jnp.int32)
        pos = jnp.asarray(state.position, jnp.int32)
        table = state.pages.table
        x, _ = self._embed_in(params, tokens, pos_offset=pos)
        B, S = tokens.shape[0], tokens.shape[1]
        lps = self.layer_params()
        valid = jnp.arange(S, dtype=jnp.int32)[None] < n_valid[:, None]

        # the layers carry the stacked pool whole: each writes its tokens
        # in place at [layer, page, offset] and reads its pages there
        def body(carry, xs, name="block"):
            xc, cache = carry
            bp, lp, layer = xs
            h = _norm(c).apply(bp["ln1"], xc)
            attn = self._attention(f"{name}/attn")
            h, cache = self._attend(
                attn, "paged_step", bp["attn"], h, lp, cache=cache,
                layer=layer, page_table=table, position=pos,
                n_valid=n_valid, policy=policy,
            )
            if c.post_norms:
                h = _norm(c).apply(bp["ln1_post"], h)
            xc = xc + h
            h = _norm(c).apply(bp["ln2"], xc)
            h, metrics = self._ffn(bp["ffn"], h, policy, name, valid=valid)
            if c.post_norms:
                h = _norm(c).apply(bp["ln2_post"], h)
            return (xc + h, cache), ({} if metrics is None
                                     else metrics["counts"])

        carry = (x, state.pages.cache)
        if c.scan_layers:
            layers = jnp.arange(c.n_layers, dtype=jnp.int32)
            carry, layer_counts = jax.lax.scan(
                body, carry, (params["blocks"], lps, layers))
        else:
            outs = []
            for i, bp in enumerate(params["blocks"]):
                carry, out = body(carry, (bp, self._layer(lps, i), i),
                                  name=f"blocks.{i}")
                outs.append(out)
            layer_counts = jax.tree_util.tree_map(
                lambda *a: jnp.stack(a), *outs)
        x, new_cache = carry

        new_state = DecodeState(
            kv=None, ssm=None, position=pos + n_valid,
            pages=PagedState(cache=new_cache, table=table),
            stats=jax.tree_util.tree_map(lambda a: a.sum(0), layer_counts),
        )
        if all_logits:
            x = _norm(c).apply(params["final_norm"], x)
            with jax.named_scope("readout"):
                logits = self.head_logits(params, x, policy)
        else:
            sel = jnp.maximum(n_valid - 1, 0)[:, None, None]
            x = jnp.take_along_axis(
                x, jnp.broadcast_to(sel, (B, 1, x.shape[-1])), axis=1)
            x = _norm(c).apply(params["final_norm"], x)
            with jax.named_scope("readout"):
                logits = self.head_logits(params, x, policy)[:, 0]
        return logits, new_state


def _sinusoid(S: int, d: int, offset=0) -> jnp.ndarray:
    pos = offset + jnp.arange(S, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None]
    angle = pos / jnp.power(10000.0, dim / d)
    out = jnp.zeros((S, d), jnp.float32)
    out = out.at[:, 0::2].set(jnp.sin(angle))
    out = out.at[:, 1::2].set(jnp.cos(angle))
    return out


def _sinusoid_at(positions: jnp.ndarray, d: int) -> jnp.ndarray:
    """Sinusoidal embeddings for explicit (B, S) positions -> (B, S, d)."""
    pos = positions.astype(jnp.float32)[..., None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)
    angle = pos / jnp.power(10000.0, dim / d)  # (B, S, d/2)
    out = jnp.zeros(positions.shape + (d,), jnp.float32)
    out = out.at[..., 0::2].set(jnp.sin(angle))
    out = out.at[..., 1::2].set(jnp.cos(angle))
    return out


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------
def cross_entropy(logits, labels, vocab: int):
    """Mean CE over tokens; labels == -1 are masked."""
    mask = labels >= 0
    labels = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[..., None], axis=-1
    )[..., 0]
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1)


def chunked_lm_loss(model: TransformerLM, params, hidden, labels, policy,
                    chunk: int):
    """CE over seq chunks so (S, vocab) logits never materialize."""
    from repro.dist import sharding as _shd

    hidden = _shd.constrain(hidden, ("batch", "seq", "embed"))
    B, S, D = hidden.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    h = hidden.reshape(B, nc, chunk, D).swapaxes(0, 1)
    y = labels.reshape(B, nc, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def body(carry, xs):
        hc, yc = xs
        logits = model.head_logits(params, hc, policy)
        mask = yc >= 0
        lab = jnp.maximum(yc, 0)
        logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        gold = jnp.take_along_axis(
            logits.astype(jnp.float32), lab[..., None], axis=-1
        )[..., 0]
        nll, cnt = carry
        return (nll + ((logz - gold) * mask).sum(),
                cnt + mask.sum()), None

    (nll, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)), (h, y)
    )
    return nll / jnp.maximum(cnt, 1)
