"""Uniform model facade over the families (dense/moe/ssm LM, hybrid,
enc-dec, VLM) so the launcher / trainer / server see one interface:

    model = build_model(cfg)
    params = model.init(key)                    # Box tree (values + axes)
    loss   = model.loss(params, batch, policy)  # batch: dict of arrays
    logits, state = model.prefill(params, batch, policy, max_len)
    logits, state = model.decode_step(params, token, state, policy)
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.policy import QuantPolicy
from repro.models import lm as lm_mod
from repro.models.encdec import EncDecLM
from repro.models.hybrid import HybridLM
from repro.models.lm import TransformerLM, chunked_lm_loss, cross_entropy


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    inner: Any

    # ------------------------------------------------------------------ api
    def init(self, key):
        return self.inner.init(key)

    def _split_batch(self, batch):
        tokens = batch["tokens"]
        kw = {}
        if self.cfg.family == "encdec":
            kw["frames"] = batch["frames"]
        if self.cfg.family == "vlm":
            kw["prefix_embeds"] = batch["patch_embeds"]
        return tokens, kw

    def apply(self, params, batch, policy=QuantPolicy(), q=None,
              return_hidden=False):
        tokens, kw = self._split_batch(batch)
        if self.cfg.family == "vlm":
            return self.inner.apply(
                params, tokens, policy=policy, q=q,
                prefix_embeds=kw["prefix_embeds"],
                return_hidden=return_hidden)
        return self.inner.apply(params, tokens, policy=policy, q=q,
                                return_hidden=return_hidden, **kw)

    def loss(self, params, batch, policy=QuantPolicy(), q=None):
        """Next-token CE (+ MoE aux).  Labels: batch['labels'], -1 masked."""
        c = self.cfg
        labels = batch["labels"]
        if (
            c.logits_chunk > 0
            and isinstance(self.inner, TransformerLM)
        ):
            hidden, aux = self.apply(params, batch, policy, q,
                                     return_hidden=True)
            if c.family == "vlm":
                np_ = batch["patch_embeds"].shape[1]
                hidden = hidden[:, np_:, :]
            ce = chunked_lm_loss(self.inner, params, hidden, labels, policy,
                                 c.logits_chunk)
        else:
            logits, aux = self.apply(params, batch, policy, q)
            if c.family == "vlm":
                np_ = batch["patch_embeds"].shape[1]
                logits = logits[:, np_:, :]
            ce = cross_entropy(logits, labels, c.vocab)
        return ce + 0.01 * aux, {"ce": ce, "aux": aux}

    def prefill(self, params, batch, policy=QuantPolicy(),
                max_len: int | None = None, n_valid=None):
        tokens, kw = self._split_batch(batch)
        if n_valid is not None:  # bucketed prefill (TransformerLM family)
            kw["n_valid"] = n_valid
        if self.cfg.family == "vlm":
            return self.inner.prefill(
                params, tokens, policy=policy, max_len=max_len,
                prefix_embeds=kw.pop("prefix_embeds", None), **kw)
        return self.inner.prefill(params, tokens, policy=policy,
                                  max_len=max_len, **kw)

    @property
    def is_moe(self) -> bool:
        return getattr(self.inner, "is_moe", False)

    def expert_loads(self, params, tokens, *, policy=QuantPolicy()):
        """Routing-frequency probe: (n_layers, n_experts) routed-token
        counts (MoE TransformerLM family only; raises TypeError else)."""
        return self.inner.expert_loads(params, tokens, policy=policy)

    def decode_step(self, params, token, state, policy=QuantPolicy()):
        return self.inner.decode_step(params, token, state, policy=policy)

    def chunk_step(self, params, tokens, state, *, n_valid,
                   policy=QuantPolicy()):
        """All-position scoring of a token chunk (speculative verify)."""
        return self.inner.chunk_step(params, tokens, state,
                                     n_valid=n_valid, policy=policy)

    def init_decode_state(self, batch: int, max_len: int, **kw):
        return self.inner.init_decode_state(batch, max_len, **kw)

    def init_paged_state(self, batch: int, **kw):
        """Paged-KV serving state (TransformerLM family only)."""
        return self.inner.init_paged_state(batch, **kw)

    def paged_step(self, params, tokens, state, *, n_valid,
                   policy=QuantPolicy(), all_logits: bool = False):
        return self.inner.paged_step(params, tokens, state,
                                     n_valid=n_valid, policy=policy,
                                     all_logits=all_logits)

    def paged_read_unit(self, policy, kv: str, page_size: int):
        return self.inner.paged_read_unit(policy, kv, page_size)


def build_model(cfg: ArchConfig):
    if cfg.family == "vit":
        from repro.models.vit import VisionTransformer, VitModel

        return VitModel(cfg, VisionTransformer(cfg))
    if cfg.family == "hybrid":
        return Model(cfg, HybridLM(cfg))
    if cfg.family == "encdec":
        return Model(cfg, EncDecLM(cfg))
    # dense / moe / ssm / vlm all ride on TransformerLM
    return Model(cfg, TransformerLM(cfg))
