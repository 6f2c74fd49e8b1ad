"""Mellum2: a decoder whose every layer is sparse, with sliding and full
attention layers, the full ones under YaRN.

The architecture module of every configuration that names ``"plain":
"mellum2"``; ``bench/harness.py`` loads it by path.  It gives
``arch_config``, ``weights_fn``, ``program_params``, ``Reference`` and
``work_counter``, as ``bench/plain/decoder.py`` describes them, and
``expert_bytes`` for the expert kernel's roofline.

The configuration file holds the published ``config.json``'s keys as
published, with ``num_hidden_layers`` cut; the layers kept are the first
``num_hidden_layers`` of ``layer_types``.

The reference follows the published description (JetBrains'
Mellum2-12B-A2.5B ``config.json`` and the ``transformers`` code it names):
RMSNorm before attention and before the experts; q, k, v projections
without bias; rotary embedding over the two halves of each head, whose
frequencies and factor depend on the layer's type (``rope_parameters``):
default RoPE at ``rope_theta`` on sliding layers, YaRN on full layers
(each frequency blended between ``1 / (factor * base^(2i/d))`` and
``1 / base^(2i/d)`` by a linear ramp over the dimensions between
``beta_fast`` and ``beta_slow`` rotations of
``original_max_position_embeddings``, cos and sin both times
``attention_factor``); causal grouped-query attention, query head ``h``
reading KV head ``h // (H / KV)``, scale ``head_dim ** -0.5``, and on
sliding layers only keys less than ``sliding_window`` positions back; the
router's softmax over all experts in f32, the top ``num_experts_per_tok``
experts, their weights divided by their sum (``norm_topk_prob``), and
each chosen expert's ``down(silu(gate(x)) * up(x))`` times its weight,
summed; no shared expert; a final RMSNorm and an untied readout.  It runs
one request at a time, layer by layer, the sequence padded to a power of
two of at least ``block`` rows, attention ``block`` query rows at a time
and the experts one at a time over every row (an expert a row did not
choose adds zero), so that 2k positions fit beside the weights.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import _int4_grid, _norm_grid, _uniform_grid, padded_vocab
from bench.reference import HIGHEST, NEG, Judge, _mm, _rms
from bench.work import Need

KINDS = {"sliding_attention": "sliding", "full_attention": "full"}
EXPERT_KERNEL = "quant_matmul_grouped"  # the trace's op of the experts


def _layer_types(conf: dict) -> list[str]:
    return conf["layer_types"][:conf["num_hidden_layers"]]


def _check(conf: dict) -> None:
    """What the program and this module take as given."""
    need = {"rms_norm_eps": 1e-6, "attention_bias": False,
            "hidden_act": "silu", "tie_word_embeddings": False}
    bad = {k: conf[k] for k, v in need.items() if conf[k] != v}
    if bad:
        raise ValueError(f"mellum2 serves {need}; the file has {bad}")
    if set(conf["mlp_layer_types"][:conf["num_hidden_layers"]]) \
            != {"sparse"}:
        raise ValueError("every layer kept must be sparse")
    kinds = _layer_types(conf)
    L = len(kinds)
    if L < 1 or any(kinds[i] != kinds[i % 4] for i in range(L)) \
            or set(kinds) - set(KINDS):
        raise ValueError(f"layer types {kinds} are not the published period")
    rp = conf["rope_parameters"]
    if (rp["sliding_attention"]["rope_type"] != "default"
            or rp["full_attention"]["rope_type"] != "yarn"):
        raise ValueError("sliding layers take default RoPE, full ones YaRN")


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file's sizes."""
    from repro.configs import get_config
    from repro.configs.base import Yarn

    _check(conf)
    rp = conf["rope_parameters"]
    full, sliding = rp["full_attention"], rp["sliding_attention"]
    if full["rope_theta"] != sliding["rope_theta"]:
        raise ValueError("the program takes one rope_theta for both kinds")
    kinds = tuple(KINDS[k] for k in _layer_types(conf))
    return get_config(conf["arch"]).replace(
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["moe_intermediate_size"], vocab=conf["vocab_size"],
        n_experts=conf["num_experts"], top_k=conf["num_experts_per_tok"],
        norm_topk_prob=conf["norm_topk_prob"], tied_embeddings=False,
        qkv_bias=False, rope_theta=float(sliding["rope_theta"]),
        window=conf["sliding_window"], layer_types=kinds[:4],
        yarn=Yarn(factor=float(full["factor"]),
                  original_max_position=full[
                      "original_max_position_embeddings"],
                  beta_fast=float(full["beta_fast"]),
                  beta_slow=float(full["beta_slow"]),
                  attention_factor=float(full["attention_factor"])),
        act="swiglu", norm="rms", scan_layers=True, remat="none",
        dtype="float32", param_dtype="float32")


# -- weights ---------------------------------------------------------------

def weights_fn(conf: dict):
    """PRNG key -> the plain weight tree, stacked over layers.

    Keys: ``embed`` (V, D), ``final_norm`` (D,), ``lm_head`` (D, V), and
    per layer ``ln1``, ``ln2`` (L, D), ``wq`` (L, D, H*hd), ``wk``, ``wv``
    (L, D, KV*hd), ``wo`` (L, H*hd, D), ``router`` (L, D, E), ``w_gate``,
    ``w_up`` (L, E, D, F) and ``w_down`` (L, E, F, D).  Every projection
    and expert lies on the INT4 grid (``bench.model``); the router is f32,
    as served.  Layers are made one at a time (``lax.map``).
    """
    _check(conf)
    L, D, F = (conf["num_hidden_layers"], conf["hidden_size"],
               conf["moe_intermediate_size"])
    H, KV, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    E, V = conf["num_experts"], conf["vocab_size"]
    Vp, g = padded_vocab(V), conf["group"]

    def layer(key):
        k = iter(jax.random.split(key, 12))
        return {"ln1": _norm_grid(next(k), (D,)),
                "ln2": _norm_grid(next(k), (D,)),
                "wq": _int4_grid(next(k), (D, H * hd), g),
                "wk": _int4_grid(next(k), (D, KV * hd), g),
                "wv": _int4_grid(next(k), (D, KV * hd), g),
                "wo": _int4_grid(next(k), (H * hd, D), g),
                "router": _uniform_grid(next(k), (D, E), D ** -0.5),
                "w_gate": _int4_grid(next(k), (E, D, F), g),
                "w_up": _int4_grid(next(k), (E, D, F), g),
                "w_down": _int4_grid(next(k), (E, F, D), g)}

    def make(key):
        k_embed, k_norm, k_head, k_layers = jax.random.split(key, 4)
        w = jax.lax.map(layer, jax.random.split(k_layers, L))
        w["embed"] = jnp.where(jnp.arange(Vp)[:, None] < V,
                               _uniform_grid(k_embed, (Vp, D), 0.02), 0.0)
        w["final_norm"] = _norm_grid(k_norm, (D,))
        w["lm_head"] = jnp.where(jnp.arange(Vp)[None] < V,
                                 _int4_grid(k_head, (D, Vp), g), 0.0)
        return w

    return make


def program_params(w: dict) -> dict:
    """The plain tree under the names the program's ``TransformerLM``
    uses for a sparse decoder."""
    kernel = lambda k: {"kernel": w[k]}  # noqa: E731
    return {
        "embed": {"table": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "lm_head": kernel("lm_head"),
        "blocks": {
            "ln1": {"scale": w["ln1"]}, "ln2": {"scale": w["ln2"]},
            "attn": {"q": kernel("wq"), "k": kernel("wk"),
                     "v": kernel("wv"), "o": kernel("wo")},
            "ffn": {"router": w["router"], "wi": w["w_up"],
                    "wg": w["w_gate"], "wo": w["w_down"]},
        },
    }


# -- the plain reference ---------------------------------------------------

def rope_of(conf: dict, kind: str) -> tuple[np.ndarray, float]:
    """A layer kind's inverse frequencies (head_dim/2,) and the factor on
    cos and sin, as ``rope_parameters`` gives them."""
    p = conf["rope_parameters"][kind]
    hd, base = conf["head_dim"], float(p["rope_theta"])
    pos_freqs = base ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    if p["rope_type"] == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    factor, orig = float(p["factor"]), p["original_max_position_embeddings"]

    def dim(rotations):  # the dimension that turns ``rotations`` times
        return hd * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim(p["beta_fast"])), 0)
    high = min(math.ceil(dim(p["beta_slow"])), hd - 1)
    ramp = np.clip((np.arange(hd // 2) - low) / max(high - low, 1e-3), 0, 1)
    keep = 1 - ramp  # the share of each frequency left unscaled
    inv = (1 / (factor * pos_freqs)) * (1 - keep) + (1 / pos_freqs) * keep
    return inv.astype(np.float32), float(p["attention_factor"])


def _rope(x, pos, inv_freq, factor):
    ang = pos[:, None, None].astype(jnp.float32) * inv_freq
    sin, cos = jnp.sin(ang) * factor, jnp.cos(ang) * factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit,
                   static_argnames=("m", "window", "precision", "block"))
def _layer(x, lw, inv_freq, factor, *, m: tuple, window: int,
           precision: str, block: int):
    """One layer over ``x`` (S, D), S a multiple of ``block``; ``window``
    0 is a full layer."""
    H, KV, hd, k_top, renorm = m
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, lw["ln1"], precision)
    q = _rope(_mm(h, lw["wq"], precision).reshape(S, H, hd), pos,
              inv_freq, factor)
    k = _rope(_mm(h, lw["wk"], precision).reshape(S, KV, hd), pos,
              inv_freq, factor)
    v = _mm(h, lw["wv"], precision).reshape(S, KV, hd)
    g = H // KV

    def attend(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        qb = qb.reshape(block, KV, g, hd)
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=HIGHEST) \
            * hd ** -0.5
        qpos = (i * block + jnp.arange(block))[None, None, :, None]
        kpos = pos[None, None, None, :]
        seen = kpos <= qpos
        if window:
            seen &= kpos > qpos - window
        p = jax.nn.softmax(jnp.where(seen, s, NEG), axis=-1)
        o = jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST)
        return o.reshape(block, H * hd)

    att = jax.lax.map(attend, jnp.arange(S // block)).reshape(S, H * hd)
    x = x + _mm(att, lw["wo"], precision)
    h = _rms(x, lw["ln2"], precision).astype(jnp.float32)
    probs = jax.nn.softmax(jnp.dot(h, lw["router"], precision=HIGHEST), -1)
    top, idx = jax.lax.top_k(probs, k_top)
    if renorm:
        top = top / top.sum(-1, keepdims=True)
    weight = (jax.nn.one_hot(idx, probs.shape[-1]) * top[..., None]).sum(1)

    def expert(acc, e):
        wg, wu, wd, we = e
        f = jax.nn.silu(_mm(h, wg, precision)) * _mm(h, wu, precision)
        return acc + we[:, None] * _mm(f, wd, precision), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (lw["w_gate"], lw["w_up"], lw["w_down"],
                           weight.T))
    return x + out


class Reference(Judge):
    """Teacher-forced logits of the plain model over served requests."""

    def __init__(self, conf: dict, weights: dict, block: int = 512):
        _check(conf)
        self.m = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"], conf["num_experts_per_tok"],
                  bool(conf["norm_topk_prob"]))
        self.kinds = _layer_types(conf)
        self.ropes = {k: rope_of(conf, k) for k in set(self.kinds)}
        self.window = conf["sliding_window"]
        self.vocab = conf["vocab_size"]
        self.w = weights
        self.final_norm = weights["final_norm"]
        self.head = weights["lm_head"]
        self.block = block

    def _hidden(self, ids: np.ndarray, precision: str):
        if precision not in ("f32", "a4"):
            raise ValueError(f"no {precision!r} control for mellum2")
        S = len(ids)
        Sp = max(self.block, 1 << (S - 1).bit_length())
        x = self.w["embed"][jnp.asarray(np.pad(ids, (0, Sp - S)))]
        for i, kind in enumerate(self.kinds):
            lw = {k: v[i] for k, v in self.w.items()
                  if k not in ("embed", "final_norm", "lm_head")}
            inv_freq, factor = self.ropes[kind]
            x = _layer(x, lw, jnp.asarray(inv_freq), factor, m=self.m,
                       window=self.window if kind == "sliding_attention"
                       else 0, precision=precision, block=self.block)
        return x


# -- needed work -----------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Shape:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_expert: int
    n_experts: int
    top_k: int
    vocab: int
    page_size: int
    window: int
    kinds: tuple  # 'sliding_attention' / 'full_attention' per layer
    group: int
    code_bytes: int = 1  # int8 KV codes
    q_bytes: int = 4  # f32 queries and outputs


def shape(conf: dict) -> Shape:
    s = conf["serving"]
    return Shape(d_model=conf["hidden_size"],
                 n_heads=conf["num_attention_heads"],
                 n_kv=conf["num_key_value_heads"], head_dim=conf["head_dim"],
                 d_expert=conf["moe_intermediate_size"],
                 n_experts=conf["num_experts"],
                 top_k=conf["num_experts_per_tok"],
                 vocab=conf["vocab_size"], page_size=s["page_size"],
                 window=conf["sliding_window"],
                 kinds=tuple(_layer_types(conf)), group=conf["group"],
                 code_bytes=1 if s["kv"] in ("int8", "fp8") else 4)


def expert_bytes(conf: dict) -> int:
    """Bytes of one expert's stored INT4 codes and f32 group scales, its
    three matrices: what the grouped kernel reads of an expert it
    touches in one layer."""
    s = shape(conf)
    D, F = s.d_model, s.d_expert
    return 3 * D * F // 2 + 4 * (2 * (D // s.group) * F
                                 + (F // s.group) * D)


def routed_row_bytes(s: Shape) -> int:
    """Bytes the grouped kernel reads and writes for one routed row: the
    row's int8 codes and f32 group steps into ``wg`` and ``wi`` and its
    f32 outputs, then into ``wo`` and out."""
    D, F = s.d_model, s.d_expert
    into = lambda k: k + 4 * (k // s.group)  # noqa: E731
    return 2 * (into(D) + 4 * F) + into(F) + 4 * D


def _capped_sum(a: int, b: int, cap: int) -> int:
    """``sum(min(p, cap) for p in range(a, b + 1))``."""
    lo, hi = a, min(b, cap)
    below = (lo + hi) * (hi - lo + 1) // 2 if hi >= lo else 0
    return below + cap * max(0, b - max(a, cap + 1) + 1)


class WorkCounter(Need):
    """Needed model FLOPs; the attention kernel's FLOPs and bytes over
    each query's live context (a sliding layer's window at most); the
    expert kernel's FLOPs (``top_k`` experts a valid row) and the bytes
    of its rows (the codes of the experts it touches come from the
    program's ``experts_touched``: ``expert_bytes``)."""

    def __init__(self, s: Shape, attention: str | None):
        super().__init__(([attention] if attention else []) + [EXPERT_KERNEL])
        self.s = s
        self.attention = attention
        proj = s.d_model * s.head_dim * (s.n_heads + 2 * s.n_kv) \
            + s.n_heads * s.head_dim * s.d_model
        self.expert_flops = 2 * len(s.kinds) * s.top_k * 3 * s.d_model \
            * s.d_expert  # of one row
        self.row_flops = 2 * len(s.kinds) * (proj + s.d_model * s.n_experts) \
            + self.expert_flops

    def _cap(self, kind: str) -> int:
        return self.s.window if kind == "sliding_attention" else 1 << 62

    def _attention(self, first: int, last: int) -> int:
        """FLOPs of attention for the queries whose contexts run from
        ``first`` to ``last`` positions; adds the kernel's FLOPs and bytes
        (each layer's longest live context read once, and the queries and
        outputs)."""
        s = self.s
        flops = sum(4 * s.n_heads * s.head_dim
                    * _capped_sum(first, last, self._cap(k))
                    for k in s.kinds)
        if self.attention:
            kv = 0
            for kind in s.kinds:
                c = min(last, self._cap(kind))
                kv += 2 * s.n_kv * (s.head_dim * c * s.code_bytes
                                    + math.ceil(c / s.page_size) * 4)
            io = 2 * len(s.kinds) * (last - first + 1) * s.n_heads \
                * s.head_dim * s.q_bytes
            k = self.kernels[self.attention]
            k[0] += flops
            k[1] += kv + io
        return flops

    def _rows(self, n: int, emits: bool) -> int:
        s = self.s
        k = self.kernels[EXPERT_KERNEL]
        k[0] += n * self.expert_flops
        k[1] += n * len(s.kinds) * s.top_k * routed_row_bytes(s)
        return n * self.row_flops + (2 * s.d_model * s.vocab if emits else 0)

    def prefill(self, start: int, stop: int, emits: bool) -> None:
        self.model_flops += self._rows(stop - start, emits) \
            + self._attention(start + 1, stop)

    def decode(self, pos: int) -> None:
        self.model_flops += self._rows(1, True) \
            + self._attention(pos + 1, pos + 1)


def work_counter(conf: dict) -> WorkCounter:
    return WorkCounter(shape(conf), conf.get("attention_kernel"))
