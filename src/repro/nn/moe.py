"""Mixture-of-Experts FFN: dropless top-k routing, rows grouped by expert.

Routing: softmax over the router's logits in f32 (at full precision), the
top ``top_k`` experts, and — where the architecture renormalises
(``norm_topk_prob``) — their weights divided by their sum.  Every routed
row is computed: there is no capacity and no dropped row.  Rows a step
marks invalid (``valid``: a prefill chunk's padding, an empty slot) route
to no expert and take no expert work.

Dispatch (``expert_layout``): the (token, choice) rows are laid out
grouped by expert, each group padded to whole tiles of ``bm`` rows, so
every tile belongs to one expert; tiles past the last used one are
skipped.  The experts' contractions (``wg`` and ``wi``, then ``wo``) run
on that layout, by the storage of the expert stack:

  * one stacked ``CompressedKernel`` (``compress_weights`` stores a bank
    that way when every expert shares one integer rule) with an int-ABFP
    input at the stored group: the rows' ABFP codes against the stored
    codes, in the grouped Pallas kernel
    (``kernels.quant_matmul.quant_matmul_grouped``) where
    ``core.simulate.codes_kernel_takes`` says so (the TPU), else tile by
    tile in jnp — never a dense copy of the stack;
  * a dense stack ``(E, K, N)``: the policy's QDQ, then one ragged
    contraction over the groups;
  * an ``ExpertBank`` of per-expert entries in mixed formats: each entry
    contracts the rows of its own group.

The results are combined by the routing weights.  Activations resolve
once at the block site (``self.name``); dense expert weights honour
per-expert sub-sites ``{self.name}/experts.{e}``.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import abfp as abfp_mod
from repro.core.policy import Policy, has_expert_rules, resolve_policy
from repro.core.simulate import (codes_aligned, codes_contraction,
                                 codes_kernel_takes, qdq_activation,
                                 qdq_weight, qmatmul, tally_site,
                                 _compressed_group_matmul)
from repro.dist import sharding as shd
from repro.nn.ffn import _ACTS, GATED
from repro.nn.module import Box, truncated_normal

HIGHEST = jax.lax.Precision.HIGHEST
COUNT_KEYS = ("expert_rows", "expert_rows_computed", "experts_touched",
              "expert_rows_max")


class Layout(NamedTuple):
    """Routed rows grouped by expert, in tiles of ``bm`` rows.

    ``dest`` (T, k): each (token, choice)'s row, ``n_rows`` where the
    token is invalid; ``src`` (n_rows,): each row's token, T on padding;
    ``tile_group`` (n_tiles,): each tile's expert; ``tiles_used`` (): the
    tiles in use, which come first; ``group_rows`` (E,): valid rows per
    expert.
    """

    dest: jnp.ndarray
    src: jnp.ndarray
    tile_group: jnp.ndarray
    tiles_used: jnp.ndarray
    group_rows: jnp.ndarray

    @property
    def bm(self) -> int:
        return self.src.shape[0] // self.tile_group.shape[0]

    @property
    def n_rows(self) -> int:
        return self.src.shape[0]

    def row_group(self) -> jnp.ndarray:
        """Each row's expert, -1 in tiles past the used ones."""
        t = jnp.arange(self.tile_group.shape[0])
        g = jnp.where(t < self.tiles_used, self.tile_group, -1)
        return jnp.repeat(g, self.bm)

    def counts(self) -> dict:
        """What the routing gave and what the grouped contraction
        computes: ``expert_rows`` (valid routed rows),
        ``expert_rows_computed`` (rows of the tiles in use, padding
        included), ``experts_touched`` (experts with a row) and
        ``expert_rows_max`` (the busiest expert's rows), int32."""
        rows = self.group_rows
        counts = (rows.sum(), self.tiles_used * self.bm, (rows > 0).sum(),
                  rows.max())
        return {k: v.astype(jnp.int32) for k, v in zip(COUNT_KEYS, counts)}


def expert_layout(idx: jnp.ndarray, valid: jnp.ndarray, n_experts: int,
                  bm: int) -> Layout:
    """The tile layout of routing choices ``idx`` (T, k) over
    ``n_experts`` experts, with invalid tokens (``valid`` (T,) False)
    routed nowhere.  ``n_tiles`` is static: the rows' tiles plus one
    partial tile per expert, which the groups never all fill, so the
    last tile is never in use."""
    T, K = idx.shape
    R, E = T * K, n_experts
    n_tiles = -(-R // bm) + min(E, R)
    e = jnp.where(valid[:, None], idx, E).reshape(R).astype(jnp.int32)
    onehot = (e[:, None] == jnp.arange(E, dtype=jnp.int32)[None]
              ).astype(jnp.int32)  # (R, E); invalid rows are all zero
    rows = onehot.sum(0)
    rank = ((jnp.cumsum(onehot, axis=0) - onehot) * onehot).sum(1)
    tiles = -(-rows // bm)
    ends = jnp.cumsum(tiles)
    start = (ends - tiles) * bm  # first row of each expert's group
    n_rows = n_tiles * bm
    dest = jnp.where(e < E, start[jnp.minimum(e, E - 1)] + rank, n_rows)
    src = jnp.full((n_rows,), T, jnp.int32).at[dest].set(
        jnp.arange(R, dtype=jnp.int32) // K, mode="drop")
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(n_tiles), side="right"),
        E - 1).astype(jnp.int32)
    return Layout(dest=dest.reshape(T, K), src=src, tile_group=tile_group,
                  tiles_used=ends[-1].astype(jnp.int32), group_rows=rows)


def _take_rows(a: jnp.ndarray, src: jnp.ndarray) -> jnp.ndarray:
    """Rows ``src`` of ``a`` (T, ...), zero where ``src`` is T."""
    pad = jnp.zeros((1,) + a.shape[1:], a.dtype)
    return jnp.concatenate([a, pad])[src]


@dataclasses.dataclass(frozen=True)
class MoE:
    d_model: int
    d_ff: int
    n_experts: int
    top_k: int = 2
    norm_topk_prob: bool = False
    act: str = "swiglu"
    param_dtype: str = "float32"
    dtype: str = "float32"
    name: str = "moe"

    @property
    def gated(self) -> bool:
        return self.act in GATED

    def init(self, key) -> dict:
        kr, ki, kg, ko = jax.random.split(key, 4)
        pdt = jnp.dtype(self.param_dtype)
        E, D, F = self.n_experts, self.d_model, self.d_ff
        p = {
            "router": Box(
                truncated_normal(kr, (D, E), pdt, D**-0.5),
                ("embed", "experts"),
            ),
            "wi": Box(
                truncated_normal(ki, (E, D, F), pdt, D**-0.5),
                ("experts", "embed", "moe_mlp"),
            ),
            "wo": Box(
                truncated_normal(ko, (E, F, D), pdt, F**-0.5),
                ("experts", "moe_mlp", "embed"),
            ),
        }
        if self.gated:
            p["wg"] = Box(
                truncated_normal(kg, (E, D, F), pdt, D**-0.5),
                ("experts", "embed", "moe_mlp"),
            )
        return p

    def route(self, x2: jnp.ndarray, router: jnp.ndarray):
        """(probs (T, E), top-k weights (T, k), top-k experts (T, k))."""
        logits = jnp.dot(x2.astype(jnp.float32), router.astype(jnp.float32),
                         precision=HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, self.top_k)
        if self.norm_topk_prob:
            gate = gate / gate.sum(-1, keepdims=True)
        return probs, gate, idx

    def apply(
        self, params: dict, x: jnp.ndarray, policy: Policy,
        q: dict | None = None, valid: jnp.ndarray | None = None,
    ) -> tuple[jnp.ndarray, dict]:
        """Returns (output, metrics): ``moe_aux_loss`` (Switch's load
        balancing loss), ``expert_load`` (E,) f32 routed rows per expert,
        and ``counts`` (``Layout.counts``).  ``valid`` (B, S) marks the
        tokens that route; None routes every token."""
        from repro.kernels.quant_matmul import grouped_tile_rows

        pmap = policy
        policy = resolve_policy(policy, self.name)
        B, S, D = x.shape
        E, K = self.n_experts, self.top_k
        T = B * S
        scope = self.name.rsplit("/", 1)[0] + "/moe" \
            if "/" in self.name else "moe"
        x2 = shd.constrain(x.reshape(T, D), ("batch", "embed"))
        valid2 = (jnp.ones((T,), bool) if valid is None
                  else valid.reshape(T))

        with jax.named_scope(scope + "/route"):
            probs, gate, idx = self.route(x2, params["router"])
            lay = expert_layout(idx, valid2, E, grouped_tile_rows(T * K, E))
            n_tok = jnp.maximum(valid2.sum(), 1).astype(jnp.float32)
            density = lay.group_rows.astype(jnp.float32) / n_tok
            mean_prob = (probs * valid2[:, None]).sum(0) / n_tok
            aux_loss = (density * mean_prob).mean() * E * E

        with jax.named_scope(scope + "/experts"):
            tq = policy.input if policy.enabled else None
            per_expert = has_expert_rules(pmap)

            def contract(a, kind, tokens):
                return self._contract(a, params[kind], policy, pmap, tq,
                                      lay, kind, tokens, per_expert)

            hi = contract(x2, "wi", True)
            if self.gated:
                h = _ACTS[GATED[self.act]](contract(x2, "wg", True)) * hi
            else:
                h = _ACTS[self.act](hi)
            eout = contract(h.astype(x.dtype), "wo", False)

        with jax.named_scope(scope + "/combine"):
            rows = jnp.take(eout, jnp.minimum(lay.dest, lay.n_rows - 1),
                            axis=0)  # (T, k, D)
            keep = valid2[:, None, None]
            y = jnp.where(keep, rows * gate[..., None], 0.0).sum(1)
        y = y.reshape(B, S, D).astype(jnp.dtype(self.dtype))
        y = shd.constrain(y, ("batch", "seq_res", "embed"))
        metrics = {"moe_aux_loss": aux_loss,
                   "expert_load": lay.group_rows.astype(jnp.float32),
                   "counts": lay.counts()}
        return y, metrics

    # ------------------------------------------------------ contractions
    def _contract(self, a, w, policy, pmap, tq, lay: Layout, kind: str,
                  tokens: bool, per_expert: bool) -> jnp.ndarray:
        """The layout's rows times expert stack ``w``: (n_rows, N) f32.

        ``a`` is (T, K) tokens, taken to the layout here (after their
        quantization, once per token), or (n_rows, K) rows already laid
        out."""
        from repro.models.serving_transforms import (CompressedKernel,
                                                     ExpertBank)

        site = f"{self.name}/{kind}"
        lay_rows = (lambda v: _take_rows(v, lay.src)) if tokens \
            else (lambda v: v)
        if isinstance(w, CompressedKernel):
            if codes_aligned(w, tq):
                xc, xs, _ = abfp_mod.abfp_quantize(
                    a, tq.fmt, axis=-1, n=tq.group,
                    scale_dtype=jnp.dtype(tq.scale_dtype))
                return self._codes(lay_rows(xc), lay_rows(xs), w, policy,
                                   lay)
            xq = qdq_activation(a, tq, axis=-1, site=self.name + "/in")
            fp_input = policy.replace(input=None)
            tally_site(False)
            return self._tiles(
                lay, lay_rows(xq), w.scale.shape[-1],
                lambda xt, g: _compressed_group_matmul(
                    xt, w.index(g), fp_input, site=site, in_alpha=None))
        if isinstance(w, ExpertBank):
            rows, group = lay_rows(a), lay.row_group()
            out = 0.0
            for e, entry in enumerate(w.entries):
                y = qmatmul(rows, entry, policy, site=f"{site}.{e}")
                out = out + jnp.where((group == e)[:, None], y, 0.0)
            return out
        xq = lay_rows(qdq_activation(a, tq, axis=-1, site=self.name + "/in"))
        if per_expert:
            wq = jnp.stack([
                qdq_weight(w[e], _expert_weight(pmap, self.name, e),
                           contract_axis=0)
                for e in range(self.n_experts)])
        else:
            wq = qdq_weight(w, policy.weight if policy.enabled else None,
                            contract_axis=1)
        sizes = (-(-lay.group_rows // lay.bm)) * lay.bm  # rows per group
        return jax.lax.ragged_dot(xq.astype(jnp.float32),
                                  wq.astype(jnp.float32), sizes,
                                  preferred_element_type=jnp.float32)

    def _codes(self, xc, xs, w, policy, lay: Layout) -> jnp.ndarray:
        """Laid-out ABFP codes ``(n_rows, G, n)`` and steps against the
        stacked codes: the grouped kernel, or tile by tile in jnp."""
        kernel = codes_kernel_takes(w, policy, stacked=True)
        tally_site(kernel)
        if kernel:
            from repro.kernels import ops as kops  # lazy: pallas import

            return kops.quant_matmul_grouped_fused(
                xc, xs, lay.tile_group, lay.tiles_used, w)
        return self._tiles(
            lay, (xc, xs), w.scale.shape[-1],
            lambda t, g: codes_contraction(t[0], t[1], w.index(g)))

    @staticmethod
    def _tiles(lay: Layout, rows, n_out: int, fn) -> jnp.ndarray:
        """``fn(a tile's rows, its expert)`` over the tiles in use, zeros
        past them: (n_rows, n_out)."""
        tiles = lay.tile_group.shape[0]
        rows = jax.tree_util.tree_map(
            lambda r: r.reshape(tiles, lay.bm, *r.shape[1:]), rows)

        def one(args):
            i, g, t = args
            return jax.lax.cond(
                i < lay.tiles_used, lambda: fn(t, g),
                lambda: jnp.zeros((lay.bm, n_out), jnp.float32))

        out = jax.lax.map(one, (jnp.arange(tiles), lay.tile_group, rows))
        return out.reshape(tiles * lay.bm, n_out)


def _expert_weight(pmap, site: str, e: int):
    pe = resolve_policy(pmap, f"{site}/experts.{e}")
    return pe.weight if pe.enabled else None
