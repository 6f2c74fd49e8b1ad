"""Serving-mode weight transforms (beyond-paper §Perf iterations).

The paper's simulator QDQs weights *inside every forward pass* — right for
QAT/research, but at serving time weights are frozen, so:

  * ``prequantize_weights``  — apply each site's resolved weight quantizer
    ONCE offline and serve with ``serving_policy(policy)`` (weight
    quantizers dropped).  Numerically identical (ABFP and channel-max QDQ
    are idempotent: values already on the grid map to themselves) and
    removes the entire per-layer runtime QDQ chain from the decode graph.
    §Perf: -35% memory term on qwen2 decode_32k.

  * ``compress_weights``     — store kernels as int CODES + per-group unit
    scales (the paper's storage story made real).  The ``compressed``
    execution backend (``core.simulate``) contracts the codes directly —
    int32 accumulation, per-group rescale — so HBM never sees a
    dequantized kernel.  INT4 codes pack two-per-byte, so resident weight
    bytes track the policy's bit budget.  Also shrinks checkpoints.

Both transforms are **PolicyMap-aware**: every ``kernel`` leaf is resolved
against its site address (the same contract ``qmatmul`` uses), so a mixed
map compresses each kernel against *its* rule:

  * int-format weight rules (``abfp`` or ``channel_max`` scalers) become
    ``CompressedKernel`` codes + scales;
  * float-format rules (e.g. FP8-E4M3 attention) are QDQ'd offline but
    stay dense — there is no integer code to store;
  * fp32 (disabled) rules leave the kernel untouched.

Site addresses are derived from the param-tree path: dict keys join with
``/``, list entries under ``blocks`` become ``blocks.{i}`` (the unrolled
naming) and a scan-stacked ``blocks`` dict contributes ``block`` (the
shared scan site — layer-indexed rules cannot resolve there, same
constraint the runtime has).  This matches the TransformerLM/ViT param
layout; exotic families (encdec/hybrid) only support flat policies here
(a flat policy resolves identically at every site, so the walk is exact).

The tied embedding table is NOT touched: it feeds the input lookup too,
and pre-quantizing it would change input embeddings (the runtime path only
QDQs the readout matmul).

MoE expert banks (the ``wi``/``wg``/``wo`` stacks next to a ``router``)
are walked along their stacked expert axis: each expert resolves its OWN
rule at ``{site}/experts.{e}`` (first-match-wins over the block-level
pattern), so a mixed map can keep hot experts at INT8/FP8 while cold
experts compress to INT4.  Heterogeneous per-expert storage lives in an
``ExpertBank`` — the per-expert container the serve-side expert store
(``repro.serve.experts``) caches into.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import abfp as abfp_mod
from repro.core.formats import IntFormat
from repro.core.policy import (
    Policy,
    PolicyMap,
    PolicyRule,
    QuantPolicy,
    TensorQuant,
    as_policy_map,
    has_site_rules,
    resolve_policy,
)
from repro.core.quantize import int4_nibbles, pack_int4_codes, quantize
from repro.core.simulate import qdq_weight


@jax.tree_util.register_pytree_node_class
class CompressedKernel:
    """int codes + per-group unit scales; metadata rides as pytree aux.

    codes: ``(Kp, N)`` int8 in the dense kernel's own orientation, the
    contraction zero-padded to ``Kp = G * n`` — or, when ``packed``,
    ``(Kp // 2, N)`` uint8 (INT4 storage): group g's ``n // 2`` byte rows
    hold its first half of code rows in the low nibbles and its second
    half in the high nibbles.  scale: ``(G, N)`` f32 unit scales
    (alpha / qmax), row g covering code rows ``[g*n, (g+1)*n)``.  Stacked
    kernels lead with their stack dims.  Both the jnp backend and the
    Pallas kernel read this layout as stored (on the TPU neither
    relayouts it per call).
    ``fmt_name`` records the stored integer format so reports/backends can
    reason about the bit budget without the policy in hand.
    """

    __slots__ = ("codes", "scale", "pad", "k", "dtype", "fmt_name",
                 "packed")

    def __init__(self, codes, scale, pad: int, k: int, dtype: str,
                 fmt_name: str = "int8", packed: bool = False):
        self.codes = codes
        self.scale = scale
        self.pad = pad
        self.k = k
        self.dtype = dtype
        self.fmt_name = fmt_name
        self.packed = packed

    def tree_flatten(self):
        return (self.codes, self.scale), (self.pad, self.k, self.dtype,
                                          self.fmt_name, self.packed)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    @property
    def group(self) -> int:
        """Stored group length n (in codes, not bytes — packing-aware)."""
        rows = self.codes.shape[-2] * (2 if self.packed else 1)
        return rows // self.scale.shape[-2]

    def nibbles(self):
        """Packed codes -> ``(low, high)`` int8, each ``(..., G, n//2, N)``:
        the first and second half of every group's code rows."""
        *lead, rows, N = self.codes.shape
        G = self.scale.shape[-2]
        return int4_nibbles(self.codes.reshape(*lead, G, rows // G, N))

    def grouped_codes(self):
        """The codes as int8 ``(..., G, n, N)``, unpacked if stored packed."""
        if self.packed:
            return jnp.concatenate(self.nibbles(), axis=-2)
        *lead, rows, N = self.codes.shape
        G = self.scale.shape[-2]
        return self.codes.reshape(*lead, G, rows // G, N)

    def __repr__(self):
        return (f"CompressedKernel(codes={getattr(self.codes, 'shape', None)},"
                f" scale={getattr(self.scale, 'shape', None)},"
                f" fmt={self.fmt_name}, packed={self.packed})")


@jax.tree_util.register_pytree_node_class
class ExpertBank:
    """Per-expert entries for one stacked MoE expert kernel.

    Replaces a dense ``(E, K, N)`` (or scan-stacked ``(L, E, K, N)``)
    expert stack with a tuple of per-expert entries — each a dense slice
    or a ``CompressedKernel`` — so experts can carry *different* storage
    formats (hot INT8 / cold INT4) and the serve expert cache can swap an
    individual expert for its decompressed-dense copy without touching
    its neighbours.  The expert axis is END-RELATIVE at -3 so per-layer
    slices under ``jax.lax.scan`` still line up (the same convention
    ``CompressedKernel`` uses for its -2 contraction axis).
    """

    __slots__ = ("entries",)

    def __init__(self, entries):
        self.entries = tuple(entries)

    def tree_flatten(self):
        return self.entries, len(self.entries)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children)

    @property
    def n_experts(self) -> int:
        return len(self.entries)

    def dense(self, dtype=None):
        """Stacked dense view ``(..., E, K, N)`` (XLA fuses the dequant)."""
        mats = [decompress_kernel(e, dtype)
                if isinstance(e, CompressedKernel)
                else (e if dtype is None else e.astype(dtype))
                for e in self.entries]
        return jnp.stack(mats, axis=mats[0].ndim - 2)

    def replace_entry(self, e: int, value) -> "ExpertBank":
        entries = list(self.entries)
        entries[e] = value
        return ExpertBank(entries)

    def __repr__(self):
        n_c = sum(isinstance(e, CompressedKernel) for e in self.entries)
        return (f"ExpertBank(n_experts={self.n_experts}, "
                f"compressed={n_c}, dense={self.n_experts - n_c})")


def entry_bytes(entry) -> int:
    """Resident bytes of one weight entry (dense array or codes+scales)."""
    if isinstance(entry, CompressedKernel):
        return _leaf_bytes(entry.codes) + _leaf_bytes(entry.scale)
    return _leaf_bytes(entry)


def is_expert_bank(x) -> bool:
    return isinstance(x, ExpertBank)


# MoE param sub-dicts are recognised structurally: the expert stacks sit
# next to their router.  Keys here are the ONLY non-'kernel' leaves the
# walks transform.
_EXPERT_KEYS = ("wi", "wg", "wo")


def _is_moe_bank(node) -> bool:
    return (isinstance(node, dict) and "router" in node
            and "wi" in node and "wo" in node)


def _walk_kernels(params, fn, expert_fn=None):
    """Apply ``fn(site, kernel_leaf)`` to every 'kernel' entry; keep
    structure.  ``site`` follows the runtime site-address contract (see
    module docstring).  When ``expert_fn`` is given, MoE expert stacks are
    visited too as ``expert_fn(site, kind, stack)`` with ``kind`` one of
    ``wi``/``wg``/``wo`` and ``site`` the block-level address (e.g.
    ``blocks.0/ffn``); otherwise they pass through untouched."""

    def rec(node, path):
        if isinstance(node, dict):
            out = {}
            bank = _is_moe_bank(node)
            for k, v in node.items():
                if bank and k in _EXPERT_KEYS:
                    out[k] = (expert_fn("/".join(path), k, v)
                              if expert_fn is not None else v)
                elif k == "kernel" and (hasattr(v, "ndim")
                                        or isinstance(v, (tuple,
                                                          CompressedKernel))):
                    out[k] = fn("/".join(path), v)
                elif (k == "blocks" and isinstance(v, (list, tuple))
                        and not hasattr(v, "ndim")):
                    t = type(v)
                    vals = [rec(b, path + [f"blocks.{i}"])
                            for i, b in enumerate(v)]
                    out[k] = t(*vals) if hasattr(v, "_fields") else t(vals)
                elif k == "blocks" and isinstance(v, dict):
                    # scan-stacked layers share one trace/site ('block')
                    out[k] = rec(v, path + ["block"])
                else:
                    out[k] = rec(v, path + [k])
            return out
        if isinstance(node, (list, tuple)) and not hasattr(node, "ndim"):
            t = type(node)
            vals = [rec(v, path + [str(i)]) for i, v in enumerate(node)]
            if hasattr(node, "_fields"):  # NamedTuple
                return t(*vals)
            return t(vals)
        return node

    return rec(params, [])


def _site_weight(policy: Policy, site: str) -> TensorQuant | None:
    p = resolve_policy(policy, site)
    return p.weight if p.enabled else None


def expert_site(site: str, e: int) -> str:
    """Site address of expert ``e`` inside the MoE block at ``site``.

    Matches the runtime contract in ``nn.moe``: ``blocks.0/ffn/experts.3``
    unrolled, ``block/ffn/experts.3`` under scan (expert-indexed patterns
    like ``*/experts.3`` avoid the word ``blocks`` on purpose, so they
    stay scan-compatible — `has_layer_rules` does not trip on them).
    """
    return f"{site}/experts.{e}"


def _expert_weights(policy: Policy, site: str, n_experts: int):
    return [_site_weight(policy, expert_site(site, e))
            for e in range(n_experts)]


# Param-tree top-level keys whose runtime site addresses do NOT follow the
# path-derived naming _walk_kernels produces (hybrid: 'shared/q' at runtime
# vs 'shared/attn/q' in the tree; encdec: family-level 'attn/...' names vs
# 'encoder/...'/'decoder/...' paths).  Site-rule maps would silently
# mis-resolve there, so only flat policies (which resolve identically at
# every site) are accepted for those families.  The key list lives with
# the analyzer (repro.analysis.policy_lint.NON_CONTRACT_KEYS) so lint and
# runtime can't drift; this alias keeps the old import path working.
from repro.analysis.policy_lint import NON_CONTRACT_KEYS as _NON_CONTRACT_KEYS  # noqa: E402,E501


def _check_site_rules_supported(params, policy: Policy, what: str) -> None:
    # thin shim over the static analyzer (QL008): same message, one source
    if not isinstance(params, dict):
        return
    from repro.analysis.policy_lint import non_contract_layout_diagnostic

    d = non_contract_layout_diagnostic(policy, list(params), what)
    if d is not None:
        raise NotImplementedError(d.message)


def prequantize_weights(params, policy: Policy):
    """QDQ every kernel offline per its site's resolved weight rule.

    fp32-rule sites are left untouched; all scalers ``qdq_weight`` supports
    (abfp / channel_max / dynamic_max) round-trip exactly at serving time.
    MoE expert stacks QDQ per-expert against their ``experts.{e}`` rules
    and stay stacked-dense.
    """
    _check_site_rules_supported(params, policy, "prequantize_weights")

    def one(site, w):
        tq = _site_weight(policy, site)
        if tq is None or isinstance(w, CompressedKernel):
            return w
        return qdq_weight(w, tq, contract_axis=w.ndim - 2).astype(w.dtype)

    def one_bank(site, kind, w):
        if isinstance(w, ExpertBank):
            return w
        e_axis = w.ndim - 3
        tqs = _expert_weights(policy, site, w.shape[e_axis])
        if all(tq is None for tq in tqs):
            return w
        cols = []
        for e, tq in enumerate(tqs):
            we = jnp.take(w, e, axis=e_axis)
            if tq is not None:
                we = qdq_weight(we, tq, contract_axis=we.ndim - 2)
            cols.append(we.astype(w.dtype))
        return jnp.stack(cols, axis=e_axis)

    return _walk_kernels(params, one, expert_fn=one_bank)


def serving_policy(policy: Policy) -> Policy:
    """The runtime policy to pair with prequantized/compressed weights.

    Weight quantizers drop rule-wise — EXCEPT at the tied-readout site
    ``embed/attend``: the embedding table is never transformed offline (it
    feeds the input lookup too), so that one matmul keeps its runtime
    weight QDQ or compressed serving would silently diverge from the QDQ
    simulation on tied-embedding models.  The result is therefore always a
    PolicyMap carrying the keep-rule (inert on untied models, whose
    ``lm_head`` kernel IS transformed offline).
    """
    def drop_weight(p: QuantPolicy) -> QuantPolicy:
        if p.weight is None:
            return p
        return p.replace(name=p.name + "_served", weight=None)

    pm = as_policy_map(policy)
    if all(p.weight is None for p in pm.policies):
        return policy
    keep = pm.resolve("embed/attend")
    rules = tuple(PolicyRule(r.pattern, drop_weight(r.policy))
                  for r in pm.rules)
    if keep.weight is not None:
        rules = (PolicyRule("embed/attend", keep),) + rules
    return PolicyMap(name=pm.name + "_served", rules=rules,
                     default=drop_weight(pm.default))


# ---------------------------------------------------------------------------
# Real compressed storage: int codes + scales
# ---------------------------------------------------------------------------
def compress_kernel(w, tq: TensorQuant) -> CompressedKernel:
    """One dense kernel -> CompressedKernel per an int-format weight rule.

    The contraction always sits at rank-2 (K,N / stacked L,K,N): it is
    stored END-RELATIVE so per-layer slices under scan still line up.
    ``abfp`` rules group K by ``tq.group``; ``channel_max`` rules store one
    group spanning all of K with the per-output-channel alpha (bit-exact
    with the runtime channel-max QDQ).  INT4 codes pack two-per-byte.
    """
    if not isinstance(tq.fmt, IntFormat):
        raise ValueError(
            f"compress_kernel stores integer codes; got format "
            f"{tq.fmt_name!r} (float-format rules stay dense — see "
            "compress_weights)"
        )
    axis = w.ndim - 2
    if tq.scaler == "abfp":
        codes, scales, (pad, k) = abfp_mod.abfp_quantize(
            w, tq.fmt, axis=axis, n=tq.group, dtype=jnp.int8,
            scale_dtype=jnp.dtype(tq.scale_dtype),
        )
        n = tq.group
        # (..., N, G, n) / (..., N, G) -> (..., G*n, N) / (..., G, N)
        codes = jnp.swapaxes(codes.reshape(*codes.shape[:-2], -1), -1, -2)
        scales = jnp.swapaxes(scales, -1, -2)
    elif tq.scaler == "channel_max":
        # one group spanning K, alpha = per-output-channel max (matches
        # core.simulate.qdq_weight's channel_max path bit-for-bit)
        alpha = jnp.maximum(
            jnp.max(jnp.abs(w), axis=axis, keepdims=True), 1e-8
        )
        codes, scales = quantize(w, alpha, tq.fmt, dtype=jnp.int8)
        pad, k = 0, w.shape[axis]
        n = k
    else:
        raise ValueError(
            f"compress_kernel supports 'abfp'/'channel_max' weight "
            f"scalers, got {tq.scaler!r}"
        )
    # each group packs its two halves into one byte plane, so n must be even
    packed = tq.fmt.bits <= 4 and n % 2 == 0
    if packed:
        *lead, kp, N = codes.shape
        codes = pack_int4_codes(codes.reshape(*lead, kp // n, n, N), axis=-2)
        codes = codes.reshape(*lead, kp // 2, N)
    # `scales` are already UNIT scales (alpha/qmax); keep f32 — they are
    # 1/group of the codes count, and f32 keeps serving numerics exact.
    return CompressedKernel(codes, scales.astype(jnp.float32),
                            pad, k, str(w.dtype),
                            fmt_name=tq.fmt.name, packed=packed)


# One fused program per kernel shape: run op by op, the quantize chain
# materializes several f32 copies of the kernel (an embedding-sized
# readout is 2 GiB each), which a chip holding the dense weights has no
# room for.  The codes are the same either way.
_compress_kernel_fused = jax.jit(compress_kernel, static_argnums=1)


def compress_weights(params, policy: Policy):
    """kernel -> CompressedKernel per the kernel's resolved site rule.

    Per-site behavior (the weight-uniform restriction is gone):
      * int-format rule (abfp / channel_max) — stored as codes + scales,
        consumed directly by the ``compressed`` execution backend;
      * float-format rule (e.g. FP8-E4M3) — QDQ'd offline, stays dense;
      * fp32 (disabled) rule — untouched.
    MoE expert stacks become ``ExpertBank``s of per-expert entries, each
    resolved at ``{site}/experts.{e}`` — a fully fp32 bank stays a plain
    dense stack.  Pair with ``serving_policy(policy)`` at runtime.
    """
    _check_site_rules_supported(params, policy, "compress_weights")

    def _one_entry(w, tq):
        if tq is None:
            return w
        if isinstance(tq.fmt, IntFormat) and tq.scaler in ("abfp",
                                                           "channel_max"):
            return _compress_kernel_fused(w, tq)
        # float formats / exotic scalers: no integer codes to store —
        # prequantize offline so serving still matches the QDQ simulation
        return qdq_weight(w, tq, contract_axis=w.ndim - 2).astype(w.dtype)

    def one(site, w):
        if isinstance(w, CompressedKernel):
            return w
        return _one_entry(w, _site_weight(policy, site))

    def one_bank(site, kind, w):
        if isinstance(w, ExpertBank):
            return w
        e_axis = w.ndim - 3
        tqs = _expert_weights(policy, site, w.shape[e_axis])
        if all(tq is None for tq in tqs):
            return w  # fully fp32 bank: stays a plain dense stack
        return ExpertBank([
            _one_entry(jnp.take(w, e, axis=e_axis), tq)
            for e, tq in enumerate(tqs)
        ])

    return _walk_kernels(params, one, expert_fn=one_bank)


def compress_axes(axes_tree, compressed_sds_tree):
    """Mirror ``compress_weights`` on the logical-axes tree.

    For a kernel with axes (a_contract, a_out) the codes are laid out
    (Kp, a_out) and scales (G, a_out) — sharding follows the output axis;
    the grouped contraction replicates.  Pytree aux metadata is copied from
    the compressed SDS tree so treedefs match exactly under jit.  Dense
    (uncompressed / fp32-rule) kernels keep their original axes.
    """

    from repro.dist.sharding import is_axes_leaf as _is_axes

    def rec(ax_node, sds_node):
        if isinstance(sds_node, CompressedKernel):
            axes = ax_node  # original kernel axes tuple
            lead = tuple(axes[:-2]) if len(axes) > 2 else ()
            a_out = axes[-1]
            return CompressedKernel(
                codes=lead + (None, a_out),
                scale=lead + (None, a_out),
                pad=sds_node.pad, k=sds_node.k,
                dtype=sds_node.dtype, fmt_name=sds_node.fmt_name,
                packed=sds_node.packed,
            )
        if isinstance(sds_node, ExpertBank):
            # the expert axis is consumed by the bank; each entry keeps the
            # per-expert kernel axes (contract, out)
            axes = ax_node
            sub = tuple(axes[:-3]) + tuple(axes[-2:])
            return ExpertBank([rec(sub, e) for e in sds_node.entries])
        if isinstance(ax_node, dict):
            return {k: rec(ax_node[k], sds_node[k]) for k in ax_node}
        if isinstance(ax_node, (list, tuple)) and not _is_axes(ax_node):
            t = type(ax_node)
            vals = [rec(a, s) for a, s in zip(ax_node, sds_node)]
            if hasattr(ax_node, "_fields"):
                return t(*vals)
            return t(vals)
        return ax_node

    return rec(axes_tree, compressed_sds_tree)


def decompress_kernel(entry: CompressedKernel, dtype=None):
    """codes+scales -> dense kernel (fused by XLA into the consumer)."""
    dt = jnp.dtype(dtype or entry.dtype)
    codes = entry.grouped_codes()
    w = codes.astype(dt) * entry.scale.astype(dt)[..., None, :]
    w = w.reshape(*codes.shape[:-3], -1, codes.shape[-1])
    if entry.pad:
        w = w[..., :entry.k, :]
    return w


def is_compressed(kernel) -> bool:
    return isinstance(kernel, CompressedKernel)


# ---------------------------------------------------------------------------
# Resident-weight-byte accounting (dryrun / serve / benchmark reports)
# ---------------------------------------------------------------------------
def _leaf_bytes(x) -> int:
    """Bytes of an array or ShapeDtypeStruct."""
    size = 1
    for d in x.shape:
        size *= int(d)
    return size * jnp.dtype(x.dtype).itemsize


def weight_bytes_report(dense_params, served_params) -> dict:
    """Per-site resident weight bytes: dense tree vs its served transform.

    Walks the ``kernel`` leaves of both trees in lockstep and reports the
    bytes each representation keeps resident in HBM — the cost-model
    counterpart of ``launch.roofline.policy_bits_report`` (bits are the
    budget; this is what the storage actually spends, scale overhead
    included).  MoE expert stacks report one row per expert site
    (``{site}/experts.{e}``, the wi/wg/wo kernels of one expert summed),
    so per-expert precision shows up per expert.
    """
    sites = []

    dense_by_site = {}

    def record(site, w):
        dense_by_site[site] = w
        return w

    def record_bank(site, kind, w):
        dense_by_site[(site, kind)] = w
        return w

    _walk_kernels(dense_params, record, expert_fn=record_bank)

    def one(site, w):
        dense_w = dense_by_site[site]
        if isinstance(w, CompressedKernel):
            resident = _leaf_bytes(w.codes) + _leaf_bytes(w.scale)
            kind = "compressed"
            fmt = w.fmt_name + ("_packed" if w.packed else "")
        else:
            resident = _leaf_bytes(w)
            kind = "dense"
            fmt = str(w.dtype)
        sites.append({
            "site": site, "kind": kind, "fmt": fmt,
            "dense_bytes": _leaf_bytes(dense_w),
            "resident_bytes": resident,
        })
        return w

    expert_rows = {}  # expert site -> row (wi/wg/wo summed)

    def one_bank(site, kind, w):
        dense_w = dense_by_site[(site, kind)]
        entries = (list(w.entries) if isinstance(w, ExpertBank)
                   else [jnp.take(w, e, axis=w.ndim - 3)
                         for e in range(w.shape[w.ndim - 3])])
        per_dense = _leaf_bytes(dense_w) // len(entries)
        for e, entry in enumerate(entries):
            if isinstance(entry, CompressedKernel):
                k_, fmt = "compressed", entry.fmt_name + (
                    "_packed" if entry.packed else "")
            else:
                k_, fmt = "dense", str(entry.dtype)
            row = expert_rows.setdefault(expert_site(site, e), {
                "site": expert_site(site, e), "kind": k_, "fmt": fmt,
                "dense_bytes": 0, "resident_bytes": 0,
            })
            row["dense_bytes"] += per_dense
            row["resident_bytes"] += entry_bytes(entry)
        return w

    _walk_kernels(served_params, one, expert_fn=one_bank)
    sites.extend(expert_rows.values())
    dense_total = sum(s["dense_bytes"] for s in sites)
    resident_total = sum(s["resident_bytes"] for s in sites)
    return {
        "sites": sites,
        "dense_kernel_bytes": dense_total,
        "resident_kernel_bytes": resident_total,
        "compressed_sites": sum(s["kind"] == "compressed" for s in sites),
        "dense_sites": sum(s["kind"] == "dense" for s in sites),
        "ratio": resident_total / max(dense_total, 1),
    }


def weight_bytes_summary(report: dict) -> dict:
    """Flat JSON-row form of a ``weight_bytes_report`` (the shape the
    launchers and benchmark tables both emit)."""
    return {
        "compressed_sites": report["compressed_sites"],
        "dense_sites": report["dense_sites"],
        "dense_weight_mb": round(report["dense_kernel_bytes"] / 1e6, 3),
        "resident_weight_mb": round(
            report["resident_kernel_bytes"] / 1e6, 3),
        "weight_bytes_ratio": round(report["ratio"], 4),
    }
