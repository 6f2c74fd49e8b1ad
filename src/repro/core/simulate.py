"""The simulator chokepoint: quantized matmul (paper eqns (6)-(9), Fig 2).

Every matmul-bearing layer in ``repro.nn`` routes through ``qmatmul`` (linear
layers) or ``qdq_activation`` (attention BMM operands).  This is the JAX
equivalent of INT-FP-QSim's layer replacement: instead of swapping torch
modules, the policy flows down the call tree and this module applies the
quantizer functions f_q^w, f_q^x, f_q^y around the contraction.

Execution backends — ``qmatmul`` dispatches to a registered backend, each
declaring the weight representation it consumes:

  ========== =========== =====================================================
  backend    consumes    semantics
  ========== =========== =====================================================
  ref        dense       QDQ both operands, contract in high precision
                         (paper-faithful; fp32 on CPU, bf16+f32-accum on TPU)
  int8       dense       quantize on the fly, contract int8 codes with int32
                         accumulation and per-group rescale (native MXU)
  fused      dense       Pallas fused QDQ+matmul kernel (repro.kernels)
  compressed codes       contract PRE-QUANTIZED weight codes + per-group unit
                         scales directly (int32 accumulate, per-group
                         rescale) — HBM never sees a dequantized kernel;
                         on the TPU the aligned int-ABFP case runs the
                         stored-codes Pallas kernel (``codes_kernel_takes``)
  ========== =========== =====================================================

Selection (``execution_backend``): a ``CompressedKernel`` weight always
takes the ``compressed`` backend (the representation decides); otherwise
``policy.fused`` -> fused, ``policy.compute == 'int8'`` with an eligible
int-ABFP policy -> int8, everything else -> ref.  The dispatch contract
also polices the mismatch case — should selection ever route compressed
storage to a dense-consuming backend, qmatmul raises rather than silently
densifying the kernel (unreachable under the current selection rules,
which prefer the compressed backend for compressed storage).
"""

from __future__ import annotations

import contextlib
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import abfp as abfp_mod
from repro.core.calibration import Calibrator
from repro.core.formats import IntFormat
from repro.core.policy import Policy, QuantPolicy, TensorQuant, resolve_policy
from repro.core.quantize import maybe_ste


def _dynamic_max_alpha(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.maximum(jnp.max(jnp.abs(x)), 1e-8)


def qdq_activation(
    x: jnp.ndarray,
    tq: TensorQuant | None,
    *,
    axis: int = -1,
    site: str = "",
    alpha=None,
) -> jnp.ndarray:
    """Apply an activation quantizer along the contraction ``axis``.

    ``alpha`` supplies the calibrated scale when ``tq.scaler == 'static'``
    (threaded from the QuantState by the owning layer).
    """
    if tq is None:
        return x
    calib = Calibrator.active()
    if calib is not None and site:
        calib.observe(site, x)
    if tq.scaler == "abfp":
        return abfp_mod.abfp_qdq(
            x, tq.fmt, axis=axis, n=tq.group, ste=tq.ste,
            scale_dtype=jnp.dtype(tq.scale_dtype),
        )
    if tq.scaler == "dynamic_max":
        return maybe_ste(x, _dynamic_max_alpha(x), tq.fmt, tq.ste)
    if tq.scaler == "static":
        if alpha is None:
            # Uncalibrated: fall back to dynamic max (calibration pass mode).
            alpha = _dynamic_max_alpha(x)
        return maybe_ste(x, jnp.asarray(alpha, jnp.float32), tq.fmt, tq.ste)
    raise ValueError(f"bad activation scaler {tq.scaler!r}")


def qdq_weight(
    w: jnp.ndarray, tq: TensorQuant | None, *, contract_axis: int = 0
) -> jnp.ndarray:
    """Apply the weight quantizer. ``w`` is (K, N); groups run along K."""
    if tq is None:
        return w
    if tq.scaler == "abfp":
        return abfp_mod.abfp_qdq(
            w, tq.fmt, axis=contract_axis, n=tq.group, ste=tq.ste,
            scale_dtype=jnp.dtype(tq.scale_dtype),
        )
    if tq.scaler == "channel_max":
        # Per-output-channel max over the contraction dim (paper weights).
        alpha = jnp.maximum(
            jnp.max(jnp.abs(w), axis=contract_axis, keepdims=True), 1e-8
        )
        return maybe_ste(w, alpha, tq.fmt, tq.ste)
    if tq.scaler == "dynamic_max":
        return maybe_ste(w, _dynamic_max_alpha(w), tq.fmt, tq.ste)
    raise ValueError(f"bad weight scaler {tq.scaler!r}")


def _fp_matmul(x: jnp.ndarray, w: jnp.ndarray, compute_dtype) -> jnp.ndarray:
    return jax.lax.dot_general(
        x.astype(compute_dtype),
        w.astype(compute_dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _int8_group_matmul(x, w, tq_in: TensorQuant, tq_w: TensorQuant):
    """Native path: per-group int8 contraction with int32 accumulation.

    y[..., nout] = sum_g s_x[..., g] * s_w[g, nout] * (xc_g . wc_g)
    """
    n = tq_in.group
    # honor each operand's scale_dtype so the compressed backend's aligned
    # path (which quantizes x identically) stays bit-exact with this one
    xc, xs, _ = abfp_mod.abfp_quantize(
        x, tq_in.fmt, axis=-1, n=n,
        scale_dtype=jnp.dtype(tq_in.scale_dtype))
    wc, ws, _ = abfp_mod.abfp_quantize(
        w, tq_w.fmt, axis=0, n=n,
        scale_dtype=jnp.dtype(tq_w.scale_dtype))
    # xc: (..., G, n) int8 ; wc: (N, G, n) int8 (axis 0 moved last by grouping)
    # partial[..., g, nout] — contract the n dim per group, int32 accum.
    partial = jnp.einsum(
        "...gk,ngk->...gn", xc, wc, preferred_element_type=jnp.int32
    )
    y = jnp.einsum(
        "...gn,...g,ng->...n",
        partial.astype(jnp.float32),
        xs.astype(jnp.float32),
        ws.astype(jnp.float32),
    )
    return y


def _is_compressed(w) -> bool:
    # name check: serving_transforms imports this module (no cycle)
    return type(w).__name__ == "CompressedKernel"


def codes_contraction(xc, xs, wk) -> jnp.ndarray:
    """``sum_g xs[..., g] * scale[g] * (xc_g . codes_g)`` in f32, each
    group's product exact in int32: x's ABFP codes ``(..., G, n)`` and
    steps ``(..., G)`` (``core.abfp.abfp_quantize``) against a rank-2
    ``CompressedKernel``'s stored codes."""
    ws = wk.scale.astype(jnp.float32)  # (G, N)
    if wk.packed:
        # each nibble plane meets its half of every group, exact in
        # int32, so the unpacked codes are never assembled
        lo, hi = wk.nibbles()
        partial = sum(
            jnp.einsum("...gk,gkn->...gn", xh, ch,
                       preferred_element_type=jnp.int32)
            for xh, ch in zip(jnp.split(xc, 2, axis=-1), (lo, hi)))
    else:
        partial = jnp.einsum("...gk,gkn->...gn", xc, wk.grouped_codes(),
                             preferred_element_type=jnp.int32)
    return jnp.einsum(
        "...gn,...g,gn->...n", partial.astype(jnp.float32),
        xs.astype(jnp.float32), ws,
    )


def codes_aligned(w, tq) -> bool:
    """Whether input quantizer ``tq`` gives x codes that meet compressed
    weight ``w``'s stored codes group for group (int ABFP at its group)."""
    return (tq is not None and isinstance(tq.fmt, IntFormat)
            and tq.scaler == "abfp" and tq.group == w.group)


def _compressed_group_matmul(x, wk, policy: QuantPolicy, *, site: str,
                             in_alpha, compute_dtype=jnp.float32):
    """Contract pre-quantized weight codes + unit scales directly.

    Aligned fast path (int-ABFP input whose group matches the stored
    grouping): quantize x to codes, contract int8xint8 with int32
    accumulation, rescale per (x-group, w-group) — bit-identical to the
    ``int8`` backend given identical codes.  Everything else (static /
    per-tensor / float-format / absent input quantizers) QDQs x per its
    rule and contracts the fp activations against the codes grouped by the
    stored structure, rescaling by the weight's unit scales — exactly
    QDQ(x) @ (codes * scales) without materializing the dense kernel.

    Precision contract: at f32 ``compute_dtype`` (the ServeEngine /
    benchmark configuration) this matches the ref backend up to f32
    accumulation order — greedy tokens are asserted identical.  Under a
    reduced compute dtype (bf16 dry-run graphs) the activation operand is
    rounded to ``compute_dtype`` exactly like ``_fp_matmul``; the weight
    side stays codes*scales (int codes are exact in bf16, but the fused
    product rounding of a dense bf16 operand cannot be reproduced without
    materializing the kernel) — the same documented
    equivalent-not-bit-identical deviation the int8 backend has.
    """
    if wk.codes.ndim != 2:
        raise ValueError(
            "compressed backend expects rank-2 (Kp, N) codes at apply "
            f"time, got {wk.codes.shape} (stacked kernels are sliced per "
            "layer by scan before they reach qmatmul)"
        )
    ws = wk.scale.astype(jnp.float32)  # (G, N)
    G, N = ws.shape
    n = wk.group
    tq = policy.input

    if codes_aligned(wk, tq):
        # abfp_quantize zero-pads x along K exactly like the stored codes
        xc, xs, _ = abfp_mod.abfp_quantize(
            x, tq.fmt, axis=-1, n=n,
            scale_dtype=jnp.dtype(tq.scale_dtype),
        )
        return codes_contraction(xc, xs, wk)

    xq = qdq_activation(x, tq, axis=-1, site=site + "/in", alpha=in_alpha)
    # mirror _fp_matmul's activation-operand rounding, then contract in f32
    xq = xq.astype(compute_dtype).astype(jnp.float32)
    if wk.pad:
        xq = jnp.pad(xq, [(0, 0)] * (xq.ndim - 1) + [(0, wk.pad)])
    xg = xq.reshape(*xq.shape[:-1], G, n)
    partial = jnp.einsum("...gk,gkn->...gn", xg,
                         wk.grouped_codes().astype(jnp.float32))
    return jnp.einsum("...gn,gn->...n", partial, ws)


# ---------------------------------------------------------------------------
# Execution-backend registry
# ---------------------------------------------------------------------------
class ExecBackend(NamedTuple):
    """One way to execute the quantized contraction.

    ``weight_repr`` declares the weight representation the backend
    consumes: 'dense' (an (K, N) array) or 'compressed'
    (``CompressedKernel`` codes + scales).
    """

    name: str
    weight_repr: str
    fn: Callable


_BACKENDS: dict[str, ExecBackend] = {}


def register_backend(name: str, weight_repr: str = "dense"):
    def deco(fn):
        _BACKENDS[name] = ExecBackend(name, weight_repr, fn)
        return fn
    return deco


def backends() -> dict[str, ExecBackend]:
    """The registered execution backends (read-only view)."""
    return dict(_BACKENDS)


@register_backend("ref")
def _ref_backend(x, w, policy, *, site, in_alpha, compute_dtype):
    """Paper-faithful: QDQ both operands, contract in high precision."""
    if not policy.enabled:
        return _fp_matmul(x, w, compute_dtype)
    xq = qdq_activation(
        x, policy.input, axis=-1, site=site + "/in", alpha=in_alpha
    )
    wq = qdq_weight(w, policy.weight, contract_axis=0)
    return _fp_matmul(xq, wq, compute_dtype)


@register_backend("int8")
def _int8_backend(x, w, policy, *, site, in_alpha, compute_dtype):
    """Beyond-paper: real int8 MXU contraction of freshly quantized codes."""
    return _int8_group_matmul(x, w, policy.input, policy.weight)


@register_backend("fused")
def _fused_backend(x, w, policy, *, site, in_alpha, compute_dtype):
    """Pallas fused QDQ+matmul (TPU target; interpret on CPU)."""
    from repro.kernels import ops as kops  # lazy: pallas import

    return kops.abfp_matmul_fused(
        x, w, policy, interpret=kops.should_interpret()
    )


_SITE_TALLIES: list[dict] = []


@contextlib.contextmanager
def compressed_site_tally():
    """Count the compressed sites traced inside the block, by contraction.

    Yields ``{"qmm_kernel_sites": k, "qmm_fallback_sites": f}``: sites
    that lowered to the stored-codes Pallas kernel, and sites that took
    the jnp einsum path.  The counts are of traced sites, so a site inside
    a scanned layer stack counts once.
    """
    tally = {"qmm_kernel_sites": 0, "qmm_fallback_sites": 0}
    _SITE_TALLIES.append(tally)
    try:
        yield tally
    finally:
        _SITE_TALLIES.pop()  # blocks nest: the last one opened closes


def codes_kernel_takes(w, policy: QuantPolicy, stacked: bool = False
                       ) -> bool:
    """Whether a compressed site contracts in a stored-codes kernel:
    ``quant_matmul_codes`` for one kernel, ``quant_matmul_grouped`` for a
    ``stacked`` one (an expert stack, ``(E, Kp, N)``).

    It does where the input is int ABFP at the stored group (the aligned
    case) and either the backend is a TPU and the shapes tile — N a
    multiple of 128, the stored codes whole groups, a group's stored rows
    whole (32, 128) int8 tiles — or ``policy.fused`` forces the kernel (in
    interpret mode off the TPU).  Everything else keeps the jnp
    contraction.
    """
    from repro.kernels import ops as kops  # lazy: pallas import

    if not (codes_aligned(w, policy.input)
            and w.codes.ndim == (3 if stacked else 2)):
        return False
    G, N = w.scale.shape[-2:]
    per_code = 2 if w.packed else 1
    tiles = (N % 128 == 0 and w.codes.shape[-2] * per_code == G * w.group
             and w.group // per_code % 32 == 0)
    return policy.fused or (not kops.should_interpret() and tiles)


def tally_site(kernel: bool) -> None:
    """Count one traced compressed site in every open
    ``compressed_site_tally``."""
    for tally in _SITE_TALLIES:
        tally["qmm_kernel_sites" if kernel else "qmm_fallback_sites"] += 1


@register_backend("compressed", weight_repr="compressed")
def _compressed_backend(x, w, policy, *, site, in_alpha, compute_dtype):
    """Serve pre-quantized weight codes straight into the contraction."""
    kernel = codes_kernel_takes(w, policy)
    tally_site(kernel)
    if kernel:
        from repro.kernels import ops as kops  # lazy: pallas import

        return kops.quant_matmul_fused(
            x, w, policy.input, interpret=kops.should_interpret()
        )
    return _compressed_group_matmul(x, w, policy, site=site,
                                    in_alpha=in_alpha,
                                    compute_dtype=compute_dtype)


def _int8_native_ok(policy: QuantPolicy) -> bool:
    tin, tw = policy.input, policy.weight
    return (
        tin is not None and tw is not None
        and tin.scaler == "abfp" and tw.scaler == "abfp"
        and tin.group == tw.group
        and isinstance(tin.fmt, IntFormat) and isinstance(tw.fmt, IntFormat)
    )


def execution_backend(policy: QuantPolicy, w) -> ExecBackend:
    """Select the backend for a *resolved* flat policy + weight.

    The weight representation wins: compressed storage always executes in
    the compressed domain (that backend internally handles every input
    spec, including fp32/no-input rules, without densifying the kernel).
    Dense weights follow the policy: fused -> int8 (when the policy is an
    int-ABFP pair with matched groups) -> ref.
    """
    if _is_compressed(w):
        return _BACKENDS["compressed"]
    if not policy.enabled:
        return _BACKENDS["ref"]
    if policy.fused:
        return _BACKENDS["fused"]
    if policy.compute == "int8" and _int8_native_ok(policy):
        return _BACKENDS["int8"]
    return _BACKENDS["ref"]


def qmatmul(
    x: jnp.ndarray,
    w,
    policy: Policy,
    *,
    site: str = "",
    in_alpha=None,
    out_alpha=None,
    compute_dtype=jnp.float32,
) -> jnp.ndarray:
    """Quantized-simulated ``x @ w`` with ``x: (..., K)`` and ``w: (K, N)``
    dense or a ``CompressedKernel`` (codes + per-group scales).

    Layers with multi-dim contractions flatten to this canonical form first
    (see nn.linear.DenseGeneral) so the kernels and the int8 path stay simple.
    A site-addressed PolicyMap is resolved here against ``site`` — the one
    chokepoint where per-site mixed precision takes effect (resolution is on
    static strings at trace time; the compiled graph sees a flat policy).
    The resolved policy + weight representation then pick an execution
    backend (see module docstring).
    """
    policy = resolve_policy(policy, site)
    backend = execution_backend(policy, w)
    if backend.weight_repr == "dense" and _is_compressed(w):
        # repr-mismatch guard: unreachable under the current selection
        # (compressed storage always routes to the compressed backend);
        # raising — instead of silently densifying — surfaces any future
        # selection bug that would defeat the keep-weights-compressed
        # invariant as an error rather than a memory regression
        raise ValueError(
            f"execution backend {backend.name!r} consumes dense weights "
            f"but site {site!r} holds compressed storage; selection must "
            "route CompressedKernel weights to a compressed-consuming "
            "backend (decompress explicitly if densification is intended)"
        )
    # the site names the matmul's ops in a profiler trace
    with jax.named_scope(site) if site else contextlib.nullcontext():
        y = backend.fn(x, w, policy, site=site, in_alpha=in_alpha,
                       compute_dtype=compute_dtype)
        if policy.output is not None:
            y = qdq_activation(
                y, policy.output, axis=-1, site=site + "/out",
                alpha=out_alpha)
    return y


# ---------------------------------------------------------------------------
# Attention-backend registry (mirror of the execution-backend registry)
# ---------------------------------------------------------------------------
class AttnBackend(NamedTuple):
    """One way to execute the attention block's contractions.

    ``kv_repr`` declares the KV representation the backend consumes:
    'dense' (fp K/V, dequantized if stored quantized) or 'codes'
    (int8/fp8 cache codes + unit scales, contracted in-kernel).
    """

    name: str
    kv_repr: str
    fn: Callable | None  # kernel entry; None when module heuristics decide


_ATTN_BACKENDS: dict[str, AttnBackend] = {}


def register_attn_backend(name: str, kv_repr: str = "dense"):
    def deco(fn):
        _ATTN_BACKENDS[name] = AttnBackend(name, kv_repr, fn)
        return fn
    return deco


def attn_backends() -> dict[str, AttnBackend]:
    """The registered attention backends (read-only view)."""
    return dict(_ATTN_BACKENDS)


def attention_backend(policy: QuantPolicy) -> AttnBackend:
    """Look up the backend a *resolved* flat policy selects.

    ``nn.attention`` resolves the PolicyMap at the block site and calls
    this — an unknown name raises here (the registry is the source of
    truth), the same contract ``execution_backend`` pins for matmuls.
    """
    name = getattr(policy, "attn_backend", "auto") or "auto"
    if name not in _ATTN_BACKENDS:
        raise ValueError(
            f"unknown attention backend {name!r} "
            f"(registered: {sorted(_ATTN_BACKENDS)})")
    return _ATTN_BACKENDS[name]


# 'auto' / 'ref' carry no kernel: the module's heuristics (reference /
# blockwise / opt-in flash) or the forced-jnp path decide respectively.
_ATTN_BACKENDS["auto"] = AttnBackend("auto", "dense", None)
_ATTN_BACKENDS["ref"] = AttnBackend("ref", "dense", None)


@register_attn_backend("fused")
def _fused_attn_backend(*args, **kw):
    """Dense Pallas flash kernel (TPU target; interpret on CPU)."""
    from repro.kernels import ops as kops  # lazy: pallas import

    return kops.flash_attention_gqa(*args, **kw)


@register_attn_backend("compressed", kv_repr="codes")
def _compressed_attn_backend(*args, **kw):
    """Quantized-KV flash kernel: cache codes contracted in VMEM."""
    from repro.kernels import ops as kops  # lazy: pallas import

    return kops.flash_attention_quant_gqa(*args, **kw)
