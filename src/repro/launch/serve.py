"""Serving launcher: continuous-batching engine over a reduced or full arch.

``python -m repro.launch.serve --arch qwen2-7b --reduced --policy w4a8_abfp``
drives synthetic requests through the ServeEngine and reports what it
served, with the spans and waste counts the paged engine records
(``serve.tracing``), summed over the run.  The full-size serving graphs
(decode_32k / long_500k) are exercised by the dry-run; ``chip_smoke.py``
builds its engines through ``build_engine`` at published widths on the
chip.
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--policy", default=None,
                    help="policy preset (default fp32, or the --recipe's "
                    "paired policy)")
    ap.add_argument("--recipe", default=None,
                    help="QuantRecipe name applied to the weights before "
                    "serving (e.g. smoothquant+gptq); calibrates on "
                    "synthetic prompts")
    ap.add_argument("--compress", action="store_true",
                    help="compressed-domain serving: store each kernel per "
                    "its resolved site rule (int codes + group scales; "
                    "INT4 packs two-per-byte) and contract the codes "
                    "directly — reports resident weight bytes")
    ap.add_argument("--expert-cache", type=int, default=None,
                    help="expert-resident MoE serving (requires --compress "
                    "on an MoE arch): LRU capacity, in experts per MoE "
                    "site, of decompressed-dense copies admitted by "
                    "routing frequency; reports hit/miss + residency "
                    "stats (E//4 is the useful starting point)")
    ap.add_argument("--expert-precision", default="flat",
                    choices=("flat", "auto"),
                    help="'auto' probes routing frequencies and assigns "
                    "per-expert weight formats (hot experts INT8, cold "
                    "INT4) as */experts.{e} policy rules before serving; "
                    "'flat' keeps the policy's single weight format")
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--n-requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="serve with the paged-KV engine (block pool + "
                    "chunked prefill) instead of fixed ring-buffer slots; "
                    "reports page-pool and resident-KV-byte accounting")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (--paged)")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="physical pages in the shared pool (--paged; "
                    "default sizes for full occupancy of every slot)")
    ap.add_argument("--kv", default="auto",
                    choices=("auto", "fp", "int8", "fp8"),
                    help="page storage format (--paged); 'auto' follows "
                    "the policy's kv_cache mode")
    ap.add_argument("--attn-backend", default="auto",
                    choices=("auto", "ref", "fused", "compressed"),
                    help="attention-backend dispatch at the attention "
                    "block sites: 'compressed' contracts stored int8/fp8 "
                    "KV codes inside the quantized flash kernel (needs "
                    "quantized storage — QL601), 'fused' runs the dense "
                    "Pallas kernel where eligible, 'ref' pins the jnp "
                    "path, 'auto' keeps the module defaults")
    ap.add_argument("--speculate", action="store_true",
                    help="speculative serving: a compressed low-precision "
                    "draft (same param tree, --draft-preset policy) "
                    "proposes --draft-k tokens per round and the target "
                    "verifies them in one chunked pass; reports "
                    "acceptance stats (--paged selects paged KV with fp "
                    "pages — --kv is ignored)")
    ap.add_argument("--draft-preset", default="w4a8_abfp",
                    help="draft-side policy preset (--speculate)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="draft tokens proposed per verify pass "
                    "(--speculate)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy; "
                    "under --speculate, > 0 switches acceptance to "
                    "rejection sampling)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="per-request top-k sampling cutoff (0 = full "
                    "distribution)")
    ap.add_argument("--no-lint", action="store_true",
                    help="skip the qlint pre-flight gate")
    return ap


def build_engine(args, cfg=None):
    """Build the serving engine ``args`` (the parsed CLI options) describe.

    ``cfg`` replaces the ``--arch``/``--reduced`` lookup when given (the
    chip smoke serves a depth-cut config at published widths).  Returns
    ``(engine, cfg, info)``; ``info`` carries the resolved policy name and
    the recipe / expert-precision reports for the run summary.
    """
    from repro.configs import get_config
    from repro.core.policy import preset
    from repro.models import build_model
    from repro.nn.module import unbox
    from repro.serve.engine import PagedServeEngine, ServeEngine
    from repro.serve.kv_pages import PageGeometry, pages_for
    from repro.serve.speculative import SpeculativeServeEngine

    if cfg is None:
        cfg = get_config(args.arch)
        if args.reduced:
            cfg = cfg.reduced()
    if cfg.family == "vit":
        raise SystemExit(
            f"{args.arch} is an encoder-only classifier: nothing to "
            "decode. Use `python -m benchmarks.run --only vit_table`.")

    from repro.core.policy import has_layer_rules

    rec = None
    if args.recipe:
        from repro.core.recipe import get_recipe

        rec = get_recipe(args.recipe)
    # an explicit --policy wins; otherwise the recipe's paired policy
    policy_name = args.policy or (rec.policy_preset if rec else None) or "fp32"
    policy = preset(policy_name, n_layers=cfg.n_layers)
    if args.attn_backend != "auto":
        from repro.core.policy import with_attn_backend

        policy = with_attn_backend(policy, args.attn_backend)
    if has_layer_rules(policy):
        # layer-indexed PolicyMap rules need per-layer sites (eager unroll)
        cfg = cfg.replace(scan_layers=False)
    recipe_info = {}
    if rec is not None:
        # calibration observers need eager per-layer execution
        cfg = cfg.replace(scan_layers=False, remat="none")
    pages_geo = None
    if args.paged:
        # mirror PagedServeEngine's defaults so the gate lints what runs
        chunk = max(args.page_size, -(-64 // args.page_size) * args.page_size)
        n_pages = (args.n_pages if args.n_pages is not None
                   else args.n_slots * pages_for(args.max_len,
                                                 args.page_size))
        pages_geo = PageGeometry(page_size=args.page_size, n_pages=n_pages,
                                 max_len=args.max_len, prefill_chunk=chunk)
    experts = None
    if args.expert_cache is not None or args.expert_precision != "flat":
        if args.speculate:
            raise SystemExit(
                "--expert-cache / --expert-precision are not supported "
                "under --speculate (the draft/target pair shares no "
                "expert store)")
        if args.expert_cache is not None and not args.compress:
            from repro.analysis.messages import \
                expert_cache_requires_compress_message

            raise SystemExit(expert_cache_requires_compress_message())
        experts = {"cache_capacity": args.expert_cache}
    draft_policy = None
    speculative = None
    if args.speculate:
        draft_policy = preset(args.draft_preset, n_layers=cfg.n_layers)
        if has_layer_rules(draft_policy):
            cfg = cfg.replace(scan_layers=False)
        speculative = {"draft_policy": draft_policy,
                       "draft_k": args.draft_k}
    attn_ctx = {"engine": "paged" if args.paged else "fixed"}
    if args.paged and args.kv != "auto":
        attn_ctx["kv"] = args.kv
    if not args.no_lint:
        # pre-flight gate: errors abort before any weights are built
        from repro.launch.lint import preflight

        preflight(cfg, policy, rec, compress=args.compress,
                  scan_layers=cfg.scan_layers, pages=pages_geo,
                  speculative=speculative, experts=experts, attn=attn_ctx,
                  where="serve")
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(args.seed)))
    if rec is not None:
        import sys

        from repro.core.policy import replace_enabled
        from repro.core.recipe import apply_recipe, quantizes_weights_offline

        crng = np.random.RandomState(args.seed + 1)
        batches = [
            {"tokens": crng.randint(0, cfg.vocab, (2, 32)).astype(np.int32)}
            for _ in range(2)
        ]
        # observers only fire at quantized matmuls: calibrate under an
        # enabled policy even when serving fp32
        obs = policy if policy.enabled else preset("w4a8_mse")
        res = apply_recipe(rec, model, params, batches, policy,
                           calib_policy=obs)
        params = res.params
        if quantizes_weights_offline(rec):
            # GPTQ left pre-quantized kernels: drop runtime weight QDQ
            # (the prequant serving convention — re-quantization adds
            # pure double-quantization noise)
            policy = replace_enabled(policy, weight=None)
        recipe_info = {"recipe": rec.name,
                       "recipe_calibrations": res.n_calibrations}
        if res.qtree is not None:
            # the serving path has no static-q plumbing: static scalers
            # fall back to dynamic-max at prefill/decode
            print(f"note: recipe {rec.name!r} produced a static q tree; "
                  "serving ignores it (dynamic-max fallback)",
                  file=sys.stderr)
    expert_info = {}
    if args.expert_precision == "auto":
        from repro.serve.experts import (assign_expert_precision,
                                         hot_experts, route_frequencies)

        if not getattr(model, "is_moe", False):
            # the QL502 gate blocks this before weights are built; mirror
            # it here for --no-lint runs
            from repro.analysis.messages import expert_non_moe_message

            raise SystemExit(expert_non_moe_message(
                "--expert-precision auto", cfg.name))
        # offline assignment pass: probe routing frequencies on synthetic
        # prompts (group-size-aligned), hottest E//4 experts -> INT8,
        # the rest INT4, emitted as a serializable per-expert PolicyMap
        prng = np.random.RandomState(args.seed + 2)
        gt = max(1, cfg.moe_group_tokens)
        probe = [prng.randint(0, cfg.vocab, (1, gt)).astype(np.int32)
                 for _ in range(2)]
        loads = route_frequencies(model, params, probe, policy=policy)
        n_hot = max(1, cfg.n_experts // 4)
        hot = hot_experts(loads, n_hot)
        try:
            policy = assign_expert_precision(loads, policy, n_hot=n_hot)
        except ValueError as e:  # e.g. fp32 base: no weight rule to split
            raise SystemExit(f"--expert-precision auto: {e}")
        policy_name = policy.name
        expert_info["expert_precision"] = {
            "mode": "auto",
            "hot_experts": [int(e) for e in hot],
            "loads": [float(x) for x in np.asarray(loads).sum(axis=0)],
        }
        if not args.no_lint:
            # re-gate with the assigned map + hot set (QL503 inversion)
            preflight(cfg, policy, rec, compress=args.compress,
                      scan_layers=cfg.scan_layers, pages=pages_geo,
                      experts={"cache_capacity": args.expert_cache,
                               "hot_experts": hot}, where="serve")
    if args.speculate:
        kw = {}
        if args.paged:
            kw = dict(kv_cache="paged", page_size=pages_geo.page_size,
                      n_pages=pages_geo.n_pages,
                      prefill_chunk=pages_geo.prefill_chunk)
        engine = SpeculativeServeEngine(
            model, params, target_policy=policy, draft_policy=draft_policy,
            draft_k=args.draft_k, n_slots=args.n_slots,
            max_len=args.max_len, **kw,
        )
    elif args.paged:
        engine = PagedServeEngine(
            model, params, n_slots=args.n_slots, max_len=args.max_len,
            policy=policy, compress=args.compress,
            page_size=pages_geo.page_size, n_pages=pages_geo.n_pages,
            prefill_chunk=pages_geo.prefill_chunk, kv=args.kv,
            expert_cache=args.expert_cache,
        )
    else:
        engine = ServeEngine(
            model, params, n_slots=args.n_slots, max_len=args.max_len,
            policy=policy, compress=args.compress,
            expert_cache=args.expert_cache,
        )
    return engine, cfg, {"policy": policy_name, "recipe": recipe_info,
                         "experts": expert_info}


def host_seconds(spans: dict):
    """The host's own seconds in the engine's ticks, from the recorder's
    totals: ticks less the step calls and read-backs inside them; None
    for an engine that records no ticks."""
    if "serve.tick" not in spans:
        return None
    return spans["serve.tick"]["s"] - sum(
        spans[k]["s"] for k in ("serve.step", "serve.readback")
        if k in spans)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve import tracing
    from repro.serve.engine import Request

    enable_compile_cache()
    engine, cfg, info = build_engine(args)
    policy_name = info["policy"]
    recipe_info, expert_info = info["recipe"], info["experts"]
    compress_info = {}
    if args.compress:
        from repro.models.serving_transforms import weight_bytes_summary

        wb = engine.weight_bytes
        if wb["compressed_sites"] == 0:
            import sys

            print("note: --compress found no int-format weight rules to "
                  "compress (all sites dense)", file=sys.stderr)
        compress_info = weight_bytes_summary(wb)

    rng = np.random.RandomState(args.seed)
    for uid in range(args.n_requests):
        plen = int(rng.randint(4, 17))
        engine.submit(
            Request(
                uid=uid,
                prompt=rng.randint(0, cfg.vocab, size=plen).astype(np.int32),
                max_new_tokens=args.max_new_tokens,
                temperature=args.temperature,
                top_k=args.top_k,
            )
        )
    done = engine.run_until_done()
    spans = tracing.totals()
    total_tokens = sum(len(c.tokens) for c in done)
    # per-request completion metadata (not just aggregate tok/s): accept
    # counts and target steps are per-request facts, so report them there
    completions = []
    for c in done:
        row = {
            "uid": c.uid,
            "prompt_len": c.prompt_len,
            "n_tokens": len(c.tokens),
            "finished_reason": c.finished_reason,
        }
        if args.speculate:
            row.update({
                "target_steps": c.target_steps,
                "drafted_tokens": c.drafted_tokens,
                "accepted_draft_tokens": c.accepted_draft_tokens,
                "acceptance_rate": round(
                    c.accepted_draft_tokens / c.drafted_tokens, 4)
                    if c.drafted_tokens else 0.0,
            })
        completions.append(row)
    spec_info = {}
    if args.speculate:
        stats = engine.acceptance_stats()
        spec_info = {
            "speculative": {
                "draft_preset": args.draft_preset,
                "draft_k": args.draft_k,
                "kv_cache": engine.kv_cache,
                "rounds": stats["rounds"],
                "target_steps": stats["target_steps"],
                "draft_steps": stats["draft_steps"],
                "drafted": stats["drafted"],
                "accepted": stats["accepted"],
                "acceptance_rate": round(stats["acceptance_rate"], 4),
                "accepted_per_target_step": round(
                    stats["accepted_per_target_step"], 4),
            }
        }
        if engine.weight_bytes is not None:
            from repro.models.serving_transforms import weight_bytes_summary

            spec_info["speculative"]["draft_weights"] = \
                weight_bytes_summary(engine.weight_bytes)
        if args.paged:
            spec_info["speculative"]["page_stats"] = engine.page_stats()
    estats = None if args.speculate else engine.expert_stats()
    if estats is not None:
        expert_info["experts"] = {
            "capacity": estats["capacity"],
            "n_experts": estats["n_experts"],
            "n_sites": estats["n_sites"],
            "cached_experts": estats["cached_experts"],
            "hits": estats["hits"],
            "misses": estats["misses"],
            "evictions": estats["evictions"],
            "hit_rate": round(estats["hit_rate"], 4),
            "store_bytes": estats["store_bytes"],
            "cache_bytes": estats["cache_bytes"],
            "resident_bytes": estats["resident_bytes"],
            "hot_bytes": estats["hot_bytes"],
            "cold_bytes": estats["cold_bytes"],
            "dense_bytes": estats["dense_bytes"],
            "resident_ratio": round(estats["ratio"], 4),
            "sites": estats["sites"],
        }
    attn_info = {"attention": {
        "backend": getattr(engine, "attn_backend", "auto"),
        "engine": "paged" if args.paged else "fixed",
    }}
    paged_info = {}
    if args.paged and not args.speculate:
        stats = engine.page_stats()
        # capacity quoted per fully-occupied page, not the drained pool
        cap = engine.kv_bytes()
        paged_info = {
            "paged": True,
            "kv": engine.kv,
            "page_size": engine.geometry.page_size,
            "prefill_chunk": engine.geometry.prefill_chunk,
            **stats,
        }
        if stats["pages_in_use"]:
            paged_info.update(cap)
    print(
        json.dumps(
            {
                "arch": cfg.name,
                "policy": policy_name,
                "requests": len(done),
                "generated_tokens": total_tokens,
                "ticks": engine.ticks,
                "host_s": host_seconds(spans),
                "spans": spans,
                "completions": completions,
                **recipe_info,
                **compress_info,
                **expert_info,
                **spec_info,
                **attn_info,
                **paged_info,
            }
        )
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
