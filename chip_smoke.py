#!/usr/bin/env python3
"""Run the serving path once on one TPU chip and check what comes out.

    python3 chip_smoke.py

Everything runs in this one process, which starts no child: a chip belongs
to one process at a time.  Phases, in order:

  device   JAX's first device must be a TPU.  On anything else the script
           says what it found and exits 1 without printing a result.
  kernels  every Pallas kernel of ``src/repro/kernels`` at qwen2-7b widths
           (``repro.kernels.tpu_cases``), compiled with interpret=False.
           Each compiled program must hold a ``tpu_custom_call`` and its
           output must agree with the kernel's ``kernels/ref.py`` oracle.
  serve    qwen2-7b at its published widths, cut to 4 layers, built by
           ``launch/serve``'s ``build_engine``: 8 seeded requests (prompts
           of 32-256 tokens, 32 new tokens each) on the paged engine, first
           fp32 with fp pages, then W4A8 compressed weights with int8 pages
           and the ``compressed`` attention backend.  The second run's
           compiled steps must hold the quantized flash kernel, and its
           prefill and decode logits must agree with the ``ref`` attention
           backend on the same weights and pages.

Compile seconds, wall seconds and peak device bytes are bring-up facts,
not benchmark numbers.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import gc
import json
import os
import re
import sys
import time
from pathlib import Path

# libtpu reads this as it loads; unset, it logs under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
N_LAYERS = 4
SERVE_ARGS = ["--arch", "qwen2-7b", "--full", "--paged", "--n-slots", "4",
              "--max-len", "512", "--page-size", "16", "--n-requests", "8",
              "--max-new-tokens", "32", "--seed", str(SEED)]
RUNS = [
    ("fp32, fp pages, default attention",
     ["--policy", "fp32", "--kv", "fp"]),
    ("w4a8_abfp, compressed weights, int8 pages, compressed attention",
     ["--policy", "w4a8_abfp", "--compress", "--kv", "int8",
      "--attn-backend", "compressed"]),
]
PROMPT_LENS = (32, 256)
# compressed vs ref attention logits, as a share of max |ref logits|
LOGIT_TOL = 1e-2
LOGIT_WHY = ("both backends round the same operands to bf16 for one MXU "
             "pass; f32 accumulation order and an int8 probability code "
             "one step (1/127 of its group max) apart at a rounding "
             "boundary remain, and four layers keep that under 1% of the "
             "logit range, which a wrong mask, scale or group would exceed")


def log(msg: str) -> None:
    print(msg, flush=True)


def pallas_kernels(hlo_text: str) -> set[str]:
    """Names of the Pallas kernels a compiled TPU program calls."""
    return {m.group(1) for m in re.finditer(
        r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*"
        r"custom_call_target=\"tpu_custom_call\"", hlo_text)}


def find_tpu():
    """(device facts, None) on a TPU, else (None, why not)."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        return None, f"JAX found no device: {e}"
    d = devices[0]
    if d.platform != "tpu":
        return None, (f"no TPU found: JAX's first device is {d.platform} "
                      f"({d.device_kind}); this smoke never runs on it")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}, None


def kernel_phase() -> bool:
    import jax
    import numpy as np

    from repro.kernels.tpu_cases import kernel_cases

    ok = True
    for i, case in enumerate(kernel_cases()):
        args = jax.jit(case.make)(jax.random.PRNGKey(SEED + i))
        t0 = time.perf_counter()
        compiled = jax.jit(case.kernel).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        kernels = pallas_kernels(compiled.as_text())
        got = np.asarray(compiled(*args), np.float32)
        want = np.asarray(jax.jit(case.ref)(*args), np.float32)
        err = float(np.max(np.abs(got - want)))
        tol = case.tol * float(np.max(np.abs(want)))
        good = (bool(kernels) and got.shape == want.shape
                and bool(np.isfinite(got).all()) and err <= tol)
        ok &= good
        log(f"kernel {'ok  ' if good else 'FAIL'} {case.name}: max|err| "
            f"{err:.3e}, tol {tol:.3e} ({case.tol:.3g} of max|ref|: "
            f"{case.why}); Pallas kernels {sorted(kernels)}; "
            f"compile {compile_s:.2f}s")
    return ok


def make_requests(args, vocab: int):
    import numpy as np

    from repro.serve.engine import Request

    rng = np.random.RandomState(args.seed)
    lo, hi = PROMPT_LENS
    return [Request(uid=uid,
                    prompt=rng.randint(0, vocab, size=int(
                        rng.randint(lo, hi + 1))).astype(np.int32),
                    max_new_tokens=args.max_new_tokens)
            for uid in range(args.n_requests)]


def compare_attention_backends(engine, prompt) -> bool:
    """Prefill one chunk, then decode one token, through the model's paged
    step under the engine's policy (compressed attention) and under the
    ``ref`` backend, on the same compressed weights and the same pages."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.policy import with_attn_backend
    from repro.serve.kv_pages import pages_for

    model, params = engine.model, engine.params
    policies = {"compressed": engine.policy,
                "ref": with_attn_backend(engine.policy, "ref")}
    steps = {name: jax.jit(lambda p, t, s, v, pol=pol: model.paged_step(
                 p, t, s, n_valid=v, policy=pol))
             for name, pol in policies.items()}
    n, chunk = engine.n_slots, engine.geometry.prefill_chunk
    table = np.full(engine.table.shape, -1, np.int32)
    need = pages_for(min(len(prompt), chunk) + 1, engine.geometry.page_size)
    table[0, :need] = np.arange(need)
    state = engine.state._replace(pages=engine.state.pages._replace(
        table=jnp.asarray(table)))
    only_row0 = lambda k: jnp.asarray([k] + [0] * (n - 1), jnp.int32)
    first = prompt[:chunk]
    tokens = np.zeros((n, chunk), np.int32)
    tokens[0, :len(first)] = first
    n_valid = len(first)

    ok = True
    for phase in ("prefill", "decode"):
        out = {name: f(params, jnp.asarray(tokens), state,
                       only_row0(n_valid))
               for name, f in steps.items()}
        got, want = (np.asarray(out[k][0][0, :model.cfg.vocab], np.float32)
                     for k in ("compressed", "ref"))
        err = float(np.max(np.abs(got - want)))
        tol = LOGIT_TOL * float(np.max(np.abs(want)))
        good = bool(np.isfinite(got).all()) and err <= tol
        ok &= good
        log(f"serve {'ok  ' if good else 'FAIL'} {phase} logits, compressed "
            f"vs ref attention: max|err| {err:.3e}, tol {tol:.3e} "
            f"({LOGIT_TOL:g} of max|ref|: {LOGIT_WHY}); argmax "
            f"{int(got.argmax())} vs {int(want.argmax())}")
        # decode from the ref run's pages, which both backends then read
        state = out["ref"][1]
        tokens = np.zeros((n, 1), np.int32)
        tokens[0, 0] = int(want.argmax())
        n_valid = 1
    return ok


def serve_phase(cfg) -> bool:
    import jax

    from repro.launch import serve

    ok = True
    for label, extra in RUNS:
        args = serve.build_parser().parse_args(SERVE_ARGS + extra)
        t0 = time.perf_counter()
        engine, run_cfg, _ = serve.build_engine(args, cfg=cfg)
        jax.block_until_ready(engine.params)
        build_s = time.perf_counter() - t0
        param_bytes = sum(a.nbytes for a in jax.tree.leaves(engine.params))
        t0 = time.perf_counter()
        kernels = {k: pallas_kernels(c.as_text())
                   for k, c in engine.compile_steps().items()}
        compile_s = time.perf_counter() - t0
        log(f"serve run: {label}; {run_cfg.n_layers} layers at d_model "
            f"{run_cfg.d_model}, {param_bytes} bytes of served weights; "
            f"engine built in {build_s:.1f}s, both steps compiled in "
            f"{compile_s:.1f}s")
        reqs = make_requests(args, run_cfg.vocab)
        if args.attn_backend == "compressed":
            for name, names in kernels.items():
                good = "flash_attention_quant" in names
                ok &= good
                log(f"serve {'ok  ' if good else 'FAIL'} compiled {name} "
                    f"step calls Pallas kernels {sorted(names)}")
            ok &= compare_attention_backends(engine, reqs[0].prompt)
        for r in reqs:
            engine.submit(r)
        t0 = time.perf_counter()
        done = engine.run_until_done()
        wall_s = time.perf_counter() - t0
        n_tok = sum(len(c.tokens) for c in done)
        good = (len(done) == len(reqs) and all(
            len(c.tokens) == args.max_new_tokens
            and c.finished_reason == "length"
            and all(0 <= t < run_cfg.vocab for t in c.tokens) for c in done))
        ok &= good
        peak = jax.devices()[0].memory_stats() or {}
        log(f"serve {'ok  ' if good else 'FAIL'} {label}: {len(done)}/"
            f"{len(reqs)} requests, {n_tok} generated tokens, "
            f"{engine.ticks} ticks, wall {wall_s:.1f}s, peak_bytes_in_use "
            f"{peak.get('peak_bytes_in_use', 'not reported')}")
        engine = done = None
        gc.collect()
    return ok


def main() -> int:
    dev, why = find_tpu()
    if dev is None:
        log(why)
        return 1
    log(f"device: {dev}")
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    cache = Path(enable_compile_cache())
    entries = sum(1 for _ in cache.iterdir()) if cache.is_dir() else 0
    log(f"compile cache: {cache}, {entries} entries at start")
    cfg = get_config("qwen2-7b").replace(n_layers=N_LAYERS)
    failed = [name for name, phase in (("kernel", kernel_phase),
                                       ("serve", lambda: serve_phase(cfg)))
              if not phase()]
    if failed:
        log(f"failed phases: {', '.join(failed)}")
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
