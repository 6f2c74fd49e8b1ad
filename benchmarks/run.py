"""Benchmark harness entry point: ``python -m benchmarks.run``.

Runs one benchmark per paper table/figure (see benchmarks/tables.py) on
in-framework-trained proxy models, printing rows + qualitative claim
checks, and writes artifacts/bench/results.{json,csv}.

Also emits the roofline summary (reads the dry-run artifacts produced by
``python -m repro.launch.dryrun --all``) so the two reports land in one
place for EXPERIMENTS.md.

Flags:
    --only table1,fig3     run a subset
    --quick                tiny proxies / few steps (CI smoke, ~2 min)
    --steps N --qat-steps N  override training budgets
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import time


def roofline_summary(out_dir="artifacts/dryrun"):
    rows = []
    for path in sorted(glob.glob(os.path.join(out_dir, "*__sp.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("status") != "ok":
            continue
        t = rec["terms"]
        rows.append({
            "arch": rec["arch"], "shape": rec["shape"],
            "dominant": t["dominant"],
            "bound_s": round(t["roofline_bound_s"], 4),
            "compute_frac": round(t["compute_fraction_of_bound"], 4),
            "hbm_gb": rec["hbm_gb_per_device"],
            "useful_ratio": round(rec["useful_compute_ratio"], 3),
        })
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated table names")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--qat-steps", type=int, default=None)
    ap.add_argument("--out", default="artifacts/bench/results")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import common as C
    from benchmarks import tables as T

    steps = args.steps or (60 if args.quick else 500)
    qat_steps = args.qat_steps or (10 if args.quick else 60)

    rep = C.Report(args.out)
    names = list(T.ALL) if not args.only else args.only.split(",")
    t0 = time.time()
    for name in names:
        fn = T.ALL[name]
        print(f"=== {name} ===", flush=True)
        kw = {"steps": steps}
        if "qat_steps" in fn.__code__.co_varnames[: fn.__code__.co_argcount]:
            kw["qat_steps"] = qat_steps
        fn(rep, **kw)
    # roofline summary (from dry-run artifacts, if present)
    for r in roofline_summary():
        rep.row("roofline", **r)
    rep.save()
    n_ok = sum(c["ok"] for c in rep.claims)
    print(f"\n{len(rep.rows)} rows, claims {n_ok}/{len(rep.claims)} OK, "
          f"{time.time() - t0:.0f}s -> {args.out}.json", flush=True)
    return 0 if n_ok == len(rep.claims) else 1


if __name__ == "__main__":
    raise SystemExit(main())
