#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the TPU this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Measures the cell for ``--seconds`` and prints, as the last line of
standard output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics from a profiler trace of the window with ``--trace 1``),
``device`` and, traced, ``breakdown``; ``compared`` comes last, each
number compared with its limit, and the same numbers end standard error.
Without a TPU, or with fewer chips than the cell needs, it exits 3 and
prints no result.  ``--control <bf16|a4|fp8>`` puts the reference at that
lower precision in the program's place for the check
(``bench/correct.py``), which then has to come out not correct; the
benchmark's own runs never pass it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu reads this as it loads; unset, it logs under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None, choices=("bf16", "a4", "fp8"),
                    help="a lower precision that stands in for the program")
    args = ap.parse_args(argv)

    from bench.harness import find_device, load_cell, run_cell

    cell = load_cell(args.workload)
    device, why = find_device(cell.chips)
    if device is None:
        print(why, file=sys.stderr)
        return 3
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    # every program, however quick to compile, is read back next run
    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device=device, t_start=T_START, control=args.control)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
