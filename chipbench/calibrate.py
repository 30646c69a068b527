"""Readings the benchmark's fixed numbers were set from (not run by the
benchmark itself; PERF.md records what it printed).  Each reading is one
whole run of the cell through ``run_cell.run_cell``, in one process.

    # correctness: the program's mean logit gap beside the float8
    # control's, on many seeds
    python3 chipbench/calibrate.py gaps --workload <cell> --seconds 50 \
        --seeds 1 2 3 ...

    # the knee: the end-to-end metrics and failures at fixed rates
    python3 chipbench/calibrate.py sweep --workload <cell> --seconds 50 \
        --seeds 1 --rates 0.4 0.5 0.6

Prints one JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import run_cell


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("gaps", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=50)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(run_cell.ROOT / "src"))
    cell = run_cell.Cell(args.workload)
    devs = run_cell.require_tpu(cell.spec["chips"])

    import jax

    from peaks import peaks_for
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    peaks = peaks_for(devs[0].device_kind)
    mix = cell.mix
    runs = [(s, None) for s in args.seeds] if args.mode == "gaps" else \
        [(s, r) for r in args.rates for s in args.seeds]
    for seed, rate in runs:
        cell.mix = mix if rate is None else dict(mix, rate_per_s=rate)
        t0 = time.perf_counter()
        res = run_cell.run_cell(cell, seed, args.seconds, False, devs, peaks,
                                t0, say=lambda m: None,
                                control=args.mode == "gaps")
        res.pop("_runlog")
        print(json.dumps({"seed": seed, "rate_per_s": cell.mix["rate_per_s"],
                          "seconds_taken": time.perf_counter() - t0, **res}),
              flush=True)


if __name__ == "__main__":
    main()
