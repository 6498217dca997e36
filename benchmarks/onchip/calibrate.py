#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` over many seeds in one process,
with the lower-precision control's beside them, to set the limits from.

    python3 benchmarks/onchip/calibrate.py --workload <cell> \
        --seeds 11,12,13 --seconds 10 [--control | --fault half_batch]

One JSON line per seed: whether the run came out correct, the compared
numbers beside their limits (``checks``: the program's, or with
``--control`` the lower-precision control's in its place), every number
read (``info.readings``, the program's), the end-to-end metrics and the
run's counts. ``--fault`` breaks the timed path as the fault tests do.
Needs the chip, as ``run.py`` does; the benchmark's own runs never run the
control or a fault.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=("unchanged", "half_batch"))
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import harness

    cell = harness.find_cell(args.workload)
    try:
        harness.device_info(cell.chips)
    except harness.NoDevice as e:
        print(f"[calibrate] {e}", file=sys.stderr)
        return 3
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    driver = harness.load_driver(cell.driver)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = driver.run(cell, seed, args.seconds, time.perf_counter(),
                         control=args.control, fault=args.fault)
        print(json.dumps({
            "seed": seed, "correct": res.correct,
            "checks": {c.name: [c.value, c.limit] for c in res.checks},
            "end_to_end": res.end_to_end, "attempted": res.attempted,
            "failed": res.failed,
            "info": {k: v for k, v in res.info.items()
                     if k not in ("losses", "bursts")}},
            default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
