#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once and print its result line.

    python3 benchmarks/onchip/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

The cell is looked up in ``BENCHMARK.json`` at the checkout's root; its
configuration, traffic mix, limits and per-layer readers are files under
this directory named after it (see ``harness.py``). With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace of the window. The last line of
standard output is one JSON object; the numbers that decide ``correct`` are
the last lines of standard error. Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# the compile cache lives at one fixed path inside the checkout, whatever the
# environment says, so that only a checkout's first run of a cell compiles
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import harness

    cell = harness.find_cell(args.workload)
    try:
        device = harness.device_info(cell.chips)
    except harness.NoDevice as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    driver_mod = harness.load_driver(cell.driver)
    profiler = None
    if args.trace:
        from tracing import Profiler

        profiler = Profiler(str(TRACE_DIR), cell.chips, driver_mod.SPAN_NAMES)
    res = driver_mod.run(cell, args.seed, args.seconds, T_START,
                         profiler=profiler)
    device["memory_peak_bytes"] = res.info["memory_peak_bytes"]
    if args.trace:
        t = res.context.trace
        device["busy_s"], device["window_s"] = t.busy_s, t.window_s
    print("[bench] info " + json.dumps(res.info, default=str),
          file=sys.stderr)
    harness.print_checks(res.checks)
    print(json.dumps(harness.result_line(cell, res, device,
                                         bool(args.trace))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
