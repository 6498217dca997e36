#!/usr/bin/env python3
"""Run one monitored train cell once, traced, and report the program's own
spans and counters beside the benchmark's metrics.

    python3 benchmarks/onchip/span_report.py --workload gpt2-train-stream \
        --seed <n> --seconds <s> [--out <file>]

The cell runs through its driver as ``run.py --trace 1`` runs it, with a
profiler that also keeps, from the same trace and window:

* ``self_stats``: the window's growth of ``Session.self_stats()`` (probe
  self time, the detection executor's queue wait and run time), which the
  readers ``probe_ms.train``, ``sweep_wait_ms.train`` and
  ``sweep_run_ms.train`` in ``metrics/`` read;
* ``program_idle_gaps``: the device's idle seconds charged per thread to
  the monitor's ``eacgm.*`` span that was running (`program_trace`);
* ``stalls``: each ``job_step`` longer than twice the window's median, with
  the program spans' self time inside it on both threads;
* ``gmm_ops``: the device operations whose name holds ``gmm``.

It prints one JSON line (and writes it to ``--out``). Off a TPU it exits
non-zero, as ``run.py`` does.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [p for p in (str(HERE), str(ROOT / "src"))
                if p not in sys.path]

import harness  # noqa: E402
import program_trace as pt  # noqa: E402
import tracing  # noqa: E402

HOST_READERS = ("probe_ms.train", "sweep_wait_ms.train",
                "sweep_run_ms.train")


class Sessions:
    """Keeps every `repro.session.Session` made while installed, so the
    profiler can read the driver's session at the window's edges."""

    def __init__(self) -> None:
        self.made: List[Any] = []
        self._undo = None

    def install(self) -> None:
        from repro.session import Session

        orig = Session.__init__

        def init(s, *args, **kw):
            orig(s, *args, **kw)
            self.made.append(s)

        Session.__init__ = init
        self._undo = lambda: setattr(Session, "__init__", orig)

    def uninstall(self) -> None:
        if self._undo is not None:
            self._undo()
            self._undo = None


class ProgramProfiler(tracing.Profiler):
    """`tracing.Profiler` that also keeps the program's spans and counters
    of the window. Off a TPU the trace has no device plane: ``stop``
    returns None there and keeps the host's spans."""

    def __init__(self, out_dir: str, chips: int, span_names, sessions):
        super().__init__(out_dir, chips, span_names)
        self.sessions = sessions
        self._stats0: Dict[str, Any] = {}
        self.self_stats: Dict[str, Any] = {}
        self.threads: List = []
        self.program_idle_gaps: Dict[str, float] = {}
        self.job_steps: List[pt.Interval] = []

    def _stats(self) -> Dict[str, Any]:
        return self.sessions.made[-1].self_stats()

    def start(self) -> None:
        self._stats0 = self._stats()
        super().start()

    def stop(self) -> Optional[tracing.TraceSummary]:
        import jax
        from jax.profiler import ProfileData

        self.self_stats = pt.stats_delta(self._stats0, self._stats())
        jax.profiler.stop_trace()
        try:
            paths = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise ValueError(f"the profiler wrote no trace under "
                                 f"{self.out_dir}")
            pd = ProfileData.from_file(paths[0])
            self.threads = [(label, pt.self_intervals(spans))
                            for label, spans in pt.thread_spans(pd)]
            self.job_steps = [
                (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                for plane in pd.planes if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events
                if ev.name == "job_step"]
            if not tracing._device_planes(pd, self.chips):
                return None
            self.program_idle_gaps = pt.program_idle_gaps(pd, self.chips)
            return tracing.reduce_profile(pd, self.chips, self.span_names)
        finally:
            shutil.rmtree(self.out_dir, ignore_errors=True)

    def stalls(self, top: int = 6) -> List[Dict[str, Any]]:
        """The longest ``job_step``s over twice the median, each with the
        program spans' self seconds inside it."""
        if not self.job_steps:
            return []
        med = statistics.median(b - a for a, b in self.job_steps)
        slow = sorted((iv for iv in self.job_steps
                       if iv[1] - iv[0] > 2 * med),
                      key=lambda iv: iv[0] - iv[1])[:top]
        return [{"ms": 1e3 * (b - a), "median_ms": 1e3 * med,
                 "spans": pt.top(pt.overlaps(self.threads, a, b))}
                for a, b in slow]


def run_cell(cell, seed: int, seconds: float, t_start: float,
             trace_dir: str, sessions: Optional[Sessions] = None
             ) -> Dict[str, Any]:
    """One traced run of ``cell``; the report as a dict (see the module).
    The run's sessions stay in ``sessions``, where one is given."""
    drv = harness.load_driver(cell.driver)
    sessions = Sessions() if sessions is None else sessions
    sessions.install()
    try:
        prof = ProgramProfiler(trace_dir, cell.chips, drv.SPAN_NAMES,
                               sessions)
        res = drv.run(cell, seed, seconds, t_start, profiler=prof)
    finally:
        sessions.uninstall()
    ctx = res.context
    ctx.self_stats = prof.self_stats
    # the cell's own readers need the device (its trace and peaks); the
    # program's counters are read on any backend
    names = list(HOST_READERS)
    if ctx.trace is not None:
        names = [m["name"] for m in cell.per_layer] + names
    metrics = {n: harness.metric_reader(n)(ctx) for n in names}
    out: Dict[str, Any] = {
        "correct": res.correct, "end_to_end": res.end_to_end,
        "metrics": metrics, "steps": ctx.steps,
        "self_stats": prof.self_stats,
        "program_idle_gaps": pt.top(prof.program_idle_gaps),
        "stalls": prof.stalls(), "threads": [t for t, _ in prof.threads],
        "job_step_ms": res.info.get("job_step_ms"),
    }
    if ctx.trace is not None:
        out["breakdown"] = ctx.trace.breakdown()
        out["gmm_ops"] = pt.top({n: s for n, s in ctx.trace.op_seconds.items()
                                 if "gmm" in n}, 20)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cache = ROOT / ".jax_cache"  # the same cache as run.py's
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    cell = harness.find_cell(args.workload)
    try:
        device = harness.device_info(cell.chips)
    except harness.NoDevice as e:
        print(f"[span_report] {e}", file=sys.stderr)
        return 3
    import jax

    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    out = run_cell(cell, args.seed, args.seconds, T_START,
                   str(ROOT / ".bench_trace"))
    out.update(workload=cell.name, seed=args.seed, device=device)
    line = json.dumps(out, default=str)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
