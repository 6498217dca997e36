"""Host spans of the benchmark's own loop, and the reduction of a profiler
trace to device busy time, per-operation time and attributed idle gaps.

Spans are `jax.profiler.TraceAnnotation`s, so a traced run finds them in the
profiler's host plane on the same clock as the device's operations; their
host-clock durations are also kept here for readers that need no trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil
import time
from typing import Dict, Iterator, List, Optional, Tuple

WINDOW_SPAN = "bench_window"
MIN_GAP_S = 20e-6  # idle gaps shorter than this are launch jitter


class Spans:
    """Named host spans around the calls of the benchmark's loop. Durations
    are recorded while ``recording`` is set (the measured window), with
    each span's start on the host clock."""

    def __init__(self) -> None:
        self.recording = False
        self.durations: Dict[str, List[float]] = {}
        self.starts: Dict[str, List[float]] = {}

    @contextlib.contextmanager
    def __call__(self, name: str) -> Iterator[None]:
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        if self.recording:
            self.durations.setdefault(name, []).append(
                time.perf_counter() - t0)
            self.starts.setdefault(name, []).append(t0)

    def mean_ms(self, name: str) -> Optional[float]:
        d = self.durations.get(name)
        return 1e3 * sum(d) / len(d) if d else None


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _overlap(a: float, b: float, spans: List[Tuple[float, float]]) -> float:
    return sum(max(0.0, min(b, e) - max(a, s)) for s, e in spans)


@dataclasses.dataclass
class TraceSummary:
    """What one traced window holds, in seconds, averaged over the chips."""

    window_s: float
    busy_s: float
    op_seconds: Dict[str, float]  # device operations by name
    module_seconds: Dict[str, float]  # device programs by name
    idle_gaps: Dict[str, float]  # idle device time by host span under it

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds_matching(self, pattern: str) -> float:
        """Device time of the operations whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(s for n, s in self.op_seconds.items() if rx.search(n))

    def breakdown(self) -> Dict[str, List[List]]:
        def top(d: Dict[str, float]) -> List[List]:
            return [[n, s] for n, s in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:10]]

        return {"device_ops": top(self.op_seconds),
                "idle_gaps": top(self.idle_gaps)}


def _short(name: str) -> str:
    """An op's name without its HLO text: ``%fusion.12 = bf16[8,1024]{..}
    fusion(...)`` becomes ``%fusion.12 bf16[8,1024]``."""
    if " = " not in name:
        return name
    op, rest = name.split(" = ", 1)
    return f"{op} {rest.split('{', 1)[0].split(' ', 1)[0]}"[:120]


def _device_planes(pd, chips: int):
    planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")
              and p.name[len("/device:TPU:"):].isdigit()]
    planes.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    return planes[:chips]


def reduce_profile(pd, chips: int, span_names: Tuple[str, ...]
                   ) -> TraceSummary:
    """Reduce a `jax.profiler.ProfileData` to a `TraceSummary` over the
    benchmark's window span (``WINDOW_SPAN`` on the host plane).

    Busy time is the union of the intervals of the device's ``XLA Ops``
    line; op time sums those events by name; module time sums the ``XLA
    Modules`` line. Each idle gap of the device is charged to the host span
    of ``span_names`` that overlaps it most (``other`` where none does)."""
    host: Dict[str, List[Tuple[float, float]]] = {n: [] for n in span_names}
    window: Optional[Tuple[float, float]] = None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9)
                elif ev.name in host:
                    host[ev.name].append(
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9))
    if window is None:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = window
    planes = _device_planes(pd, chips)
    if not planes:
        raise ValueError("trace holds no TPU device plane")
    busy = 0.0
    ops: Dict[str, float] = {}
    modules: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for plane in planes:
        ivs = []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            acc = ops if line.name == "XLA Ops" else modules
            for ev in line.events:
                a = max(w0, ev.start_ns * 1e-9)
                b = min(w1, (ev.start_ns + ev.duration_ns) * 1e-9)
                if b <= a:
                    continue
                name = _short(ev.name)
                acc[name] = acc.get(name, 0.0) + (b - a)
                if line.name == "XLA Ops":
                    ivs.append((a, b))
        merged = _union(ivs)
        busy += sum(b - a for a, b in merged)
        prev = w0
        for a, b in merged + [(w1, w1)]:
            if a - prev >= MIN_GAP_S:
                best, cover = "other", 0.0
                for name, spans in host.items():
                    c = _overlap(prev, a, spans)
                    if c > cover:
                        best, cover = name, c
                gaps[best] = gaps.get(best, 0.0) + (a - prev)
            prev = max(prev, b)
    n = len(planes)
    return TraceSummary(
        window_s=w1 - w0, busy_s=busy / n,
        op_seconds={k: v / n for k, v in ops.items()},
        module_seconds={k: v / n for k, v in modules.items()},
        idle_gaps={k: v / n for k, v in gaps.items()})


class Profiler:
    """Starts and stops the JAX profiler around the window and reduces what
    it wrote. The trace directory is removed once it has been read."""

    def __init__(self, out_dir: str, chips: int,
                 span_names: Tuple[str, ...]) -> None:
        self.out_dir = out_dir
        self.chips = chips
        self.span_names = span_names

    def start(self) -> None:
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        # no Python function tracing: it would slow every call of the loop
        # and of the program; the host spans are TraceAnnotations
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)

    def stop(self) -> TraceSummary:
        import jax
        from jax.profiler import ProfileData

        jax.profiler.stop_trace()
        try:
            paths = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise ValueError(f"the profiler wrote no trace under "
                                 f"{self.out_dir}")
            pd = ProfileData.from_file(paths[0])
            return reduce_profile(pd, self.chips, self.span_names)
        finally:
            shutil.rmtree(self.out_dir, ignore_errors=True)
