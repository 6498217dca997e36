"""What the program itself names on a profiler trace: the monitor's own
``eacgm.*`` spans (`jax.profiler.TraceAnnotation`s on the host plane) and
the GMM kernels' operations on the device.

Every Python thread's line on the host plane is named ``python``, so a line
is told apart by the spans on it: ``step`` holds the step probe's and the
session's spans, ``eacgm-detect`` the detection executor's sweeps (an
``inline`` executor sweeps on the step thread, which stays ``step``).

Each span is reduced to its self intervals: its interval less those of the
``eacgm.*`` spans nested in it on the same line. A device idle gap is
charged once per thread, to the span whose self intervals overlap it most,
under ``"<thread>/<span>"``.
"""
from __future__ import annotations

import re
from typing import Dict, List, Tuple

from tracing import MIN_GAP_S, WINDOW_SPAN, _device_planes, _overlap, _union

PREFIX = "eacgm."
STEP_THREAD, DETECT_THREAD = "step", "eacgm-detect"
# the four GMM Pallas kernels by their ``pallas_call`` names, as the device
# trace's ``XLA Ops`` line names their operations (``%gmm_best.3 ...``);
# the jitted wrappers (``gmm_best_pallas``, ``score_samples``) do not match
GMM_KERNEL_OPS = re.compile(r"^%?gmm_(score|best|stats|update)(\.\d+)?( |$)")

Interval = Tuple[float, float]
Span = Tuple[str, float, float]  # name, start, end (s)


def named_kernel_seconds(trace) -> float:
    """Device time of the four named GMM kernels in a `TraceSummary`."""
    return sum(s for n, s in trace.op_seconds.items()
               if GMM_KERNEL_OPS.search(n))


def _label(line_name: str, names) -> str:
    if any(n.startswith(("eacgm.step.", "eacgm.probe.", "eacgm.session."))
           for n in names):
        return STEP_THREAD
    if "eacgm.detect.sweep" in names:
        return DETECT_THREAD
    return line_name


def thread_spans(pd) -> List[Tuple[str, List[Span]]]:
    """(thread, spans) of every host line that holds ``eacgm.*`` spans."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(ev.name, ev.start_ns * 1e-9,
                      (ev.start_ns + ev.duration_ns) * 1e-9)
                     for ev in line.events if ev.name.startswith(PREFIX)]
            if spans:
                out.append((_label(line.name, {s[0] for s in spans}),
                            spans))
    return out


def self_intervals(spans: List[Span]) -> Dict[str, List[Interval]]:
    """Each span name's self intervals on one line: every span's interval
    less the intervals of the spans nested in it."""
    order = sorted(range(len(spans)), key=lambda i: (spans[i][1],
                                                     -spans[i][2]))
    children: Dict[int, List[Interval]] = {i: [] for i in order}
    stack: List[int] = []
    for i in order:
        _, a, b = spans[i]
        while stack and spans[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            children[stack[-1]].append((a, b))
        stack.append(i)
    out: Dict[str, List[Interval]] = {}
    for i, (name, a, b) in enumerate(spans):
        rest, t = out.setdefault(name, []), a
        for s, e in _union(children[i]):
            if s > t:
                rest.append((t, s))
            t = max(t, min(e, b))
        if b > t:
            rest.append((t, b))
    return out


def window_of(pd) -> Interval:
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        return (ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9)
    raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")


def device_idle_gaps(plane, window: Interval) -> List[Interval]:
    """Gaps of at least ``MIN_GAP_S`` in the union of one device plane's
    ``XLA Ops`` intervals inside the window (as `tracing.reduce_profile`
    finds them)."""
    w0, w1 = window
    ivs = []
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            a = max(w0, ev.start_ns * 1e-9)
            b = min(w1, (ev.start_ns + ev.duration_ns) * 1e-9)
            if b > a:
                ivs.append((a, b))
    gaps, prev = [], w0
    for a, b in _union(ivs) + [(w1, w1)]:
        if a - prev >= MIN_GAP_S:
            gaps.append((prev, a))
        prev = max(prev, b)
    return gaps


def overlaps(threads, a: float, b: float) -> Dict[str, float]:
    """Seconds of self time of each ``"<thread>/<span>"`` inside [a, b)."""
    out: Dict[str, float] = {}
    for label, selfs in threads:
        for name, ivs in selfs.items():
            c = _overlap(a, b, ivs)
            if c > 0:
                key = f"{label}/{name}"
                out[key] = out.get(key, 0.0) + c
    return out


def program_idle_gaps(pd, chips: int) -> Dict[str, float]:
    """Idle device seconds in the window by ``"<thread>/<span>"``: each gap
    charged, once per thread, to the span whose self intervals overlap it
    most (gaps no span overlaps on a thread are not charged there),
    averaged over the chips."""
    window = window_of(pd)
    threads = [(label, self_intervals(spans))
               for label, spans in thread_spans(pd)]
    planes = _device_planes(pd, chips)
    gaps: Dict[str, float] = {}
    for plane in planes:
        for a, b in device_idle_gaps(plane, window):
            for label, selfs in threads:
                best, cover = None, 0.0
                for name, ivs in selfs.items():
                    c = _overlap(a, b, ivs)
                    if c > cover:
                        best, cover = name, c
                if best is not None:
                    key = f"{label}/{best}"
                    gaps[key] = gaps.get(key, 0.0) + (b - a)
    n = max(1, len(planes))
    return {k: v / n for k, v in gaps.items()}


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def stats_delta(before, after):
    """``after - before`` of two `Session.self_stats()` results, leaf by
    leaf (a leaf missing before counts from zero)."""
    if isinstance(after, dict):
        before = before or {}
        return {k: stats_delta(before.get(k), v) for k, v in after.items()}
    return after - (before or 0)
