"""CPU tests of what the benchmark reads of the program's own spans,
counters and kernel names: self intervals and idle-gap charging on a
recorded trace, the five readers against hand-computed values, and a tiny
stream cell run traced on the CPU."""
from __future__ import annotations

import time
from types import SimpleNamespace as NS

import pytest

import tiny
import harness
import program_trace as pt
import tracing
from test_onchip_units import ev, recorded_trace

STEP_SPANS = [ev("eacgm.session.on_step", 400, 200),
              ev("eacgm.session.admit", 450, 130),
              ev("eacgm.step.call", 600, 10), ev("eacgm.step.wait", 610, 350),
              ev("eacgm.probe.emit", 960, 40)]
DETECT_SPANS = [ev("eacgm.detect.sweep", 300, 650),
                ev("eacgm.detect.featurize", 320, 40),
                ev("eacgm.detect.score", 360, 40),
                ev("eacgm.detect.fit", 400, 500)]


def program_trace():
    """`recorded_trace` with the monitor's spans on the step thread's line
    and on a second thread's, a named GMM kernel inside a busy interval and
    an unnamed kernel's wrapper op (neither moves the busy time)."""
    pd = recorded_trace()
    host = pd.planes[0]
    host.lines[0].events.extend(STEP_SPANS)
    host.lines.append(NS(name="python", events=list(DETECT_SPANS)))
    ops = pd.planes[1].lines[0]
    ops.events.extend([
        ev("%gmm_best.3 = f32[2,1,256]{2,1,0} custom-call(%p)", 310, 30),
        ev("%gmm_score_pallas.1 = f32[256,3]{1,0} custom-call(%p)", 100, 20)])
    return pd


def test_the_recorded_trace_reads_as_before():
    """The spans and kernels added leave `reduce_profile`'s readings of the
    recorded trace as `test_trace_reduction_on_a_recorded_trace` has them."""
    t = tracing.reduce_profile(program_trace(), chips=1,
                               span_names=("job_step", "on_step"))
    assert t.window_s == pytest.approx(1e-3)
    assert t.busy_s == pytest.approx(650e-6)
    assert t.idle_share == pytest.approx(0.35)
    assert t.seconds_matching(r"_best_kernel") == pytest.approx(50e-6)
    assert t.op_seconds["fusion.1"] == pytest.approx(600e-6)
    assert t.idle_gaps["on_step"] == pytest.approx(270e-6)
    assert t.idle_gaps["job_step"] == pytest.approx(70e-6)
    b = t.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(600e-6)]
    assert len(b["idle_gaps"]) <= 10


def test_self_intervals_leave_out_nested_spans():
    spans = [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
             for e in DETECT_SPANS]
    selfs = pt.self_intervals(spans)
    got = {n: sum(b - a for a, b in ivs) for n, ivs in selfs.items()}
    # the sweep [300, 950) less featurize, score and fit [320, 900)
    assert got["eacgm.detect.sweep"] == pytest.approx(70e-6)
    assert got["eacgm.detect.fit"] == pytest.approx(500e-6)


def test_idle_gaps_are_charged_per_thread_by_self_time():
    """Gaps [350, 620) and [920, 990) us. On the step thread the first lies
    mostly under ``admit`` (130 of its own us against ``on_step``'s 70),
    the second under ``wait``. On the detection thread the sweep covers
    both, but its self time covers only the second: the first goes to
    ``fit``, nested in it."""
    pd = program_trace()
    assert [t for t, _ in pt.thread_spans(pd)] == ["step", "eacgm-detect"]
    gaps = pt.program_idle_gaps(pd, chips=1)
    assert gaps == {
        "step/eacgm.session.admit": pytest.approx(270e-6),
        "step/eacgm.step.wait": pytest.approx(70e-6),
        "eacgm-detect/eacgm.detect.fit": pytest.approx(270e-6),
        "eacgm-detect/eacgm.detect.sweep": pytest.approx(70e-6)}


def test_the_gmm_kernel_readers():
    t = tracing.reduce_profile(program_trace(), chips=1, span_names=())
    # only the named kernel's 30 us: not the wrapper's op, nor the 1 ms
    assert pt.named_kernel_seconds(t) == pytest.approx(30e-6)
    ms = harness.metric_reader("gmm_kernel_ms.train")(NS(trace=t))
    assert ms == pytest.approx(30.0)  # ms per second of window
    ctx = NS(trace=t, gmm_shapes=[("best", 256, 3, 2)],
             device_kind="TPU v5 lite")
    roof = harness.metric_reader("gmm_kernel_roofline.train")(ctx)
    # memory-bound: 4 B x (256 x 3 rows in + 2 x 256 out) at 819 GB/s
    assert roof == pytest.approx(100 * 4 * (768 + 512) / 819e9 / 30e-6)
    bare = tracing.reduce_profile(recorded_trace(), chips=1, span_names=())
    for name in ("gmm_kernel_ms.train", "gmm_kernel_roofline.train"):
        assert harness.metric_reader(name)(NS(
            trace=bare, gmm_shapes=ctx.gmm_shapes,
            device_kind=ctx.device_kind)) is None


def test_the_host_counter_readers():
    before = {"probes": {0: {"python": 0.25, "step": 0.05}},
              "detect": {"started": 2, "completed": 1, "wait_seconds": 0.1,
                         "busy_seconds": 1.0}}
    after = {"probes": {0: {"python": 0.75, "step": 0.15}},
             "detect": {"started": 6, "completed": 6, "wait_seconds": 0.3,
                        "busy_seconds": 11.0}}
    ctx = NS(self_stats=pt.stats_delta(before, after), steps=100)
    read = {n: harness.metric_reader(n)(ctx) for n in (
        "probe_ms.train", "sweep_wait_ms.train", "sweep_run_ms.train")}
    assert read == {"probe_ms.train": pytest.approx(6.0),
                    "sweep_wait_ms.train": pytest.approx(50.0),
                    "sweep_run_ms.train": pytest.approx(2000.0)}
    # a context without the counters (a program that lacks them, or the
    # driver's own context) reads nothing and raises nothing
    for ctx in (NS(steps=100), NS(self_stats={}, steps=100),
                NS(self_stats={"probes": {}, "detect": {}}, steps=100)):
        for n in read:
            assert harness.metric_reader(n)(ctx) is None


@pytest.fixture(scope="module")
def stream_run(tmp_path_factory):
    import span_report

    cell = tiny.tiny_cell(tmp_path_factory.mktemp("tiny"),
                          "gpt2-train-stream")
    sessions = span_report.Sessions()
    out = span_report.run_cell(
        cell, 2 ** 31 + 1013, 1.5, time.perf_counter(),
        str(tmp_path_factory.mktemp("trace")), sessions=sessions)
    return out, sessions.made[-1]


def test_tiny_stream_cell_reads_the_program_counters(stream_run):
    out, _ = stream_run
    assert out["steps"] > 0
    for name in ("probe_ms.train", "sweep_wait_ms.train",
                 "sweep_run_ms.train"):
        assert isinstance(out["metrics"][name], float), name
        assert out["metrics"][name] >= 0.0
    probes = out["self_stats"]["probes"][0]
    assert probes["python"] > 0 and probes["step"] > 0


def test_tiny_stream_trace_holds_the_sweep_on_the_detect_thread(stream_run):
    out, _ = stream_run
    assert "eacgm-detect" in out["threads"] and "step" in out["threads"]


def test_spans_fire_no_python_probe_events(stream_run):
    """The spans are entered directly, so the python probe's hook records
    no call for them on the step thread, where it sees the step wrapper."""
    from repro.core.events import Layer

    _, session = stream_run
    names = {str(n) for n in
             session.detector.aggregator.window(Layer.PYTHON).view()["name"]}
    assert "repro.core.probes.step_probe.monitored" in names
    assert not [n for n in names if "eacgm" in n or "TraceAnnotation" in n
                or "TraceMe" in n]
