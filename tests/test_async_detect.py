"""Async detection plane lock-in: executor semantics, async == sync parity
(inline mode), incremental-EM vs full-refit parity, snapshot determinism,
and a no-torn-reads race regression under concurrent ingest.

These are the tests docs/detection.md promises — the contract of
`repro.detect` plus the monitor trio (snapshot / detect_snapshot / admit).
"""
import threading
import time

import numpy as np
import pytest

from repro.core.events import Event, Layer
from repro.detect import (DetectionExecutor, SweepResult, detection_zone,
                          in_detection_zone)
from repro.session.detectors import BatchGMMBackend, OnlineGMMBackend
from repro.session.registry import detector_backend
from repro.session.spec import DetectorSpec

# the async plane is family-agnostic: lag accounting, coalescing, and
# error-as-data must hold for the bake-off families too, not just the GMM
FAMILY_NAMES = ("gmm", "mad", "spectral")
from repro.stream import wire
from repro.stream.monitor import StreamMonitor
from repro.stream.online import OnlineGMMDetector


# ---------------------------------------------------------------------------
# synthetic traces (same shape as test_stream's chaos trace)
# ---------------------------------------------------------------------------

def _node_trace(rng, n_steps, fault_steps=(), fault_scale=8.0, t0=0.0):
    evs = []
    base = {"matmul": 2e-3, "softmax": 4e-4, "layernorm": 2e-4}
    for s in range(n_steps):
        t = t0 + 0.05 * s
        scale = fault_scale if s in fault_steps else 1.0
        for op, b in base.items():
            evs.append(Event(layer=Layer.OPERATOR, name=op, ts=t,
                             dur=b * scale * rng.lognormal(0, 0.05),
                             size=1e5, step=s))
        evs.append(Event(layer=Layer.STEP, name="train_step", ts=t,
                         dur=3e-3 * scale * rng.lognormal(0, 0.05), step=s))
    return evs


def _chunk(evs, lo, hi):
    return [e for e in evs if lo <= e.step < hi]


# ---------------------------------------------------------------------------
# executor semantics
# ---------------------------------------------------------------------------

def test_executor_inline_runs_at_submit():
    ex = DetectionExecutor(mode="inline")
    ran = []
    seq = ex.submit("k", lambda: ran.append(1) or "v", step=7)
    assert ran == [1]  # executed on the calling thread, before submit returned
    (r,) = ex.drain()
    assert isinstance(r, SweepResult)
    assert (r.key, r.seq, r.step, r.value, r.error) == ("k", seq, 7, "v", None)
    s = ex.stats()
    assert s["mode"] == "inline" and s["queue_depth"] == 0
    assert s["submitted"] == s["completed"] == 1
    ex.close()


def test_executor_thread_coalesces_queued_tasks():
    ex = DetectionExecutor(mode="thread")
    started = threading.Event()
    release = threading.Event()

    def blocker():
        started.set()
        assert release.wait(30)
        return "blocker"

    ex.submit("a", blocker)
    assert started.wait(30)  # worker is now busy inside task "a"
    # three tasks pile up behind it on key "b": only the newest survives
    ex.submit("b", lambda: "b1")
    ex.submit("b", lambda: "b2")
    ex.submit("b", lambda: "b3")
    release.set()
    assert ex.flush(timeout=30)
    values = [r.value for r in ex.drain()]
    assert values == ["blocker", "b3"]
    s = ex.stats()
    assert s["coalesced"] == 2 and s["completed"] == 2 and s["submitted"] == 4
    ex.close()


def test_executor_wait_counts_started_tasks_only():
    """``wait_seconds`` sums submit-to-start over the tasks that started:
    a task coalesced away while queued never starts and adds nothing."""
    ex = DetectionExecutor(mode="thread")
    started = threading.Event()
    release = threading.Event()

    def blocker():
        started.set()
        assert release.wait(30)

    ex.submit("a", blocker)
    assert started.wait(30)
    ex.submit("b", lambda: "b1")  # coalesced away by b2
    time.sleep(0.2)
    ex.submit("b", lambda: "b2")
    time.sleep(0.1)
    release.set()
    assert ex.flush(timeout=30)
    results = ex.drain()
    s = ex.stats()
    assert s["started"] == s["completed"] == 2 and s["coalesced"] == 1
    waits = [r.started_ts - r.submitted_ts for r in results]
    assert s["wait_seconds"] == pytest.approx(sum(waits))
    # b2 waited behind the blocker for >= 0.1 s; b1's 0.3 s counts nowhere
    assert 0.1 <= waits[1] < 0.3 and s["wait_seconds"] < 0.3
    assert s["busy_seconds"] >= 0.3
    ex.close()


def test_executor_inline_never_waits():
    ex = DetectionExecutor(mode="inline")
    for _ in range(3):
        ex.submit("k", lambda: time.sleep(0.01))
    s = ex.stats()
    assert s["started"] == s["completed"] == s["submitted"] == 3
    assert s["coalesced"] == 0
    assert 0.0 <= s["wait_seconds"] < 0.01 <= s["busy_seconds"] / 3
    ex.close()


def test_executor_error_is_data_and_worker_survives():
    ex = DetectionExecutor(mode="thread")

    def boom():
        raise ValueError("sweep exploded")

    ex.submit("k", boom)
    assert ex.flush(timeout=30)
    (r,) = ex.drain()
    assert isinstance(r.error, ValueError) and r.value is None
    # the worker did not die with the task
    ex.submit("k", lambda: "alive")
    assert ex.flush(timeout=30)
    assert ex.drain()[0].value == "alive"
    assert ex.stats()["errors"] == 1
    ex.close()
    with pytest.raises(RuntimeError):
        ex.submit("k", lambda: None)
    ex.close()  # idempotent


def test_detection_zone_is_thread_local_and_reentrant():
    assert not in_detection_zone()
    with detection_zone():
        assert in_detection_zone()
        with detection_zone():
            assert in_detection_zone()
        assert in_detection_zone()
    assert not in_detection_zone()
    seen = {}
    ex = DetectionExecutor(mode="thread")
    ex.submit("k", lambda: seen.setdefault("zone", in_detection_zone()))
    assert ex.flush(timeout=30)
    assert seen["zone"] is True  # sweeps run inside the zone
    assert not in_detection_zone()  # ... but only on the worker thread
    ex.close()


# ---------------------------------------------------------------------------
# async == sync parity (the inline determinism anchor)
# ---------------------------------------------------------------------------

def _warmed_monitor(rng_seed=0, n_warm=100):
    rng = np.random.default_rng(rng_seed)
    mon = StreamMonitor(min_events=64, contamination=0.02, seed=0,
                        horizon_s=1000.0, incident_gap_s=0.5,
                        incident_close_after_s=0.5, min_flags=5)
    mon.aggregator.ingest(
        wire.encode_events(_node_trace(rng, n_warm), node_id=0, seq=0))
    mon.warmup()
    return mon, rng


def test_async_trio_matches_sync_tick_byte_for_byte():
    """tick() == admit(detect_snapshot(snapshot())) — the same chaos stream
    through the legacy synchronous path and the inline async trio yields
    byte-identical flags, scores, thresholds, and incidents."""
    sync_mon, _ = _warmed_monitor()
    async_mon, _ = _warmed_monitor()
    ex = DetectionExecutor(mode="inline")
    rng = np.random.default_rng(1)
    fault_steps = set(range(140, 160))
    trace = _node_trace(rng, 200, fault_steps)
    for i, lo in enumerate(range(100, 200, 20)):
        buf = wire.encode_events(_chunk(trace, lo, lo + 20), node_id=0,
                                 seq=1 + i)
        sync_mon.aggregator.ingest(buf)
        async_mon.aggregator.ingest(buf)
        closed_sync = sync_mon.tick()
        snap = async_mon.snapshot()
        assert snap is not None
        ex.submit("stream", lambda: async_mon.detect_snapshot(snap))
        (r,) = ex.drain()
        assert r.error is None
        closed_async = async_mon.admit(r.value)
        assert len(closed_sync) == len(closed_async)
        assert set(sync_mon.last_detections) == set(async_mon.last_detections)
        for layer, want in sync_mon.last_detections.items():
            got = async_mon.last_detections[layer]
            assert np.array_equal(want.flags, got.flags), layer
            assert np.array_equal(want.scores, got.scores), layer
            assert want.log_delta == got.log_delta
            assert want.refit == got.refit
    ex.close()
    sync_inc = sync_mon.finish() + sync_mon.incidents
    async_inc = async_mon.finish() + async_mon.incidents
    assert len(sync_inc) == len(async_inc)
    for a, b in zip(sync_mon.incidents, async_mon.incidents):
        assert (a.suspect_layer, a.suspect_nodes, a.n_flags) == \
               (b.suspect_layer, b.suspect_nodes, b.n_flags)
        assert (a.t_start, a.t_end) == (b.t_start, b.t_end)


@pytest.mark.parametrize("family", FAMILY_NAMES)
def test_thread_executor_publishes_at_next_cadence_with_lag(family):
    """With the real background worker, a sweep submitted at cadence point k
    is admitted at k+1, and the backend accounts for the staleness — for
    every detector family behind the stream registry."""
    backend = detector_backend(family, "stream")(
        DetectorSpec(backend=family, min_events=64, seed=0,
                     horizon_s=1000.0))
    ex = DetectionExecutor(mode="thread")
    backend.attach_executor(ex)
    rng = np.random.default_rng(2)
    trace = _node_trace(rng, 160)
    backend.monitor.aggregator.ingest(
        wire.encode_events(_chunk(trace, 0, 100), node_id=0, seq=0))
    backend.fit()
    assert backend.fitted
    backend.monitor.aggregator.ingest(
        wire.encode_events(_chunk(trace, 100, 130), node_id=0, seq=1))
    backend.update_async(step=1)
    assert ex.flush(timeout=30)  # let the sweep land before the next cadence
    backend.monitor.aggregator.ingest(
        wire.encode_events(_chunk(trace, 130, 160), node_id=0, seq=2))
    out = backend.update_async(step=2)
    # what published at step 2 is the sweep of step 1's snapshot
    assert backend.sweeps_admitted == 1
    assert backend.lag_steps == 1
    assert backend.lag_seconds >= 0.0
    assert Layer.OPERATOR in out
    # step 1's snapshot had only rows up to step < 130
    assert int(out[Layer.OPERATOR].steps.max()) < 130
    backend.finish(step=2)
    # shutdown quiesced the plane: every submitted sweep was admitted
    assert backend.sweeps_admitted == 2
    ex.close()


@pytest.mark.parametrize("family", ("mad", "spectral"))
def test_family_sweeps_coalesce_under_backpressure(family):
    """When a family's sweep outlives the cadence interval, queued sweeps
    coalesce to the newest snapshot — the backpressure contract is not
    GMM-specific."""
    backend = detector_backend(family, "stream")(
        DetectorSpec(backend=family, min_events=64, seed=0,
                     horizon_s=1000.0))
    ex = DetectionExecutor(mode="thread")
    backend.attach_executor(ex)
    rng = np.random.default_rng(7)
    trace = _node_trace(rng, 180)
    backend.monitor.aggregator.ingest(
        wire.encode_events(_chunk(trace, 0, 100), node_id=0, seq=0))
    backend.fit()
    assert backend.fitted
    started = threading.Event()
    release = threading.Event()
    real = backend.monitor.detect_snapshot

    def slow(snap):
        started.set()
        assert release.wait(30)
        return real(snap)

    backend.monitor.detect_snapshot = slow
    for i, lo in enumerate(range(100, 160, 20)):
        backend.monitor.aggregator.ingest(wire.encode_events(
            _chunk(trace, lo, lo + 20), node_id=0, seq=1 + i))
        backend.update_async(step=1 + i)
        if i == 0:
            assert started.wait(30)  # worker is now stuck inside sweep #1
    release.set()
    backend.monitor.detect_snapshot = real
    assert ex.flush(timeout=30)
    backend.finish(step=4)
    s = ex.stats()
    # sweeps #2 and #3 piled up behind the slow #1: only the newest ran
    assert s["submitted"] == 3
    assert s["coalesced"] == 1
    assert s["completed"] == 2
    assert backend.sweeps_admitted == 2
    ex.close()


@pytest.mark.parametrize("family", FAMILY_NAMES)
def test_family_sweep_error_is_data_then_raised_at_admit(family):
    """A family sweep that throws comes back as error-data on the
    SweepResult (the worker survives) and is re-raised at the next admit
    point — same surfacing contract for every stream family."""
    backend = detector_backend(family, "stream")(
        DetectorSpec(backend=family, min_events=64, seed=0,
                     horizon_s=1000.0))
    ex = DetectionExecutor(mode="thread")
    backend.attach_executor(ex)
    rng = np.random.default_rng(8)
    trace = _node_trace(rng, 140)
    backend.monitor.aggregator.ingest(
        wire.encode_events(_chunk(trace, 0, 100), node_id=0, seq=0))
    backend.fit()

    def boom(snap):
        raise RuntimeError("family sweep exploded")

    backend.monitor.detect_snapshot = boom
    backend.monitor.aggregator.ingest(
        wire.encode_events(_chunk(trace, 100, 140), node_id=0, seq=1))
    backend.update_async(step=1)
    assert ex.flush(timeout=30)
    with pytest.raises(RuntimeError, match="family sweep exploded"):
        backend.update_async(step=2)
    assert ex.stats()["errors"] == 1
    ex.close()


# ---------------------------------------------------------------------------
# incremental EM vs full-refit parity
# ---------------------------------------------------------------------------

def test_incremental_em_tracks_full_refit():
    """Stepwise-EM warm refits and bootstrap full refits, run side by side
    over the same steady-state stream (a time-horizon window, so eviction
    balances ingest and the row count stays flat — the regime where folds
    actually run; ramp-up windows bootstrap by design), agree on the clean
    stream's anomaly-rate envelope, mostly agree row-by-row, and both
    localise an injected fault."""
    rng = np.random.default_rng(3)
    fault_steps = set(range(300, 320))
    trace = _node_trace(rng, 400, fault_steps)
    from repro.stream.window import FleetAggregator
    # 10s horizon at 0.05s/step = a ~200-step sliding window
    agg = FleetAggregator(horizon_s=10.0)
    agg.ingest(wire.encode_events(_chunk(trace, 0, 240), node_id=0, seq=0))
    det_inc = OnlineGMMDetector(min_events=64, contamination=0.02, seed=0,
                                incremental=True)
    det_full = OnlineGMMDetector(min_events=64, contamination=0.02, seed=0,
                                 incremental=False)
    det_inc.warmup(agg)
    det_full.warmup(agg)
    clean_diff, fault_rates, max_folds = [], {"inc": [], "full": []}, 0
    for i, lo in enumerate(range(240, 400, 20)):
        agg.ingest(wire.encode_events(_chunk(trace, lo, lo + 20), node_id=0,
                                      seq=1 + i))
        d_inc = det_inc.detect(agg)[Layer.OPERATOR]
        d_full = det_full.detect(agg)[Layer.OPERATOR]
        max_folds = max(max_folds,
                        det_inc.states[Layer.OPERATOR].folds_since_anchor)
        assert d_inc.flags.shape == d_full.flags.shape
        if lo + 20 <= min(fault_steps):  # window is all-clean so far
            clean_diff.append(abs(d_inc.anomaly_rate - d_full.anomaly_rate))
            # row-by-row: the two trackers may disagree only at the margin
            assert np.mean(d_inc.flags != d_full.flags) < 0.1
        if set(range(lo, lo + 20)) & fault_steps:
            fault_rates["inc"].append(d_inc.anomaly_rate)
            fault_rates["full"].append(d_full.anomaly_rate)
            # both flag the injected burst, and on the same steps
            inc_steps = set(d_inc.anomalous_steps().tolist())
            full_steps = set(d_full.anomalous_steps().tolist())
            assert len(inc_steps & fault_steps) >= len(fault_steps) // 2
            assert len(full_steps & fault_steps) >= len(fault_steps) // 2
    # clean stream: anomaly rates stay in the contamination envelope for
    # BOTH trackers, and they stay close to each other
    assert clean_diff and max(clean_diff) < 0.05
    assert max(fault_rates["inc"]) > 0.05
    assert max(fault_rates["full"]) > 0.05
    # the incremental tracker actually took the cheap path: at least one
    # sweep folded new rows instead of bootstrapping
    assert max_folds > 0
    assert det_inc.stats()["operator"]["n_seen"] > 0
    assert det_inc.states[Layer.OPERATOR].stats is not None
    assert det_full.states[Layer.OPERATOR].stats is None


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------

def test_stream_detector_snapshot_determinism():
    """Scoring the same frozen snapshot twice (refit=False: pure scoring)
    is byte-identical — no hidden RNG, clock, or ordering dependence."""
    mon, rng = _warmed_monitor(rng_seed=4)
    mon.aggregator.ingest(wire.encode_events(
        _chunk(_node_trace(rng, 130), 100, 130), node_id=0, seq=1))
    snap = mon.aggregator.freeze()
    first = mon.detector.detect(snap, refit=False)
    second = mon.detector.detect(snap, refit=False)
    assert set(first) == set(second) and first
    for layer in first:
        assert first[layer].flags.tobytes() == second[layer].flags.tobytes()
        assert first[layer].scores.tobytes() == second[layer].scores.tobytes()
        assert first[layer].log_delta == second[layer].log_delta


def test_batch_backend_snapshot_determinism():
    """The batch backend scoring the same drained columns twice — and two
    identically-specced backends fit on the same prefix — agree byte for
    byte."""
    rng = np.random.default_rng(5)
    trace = _node_trace(rng, 120, fault_steps=set(range(100, 110)))
    spec = DetectorSpec(min_events=16)
    b1, b2 = BatchGMMBackend(spec), BatchGMMBackend(spec)
    train = _chunk(trace, 0, 90)
    b1.fit(train)
    b2.fit(train)
    score = _chunk(trace, 90, 120)
    outs = [b1.update(score), b1.update(score), b2.update(score)]
    assert outs[0] and set(outs[0]) == set(outs[1]) == set(outs[2])
    for layer in outs[0]:
        ref = outs[0][layer]
        for other in outs[1:]:
            assert ref.flags.tobytes() == other[layer].flags.tobytes()
            assert ref.scores.tobytes() == other[layer].scores.tobytes()
            assert ref.log_delta == other[layer].log_delta


# ---------------------------------------------------------------------------
# no torn reads under concurrent ingest
# ---------------------------------------------------------------------------

def test_no_torn_reads_under_concurrent_ingest():
    """The production threading model under load: the step thread keeps
    ingesting/evicting/freezing while the worker sweeps earlier snapshots
    concurrently. Every sweep must see internally consistent columns, none
    may error, the coalescing accounting must balance, and shutdown must
    join in bounded time."""
    mon, rng = _warmed_monitor(rng_seed=6, n_warm=100)
    ex = DetectionExecutor(mode="thread")
    trace = _node_trace(rng, 2000, t0=5.0)

    def sweep(snap):
        # torn-read detector: every column of every frozen window must have
        # the same length, and the timestamps must be real numbers
        for layer, w in snap.windows.items():
            lens = {k: c.shape[0] for k, c in w.cols.items()}
            assert len(set(lens.values())) <= 1, (layer, lens)
            assert np.isfinite(w.cols["ts"]).all()
        return mon.detect_snapshot(snap)

    n_submits = 40
    for i in range(n_submits):
        lo = (i * 40) % 1900
        mon.aggregator.ingest(wire.encode_events(
            _chunk(trace, lo, lo + 40), node_id=i % 3, seq=1 + i))
        mon.aggregator.evict()
        # no flush between submits: the worker sweeps snapshot i-k while
        # this thread keeps appending into the live windows
        ex.submit("stream", lambda s=mon.aggregator.freeze(): sweep(s),
                  step=i)
    t0 = time.monotonic()
    assert ex.flush(timeout=60)
    results = ex.drain()
    ex.close(timeout=30)
    assert time.monotonic() - t0 < 60.0  # bounded-time join, no deadlock
    assert results
    assert [r.error for r in results] == [None] * len(results)
    s = ex.stats()
    # every submitted sweep either ran or was superseded by a newer snapshot
    assert s["submitted"] == n_submits
    assert s["completed"] == len(results)
    assert s["completed"] + s["coalesced"] == n_submits
    for r in results:
        # a real sweep came back: per-layer detections over consistent rows
        for layer, det in r.value.detections.items():
            n = det.flags.shape[0]
            assert det.scores.shape[0] == n
            assert det.steps.shape[0] == det.ts.shape[0] == n
