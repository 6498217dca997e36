"""Monitor self-telemetry (`repro.obs`): metric-registry semantics, strict
exposition-format validation, the HTML status board, the live `/metrics`
endpoint, and fleet freshness (a node that stops flushing flips to stale)."""
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Layer
from repro.obs import (Counter, ExpositionError, Gauge, Histogram,
                       MetricRegistry, METRIC_NAMES, parse_exposition)
from repro.obs.board import (BoardModel, DiagnosisCard, IncidentRow,
                             LayerRow, NodeCard, render_board)
from repro.session import DetectorSpec, MonitorSpec, Session, SinkSpec


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_monotonicity():
    reg = MetricRegistry()
    c = reg.counter("t_total", "help", labels=("node",))
    c.inc(node="0")
    c.inc(2.5, node="0")
    assert c.value(node="0") == 3.5
    with pytest.raises(ValueError, match="negative increment"):
        c.inc(-1.0, node="0")
    # set_total mirrors an external cumulative stat but never goes backwards
    c.set_total(10.0, node="0")
    assert c.value(node="0") == 10.0
    c.set_total(4.0, node="0")  # source reset must not rewind the series
    assert c.value(node="0") == 10.0


def test_gauge_and_type_conflicts():
    reg = MetricRegistry()
    g = reg.gauge("t_gauge", "help")
    g.set(5.0)
    g.set(-2.0)  # gauges may go down
    assert g.value() == -2.0
    # re-registering with a different type or label set is an error
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("t_gauge", "help")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("t_gauge", "help", labels=("x",))
    # same type + labels is get-or-create
    assert reg.gauge("t_gauge", "help") is g


def test_histogram_cumulative_buckets():
    reg = MetricRegistry()
    h = reg.histogram("t_ms", "help", buckets=(1.0, 5.0, 10.0))
    for v in (0.5, 0.7, 3.0, 7.0, 100.0):
        h.observe(v)
    assert h.count() == 5
    samples = {name + labels: v for name, labels, v in h.samples()}
    assert samples['t_ms_bucket{le="1"}'] == 2
    assert samples['t_ms_bucket{le="5"}'] == 3  # cumulative, not per-bucket
    assert samples['t_ms_bucket{le="10"}'] == 4
    assert samples['t_ms_bucket{le="+Inf"}'] == 5
    assert samples["t_ms_count"] == 5
    assert samples["t_ms_sum"] == pytest.approx(111.2)
    with pytest.raises(ValueError, match="distinct and sorted"):
        reg.histogram("t_bad", "help", buckets=(1.0, 1.0))


def test_label_cardinality_cap_counts_drops():
    reg = MetricRegistry(max_label_sets=3)
    c = reg.counter("t_total", "help", labels=("op",))
    for i in range(10):
        c.inc(op=f"op{i}")
    # only the first 3 series exist; the other 7 increments were dropped
    assert sum(v for _, _, v in c.samples()) == 3
    dropped = reg.get(MetricRegistry.LABELS_DROPPED)
    assert dropped.value(metric="t_total") == 7
    # existing series still update fine at the cap
    c.inc(op="op0")
    assert c.value(op="op0") == 2


def test_label_mismatch_raises():
    reg = MetricRegistry()
    c = reg.counter("t_total", "help", labels=("node",))
    with pytest.raises(ValueError, match="takes labels"):
        c.inc(layer="step")
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("0bad", "help")
    with pytest.raises(ValueError, match="invalid label name"):
        reg.counter("t2_total", "help", labels=("bad-label",))


# ---------------------------------------------------------------------------
# exposition format: everything we render parses strictly, bad docs don't
# ---------------------------------------------------------------------------

def test_rendered_registry_is_valid_exposition():
    reg = MetricRegistry()
    c = reg.counter("req_total", "requests", labels=("node", "layer"))
    c.inc(3, node="0", layer="step")
    c.inc(1, node="1", layer='we"ird\nname')  # needs label escaping
    reg.gauge("occ", "occupancy").set(0.75)
    h = reg.histogram("lat_ms", "latency", labels=("layer",),
                      buckets=(1.0, 10.0))
    h.observe(0.5, layer="step")
    h.observe(50.0, layer="step")
    exp = parse_exposition(reg.render())
    assert set(exp.families) == {"req_total", "occ", "lat_ms",
                                 MetricRegistry.LABELS_DROPPED}
    assert exp.families["lat_ms"] == "histogram"
    assert exp.sample("req_total", node="0", layer="step").value == 3
    # escaped label round-trips through the parser
    assert exp.sample("req_total", node="1").labels["layer"] == 'we"ird\nname'
    assert exp.sample("lat_ms_bucket", layer="step", le="+Inf").value == 2
    assert exp.sample("lat_ms_count", layer="step").value == 2


@pytest.mark.parametrize("doc,msg", [
    ("up 1\n", "no preceding # TYPE"),
    ("# TYPE up gauge\nup 1\nup 1\n", "duplicate series"),
    ("# TYPE up gauge\n# TYPE up gauge\nup 1\n", "duplicate TYPE"),
    ("# TYPE up widget\nup 1\n", "unknown type"),
    ("# TYPE c_total counter\nc_total -1\n", "non-monotone"),
    ("# TYPE up gauge\nup x\n", "unparseable value"),
    ("# TYPE a gauge\n# TYPE b gauge\na 1\nb 2\na 3\n", "not contiguous"),
    ('# TYPE h histogram\nh_bucket{le="1"} 1\nh_count 1\n',
     "missing .Inf bucket"),
    ('# TYPE h histogram\nh_bucket{le="1"} 5\nh_bucket{le="+Inf"} 3\n',
     "not cumulative"),
    ('# TYPE h histogram\nh_bucket{le="+Inf"} 3\nh_count 2\n',
     "_count"),
])
def test_parser_rejects_invalid_documents(doc, msg):
    with pytest.raises(ExpositionError, match=msg):
        parse_exposition(doc)


# ---------------------------------------------------------------------------
# status board HTML
# ---------------------------------------------------------------------------

def _board_model(refresh_s=2):
    return BoardModel(
        title="test fleet", mode="stream", generated="2026-01-01 00:00:00",
        uptime_s=42.0, refresh_s=refresh_s,
        nodes=[NodeCard(node_id=0, state="healthy", freshness_s=0.2,
                        events_shipped=1200, bytes_shipped=64000),
               NodeCard(node_id=1, state="stale", freshness_s=31.0,
                        events_shipped=400, ring_dropped=7)],
        layers=[LayerRow(layer="operator", window_rows=512, flag_rate=0.21,
                         log_delta=3.4, spark=(0.0, 0.05, 0.21))],
        incidents=[IncidentRow(incident_id=1, t_start=10.0, t_end=12.5,
                               suspect_layer="operator", suspect_nodes=[1],
                               severity=8.5, n_flags=42, status="closed")],
        diagnoses=[DiagnosisCard(incident_id=1, fault_kind="op_latency",
                                 confidence=0.93, severity=8.5,
                                 blamed_nodes=[1],
                                 causal_chain=["operator", "step"],
                                 action_kind="alert",
                                 action_reason="<script>x</script> latency")],
        totals={"events ingested": 99_000})


def test_board_golden_shows_incident_and_diagnosis():
    html_text = render_board(_board_model())
    # structural markers the fleet demo / CI grep for
    for marker in ('id="fleet"', 'id="incidents"', 'id="diagnoses"',
                   'data-node="1"', 'data-state="stale"',
                   'data-kind="op_latency"'):
        assert marker in html_text
    assert "operator" in html_text and "op_latency" in html_text
    assert "alert" in html_text
    assert '<meta http-equiv="refresh" content="2">' in html_text
    assert "<svg" in html_text  # sparkline rendered inline
    # untrusted strings (action reasons can embed arbitrary text) are escaped
    assert "<script>" not in html_text
    assert "&lt;script&gt;" in html_text


def test_board_final_render_stops_refreshing():
    html_text = render_board(_board_model(refresh_s=0))
    assert 'http-equiv="refresh"' not in html_text


def test_board_empty_model_renders():
    model = BoardModel(title="empty", mode="batch", generated="t",
                       uptime_s=0.0, refresh_s=2, nodes=[], layers=[],
                       incidents=[], diagnoses=[], totals={})
    html_text = render_board(model)
    assert "no incidents" in html_text and "no nodes registered" in html_text


# ---------------------------------------------------------------------------
# live session: endpoint smoke + freshness flip
# ---------------------------------------------------------------------------

OPS = np.array(["matmul", "sin", "div", "sum"])


def _emit_steps(buf, steps, t0=0.0, dt=0.05):
    """Synthetic operator+step activity straight into a node's ring (the
    probe suite is empty — tests drive the pipeline deterministically)."""
    for s in steps:
        t = t0 + dt * s
        durs = 1e-4 * (1.0 + np.arange(len(OPS)))
        buf.append_rows(Layer.OPERATOR, OPS, np.full(len(OPS), t), dur=durs,
                        step=np.full(len(OPS), s))
        buf.append_rows(Layer.STEP, "step", t, dur=5e-3, step=s)


def _stream_spec(tmp_path, sink_options=None):
    return MonitorSpec(
        mode="stream", probes=[],
        detector=DetectorSpec(flush_every=5, min_events=32, min_flags=4),
        sinks=[SinkSpec(kind="prometheus",
                        path=str(tmp_path / "metrics.prom"),
                        options=dict(sink_options or {})),
               SinkSpec(kind="board", path=str(tmp_path / "board.html"))],
        governor=False)


def test_endpoint_serves_valid_exposition_and_health(tmp_path):
    spec = _stream_spec(tmp_path, {"serve": True, "port": 0})
    session = Session(spec)
    with session.monitoring():
        _emit_steps(session.node(0).collector.buffer, range(40))
        session.warmup()
        url = session.sink("prometheus").url
        assert url is not None
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            assert "version=0.0.4" in r.headers["Content-Type"]
            body = r.read().decode("utf-8")
        exp = parse_exposition(body)  # strict: raises if malformed
        families = exp.family_names()
        assert len(families) >= 20, families
        # every declared self-metric family is present in the scrape
        assert set(METRIC_NAMES) <= set(families)
        assert exp.sample("eacgm_ring_events_appended_total",
                          node="0").value > 0
        with urllib.request.urlopen(url + "/healthz", timeout=10) as r:
            health = json.loads(r.read().decode("utf-8"))
        assert health["status"] == "ok" and health["mode"] == "stream"
        assert health["scrapes"] >= 1
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(url + "/nope", timeout=10)
    # endpoint is down after finalise; the exposition file survives, valid
    report = session.result()
    with open(report.sink_outputs["prometheus"]) as f:
        parse_exposition(f.read())
    assert "board" in report.sink_outputs


def test_self_time_counters_mirror_session_self_stats(tmp_path):
    """``Session.self_stats()`` holds each probe's self time and the
    executor's wait/run totals; the exposition mirrors them."""
    spec = _stream_spec(tmp_path)
    spec.probes = ["operator", "collective", "device", "step"]
    spec.detector.executor = "inline"
    session = Session(spec)

    @jax.jit
    def step(x):
        return x + 1.0

    with session.monitoring():
        fn = session.observe_step_fn(step)
        _emit_steps(session.node(0).collector.buffer, range(40))
        session.warmup()
        x = jnp.zeros(4)
        for s in range(1, 21):
            x = fn(x)
            session.on_step(s)
        stats = session.self_stats()
        exp = parse_exposition(session.obs_layer().registry.render())
    probes = stats["probes"][0]
    assert set(probes) == {"operator", "collective", "device", "step"}
    assert probes["step"] > 0.0
    for name, sec in probes.items():
        assert exp.sample("eacgm_probe_self_seconds_total", node="0",
                          probe=name).value == pytest.approx(sec)
    detect = stats["detect"]
    assert detect["started"] == detect["completed"] >= 1
    assert detect["busy_seconds"] > 0.0 and detect["wait_seconds"] >= 0.0
    assert exp.sample("eacgm_detect_wait_seconds_total").value == \
        pytest.approx(detect["wait_seconds"])
    assert Session(MonitorSpec(mode="off")).self_stats() == {
        "probes": {}, "detect": {}}


def test_stale_node_flips_when_agent_stops_flushing(tmp_path):
    spec = _stream_spec(tmp_path, {"degraded_after_s": 0.5,
                                   "stale_after_s": 1.0})
    session = Session(spec)
    with session.monitoring():
        b0 = session.node(0).collector.buffer
        b1 = session.node(1).collector.buffer
        _emit_steps(b0, range(40))
        _emit_steps(b1, range(40))
        session.warmup()
        states = {nid: state for nid, state, _ in session.obs.node_states()}
        assert states == {0: "healthy", 1: "healthy"}
        # node 1 goes quiet; node 0 keeps producing, advancing fleet
        # event-time 2s past node 1's last flush (> stale_after_s=1)
        _emit_steps(b0, range(40, 80))
        session.tick()
        states = {nid: (state, fresh)
                  for nid, state, fresh in session.obs.node_states()}
        assert states[0][0] == "healthy"
        assert states[1][0] == "stale" and states[1][1] >= 1.0
        # the gauge and the /healthz detail agree with node_states()
        exp = parse_exposition(session.obs.scrape())
        assert exp.sample("eacgm_node_state", node="0").value == 0
        assert exp.sample("eacgm_node_state", node="1").value == 2
        assert exp.sample("eacgm_node_freshness_seconds",
                          node="1").value >= 1.0
        health = session.obs.health()
        assert health["status"] == "degraded"
        assert health["node_states"]["1"] == "stale"


def test_board_sink_tracks_live_session(tmp_path):
    spec = _stream_spec(tmp_path)
    session = Session(spec)
    with session.monitoring():
        _emit_steps(session.node(0).collector.buffer, range(40))
        session.warmup()
        live = (tmp_path / "board.html").read_text()
        assert 'http-equiv="refresh"' in live  # mid-run board auto-refreshes
        assert 'data-node="0"' in live
    final = (tmp_path / "board.html").read_text()
    assert 'http-equiv="refresh"' not in final  # final render is static
    assert 'id="fleet"' in final
