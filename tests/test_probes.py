"""Probe suite: ring buffer invariants, runtime attach/detach, HLO collective
parsing, operator extraction, Perfetto export."""
import json
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.collector import Collector
from repro.core.events import Event, Layer, RingBuffer, to_chrome_trace
from repro.core.probes import PythonProbe
from repro.core.probes.collective_probe import (collective_bytes_by_op,
                                                parse_hlo_collectives)
from repro.core.probes.operator_probe import extract_operator_records


@settings(max_examples=25, deadline=None)
@given(cap=st.integers(1, 50), n=st.integers(0, 200))
def test_ring_buffer_bounded_and_ordered(cap, n):
    rb = RingBuffer(cap)
    for i in range(n):
        rb.push(Event(layer=Layer.STEP, name=f"e{i}", ts=float(i)))
    assert len(rb) == min(n, cap)
    assert rb.dropped == max(0, n - cap)
    got = rb.drain()
    assert len(rb) == 0
    ts = [e.ts for e in got]
    assert ts == sorted(ts)
    if n:
        assert got[-1].name == f"e{n-1}"  # newest survives


def test_python_probe_attach_detach_restores_hook():
    before = sys.getprofile()
    rb = RingBuffer(1000)
    p = PythonProbe(include=("repro",), sample_every=1)
    p.attach(rb)
    assert sys.getprofile() is not None

    from repro.core import gmm  # call something in repro namespace
    _ = gmm.LOG2PI
    p.detach()
    assert sys.getprofile() is before  # zero residue after detach


def test_python_probe_records_repro_calls():
    rb = RingBuffer(10000)
    p = PythonProbe(include=("repro",))
    p.attach(rb)
    from repro.core.features import Standardizer
    Standardizer().fit(np.ones((10, 2)))
    p.detach()
    names = [e.name for e in rb.drain()]
    assert any("Standardizer" in n or "features" in n for n in names)


def test_python_probe_self_time_is_scaled_from_timed_events():
    """One hook event in TIME_EVERY is timed; the estimate scales their
    mean by the exact count of hook events."""
    from repro.core.probes.python_probe import TIME_EVERY
    from repro.core.features import Standardizer

    p = PythonProbe(include=("repro",))
    assert p.self_seconds == 0.0
    p.attach(RingBuffer(100000))
    for _ in range(50):
        Standardizer().fit(np.ones((10, 2)))
    p.detach()
    assert p.hook_events >= 2 * TIME_EVERY
    assert p._timed_events == p.hook_events // TIME_EVERY
    assert p.self_seconds == pytest.approx(
        p._timed_seconds / p._timed_events * p.hook_events)
    assert p.self_seconds > 0.0


def test_step_probe_charges_each_probes_self_time():
    """The step probe times its own emission and each dependent probe's
    per-step hook; the other probes' counters start at nought."""
    col = Collector.standard(with_python=False, device_interval=10.0)

    @jax.jit
    def step(x):
        return x * 2.0

    with col.monitoring():
        fn = col.observe_step_fn(step, sample_args=(jnp.ones((8, 8)),))
        x = jnp.ones((8, 8))
        for _ in range(5):
            x = fn(x)
    own = {p.name: p.self_seconds for p in col.probes}
    assert set(own) == {"xla", "operator", "collective", "device", "step"}
    for name in ("operator", "collective", "device", "step"):
        assert own[name] > 0.0, name
    assert own["xla"] >= 0.0


def test_hlo_collective_parsing_sharded_module():
    """Compile a genuinely sharded module in a subprocess (needs >1 device)."""
    import subprocess

    script = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
import sys
sys.path.insert(0, "src")
from repro.core.probes.collective_probe import collective_bytes_by_op
from repro.launch.mesh import make_local_mesh
mesh = make_local_mesh(1, 4)
def f(x, w):
    return (x @ w).sum()
x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
w = jax.ShapeDtypeStruct((128, 64), jnp.float32)
j = jax.jit(f, in_shardings=(NamedSharding(mesh, P(None, "model")),
                             NamedSharding(mesh, P("model", None))))
agg = collective_bytes_by_op(j.lower(x, w).compile().as_text())
assert "all-reduce" in agg and agg["all-reduce"] > 0, agg
print("OK", agg)
"""
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, cwd=".")
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


def test_operator_extraction_counts_scan_trips():
    def body(c, _):
        return c @ c, None

    def f(x):
        return jax.lax.scan(body, x, None, length=7)[0]

    recs = extract_operator_records(f, jnp.ones((32, 32)))
    dots = [r for r in recs if r["prim"] == "dot_general"]
    assert dots and dots[0]["count"] == 7
    assert dots[0]["flops"] == 7 * 2 * 32 ** 3


def test_collector_step_wrap_and_perfetto(tmp_path):
    col = Collector.standard(with_python=False, device_interval=0.01)

    @jax.jit
    def step(x):
        return x * 2.0

    with col.monitoring():
        fn = col.observe_step_fn(step, sample_args=(jnp.ones((8, 8)),))
        x = jnp.ones((8, 8))
        for _ in range(5):
            x = fn(x)
        time.sleep(0.05)
    events = col.snapshot()
    layers = {e.layer for e in events}
    assert Layer.STEP in layers and Layer.OPERATOR in layers
    steps = [e for e in events if e.layer == Layer.STEP]
    assert len(steps) == 5
    path = col.export_trace(str(tmp_path / "trace.json"))
    data = json.load(open(path))
    assert len(data["traceEvents"]) == len(events)


def test_monitoring_is_nonintrusive():
    """Wrapped step returns bit-identical results."""
    col = Collector.standard(with_python=False)

    @jax.jit
    def step(x):
        return jnp.sin(x) @ jnp.cos(x)

    x = jax.random.normal(jax.random.PRNGKey(0), (16, 16))
    want = step(x)
    with col.monitoring():
        fn = col.observe_step_fn(step)
        got = fn(x)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    assert getattr(fn, "__wrapped__") is step
