"""End-to-end behaviour tests for the paper's system: monitored training with
injected faults -> GMM detection -> governance, plus sharded-vs-local parity
and the hloanalysis cost model."""
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import TrainConfig, get_arch, reduced
from repro.core import (Collector, FaultInjector, FullStackMonitor, Governor,
                        Layer)
from repro.data import SyntheticLMData
from repro.models.model import Runtime
from repro.train.step import (init_train_state, make_optimizer_for,
                              make_train_step)


def test_monitored_training_detects_injected_faults():
    """The paper's core loop: train, inject faults, fit GMM on a clean
    window, detect — anomalous steps must overlap the injected windows
    far above chance."""
    cfg = reduced(get_arch("gpt2"))
    rt = Runtime(mesh=None, compute_dtype=jnp.float32)
    tcfg = TrainConfig(learning_rate=1e-3, total_steps=120, warmup_steps=5)
    opt = make_optimizer_for(tcfg)
    data = SyntheticLMData(cfg, seq_len=32, global_batch=4, seed=0)
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    step_fn = jax.jit(make_train_step(cfg, rt, opt))

    col = Collector.standard(with_python=False, device_interval=0.01)
    inj = FaultInjector.random_schedule(
        120, ["op_latency"], seed=7, anomaly_fraction=1 / 6,
        magnitudes={"op_latency": 0.03})
    with col.monitoring():
        fn = col.observe_step_fn(step_fn,
                                 sample_args=(state, jax.tree.map(
                                     jnp.asarray, data.batch(0))))
        for s in range(120):
            inj.apply(s, col)
            state, m = fn(state, jax.tree.map(jnp.asarray, data.batch(s)))
        inj.clear(col)
    events = col.drain()
    labels = inj.labels(120)
    clean = [e for e in events if 0 <= e.step < 120 and not labels[e.step]]
    mon = FullStackMonitor(n_components=3, min_events=32).fit(clean)
    results = mon.detect(events)
    assert Layer.STEP in results
    res = results[Layer.STEP]
    flagged = set(res.anomalous_steps().tolist())
    true_steps = set(np.nonzero(labels)[0].tolist())
    hit_rate = len(flagged & true_steps) / len(true_steps)
    false_rate = len(flagged - true_steps) / (120 - len(true_steps))
    assert hit_rate > 0.5, (hit_rate, false_rate)
    assert hit_rate > 2 * false_rate, (hit_rate, false_rate)
    # governance reacts
    actions = Governor(rate_threshold=0.05).decide(results)
    assert actions


def test_loss_decreases_over_training():
    cfg = reduced(get_arch("gpt2"))
    rt = Runtime(mesh=None, compute_dtype=jnp.float32)
    tcfg = TrainConfig(learning_rate=3e-3, total_steps=40, warmup_steps=4)
    opt = make_optimizer_for(tcfg)
    data = SyntheticLMData(cfg, seq_len=32, global_batch=8, seed=0)
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    step_fn = jax.jit(make_train_step(cfg, rt, opt))
    losses = []
    for s in range(40):
        state, m = step_fn(state, jax.tree.map(jnp.asarray, data.batch(s)))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-8:]) < np.mean(losses[:8]) - 0.2, losses[::8]


def test_serving_engine_generates():
    from repro.serve.engine import ServeEngine
    from repro.models.model import init_params

    cfg = reduced(get_arch("llama3.2-1b"))
    rt = Runtime(mesh=None, compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg=cfg, rt=rt, params=params, batch_size=2,
                      max_len=64)
    out = eng.generate(np.array([[1, 2, 3], [4, 5, 6]], np.int32), 10)
    assert out.shape == (2, 13)
    assert (out[:, :3] == [[1, 2, 3], [4, 5, 6]]).all()
    assert (out >= 0).all() and (out < cfg.vocab_size).all()


def test_sharded_matches_local_all_families():
    """GSPMD + shard_map MoE parity on 8 fake devices (subprocess: device
    count must not leak into this process)."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.config import get_arch, reduced
from repro.launch.mesh import make_local_mesh
from repro.models.model import Runtime, init_params, loss_fn, param_partition_specs
mesh = make_local_mesh(2, 4)
for arch in ["deepseek-v2-236b", "arctic-480b", "zamba2-7b", "mamba2-2.7b",
             "h2o-danube-3-4b", "hubert-xlarge"]:
    cfg = reduced(get_arch(arch))
    rt = Runtime(mesh=mesh, compute_dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    pspecs = param_partition_specs(cfg, rt, params)
    params_s = jax.device_put(params, jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs))
    B, S = 4, 32
    if cfg.input_mode == "tokens":
        batch = {"tokens": jax.random.randint(key, (B,S), 0, cfg.vocab_size),
                 "labels": jax.random.randint(key, (B,S), 0, cfg.vocab_size)}
    else:
        batch = {"embeddings": 0.1*jax.random.normal(key, (B,S,cfg.d_model)),
                 "labels": jax.random.randint(key, (B,S), 0, cfg.vocab_size)}
    batch_s = jax.device_put(batch, NamedSharding(mesh, P("data")))
    with jax.set_mesh(mesh):
        loss_sharded, _ = jax.jit(lambda p,b: loss_fn(p, cfg, rt, b))(params_s, batch_s)
    rt0 = Runtime(mesh=None, compute_dtype=jnp.float32)
    loss_local, _ = jax.jit(lambda p,b: loss_fn(p, cfg, rt0, b))(params, batch)
    diff = abs(float(loss_sharded) - float(loss_local))
    assert diff < 5e-3, (arch, diff)
    print("OK", arch, diff)
"""
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, cwd=".")
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.count("OK") == 6


def test_hlo_cost_model_scan_exact():
    from repro.hloanalysis import HloCostModel

    def body(c, _):
        return c @ c, None

    def f(x):
        return jax.lax.scan(body, x, None, length=11)[0]

    x = jnp.ones((64, 64))
    m = HloCostModel(jax.jit(f).lower(x).compile().as_text())
    assert m.flops == 11 * 2 * 64 ** 3
    assert list(m.while_trips.values()) == [11.0]
