"""Training driver with first-class eACGM monitoring.

    PYTHONPATH=src python -m repro.launch.train --arch gpt2 --reduced \
        --steps 200 --batch 8 --seq 128 --monitor-spec '{"mode": "batch"}' \
        --inject-faults

Monitoring is described by one declarative `MonitorSpec` (inline JSON, a JSON
file path, or the REPRO_MONITOR_SPEC env var); the `Session` facade attaches
the probe suite at runtime, so the model/step code is IDENTICAL with and
without monitoring (the paper's zero-instrumentation contract). The old
``--monitor`` / ``--stream-monitor`` / ``--stream-flush-every`` flags still
work as deprecated shims onto the spec. Fault tolerance: deterministic data
pipeline + async checkpoints + auto-resume; the Governor turns detected
anomalies into actions (its checkpoint_now action triggers an immediate
snapshot).
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import ShapeConfig, TrainConfig, get_arch, reduced
from repro.data import SyntheticLMData
from repro.detect.cache import enable_persistent_cache
from repro.launch import specs as S
from repro.launch.mesh import make_local_mesh
from repro.models.model import Runtime
from repro.roofline import model_flops
from repro.session import MonitorReport, MonitorSpec, Session, SinkSpec
from repro.train.checkpoint import CheckpointManager
from repro.train.step import (init_train_state, make_optimizer_for,
                              make_train_step)

# historical tuning of the train driver, applied only on the legacy-flag path
# (an explicit --monitor-spec keeps full control of these)
LEGACY_PROBE_OPTIONS = {"python": {"sample_every": 25},
                        "device": {"interval": 0.05}}


@dataclasses.dataclass
class TrainRun:
    """What one `run` produced: the exit code `main` returns, plus the
    artifacts a caller may check (the smoke test on the chip does)."""

    exit_code: int
    losses: List[float]
    state: Any  # final TrainState
    compiled: Any  # the jax.stages.Compiled step program that ran
    report: Optional[MonitorReport]  # None when monitoring is off
    collectives: List[str]  # ops of the collective probe's schedule


def main(argv=None) -> int:
    return run(argv).exit_code


def run(argv=None) -> TrainRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-mesh", type=int, default=0,
                    help="data-axis size of a local mesh (0 = no mesh)")
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    MonitorSpec.add_cli_args(ap)
    ap.add_argument("--monitor", action="store_true",
                    help="[deprecated] = --monitor-spec '{\"mode\":\"batch\"}'")
    ap.add_argument("--stream-monitor", action="store_true",
                    help="[deprecated] = --monitor-spec "
                         "'{\"mode\":\"stream\"}'")
    ap.add_argument("--stream-flush-every", type=int, default=25,
                    help="[deprecated] = spec detector.flush_every")
    ap.add_argument("--inject-faults", action="store_true")
    ap.add_argument("--trace-out", default="",
                    help="perfetto trace path (= a \"perfetto\" sink)")
    ap.add_argument("--metrics-port", type=int, default=-1,
                    help="serve monitor self-metrics on this port "
                         "(= a \"prometheus\" sink; 0 = ephemeral)")
    ap.add_argument("--board-out", default="",
                    help="write a live HTML status board here "
                         "(= a \"board\" sink)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_persistent_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh = None
    if args.data_mesh:
        mesh = make_local_mesh(args.data_mesh, args.model_mesh)
    rt = Runtime(mesh=mesh, compute_dtype=jnp.float32 if args.reduced
                 else jnp.bfloat16)
    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       optimizer=args.optimizer, warmup_steps=args.steps // 10)
    opt = make_optimizer_for(tcfg)
    shp = ShapeConfig("run", args.seq, args.batch, "train")

    data = SyntheticLMData(cfg, seq_len=args.seq, global_batch=args.batch,
                           seed=args.seed)
    key = jax.random.PRNGKey(args.seed)
    state = init_train_state(key, cfg, opt)

    # ---- fault tolerance: auto-resume ----
    ckpt = None
    start_step = 0
    if args.checkpoint_dir:
        ckpt = CheckpointManager(args.checkpoint_dir)
        restored, meta, rstep = ckpt.restore_latest(state)
        if restored is not None:
            state, start_step = restored, rstep
            print(f"[resume] restored checkpoint at step {rstep}")

    # ---- placement + one compile ----
    # On a mesh the state is placed by the model's partition rules and the
    # batch is split over the data axis; the step keeps both placements, so
    # every call runs the one program compiled here (the collective probe
    # reads this same program's HLO)
    jit_kw = {}
    batch_sharding = None
    if mesh is not None:
        _, state_specs = S.train_state_specs(cfg, rt, tcfg)
        state_sharding = S.named(mesh, state_specs)
        batch_sharding = S.named(mesh, S.batch_pspecs(cfg, shp, rt))
        state = jax.device_put(state, state_sharding)
        jit_kw = dict(in_shardings=(state_sharding, batch_sharding),
                      out_shardings=(state_sharding,
                                     NamedSharding(mesh, P())))

    def device_batch(step):
        return jax.device_put(data.batch(step), batch_sharding)

    step_fn = jax.jit(make_train_step(cfg, rt, opt,
                                      microbatches=args.microbatches),
                      donate_argnums=(0,), **jit_kw)
    compiled = step_fn = step_fn.lower(
        state, device_batch(start_step)).compile()

    # ---- monitoring session (runtime attachment; user code unchanged) ----
    # the batch sweep historically fitted with min_events=48; the stream
    # path always used the StreamMonitor default (64) — preserve both
    legacy_defaults = {"probe_options": LEGACY_PROBE_OPTIONS}
    if not args.stream_monitor:
        legacy_defaults["detector"] = {"min_events": 48}
    spec = MonitorSpec.from_args(args, legacy_defaults=legacy_defaults)
    if spec.mode != "off":
        if args.metrics_port >= 0:
            spec.sinks.append(SinkSpec(
                kind="prometheus",
                options={"serve": True, "port": args.metrics_port}))
        if args.board_out:
            spec.sinks.append(SinkSpec(kind="board", path=args.board_out))
    session = Session(spec)
    if not session.off and args.metrics_port >= 0:
        print(f"[monitor] metrics endpoint: "
              f"{session.sink('prometheus').url}/metrics")
    injector = None
    if args.inject_faults and not session.off:
        from repro.core import FaultInjector

        injector = FaultInjector.random_schedule(
            args.steps, ["op_latency", "net_latency", "hw_contention"],
            seed=args.seed)

    losses = []
    collectives: List[str] = []
    t0 = time.time()
    with session.monitoring():
        if not session.off:
            step_fn = session.observe_step_fn(
                step_fn, lowered=compiled,
                flops_per_step=model_flops(cfg, shp),
                mem_gb=sum(x.size * x.dtype.itemsize for x in
                           jax.tree.leaves(state.params)) / 2**30)
            if "collective" in session.spec.probes:
                collectives = session.collector["collective"].schedule_ops

        # ---- training loop ----
        # KeyboardInterrupt is caught INSIDE the monitoring context: the
        # session still finalises and closes its sinks, so a Ctrl-C'd run
        # leaves a valid board/metrics/report instead of nothing
        try:
            for step in range(start_step, args.steps):
                if injector is not None:
                    injector.apply(step, session.collector)
                state, metrics = step_fn(state, device_batch(step))
                loss = float(metrics["loss"])
                losses.append(loss)
                if step % args.log_every == 0 or step == args.steps - 1:
                    print(f"step {step:5d} loss {loss:8.4f} "
                          f"gnorm {float(metrics['grad_norm']):8.3f} "
                          f"lr {float(metrics['lr']):.2e} "
                          f"({(time.time()-t0):6.1f}s)")
                if ckpt is not None and step \
                        and step % args.checkpoint_every == 0:
                    ckpt.save(step, state, meta={"loss": loss})
                # periodic anomaly sweep: the session owns the cadence
                out = session.on_step(step)
                if out.warmed:
                    print(f"[monitor] warmed layers: "
                          f"{[l.value for l in out.warmed]}")
                for inc in out.incidents:
                    print("[monitor] " + inc.render())
                for action in out.actions:
                    print(f"[governor] {action.kind}: {action.reason}")
                    if action.kind == "checkpoint_now" and ckpt is not None:
                        ckpt.save(step, state, meta={"loss": loss,
                                                     "reason": "governor"})
        except KeyboardInterrupt:
            interrupted = True
            print(f"\n[monitor] interrupted at step {step}; "
                  "flushing monitor artifacts")
        else:
            interrupted = False
        if injector is not None:
            injector.clear(session.collector)
    if ckpt is not None:
        if losses:
            ckpt.save(start_step + len(losses) - 1, state,
                      meta={"loss": losses[-1]})
        ckpt.close()
    report = None
    if not session.off:
        report = session.result()
        print(report.render())
        print("[monitor] overhead stats:", report.overhead)
    if losses:
        print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f}); "
              f"{len(losses)} steps in {time.time()-t0:.1f}s")
    return TrainRun(exit_code=130 if interrupted else 0, losses=losses,
                    state=state, compiled=compiled, report=report,
                    collectives=collectives)


if __name__ == "__main__":
    sys.exit(main())
