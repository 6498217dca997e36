"""Step-level observer: runtime wrapping of the already-built step callable.

The monitor (not the user) wraps the step function at attach time — exactly
the eBPF model of hooking a symbol at runtime: the training loop's code is
unchanged, the launcher simply executes whatever callable the monitor hands
back. Records wall-time per step and drives the dependent probes (operator
latency attribution, collective schedule replay, device duty cycle).
"""
from __future__ import annotations

import os
import time
from time import perf_counter
from typing import Any, Callable, Optional

import jax
from jax.profiler import TraceAnnotation

from repro.core.events import Layer
from repro.core.probes.base import Probe
from repro.roofline import device_peaks


class StepProbe(Probe):
    name = "step"

    def __init__(self, operator_probe=None, collective_probe=None,
                 device_probe=None, flops_per_step: float = 0.0,
                 peak_flops: Optional[float] = None,
                 mem_gb_per_step: float = 0.0):
        super().__init__()
        self.operator_probe = operator_probe
        self.collective_probe = collective_probe
        self.device_probe = device_probe
        self.flops_per_step = flops_per_step
        self.peak_flops = (device_peaks()["peak_flops"] if peak_flops is None
                           else peak_flops)
        self.mem_gb_per_step = mem_gb_per_step
        self.step_count = 0
        self.extra_latency = 0.0  # chaos hook: python-layer delay (real sleep)
        # chaos hooks per monitored layer (seconds added to that layer's view):
        self.extra_xla = 0.0   # DCGM kernel-timeout analogue
        self.extra_op = 0.0    # pytorchfi operator-delay analogue

    def _attach(self) -> None:
        pass

    def _detach(self) -> None:
        pass

    def wrap(self, fn: Callable) -> Callable:
        """Return a monitored version of `fn` (user code untouched).

        Spans (`jax.profiler.TraceAnnotation`, entered directly: a helper
        under ``repro.*`` would itself fire the python probe's hook) mark
        the call's dispatch (``eacgm.step.call``), the wait for its result
        (``eacgm.step.wait``) and the probes' work after it
        (``eacgm.probe.emit``), whose time is charged to each probe's
        ``self_seconds``."""

        def monitored(*args, **kwargs):
            t0 = self.now()
            with TraceAnnotation("eacgm.step.call"):
                out = fn(*args, **kwargs)
            with TraceAnnotation("eacgm.step.wait"):
                out = jax.block_until_ready(out)
            exec_dur = self.now() - t0
            if self.extra_latency:  # python-layer fault: real host-side stall
                time.sleep(self.extra_latency)
            with TraceAnnotation("eacgm.probe.emit"):
                t = perf_counter()
                dur = (self.now() - t0) + self.extra_xla + self.extra_op
                step = self.step_count
                self.step_count += 1
                # runtime/XLA layer: the executable-run duration an eBPF
                # uprobe on the runtime's execute symbol would time
                # (CUDA-layer analogue)
                pid = os.getpid()
                self.emit_rows(Layer.XLA, "executable_run", t0,
                               dur=exec_dur + self.extra_xla, step=step,
                               pid=pid)
                self.emit_rows(Layer.STEP, "train_step", t0, dur=dur,
                               step=step, pid=pid)
                now = perf_counter()
                self.self_seconds += now - t
                t = now
                comm = 0.0
                probe = self.collective_probe
                if probe is not None and probe.attached:
                    comm = probe.observe_step(step, t0)
                    now = perf_counter()
                    probe.self_seconds += now - t
                    t = now
                probe = self.operator_probe
                if probe is not None and probe.attached:
                    probe.observe_step(
                        step, max(exec_dur - comm, 0.0) + self.extra_op, t0)
                    now = perf_counter()
                    probe.self_seconds += now - t
                    t = now
                probe = self.device_probe
                if probe is not None:
                    duty = 0.0
                    if dur > 0 and self.flops_per_step:
                        duty = min(1.0, self.flops_per_step
                                   / self.peak_flops / dur)
                    elif dur > 0:
                        duty = min(1.0, 0.7 + 0.1 * (dur % 0.1))
                    probe.current_duty = duty
                    probe.current_mem_gb = self.mem_gb_per_step
                    probe.self_seconds += perf_counter() - t
            return out

        monitored.__wrapped__ = fn
        return monitored
