"""XLA/runtime-layer probe: the CUDA-event analogue.

JAX exposes a global telemetry bus (`jax.monitoring`): the runtime itself
records compilation, lowering, backend init and dispatch durations. We attach
listeners at runtime — zero instrumentation of user code, and the events come
from *inside* the framework exactly like eBPF uprobes on libcudart calls.
"""
from __future__ import annotations

import json
import os
from time import perf_counter
from typing import Callable, List

import jax

from repro.core.events import Layer
from repro.core.probes.base import Probe
from repro.detect.guard import in_detection_zone


class JaxRuntimeProbe(Probe):
    name = "xla"

    def __init__(self):
        super().__init__()
        self._dur_listener: Callable = None
        self._evt_listener: Callable = None

    def _attach(self) -> None:
        # jax.monitoring listeners are GLOBAL (every thread's compiles and
        # dispatches land here). The async detection plane runs EM on a
        # background worker while this probe stays attached, so listeners
        # drop events originating inside a detection sweep — otherwise each
        # sweep would inject its own compile/dispatch events into the very
        # stream it is scoring (the step thread's synchronous sweeps handle
        # this by detaching the probe; see Session._detection_pause).
        # self_seconds counts the listeners' time outside detection sweeps
        def on_duration(name: str, secs: float, **kw):
            if in_detection_zone():
                return
            t = perf_counter()
            extra = {k: v for k, v in kw.items()
                     if isinstance(v, (int, float, str))}
            self.emit_rows(Layer.XLA, name, self.now(), dur=secs,
                           pid=os.getpid(),
                           meta=json.dumps(extra, separators=(",", ":"))
                           if extra else "")
            self.self_seconds += perf_counter() - t

        def on_event(name: str, **kw):
            if in_detection_zone():
                return
            t = perf_counter()
            self.emit_rows(Layer.XLA, name, self.now(), pid=os.getpid())
            self.self_seconds += perf_counter() - t

        self._dur_listener = on_duration
        self._evt_listener = on_event
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def _detach(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._dur_listener)
        jax.monitoring.unregister_event_listener(self._evt_listener)
        self._dur_listener = self._evt_listener = None
