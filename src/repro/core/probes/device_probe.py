"""Device-layer probe: the libnvml analogue.

Two sources, matching the paper's split between process-level and global GPU
monitoring:

* **Host truth** (/proc, psutil): per-process RSS, CPU time, thread count —
  genuinely non-intrusive measurements of the running training process.
* **Accelerator telemetry model**: on a real TPU VM this seam reads libtpu /
  megascale counters; in this CPU container it is a simulator driven by the
  compiled artifacts (HBM bytes/step, FLOPs/step) and the observed step times,
  producing utilisation / memory / power / temperature streams with the same
  statistical structure nvml gives the paper. Chaos hooks inject contention.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np
import psutil

from repro.core.events import Layer
from repro.core.probes.base import Probe
from repro.roofline import device_peaks


class TpuTelemetryModel:
    """Telemetry simulator for one device: first-order thermal/power model."""

    def __init__(self, peak_flops: Optional[float] = None,
                 hbm_gb: Optional[float] = None,
                 idle_w: float = 60.0, peak_w: float = 250.0,
                 ambient_c: float = 30.0, seed: int = 0):
        import random

        peaks = device_peaks()
        self.peak_flops = peaks["peak_flops"] if peak_flops is None \
            else peak_flops
        self.hbm_gb = peaks["hbm_gib"] if hbm_gb is None else hbm_gb
        self.idle_w = idle_w
        self.peak_w = peak_w
        self.temp_c = ambient_c
        self.ambient_c = ambient_c
        self._rng = random.Random(seed)
        # chaos hooks
        self.contention = 0.0  # 0..1 fraction of the device stolen
        self.mem_leak_gb = 0.0

    def sample(self, duty: float, mem_gb: float) -> Dict[str, float]:
        duty = min(1.0, max(0.0, duty + self.contention * self._rng.uniform(0.5, 1.0)))
        mem = min(self.hbm_gb, mem_gb + self.mem_leak_gb
                  + self.contention * self._rng.uniform(1.0, 4.0))
        power = self.idle_w + (self.peak_w - self.idle_w) * duty
        power *= 1 + 0.03 * self._rng.gauss(0, 1)
        # first-order thermal relaxation toward power-determined equilibrium
        target = self.ambient_c + 50.0 * power / self.peak_w
        self.temp_c += 0.2 * (target - self.temp_c) + 0.3 * self._rng.gauss(0, 1)
        return {
            "util": 100.0 * duty * (1 + 0.02 * self._rng.gauss(0, 1)),
            "mem_gb": mem,
            "power_w": power,
            "temp_c": self.temp_c,
        }


class DeviceProbe(Probe):
    name = "device"

    def __init__(self, interval: float = 0.25, n_devices: int = 1,
                 telemetry: Optional[List[TpuTelemetryModel]] = None):
        super().__init__()
        self.interval = interval
        self.devices = telemetry or [TpuTelemetryModel(seed=i)
                                     for i in range(n_devices)]
        self._dev_names = np.array([f"tpu{i}"
                                    for i in range(len(self.devices))])
        self._proc = psutil.Process()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # fed by the step probe:
        self.current_duty = 0.0
        self.current_mem_gb = 0.0

    def _attach(self) -> None:
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _detach(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None

    def sample_once(self) -> None:
        """One telemetry sweep: host-truth row + one row per device, each
        with its telemetry in the dedicated columns (no meta dicts)."""
        ts = self.now()
        pid = os.getpid()
        with self._proc.oneshot():
            rss = self._proc.memory_info().rss
            cpu = self._proc.cpu_percent(interval=None)
            nthreads = self._proc.num_threads()
        self.emit_rows(Layer.DEVICE, "host.process", ts, size=float(rss),
                       pid=pid,
                       meta=f'{{"cpu_pct":{cpu},"threads":{nthreads}}}')
        samples = [dev.sample(self.current_duty, self.current_mem_gb)
                   for dev in self.devices]
        mem = np.array([m["mem_gb"] for m in samples])
        self.emit_rows(Layer.DEVICE, self._dev_names, ts, size=mem * 2**30,
                       pid=pid,
                       util=np.array([m["util"] for m in samples]),
                       mem_gb=mem,
                       power_w=np.array([m["power_w"] for m in samples]),
                       temp_c=np.array([m["temp_c"] for m in samples]))

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.sample_once()
            except Exception:
                pass
