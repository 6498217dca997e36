"""Cell lookup, device checks, metric readers and the result line.

Everything here is driven by data: a cell of ``BENCHMARK.json`` names a
configuration and a traffic mix, and this module finds their files by name:

* ``configs/<config>.json``  -- sizes, source, ``reduced``, ``assumed``,
  ``departures``, precision and deployment of one model configuration;
* ``traffic/<traffic>.json`` -- the job or request mix, and the driver
  (``drivers/<driver>.py``) that runs it;
* ``limits/<workload>.json`` -- the limits of the numbers that decide
  ``correct`` in that cell, with the readings they were set from;
* ``metrics/<metric>.py``    -- one reader per per-layer metric.

A later cell, mix or metric is added by adding files and entries alone.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from typing import Any, Callable, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]  # the checkout: BENCHMARK.json sits here


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with everything its files hold."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]  # the e2e metrics this cell reports
    per_layer: List[Dict[str, Any]]  # the per-layer metrics it reports

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def _read_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    return _read_json(root / "BENCHMARK.json")


def find_cell(name: str, bench: Optional[Dict[str, Any]] = None,
              here: pathlib.Path = HERE) -> Cell:
    """The cell called ``name``, with its configuration, traffic and limits
    read from the files that carry their names under ``here``."""
    bench = load_benchmark() if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = _read_json(here / "configs" / f"{w['config']}.json")
    traffic = _read_json(here / "traffic" / f"{w['traffic']}.json")
    limits = _read_json(here / "limits" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in reported]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer)


def metric_reader(name: str, here: pathlib.Path = HERE) -> Callable:
    """``read(ctx) -> float | None`` of ``metrics/<name>.py``."""
    path = here / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"onchip_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod.read


def load_driver(name: str, here: pathlib.Path = HERE):
    """The module ``drivers/<name>.py``; it exposes ``run(cell, ...)``."""
    path = here / "drivers" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"onchip_driver_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def device_info(chips: int) -> Dict[str, Any]:
    """Platform, kind and count as JAX reports them. Raises `NoDevice` off a
    TPU or with fewer chips than asked: the benchmark never falls back."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise NoDevice(f"no TPU: JAX's first device is on {dev.platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chip(s), JAX sees "
                       f"{len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's chips (0 where the
    backend keeps no statistics, as the CPU does)."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


@dataclasses.dataclass
class Check:
    """One number that decides ``correct``, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit  # NaN fails too


@dataclasses.dataclass
class RunResult:
    """What a driver hands back to `run.py` after one run."""

    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: List[Check]
    context: Any  # what the per-layer readers read (see metrics/)
    info: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def result_line(cell: Cell, res: RunResult, device: Dict[str, Any],
                trace: bool) -> Dict[str, Any]:
    """The last line of standard output, as the benchmark's contract has it;
    the compared numbers come last, under ``checks``."""
    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        for m in cell.per_layer:
            value = metric_reader(m["name"])(res.context)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": res.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    line: Dict[str, Any] = {"correct": res.correct,
                            "attempted": res.attempted,
                            "failed": res.failed, "metrics": metrics,
                            "device": device}
    if trace and getattr(res.context, "trace", None) is not None:
        line["breakdown"] = res.context.trace.breakdown()
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in res.checks}
    return line


def print_checks(checks: List[Check]) -> None:
    """The compared numbers beside their limits, as the last lines of
    standard error."""
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
