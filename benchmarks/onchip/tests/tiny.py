"""Tiny copies of the benchmark's cells for CPU tests: the same files and
layout as the real ones, at sizes a test run holds.

Each configuration and traffic file carries its own CPU-test sizes in a
``tiny`` block (a configuration's ``{"sizes": {...}}``, a mix's top-level
overrides). A cell is copied only where both of its files have one; a cell
added without them is left out, so no file here names a configuration or a
mix."""
from __future__ import annotations

import copy
import json
import pathlib
import sys
from typing import Any, Dict, Iterable, Optional

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402


def _tiny_config(src: pathlib.Path, name: str) -> Optional[Dict[str, Any]]:
    cfg = json.loads((src / "configs" / f"{name}.json").read_text())
    tiny = cfg.pop("tiny", None)
    if tiny is None:
        return None
    cfg["sizes"].update(tiny["sizes"])
    return cfg


def _tiny_traffic(src: pathlib.Path, name: str) -> Optional[Dict[str, Any]]:
    tr = json.loads((src / "traffic" / f"{name}.json").read_text())
    tiny = tr.pop("tiny", None)
    if tiny is None:
        return None
    tr.update(copy.deepcopy(tiny))
    return tr


def tiny_tree(tmp: pathlib.Path, bench: Optional[Dict[str, Any]] = None,
              src: pathlib.Path = HERE,
              cells: Optional[Iterable[str]] = None) -> Dict[str, Any]:
    """Write the tiny configuration, traffic and limits files of ``cells``
    (every cell of ``bench`` where None) under ``tmp``, in the benchmark's
    layout, from the files under ``src``. Cells whose configuration or mix
    has no ``tiny`` block are skipped. Returns ``bench``."""
    bench = harness.load_benchmark() if bench is None else bench
    wanted = None if cells is None else set(cells)
    for sub in ("configs", "traffic", "limits"):
        (tmp / sub).mkdir(parents=True, exist_ok=True)
    for w in bench["workloads"]:
        if wanted is not None and w["name"] not in wanted:
            continue
        cfg = _tiny_config(src, w["config"])
        tr = _tiny_traffic(src, w["traffic"])
        if cfg is None or tr is None:
            continue
        (tmp / "configs" / f"{w['config']}.json").write_text(json.dumps(cfg))
        (tmp / "traffic" / f"{w['traffic']}.json").write_text(json.dumps(tr))
        (tmp / "limits" / f"{w['name']}.json").write_text(
            (src / "limits" / f"{w['name']}.json").read_text())
    return bench


def tiny_cell(tmp: pathlib.Path, name: str) -> "harness.Cell":
    return harness.find_cell(name, tiny_tree(tmp, cells=[name]), here=tmp)
