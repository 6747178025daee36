"""What one run of one cell is given and what it hands back to the harness.

A cell is found by its name in ``BENCHMARK.json``: its configuration file
(``configs/<config>.json``), its traffic mix (``traffic/<mix>.json``, whose
``driver`` key names the module under ``drivers/`` that runs it) and the
metrics that ``BENCHMARK.json`` lists for it. Nothing here knows a cell by
name, so a new cell is new data files plus entries in ``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional, Tuple

from .trace import TraceEvents

BENCH_DIR = Path(__file__).resolve().parent.parent  # perfbench/
ROOT = BENCH_DIR.parent  # the checkout


@dataclass
class Cell:
    name: str
    config: Dict
    traffic: Dict
    chips: int
    end_to_end: Dict[str, str]  # metric name -> unit
    per_layer: Dict[str, str]


@dataclass
class Run:
    """What a driver measured and checked in one run."""

    setup_s: float
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: Dict[str, Tuple[float, float]]  # name -> (value, limit)
    device: Dict
    window: Tuple[float, float]  # perf_counter seconds
    trace: Optional[TraceEvents] = None
    trace_offset_ns: float = 0.0  # trace time = perf_counter * 1e9 + offset
    data: Dict = field(default_factory=dict)  # driver values for metric readers
    device_planes: List[str] = field(default_factory=list)  # trace planes of the chips used

    def window_ns(self) -> Tuple[float, float]:
        """The measured window on the trace's clock."""
        lo, hi = self.window
        return lo * 1e9 + self.trace_offset_ns, hi * 1e9 + self.trace_offset_ns


def _load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> Dict:
    return _load_json(root / "BENCHMARK.json")


def metrics_for(bench: Dict, workload: str) -> Tuple[Dict[str, str], Dict[str, str]]:
    """(end-to-end, per-layer) metrics that the cell reports, name -> unit.
    An end-to-end metric with no ``workloads`` key is reported by every
    cell; every per-layer metric lists its cells."""
    e2e = {m["name"]: m["unit"] for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])}
    per_layer = {m["name"]: m["unit"] for m in bench["per_layer"]
                 if workload in m["workloads"]}
    return e2e, per_layer


def find_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    e2e, per_layer = metrics_for(bench, workload)
    return Cell(
        name=workload,
        config=_load_json(root / conf["file"]),
        traffic=_load_json(root / "perfbench" / "traffic" / f"{entry['traffic']}.json"),
        chips=int(entry["chips"]),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def load_module(kind: str, name: str) -> ModuleType:
    """``perfbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH_DIR / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"perfbench_{kind}_{name}", path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
