"""What a cell is made of, found by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  Each lives in a file of its own, found by name:

- ``configs/<config>.json``: the model's sizes, source, cut and serving
  precisions;
- ``traffic/<mix>.json``: the parameters the one generator reads;
- ``limits/<cell>.json``: the correctness limits, with the readings they
  were set from;
- ``metrics/<metric>.py``: one reader per metric, ``read(record)``;
- ``families/<family>.py``: what depends on the model family the
  configuration states (its sizes, parameter layout, plain reference and
  step costs).

Adding a configuration, a mix or a metric is adding its file and its
entry in ``BENCHMARK.json``; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK = ROOT / "BENCHMARK.json"


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: Callable


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def metric_reader(name: str, base: Path = HERE) -> Callable:
    """``read`` of ``metrics/<name>.py``, loaded from its file (a name
    may hold dots, which no import statement takes)."""
    path = base / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _metrics(entries, cell: str, base: Path) -> List[Metric]:
    return [Metric(m["name"], m["unit"], metric_reader(m["name"], base))
            for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def load_cell(name: str, bench: Path = BENCHMARK,
              base: Path = HERE) -> Cell:
    """The cell ``name`` of ``bench``, with its files; an unknown name or
    a missing file raises."""
    b = load_json(bench)
    cells = {w["name"]: w for w in b["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench.name}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(base / "configs" / f"{w['config']}.json"),
                traffic=load_json(base / "traffic" / f"{w['traffic']}.json"),
                limits=load_json(base / "limits" / f"{name}.json"),
                end_to_end=_metrics(b["end_to_end"], name, base),
                per_layer=_metrics(b["per_layer"], name, base))
