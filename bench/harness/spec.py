"""A cell and the files that define it, found by the names in
``BENCHMARK.json``: ``bench/configs/<config>.json`` (the model as run,
with the engine settings a deployment would choose),
``bench/traffic/<traffic>.json`` (the mix), ``bench/cells/<cell>.json``
(the offered load and the limits of the output check) and
``bench/metrics/<metric>.py`` (one reader per per-layer metric; a
reader named for the part of the name before the first dot serves
every metric of that quantity without one of its own), and
``bench/reference/<family>.py``, the family module that the config
names under ``"reference"``: the weight tree, the work counts, the
device scope names and the plain reference of its model
(``bench/reference/dense.py`` says what each is).
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List


class SpecError(ValueError):
    """The benchmark's files do not define the cell asked for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    run_seconds: int
    config: dict
    traffic: dict
    load: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path

    @property
    def model(self) -> dict:
        return self.config["model"]

    @property
    def engine(self) -> dict:
        return self.config["engine"]

    @functools.cached_property
    def family(self) -> ModuleType:
        """The configuration's family module, loaded once per cell."""
        return family_module(self.root, self.config["reference"])


def _json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing {path}")
    return json.loads(path.read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(root: Path, workload: str) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    base = root / "bench"
    return Cell(
        name=workload, chips=int(w["chips"]),
        run_seconds=int(bench["run_seconds"]),
        config=_json(base / "configs" / f"{w['config']}.json"),
        traffic=_json(base / "traffic" / f"{w['traffic']}.json"),
        load=_json(base / "cells" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"]
                    if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)],
        root=root)


def _module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family_module(root: Path, name: str) -> ModuleType:
    """``bench/reference/<name>.py``: ``shapes(model)`` and
    ``make(model, seed)`` of the served weight tree, ``work(model)`` for
    ``harness/work.py``, ``SCOPES`` for ``harness/scopes.py`` and
    ``logit_gaps(...)``, the plain reference."""
    path = root / "bench" / "reference" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"missing {path}")
    return _module(path, f"bench_reference_{name}")


def metric_reader(root: Path, name: str) -> Callable:
    """``read(ctx) -> float | None`` of a per-layer metric."""
    base = root / "bench" / "metrics"
    for stem in (name, name.split(".")[0]):
        path = base / f"{stem}.py"
        if path.is_file():
            return _module(path,
                           f"bench_metric_{stem.replace('.', '_')}").read
    raise SpecError(f"no reader for per-layer metric {name!r} in {base}")


def readers(cell: Cell) -> Dict[str, Callable]:
    return {m["name"]: metric_reader(cell.root, m["name"])
            for m in cell.per_layer}
