"""Finds a cell's files by the names ``BENCHMARK.json`` gives.

A cell names a configuration and a traffic mix; each is a JSON file of
its own under ``benchmarks/onchip/`` (``configs/<config>.json``,
``traffic/<traffic>.json``), the numbers a cell's ``correct`` is held to
are in ``limits/<cell>.json``, and each per-layer metric is read by
``metrics/<metric>.py``.  A configuration names its model family, whose
module ``families/<family>.py`` gives the harness everything that depends
on the model: the task's fields, the check of the built model, the case
pool, the plain reference and the work counts.  Adding a cell,
configuration, model family or metric adds files and entries; it edits
none.  A metric with a ``workloads`` list is read in the cells it names
alone: a later cell that should report it adds its name to that list, an
edit of ``BENCHMARK.json``, while a cell of another family, for which it
means nothing, needs no edit.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    family: Any               # the configuration's families/<family>.py
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: Dict[str, Any], workload: str,
            bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell named ``workload`` with its configuration, traffic and
    limits loaded and the metrics it reports selected."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[workload]
    config = load_json(bench_dir / "configs" / f"{w['config']}.json")
    if "family" not in config:
        raise ValueError(f"configuration {w['config']!r} names no model "
                         "family: its file needs a \"family\" key naming "
                         "families/<family>.py")
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        family=family(config["family"], bench_dir),
        traffic=load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(bench_dir / "limits" / f"{workload}.json")["limits"],
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)])


def _load_module(path: Path, module_name: str):
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR
                  ) -> Callable[[Any], Optional[float]]:
    """``read(reading) -> value or None`` from ``metrics/<name>.py``."""
    return _load_module(bench_dir / "metrics" / f"{name}.py",
                        f"onchip_metric_{name}").read


def family(name: str, bench_dir: Path = BENCH_DIR):
    """The model family module ``families/<name>.py``: ``task_fields``,
    ``check_model``, ``make_pool``, ``run_reference``, ``round_flops``
    and ``num_params``."""
    if not isinstance(name, str) or not NAME.match(name):
        raise ValueError(f"model family name {name!r} breaks the name rules")
    return _load_module(bench_dir / "families" / f"{name}.py",
                        f"onchip_family_{name}")


def check_names(bench: Dict[str, Any]) -> List[str]:
    """Every name, config, traffic and unit in ``bench`` that breaks the
    character rules."""
    bad = []
    for c in bench["configs"]:
        bad += [c["name"]] if not NAME.match(c["name"]) else []
        bad += [k for k in c["reduced"] if not NAME.match(k)]
    for w in bench["workloads"]:
        bad += [v for v in (w["name"], w["config"], w["traffic"])
                if not NAME.match(v)]
    for m in bench["end_to_end"] + bench["per_layer"]:
        bad += [m["name"]] if not NAME.match(m["name"]) else []
        bad += [m["unit"]] if not UNIT.match(m["unit"]) else []
    return bad
