"""Finds every part of a cell by its name in ``BENCHMARK.json``.

* a configuration: the ``file`` its entry names (``bench/configs/``);
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a cell's limits on its comparison with the reference:
  ``bench/limits/<workload>.json``;
* a per-layer metric: ``bench/metrics/<metric>.py``, a module with
  ``read(run) -> float | None``;
* a language model's architecture: ``bench/models/<model_type>.py``,
  named by its configuration's ``model_type`` (``bench/models/qwen2.py``
  says what such a module holds).

Adding a configuration, mix, cell, metric or architecture is adding a
file and an entry; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, NamedTuple

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict                 # the configuration file
    mix: dict                    # the traffic file
    limits: dict                 # number -> {"limit": ...}
    end_to_end: list             # BENCHMARK.json metric entries it reports
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: dict, workload: str, e2e_of: dict) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    if "moves" in metric:           # per-layer: wherever its e2e metric is
        return workload in e2e_of.get(metric["moves"], ())
    return True


def cell(name: str, root: Path = ROOT) -> Cell:
    bm = load_benchmark(root)
    entries = {w["name"]: w for w in bm["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(entries)}")
    w = entries[name]
    configs = {c["name"]: c for c in bm["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        mix = json.load(f)
    with open(root / "bench" / "limits" / f"{name}.json") as f:
        limits = json.load(f)
    if config.get("family") == "lm":
        arch(config, root)
    names = [x["name"] for x in bm["workloads"]]
    e2e_of = {m["name"]: (m["workloads"] if "workloads" in m else names)
              for m in bm["end_to_end"]}
    e2e = [m for m in bm["end_to_end"] if name in e2e_of[m["name"]]]
    per_layer = [m for m in bm["per_layer"] if _reports(m, name, e2e_of)]
    return Cell(name, int(w["chips"]), config, mix, limits, e2e, per_layer)


def _load(path: Path, prefix: str) -> ModuleType:
    name = f"{prefix}_{path.stem.replace('.', '_').replace('-', '_')}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(metric: str, root: Path = ROOT) -> Callable:
    """``read(run)`` of ``bench/metrics/<metric>.py``."""
    return _load(root / "bench" / "metrics" / f"{metric}.py",
                 "bench_metric").read


_ARCHS: dict[str, ModuleType] = {}      # model_type -> its loaded module


def arch(config: dict, root: Path | None = None) -> ModuleType:
    """The module ``bench/models/<model_type>.py`` of a language model's
    configuration, loaded from ``root``'s ``bench/`` once per process.
    Without ``root``: the module that ``cell`` resolved for that
    ``model_type``, else the checkout's own."""
    model_type = config.get("model_type")
    if not model_type:
        raise KeyError(f"configuration {config.get('name')!r} names no "
                       f"model_type")
    path = (root or ROOT) / "bench" / "models" / f"{model_type}.py"
    mod = _ARCHS.get(model_type)
    if mod is not None and (root is None or Path(mod.__file__).resolve()
                            == path.resolve()):
        return mod
    if not path.is_file():
        raise FileNotFoundError(f"no bench/models/{model_type}.py for the "
                                f"model_type of {config.get('name')!r}")
    mod = _ARCHS[model_type] = _load(path, "bench_model")
    return mod
