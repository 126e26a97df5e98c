"""Find a cell's configuration, its model module, traffic mix, generator
and metric readers by the names ``BENCHMARK.json`` and the files give them.

Nothing here knows a cell, a model or a metric by name: a new one is a new
file and a new entry, and no existing file changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: Callable                 # read(run) -> float | None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict
    model: ModuleType              # bench/models/<config["model"]>.py
    traffic: Dict
    generator: ModuleType
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_module(path: Path, name: str) -> ModuleType:
    """Import one file by path (metric files carry dots in their names,
    so they cannot be imported as packages).  The module is entered in
    ``sys.modules`` under ``name`` before it runs, as an import would
    enter it (a dataclass looks its module up there)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def load_model(name: str, *, root: Path = ROOT) -> ModuleType:
    """The model module ``bench/models/<name>.py`` (the interface is in
    ``bench/models/__init__.py``); there is no default."""
    path = root / "bench" / "models" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"model {name!r} has no module at {path}")
    return load_module(path, f"bench_model_{name}")


def _metric(entry: Dict, bench_dir: Path) -> Metric:
    path = bench_dir / "metrics" / f"{entry['name']}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {entry['name']!r} has no reader "
                                f"at {path}")
    mod = load_module(path, f"bench_metric_{entry['name'].replace('.', '_')}")
    return Metric(entry["name"], entry["unit"], mod.read)


def _reports(entry: Dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, *, root: Path = ROOT) -> Cell:
    """Resolve workload ``name`` of ``<root>/BENCHMARK.json``."""
    bench_dir = root / "bench"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have "
                       f"{sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    if "model" not in config:
        raise KeyError(f"{cfg_entry['file']} names no model module "
                       f"(\"model\": bench/models/<name>.py)")
    model = load_model(config["model"], root=root)
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    generator = load_module(
        bench_dir / "traffic" / f"{traffic['generator']}.py",
        f"bench_traffic_{traffic['generator']}")
    e2e = [_metric(m, bench_dir) for m in spec["end_to_end"]
           if _reports(m, name)]
    per_layer = [_metric(m, bench_dir) for m in spec["per_layer"]
                 if _reports(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                model=model, traffic=traffic, generator=generator, end_to_end=e2e,
                per_layer=per_layer)


def load_peaks(device_kind: str, *, root: Path = ROOT) -> Dict:
    """The chip's published peaks; a device kind not in the table is an
    error, never a default."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]
