"""`BENCHMARK.json` and the files it names, resolved by name.

A cell names a configuration (`configs/<name>.json`) and a traffic mix
(`traffic/<name>.json`, made into a schedule by the generator it names,
`traffic/generators/<generator>.py`).  A configuration names its model
module (`"model": "<name>"`, the file `models/<name>.py`), which makes the
seed's weights in the program's layout, checks the program against the
file, runs the plain reference and counts the model's operations and bytes;
the harness reaches all of these only through it (`MODEL_FUNCTIONS`).  A
per-layer metric `<base>.<suffix>` is read by `metrics/<base>.py`, whose
`read(ctx)` returns a number or None.  Adding a cell, configuration, model,
mix or metric adds files and entries and edits none.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH_DIR = HERE.relative_to(ROOT)

MODULE_NAME = re.compile(r"^[A-Za-z0-9_]{1,64}$")
# what every model module provides
MODEL_FUNCTIONS = ("check_program", "make_program_params",
                   "teacher_forced_logits", "step_flops", "attention_cost")


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Metric]
    per_layer: List[Metric]
    model: ModuleType


def load(root: Path = ROOT) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(entry: Dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def _read_json(path: Path) -> Dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    with open(path) as f:
        return json.load(f)


def resolve(manifest: Dict, workload: str, root: Path = ROOT) -> Cell:
    """The cell `workload` with its configuration, traffic and metrics, the
    files read from the checkout at `root`."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    entry = configs[w["config"]]
    config = dict(_read_json(root / entry["file"]), name=entry["name"])
    model = model_module(config["model"], root / BENCH_DIR / "models")
    traffic = dict(_read_json(root / BENCH_DIR / "traffic"
                              / f"{w['traffic']}.json"), name=w["traffic"])

    def metrics(key: str) -> List[Metric]:
        return [Metric(m["name"], m["unit"])
                for m in manifest[key] if _applies(m, workload)]
    return Cell(workload, int(w["chips"]), config, traffic,
                metrics("end_to_end"), metrics("per_layer"), model)


def _load(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def model_module(name: str, models_dir: Path = HERE / "models") -> ModuleType:
    """`models/<name>.py`, loaded once per process.  An unknown name, or a
    module without one of `MODEL_FUNCTIONS`, is an error that names the
    modules there are."""
    path = models_dir / f"{name}.py"
    if not MODULE_NAME.match(name) or not path.is_file():
        known = sorted(p.stem for p in models_dir.glob("*.py"))
        raise KeyError(f"no model module {name!r} ({path}); known: {known}")
    mod = _load(path, f"chip_model_{name}")
    missing = [f for f in MODEL_FUNCTIONS
               if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"model module {path} lacks {missing}")
    return mod


def reader(metric_name: str, here: Path = HERE) -> Callable[[Dict], object]:
    """`read` of `metrics/<base>.py`, `<base>` being the metric's name up
    to its first '.'."""
    base = metric_name.split(".", 1)[0]
    path = here / "metrics" / f"{base}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader {path} for metric {metric_name}")
    return _load(path, f"chip_metric_{base}").read
