"""``BENCHMARK.json``: a cell's configuration, traffic mix and metrics,
each found by its name in a file of its own."""

from __future__ import annotations

import importlib
import importlib.util
import json
import re
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List

PACKAGE = Path(__file__).resolve().parent            # servebench
HERE = PACKAGE.parent                                # benchmarks/serve
ROOT = HERE.parent.parent                            # the checkout
REFERENCE_KEY = "reference"


@dataclass
class Cell:
    name: str
    chips: int
    config_file: Path
    mix_file: Path
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def mix_path(traffic: str) -> Path:
    return HERE / "traffic" / f"{traffic}.json"


def reader_path(metric: str) -> Path:
    return HERE / "metrics" / f"{metric}.py"


def resolve(bench: Dict[str, Any], workload: str,
            root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = configs[w["config"]]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config_file=root / cfg["file"], mix_file=mix_path(w["traffic"]),
        end_to_end=[m for m in bench["end_to_end"]
                    if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def load_reader(metric: str) -> Callable:
    """The ``read(run)`` function of a per-layer metric's own file."""
    path = reader_path(metric)
    mod_name = "servebench_metric_" + re.sub(r"\W", "_", metric)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(f"no reader for metric {metric!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference_path(config: Dict[str, Any], root: Path = ROOT) -> Path:
    """The file a configuration's ``"reference"`` key names, relative to
    the checkout's root."""
    name = config.get("name")
    if REFERENCE_KEY not in config:
        raise KeyError(f"configuration {name!r} has no {REFERENCE_KEY!r} "
                       f"key: it names the file of its plain reference")
    path = Path(config[REFERENCE_KEY])
    path = path if path.is_absolute() else root / path
    if not path.is_file():
        raise FileNotFoundError(
            f"configuration {name!r}: its {REFERENCE_KEY!r} key names "
            f"{config[REFERENCE_KEY]!r}, and there is no file at {path}")
    return path.resolve()


def load_reference(config: Dict[str, Any], root: Path = ROOT) -> ModuleType:
    """The reference module a configuration names: its ``compare`` decides
    ``correct``, and its ``leaves(spec)``, where it defines one, the
    parameter tree.  A file outside this package is loaded as a module of
    it, so that its relative imports (``from .modelspec import ...``)
    reach the benchmark's own modules."""
    path = reference_path(config, root)
    if path.parent == PACKAGE:
        return importlib.import_module(f"{__package__}.{path.stem}")
    name = f"{__package__}._reference_" + re.sub(r"\W", "_", str(path))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]
