"""Finds a cell's files by name.

Everything that belongs to one configuration, traffic mix, driver or
per-layer metric lives in a file of its own under this directory:

- ``configs/<config>.json``: the model configuration as it is run;
- ``reference/<reference>.py``: the plain reference of the architecture
  a configuration names under ``reference``, with its facts: ``loss``,
  ``leaf_specs`` (the program's weight layout), ``param_count`` and
  ``mixer_flops_per_token`` (model FLOPs);
- ``traffic/<traffic>.json``: the traffic's parameters, naming its driver;
- ``workloads/<cell>.json``: configuration, traffic, chips, why, limits;
- ``drivers/<driver>.py``: the one generator for a kind of traffic;
- ``metrics/<metric>.py``: the reader of one per-layer metric.

A new cell, mix or metric is added by adding files: nothing here lists
them.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import pathlib
from typing import Any, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parent
REPO = ROOT.parent


def _load(root: pathlib.Path, kind: str, name: str) -> Dict[str, Any]:
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no {kind[:-1]} named {name!r} ({path})")
    data = json.loads(path.read_text())
    data.setdefault("name", name)
    return data


def workload(name: str, root: pathlib.Path = ROOT) -> Dict[str, Any]:
    return _load(root, "workloads", name)


def config(name: str, root: pathlib.Path = ROOT) -> Dict[str, Any]:
    """The configuration file, with ``_root``: where its reference is looked for."""
    cfg = _load(root, "configs", name)
    cfg["_root"] = str(root)
    return cfg


def traffic(name: str, root: pathlib.Path = ROOT) -> Dict[str, Any]:
    return _load(root, "traffic", name)


def workload_names(root: pathlib.Path = ROOT) -> List[str]:
    return sorted(p.stem for p in (root / "workloads").glob("*.json"))


def driver(name: str):
    """The module ``chipbench.drivers.<name>``; it has ``run(ctx)``."""
    return importlib.import_module(f"chipbench.drivers.{name}")


def _module_at(path: pathlib.Path, kind: str):
    name = f"chipbench_{kind}_{path.stem.replace('.', '_').replace('-', '_')}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(cfg: Dict[str, Any]):
    """The reference module that ``cfg["reference"]`` names:
    ``reference/<name>.py`` under the configuration's root where it has
    one there, else ``chipbench.reference.<name>``."""
    name = cfg["reference"]
    root = pathlib.Path(cfg.get("_root", ROOT))
    path = root / "reference" / f"{name}.py"
    if root == ROOT or not path.is_file():
        return importlib.import_module(f"chipbench.reference.{name}")
    return _module_at(path, "reference")


def metric_reader(name: str, root: pathlib.Path = ROOT):
    """The ``read(outputs)`` function of ``metrics/<name>.py``."""
    path = root / "metrics" / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no reader for metric {name!r} ({path})")
    return _module_at(path, "metric").read


def benchmark(repo: pathlib.Path = REPO) -> Dict[str, Any]:
    return json.loads((repo / "BENCHMARK.json").read_text())


def metrics_of(cell: str, entries: List[Dict[str, Any]],
               e2e: Optional[List[str]] = None) -> List[Dict[str, Any]]:
    """The metric entries that apply to ``cell``.

    An entry with a ``workloads`` list applies to the cells it lists. One
    without applies to every cell, or, where ``e2e`` is given (per-layer
    metrics), to every cell that reports the end-to-end metric it moves.
    """
    out = []
    for m in entries:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif e2e is None or m.get("moves") in e2e:
            out.append(m)
    return out
