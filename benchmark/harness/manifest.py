"""Everything a cell is made of, found by name.

``BENCHMARK.json`` names cells, configurations and metrics; each name
resolves to a file under ``benchmark/``:

* cell ``<c>``          -> ``workloads/<c>.json`` (driver, parameters)
* configuration ``<k>`` -> the ``file`` its manifest entry gives
* driver ``<d>``        -> ``drivers/<d>.py``
* per-layer metric ``<m>`` -> ``layer_metrics/<m>.py`` (``compute(run)``)

No table of names lives in code: a later PR adds a cell, a
configuration, a driver or a metric by adding files and manifest
entries, and edits nothing that is here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional


class ManifestError(Exception):
    """The manifest or one of the files it names is missing or wrong."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError) as e:
        raise ManifestError(f"{path}: {e}") from e


def load_module(path: str, name: str):
    """Import one file by path (metric names carry dots, so these are
    not importable by module name)."""
    if not os.path.isfile(path):
        raise ManifestError(f"{name}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        "benchmark_file_" + "".join(c if c.isalnum() else "_"
                                    for c in name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: Optional[str] = None          # per-layer only
    moves: Optional[str] = None          # per-layer only
    compute: Optional[Callable] = None   # per-layer only: the reader


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]        # the configuration file, as it is run
    workload: Dict[str, Any]      # the traffic file: driver + params
    driver: Any                   # the driver module
    end_to_end: List[Metric]
    per_layer: List[Metric]
    root: str                     # the checkout

    @property
    def params(self) -> Dict[str, Any]:
        return self.workload.get("params", {})


def _applies(entry: dict, cell: str, reported: set) -> bool:
    """A metric with a ``workloads`` key is read in the cells it lists;
    one without is read wherever the end-to-end metric it ``moves`` (or,
    for an end-to-end metric, the metric itself) is reported."""
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry.get("moves", entry["name"]) in reported


def load_cell(root: str, name: str) -> Cell:
    """Resolve cell ``name`` of ``<root>/BENCHMARK.json`` to its files."""
    man = _load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in man["workloads"] if w["name"] == name), None)
    if entry is None:
        raise ManifestError(
            f"no cell {name!r} in BENCHMARK.json (cells: "
            f"{[w['name'] for w in man['workloads']]})")
    bench = os.path.join(root, man["paths"][0])
    cfg_entry = next((c for c in man["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise ManifestError(f"cell {name}: no configuration "
                            f"{entry['config']!r} in BENCHMARK.json")
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    workload = _load_json(os.path.join(bench, "workloads", name + ".json"))
    for key in ("config", "chips", "traffic"):
        if workload.get(key) != entry[key]:
            raise ManifestError(
                f"cell {name}: {key} is {entry[key]!r} in BENCHMARK.json "
                f"and {workload.get(key)!r} in its traffic file")
    driver = load_module(
        os.path.join(bench, "drivers", workload["driver"] + ".py"),
        workload["driver"])
    # setup_s is every cell's; the others say where they exist.
    e2e = [m for m in man["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    end_to_end = [Metric(m["name"], m["unit"], m["better"], m["source"])
                  for m in e2e]
    per_layer = []
    for m in man["per_layer"]:
        if not _applies(m, name, reported):
            continue
        reader = load_module(
            os.path.join(bench, "layer_metrics", m["name"] + ".py"),
            m["name"])
        per_layer.append(Metric(m["name"], m["unit"], m["better"],
                                m["source"], m["layer"], m["moves"],
                                reader.compute))
    return Cell(name=name, chips=int(entry["chips"]),
                config_name=entry["config"], config=config,
                workload=workload, driver=driver, end_to_end=end_to_end,
                per_layer=per_layer, root=root)
