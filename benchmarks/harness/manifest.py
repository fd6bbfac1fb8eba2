"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix, one driver
or one per-layer metric is a file of its own, found by the name the
manifest gives, under any of the manifest's `paths`:

    <path>/configs/...            the file a configuration names
    <path>/traffic/<mix>.json     a cell's `traffic`
    <path>/drivers/<driver>.py    the mix's `driver`
    <path>/layer_metrics/<m>.py   a per-layer metric's reader

so a later PR adds a cell, a mix or a metric as new files and entries.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional


class ManifestError(ValueError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic: str
    mix: Dict[str, Any]
    driver_path: str
    end_to_end: List[Dict[str, Any]]  # the metrics this cell reports
    per_layer: List[Dict[str, Any]]


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell in cells


class Manifest:
    def __init__(self, path: str):
        self.path = os.path.abspath(path)
        self.root = os.path.dirname(self.path)
        with open(self.path) as f:
            self.data = json.load(f)
        self.paths = [p if os.path.isabs(p) else os.path.join(self.root, p)
                      for p in self.data["paths"]]

    def find(self, *parts: str) -> Optional[str]:
        """The first file `<path>/<parts...>` that exists."""
        for base in self.paths:
            cand = os.path.join(base, *parts)
            if os.path.isfile(cand):
                return cand
        return None

    def _json(self, path: str) -> Dict[str, Any]:
        with open(path) as f:
            return json.load(f)

    def cell(self, name: str, rehearse: bool = False) -> Cell:
        entry = next((w for w in self.data["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise ManifestError(
                f"no workload {name!r}; the manifest has "
                f"{[w['name'] for w in self.data['workloads']]}")
        cfg_entry = next((c for c in self.data["configs"]
                          if c["name"] == entry["config"]), None)
        if cfg_entry is None:
            raise ManifestError(f"workload {name!r} names no known config")
        cfg_file = cfg_entry["file"]
        if not os.path.isabs(cfg_file):
            cfg_file = os.path.join(self.root, cfg_file)
        config = self._json(cfg_file)
        mix_path = self.find("traffic", entry["traffic"] + ".json")
        if mix_path is None:
            raise ManifestError(f"no traffic mix {entry['traffic']!r}")
        mix = self._json(mix_path)
        if rehearse:
            # the tiny sizes of a CPU rehearsal ride in the same files
            config = {**config, **config.get("rehearse", {})}
            mix = {**mix, **mix.get("rehearse", {})}
        driver_path = self.find("drivers", mix["driver"] + ".py")
        if driver_path is None:
            raise ManifestError(f"no driver {mix['driver']!r}")
        return Cell(
            name=name, chips=int(entry["chips"]),
            config_name=entry["config"], config=config,
            traffic=entry["traffic"], mix=mix, driver_path=driver_path,
            end_to_end=[m for m in self.data["end_to_end"]
                        if _reports(m, name)],
            per_layer=[m for m in self.data["per_layer"]
                       if _reports(m, name)])

    def reader(self, metric_name: str):
        """The per-layer metric's reader: `read(run, trace) -> number or
        None` in `layer_metrics/<metric_name>.py`."""
        path = self.find("layer_metrics", metric_name + ".py")
        if path is None:
            raise ManifestError(f"no reader for per-layer metric "
                                f"{metric_name!r}")
        return load_module(path, "layer_metric_" + metric_name).read


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(
        name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
