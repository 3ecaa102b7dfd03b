"""Where a cell's files are: everything is found by the names in
BENCHMARK.json, so a new cell, configuration, traffic mix or per-layer
metric is new files and new entries, never an edit here."""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # benchmark/
ROOT = os.path.dirname(HERE)                                        # checkout


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads` with its configuration, its traffic mix and
    the metrics that are read in it."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                             f"it has {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.config_name = cfg["name"]
        self.config = load_json(os.path.join(root, cfg["file"]))
        self.traffic_name = self.entry["traffic"]
        self.traffic = load_json(os.path.join(
            HERE, "traffic", self.traffic_name + ".json"))
        self.driver_name = self.traffic["driver"]
        self.peaks_table = load_json(os.path.join(HERE, "peaks.json"))

        def in_cell(metric: dict) -> bool:
            return name in metric.get("workloads", [name])

        self.end_to_end = [m for m in bench["end_to_end"] if in_cell(m)]
        self.per_layer = [m for m in bench["per_layer"] if in_cell(m)]

    def driver(self):
        return importlib.import_module(f"drivers.{self.driver_name}")

    def peaks(self, device_kind: str) -> dict:
        """The chip's published peaks; a device that is not in the table is
        an error, never a default."""
        table = self.peaks_table["devices"]
        if device_kind not in table:
            raise SystemExit(f"device kind {device_kind!r} is not in "
                             f"benchmark/peaks.json ({sorted(table)})")
        return table[device_kind]


def metric_reader(name: str):
    """benchmark/metrics/<name>.py: read(run) -> number or None. Loaded by
    its path, since a metric's name may hold a dot."""
    import importlib.util

    path = os.path.join(HERE, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "metrics_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read
