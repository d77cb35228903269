"""Find a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` names them; each lives in a file of its own
(``configs/<config>.json`` as the entry's ``file`` says,
``traffic/<traffic>.json``, the loop that mix names in
``drivers/<driver>.py``, ``metrics/<metric>.py``), so a later PR adds a
cell, a kind of loop or a metric with new files and entries alone."""
import importlib.util
import json
import os


class Cell:
    def __init__(self, root: str, bench: dict, name: str):
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.root = root
        self.name = name
        self.entry = by_name[name]
        self.chips = int(self.entry["chips"])
        conf = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.config = _json(os.path.join(root, conf["file"]))
        self.mix = _json(os.path.join(root, "benchmark", "traffic",
                                      self.entry["traffic"] + ".json"))
        self.end_to_end = [m for m in bench["end_to_end"] if self._has(m)]
        self.per_layer = [m for m in bench["per_layer"] if self._has(m)]

    def _has(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def reader(self, metric: str):
        """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
        return _module(self.root, "metrics", metric).read

    def driver(self):
        """The ``Driver`` class of ``drivers/<driver>.py``, the loop that
        the cell's mix names."""
        return _module(self.root, "drivers", self.mix["driver"]).Driver


def _module(root: str, kind: str, name: str):
    path = os.path.join(root, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load(root: str, name: str) -> Cell:
    return Cell(root, _json(os.path.join(root, "BENCHMARK.json")), name)
