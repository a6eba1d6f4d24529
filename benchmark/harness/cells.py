"""Finding a cell's files by the names ``BENCHMARK.json`` gives.

Later PRs add files and entries and edit nothing that is here: a cell
names a configuration and a traffic mix, the configuration names its
family, the traffic mix its kind, a per-layer metric its reader, and each
is a file of that name in the directory of its sort.
"""

import importlib
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def manifest(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(directory, name, bench_dir=BENCH_DIR):
    path = os.path.join(bench_dir, directory, name + ".json")
    with open(path) as f:
        return json.load(f)


def load_module(directory, name, bench_dir=BENCH_DIR):
    """The module ``<bench_dir>/<directory>/<name>.py``. Names may hold
    ``.`` and ``-`` (metric names do), so it is loaded by path."""
    path = os.path.join(bench_dir, directory, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            "no %s named %r: expected %s" % (directory, name, path))
    if bench_dir == BENCH_DIR and re.fullmatch(r"[A-Za-z_]\w*", name):
        return importlib.import_module(
            "benchmark.%s.%s" % (directory, name))
    mod_name = "benchmark_%s_%s" % (directory, re.sub(r"\W", "_", name))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell(object):
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, name, root=ROOT, bench_dir=BENCH_DIR):
        self.manifest = manifest(root)
        self.bench_dir = bench_dir
        entries = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in entries:
            raise KeyError("no workload %r in BENCHMARK.json (has: %s)"
                           % (name, sorted(entries)))
        self.entry = entries[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.config_name = self.entry["config"]
        self.traffic_name = self.entry["traffic"]
        cfg_entry = {c["name"]: c for c in self.manifest["configs"]}[
            self.config_name]
        with open(os.path.join(root, cfg_entry["file"])) as f:
            self.config = json.load(f)
        self.traffic = _json("traffic", self.traffic_name, bench_dir)

    def module(self, directory, name):
        return load_module(directory, name, self.bench_dir)

    @property
    def kind(self):
        return self.module("traffic_kinds", self.traffic["kind"])

    @property
    def family(self):
        return self.module("families", self.config["family"])

    @property
    def reference(self):
        return importlib.import_module(
            "benchmark.references.%s" % self.config["family"])

    @property
    def check_limits(self):
        """{number compared: limit} of this cell, set from chip readings."""
        return _json("limits", self.name, self.bench_dir)

    def _metrics(self, section):
        out = []
        for m in self.manifest[section]:
            cells = m.get("workloads")
            if cells is None or self.name in cells:
                out.append(m)
        return out

    @property
    def end_to_end(self):
        return self._metrics("end_to_end")

    @property
    def per_layer(self):
        return self._metrics("per_layer")
