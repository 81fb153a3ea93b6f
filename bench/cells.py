"""Find a cell's files by the names in BENCHMARK.json. Nothing here knows a
particular configuration, traffic mix, entry or metric: adding one is adding
files and entries (bench/README.md)."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def load_json(root: str, *parts: str) -> dict:
    with open(os.path.join(root, *parts), encoding="utf-8") as f:
        return json.load(f)


def load_module(root: str, kind: str, name: str):
    """bench/<kind>/<name>.py as a module; metric names hold dots, so the
    file is loaded by path and not imported by name."""
    path = os.path.join(root, "bench", kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    key = "bench_file_" + "".join(c if c.isalnum() else "_" for c in path)
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod  # dataclasses look their module up there
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[key]
        raise
    return mod


OWN_KEYS = ("source", "published", "reduced", "assumed", "bench")


def as_run(config: dict) -> dict:
    """The model's config.json keys as run: everything at the top level of a
    configuration file but the benchmark's own keys (`published` holds the
    source's values, for the test that compares the two)."""
    return {k: v for k, v in config.items() if k not in OWN_KEYS}


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict  # bench/configs/<config>.json
    traffic_name: str
    traffic: dict  # bench/traffic/<traffic>.json
    end_to_end: list  # metric entries of BENCHMARK.json, this cell's
    per_layer: list
    root: str

    @property
    def entry_name(self) -> str:
        return self.traffic["entry"]

    def entry(self):
        return load_module(self.root, "entries", self.entry_name)

    def generator(self):
        return load_module(self.root, "generators", self.traffic["generator"])

    def reference(self):
        return load_module(self.root, "reference",
                           self.config["bench"]["reference"])

    def reader(self, metric_name: str):
        """`<reader>--<tag>` is read by `<reader>.py`. An entry of
        BENCHMARK.json holds ONE `moves`, so a quantity that moves different
        end-to-end metrics in different cells has an entry for each (the
        contract's `dispatch_ms.train` / `dispatch_ms.serve`), and one
        reader."""
        return load_module(self.root, "metrics", metric_name.split("--")[0])


def resolve(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_file = configs[w["config"]]["file"]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=load_json(root, cfg_file), traffic_name=w["traffic"],
        traffic=load_json(root, "bench", "traffic", w["traffic"] + ".json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root)
