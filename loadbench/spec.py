"""Find a cell's pieces by name: the BENCHMARK.json entry, the
configuration file, the traffic mix, the traffic generator module and the
per-layer metric readers.

Everything that belongs to one configuration, one mix or one metric sits
in a file of its own under this directory; a new cell, mix or metric is
added by adding files and BENCHMARK.json entries, never by editing these.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _by_name(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


class Cell:
    """One workload of BENCHMARK.json with its configuration, mix and
    metric entries resolved."""

    def __init__(self, bench: dict, name: str):
        self.workload = _by_name(bench["workloads"], name, "workload")
        cfg_entry = _by_name(bench["configs"], self.workload["config"],
                             "config")
        self.name = name
        self.chips = int(self.workload["chips"])
        self.config = load_json(os.path.join(ROOT, cfg_entry["file"]))
        self.mix_name = self.workload["traffic"]
        self.mix = load_json(os.path.join(HERE, "traffic",
                                          f"{self.mix_name}.json"))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def _load_module(path: str, mod_name: str):
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(kind: str):
    """The traffic generator module for a mix's ``kind``."""
    return _load_module(os.path.join(HERE, "traffic", f"{kind}.py"),
                        f"loadbench_traffic_{kind}")


def reader(metric: str):
    """The reader module of one per-layer metric: ``metrics/<name>.py``
    if there is one, else the reader of its base name, the name without
    its last ``.<cell>`` suffix (``plan_us_per_op.rooms`` is read by
    ``metrics/plan_us_per_op.py``), so that one reader serves the same
    quantity in every cell."""
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    if not os.path.exists(path) and "." in metric:
        path = os.path.join(HERE, "metrics",
                            f"{metric.rsplit('.', 1)[0]}.py")
    safe = metric.replace(".", "_").replace("-", "_")
    return _load_module(path, f"loadbench_metric_{safe}")


def sized(entry: dict, rehearse: bool) -> dict:
    """A config or mix with its ``rehearse`` overrides applied when the
    run is a CPU rehearsal (tiny sizes); the overrides never apply on the
    chip."""
    out = {k: v for k, v in entry.items() if k != "rehearse"}
    if rehearse:
        out.update(entry.get("rehearse", {}))
    return out
