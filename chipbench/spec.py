"""Finds a cell's files by the names in BENCHMARK.json.

A cell ``<config>.<traffic>`` resolves to ``configs/<config>.json``,
``mixes/<traffic>.json``, an optional ``cells/<cell>.json`` (the cell's own
fixed numbers, laid over the mix: the offered rate of a paced cell), and
the reference, cost and per-layer readers the config and the metric
entries name. Nothing here knows any configuration, mix or metric.
"""

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(relpath):
    """A module under chipbench/ by path (names may hold dots)."""
    path = os.path.join(HERE, relpath)
    name = "chipbench_file_" + "".join(ch if ch.isalnum() else "_" for ch in relpath)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class Cell:
    """Everything one run needs to know, as data."""

    def __init__(self, workload, bench=None, toy=False):
        bench = bench or benchmark()
        entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
        if entry is None:
            raise SystemExit(f"chipbench: no workload {workload!r} in BENCHMARK.json "
                             f"(known: {[w['name'] for w in bench['workloads']]})")
        self.name = workload
        self.chips = entry["chips"]
        self.config = load_json("configs", entry["config"] + ".json")
        self.mix = dict(load_json("mixes", entry["traffic"] + ".json"))
        cell_file = os.path.join(HERE, "cells", workload + ".json")
        if os.path.exists(cell_file):
            self.mix.update(load_json("cells", workload + ".json"))
        missing = [k for k in self.mix.get("needs", []) if k not in self.mix]
        if missing:
            raise SystemExit(f"chipbench: mix {self.mix['name']!r} needs {missing} "
                             f"from cells/{workload}.json")
        if toy:   # rehearsal and tests: the same code at a size and length a CPU test holds
            for key, val in self.config.get("toy", {}).items():
                self.config[key] = {**self.config[key], **val}
            self.mix.update(self.mix.get("toy", {}))

        def mine(metric):
            return "workloads" not in metric or workload in metric["workloads"]

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        self.per_layer = [m for m in bench["per_layer"] if mine(m)]
        self.ref = load_module(self.config["reference"]["module"])
        self.cost = load_module(self.config["costs"]).cost

    @property
    def frame_shape(self):
        g = self.config["geometry"]
        return (g["height"], g["width"], g["channels"])

    @property
    def batch_size(self):
        return self.config["serve"]["batch_size"]

    @property
    def slo_ms(self):
        """The sessions' latency budget: the mix's when it states one
        (a batch tenant waits a minute), else the configuration's."""
        if self.mix.get("slo_ms") is not None:
            return float(self.mix["slo_ms"])
        return float(self.config["guarantees"]["slo_ms"])


def peaks(device_kind):
    table = load_json("peaks.json")
    if device_kind not in table:
        raise SystemExit(f"chipbench: no published peaks for device kind "
                         f"{device_kind!r} in chipbench/peaks.json")
    return table[device_kind]
