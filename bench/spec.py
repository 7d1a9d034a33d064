"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration and a traffic mix.  The traffic file names
a wire and a job.  Each lives in a file of its own:

    bench/configs/<config>.json    sizes of the model as run, its source
    bench/reference/<reference>.py the plain reference of the model's
                                   architecture, which the configuration
                                   names (``bench.reference.of``)
    bench/traffic/<traffic>.json   {"wire": <wire>, "job": <job>}
    bench/wires/<wire>.json        quantization scheme and sync mode
    bench/jobs/<job>.json          batch, tokens, optimizer, schedule
    bench/limits/<cell>.json       the limit of each number ``correct``
                                   compares
    bench/metrics/<metric>.py      the reader of one per-layer metric

so that a later architecture, cell, wire, job or metric is new files
and new entries.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    wire: dict
    job: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @property
    def quantized(self) -> bool:
        return self.wire["scheme"] != "fp32"


class Spec:
    """The benchmark under ``root``: ``BENCHMARK.json`` and ``bench/``."""

    def __init__(self, root: str):
        self.root = root
        self.bench = _read(os.path.join(root, "BENCHMARK.json"))

    def path(self, *parts: str) -> str:
        return os.path.join(self.root, "bench", *parts)

    def metric_path(self, name: str) -> str:
        return self.path("metrics", f"{name}.py")

    def _metrics_of(self, key: str, cell: str) -> list:
        return [m for m in self.bench[key]
                if cell in m.get("workloads", [cell])]

    def cell(self, name: str) -> Cell:
        by_name = {w["name"]: w for w in self.bench["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(by_name)}")
        w = by_name[name]
        traffic = _read(self.path("traffic", f"{w['traffic']}.json"))
        return Cell(
            name=name, chips=w["chips"],
            config=_read(self.path("configs", f"{w['config']}.json")),
            wire=_read(self.path("wires", f"{traffic['wire']}.json")),
            job=_read(self.path("jobs", f"{traffic['job']}.json")),
            limits=_read(self.path("limits", f"{name}.json")),
            end_to_end=self._metrics_of("end_to_end", name),
            per_layer=self._metrics_of("per_layer", name))
