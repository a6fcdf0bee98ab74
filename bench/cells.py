"""Find a cell's files by name.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Everything that
belongs to it is found from names alone:

* ``bench/configs/<config>.json``  -- the model configuration;
* ``bench/references/<reference>.py`` -- its plain reference, named by the
  configuration's ``reference`` key;
* ``bench/mixes/<traffic>.json``   -- the traffic mix;
* ``bench/cells/<workload>.json``  -- the corpus size and the limits of the
  comparison that decides ``correct``;
* ``bench/metrics/<metric>.py``    -- one reader per per-layer metric.

Adding a configuration, a mix, a cell or a metric adds files and entries;
nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the Python file at ``path`` as a module called ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]        # bench/configs/<config>.json
    mix: Dict[str, Any]           # bench/mixes/<traffic>.json
    sizes: Dict[str, Any]         # bench/cells/<name>.json
    end_to_end: List[dict]        # BENCHMARK.json metrics this cell reports
    per_layer: List[dict]
    code_dir: str = BENCH         # holds references/ and metrics/

    @property
    def reference(self):
        """The configuration's plain reference module."""
        name = self.config["reference"]
        return load_module(os.path.join(self.code_dir, "references",
                                        f"{name}.py"), f"bench_ref_{name}")

    def metric_reader(self, name: str):
        """The ``read(ctx)`` function of per-layer metric ``name``."""
        mod = load_module(os.path.join(self.code_dir, "metrics",
                                       f"{name}.py"),
                          "bench_metric_" + name.replace(".", "_")
                          .replace("-", "_"))
        return mod.read


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, *,
              benchmark: str = os.path.join(ROOT, "BENCHMARK.json"),
              data_dir: str = BENCH, code_dir: str = BENCH) -> Cell:
    """The cell called ``name`` in ``benchmark``, with its configuration, mix
    and cell files read from ``data_dir`` and its reference and metric
    readers from ``code_dir``."""
    bench = _json(benchmark)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    d = data_dir
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_json(os.path.join(d, "configs", f"{w['config']}.json")),
        mix=_json(os.path.join(d, "mixes", f"{w['traffic']}.json")),
        sizes=_json(os.path.join(d, "cells", f"{name}.json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        code_dir=code_dir)
