"""Find a cell's files by name.

``BENCHMARK.json`` at the root names each cell's configuration and
traffic mix; the harness reads them from ``bench/configs/<config>.json``
and ``bench/traffic/<traffic>.json``.  By the names in those files it
finds how the cell's units run (``bench/units/<units>.py``) and how each
checked answer is worked out (``bench/answers/<key>.py``), and by the
names in ``BENCHMARK.json`` each per-layer metric's reader
(``bench/metrics/<name>.py``).  A new cell or metric is therefore new
files plus entries in ``BENCHMARK.json``: nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path


@dataclass
class Cell:
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"cells: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=w["chips"],
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json")
            .read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def load(root: Path, kind: str, name: str, attr: str):
    """``attr`` of ``bench/<kind>/<name>.py``."""
    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, attr)


def metric_reader(root: Path, name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``: it takes a
    :class:`~fasebench.xtrace.Trace` and returns the metric's value, or
    None where the trace holds nothing to read."""
    return load(root, "metrics", name, "read")


def units_class(root: Path, config: dict):
    """The ``Units`` class of ``bench/units/<config["units"]>.py``."""
    return load(root, "units", config["units"], "Units")


def answer(root: Path, key: str):
    """The ``expected`` function of ``bench/answers/<key>.py``: it takes a
    :class:`~fasebench.jobs.JobInput` and returns the value the guest
    must print after ``key``."""
    return load(root, "answers", key, "expected")
