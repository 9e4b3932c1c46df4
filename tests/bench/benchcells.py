"""The cells of ``BENCHMARK.json``, and the smaller image and graph at
which the benchmark's tests run them on the CPU."""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
SMALL_CONFIG = {"mem_bytes": 1 << 23}
SMALL_GRAPH = {"g.bin": {"rmat": {"scale": 5, "degree": 16,
                                  "weights": True}}}


def small_traffic(cell: str) -> dict:
    return {"files": SMALL_GRAPH} if "bc" in cell else {}
