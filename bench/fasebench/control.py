"""The control of the comparison: the reference in the program's place,
one precision down.

The configuration states a 64-bit machine (RV64IMA, 64-bit registers
and memory words).  The control is ``PySim`` with every 64-bit ALU
result cut to 32 bits and sign-extended, as a datapath that kept only
the low word of each u64 would compute; it runs in the program's place
through the same window and the same check, which must report it not
correct.  The benchmark's own runs never run it; ``bench/control.py``
does.
"""
from __future__ import annotations

import time
from pathlib import Path

from . import jobs as jobmod


def _pysim32():
    from repro.core.target.pysim import PySim, _sx32

    class PySim32(PySim):
        """PySim with 64-bit ALU results kept to their low 32 bits."""

        def _alu(self, *args, **kwargs):
            return _sx32(super()._alu(*args, **kwargs))

    return PySim32


CONTROL = "PySim with 64-bit ALU results kept to their low 32 bits"


class ControlUnits(jobmod.Units):
    """Each board-job of a unit run on the 32-bit control target."""

    def __init__(self, cfg: dict):
        super().__init__(cfg)
        self.target_cls = _pysim32()

    def warm_up(self, jobs, span) -> None:
        """Nothing to compile."""

    def run(self, jobs, span) -> list:
        with span("run"):
            return [jobmod.reference(self.cfg, j, self.target_cls)
                    for j in jobs]


def main(argv=None) -> None:
    import argparse
    import json
    from .window import run_cell
    ap = argparse.ArgumentParser(description="run a cell's control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[2]
    print(f"control: {CONTROL}", flush=True)
    for seed in args.seed:
        out = run_cell(root, args.workload, seed, args.seconds, False,
                       time.perf_counter(), require_accelerator=False,
                       units_cls=ControlUnits)
        print(json.dumps({"control": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "checks": out["checks"]}), flush=True)

