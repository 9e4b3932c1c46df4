"""Run a cell's control: the reference, one precision down, in the
program's place, through the benchmark's own window and check.  It must
come out not correct.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --seed <n> [--seed <n> ...]

One JSON line per seed.  Needs no accelerator: the control is a host
program.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

if __name__ == "__main__":
    from fasebench.control import main
    main()
