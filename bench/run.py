"""Cell benchmark of FASE: guest instructions per second of whole jobs on
the JAX target, held bit for bit against PySim.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout.  ``BENCHMARK.json`` names the cells;
each cell's configuration, traffic mix and per-layer metrics are files
under ``bench/`` found by name (``fasebench.spec``).  The last line of
stdout is one JSON object: ``correct``, ``attempted`` and ``failed``
(jobs), ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones, read from profiler traces of the
window's first two jobs), ``device`` and ``checks``, each number
compared beside its limit.  Without an accelerator, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from fasebench.window import print_result, run_cell
    print_result(run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START))


if __name__ == "__main__":
    main()
