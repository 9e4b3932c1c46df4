# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark driver: `python -m benchmarks.run [--quick]`.

Each module reproduces one paper table/figure (see DESIGN.md §7 index).
"""
from __future__ import annotations

import pkgutil
import sys
import time

from repro.compile_cache import enable_compile_cache

#: benchmark-package modules that are not runnable panels
EXCLUDED = {"common", "host_spans", "run"}


def _audit(modules) -> None:
    """Every module in the package is either registered below or
    explicitly excluded — a new benchmark that forgets to register
    fails the driver instead of silently never running."""
    import benchmarks
    on_disk = {m.name for m in pkgutil.iter_modules(benchmarks.__path__)}
    registered = {mod.__name__.rsplit(".", 1)[-1] for _, mod in modules}
    missing = on_disk - registered - EXCLUDED
    assert not missing, (
        f"benchmark module(s) {sorted(missing)} exist on disk but are "
        f"not registered in benchmarks/run.py (or EXCLUDED)")


def main() -> None:
    quick = "--quick" in sys.argv
    from . import (arg_prefetch, baud_sweep, coremark_accuracy,
                   fleet_scale, gapbs_accuracy, hfutex_bench,
                   htp_vs_direct, migration, net_scale, scale_sweep,
                   serving_traffic, speedup, stall_attribution,
                   stall_breakdown, target_speed)
    modules = [
        ("target_speed", target_speed),
        ("htp_vs_direct", htp_vs_direct),
        ("coremark_accuracy", coremark_accuracy),
        ("speedup", speedup),
        ("gapbs_accuracy", gapbs_accuracy),
        ("traffic/stall_breakdown", stall_breakdown),
        ("baud_sweep", baud_sweep),
        ("hfutex", hfutex_bench),
        ("scale_sweep", scale_sweep),
        ("serving_traffic", serving_traffic),
        ("arg_prefetch", arg_prefetch),
        ("fleet_scale", fleet_scale),
        ("net_scale", net_scale),
        ("migration", migration),
        ("stall_attribution", stall_attribution),
    ]
    _audit(modules)
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = run_panels(modules, quick)
    if failed:
        sys.exit(f"# {len(failed)} panel(s) failed: {', '.join(failed)}")


def run_panels(modules, quick: bool = False) -> list:
    """Run every panel; a failing panel is reported and the rest still
    run.  Returns the names of the panels that failed."""
    failed = []
    for name, mod in modules:
        t0 = time.time()
        try:
            mod.run(quick=quick)
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:  # noqa: BLE001 — later panels still run
            failed.append(name)
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
    return failed


if __name__ == '__main__':
    main()
