"""Paper Fig 19: validation efficiency.  PK-style execution on the
pure-Python RTL-simulator stand-in vs FASE on the XLA-compiled target —
wall-clock per CoreMark iteration, plus modelled-time throughput."""
from __future__ import annotations

from .common import parse_kv, run_workload, save_json
from repro.compile_cache import enable_compile_cache


def run(quick=False):
    iters = 2 if quick else 5
    rows = []
    for target, label in (("pysim", "PK/pysim"), ("jax", "FASE/xla")):
        rt, rep, wall = run_workload("coremark", [str(iters)], mode="fase",
                                     n_cores=1, target=target)
        inst = sum(rep.instret)
        rows.append(dict(target=label, wall_s=wall, instret=inst,
                         inst_per_s=inst / wall,
                         model_s=rep.ticks / 1e8,
                         wall_per_iter=wall / iters))
        print(f"speedup,{label},{wall/iters*1e6:.0f},"
              f"{inst/wall:.0f} inst/s", flush=True)
    ratio = rows[0]["wall_per_iter"] / rows[1]["wall_per_iter"]
    print(f"speedup,ratio,{ratio:.2f},xla-vs-python per-iteration")
    rows.append(dict(target="ratio", value=ratio))
    save_json("speedup.json", rows)
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
