"""Layer-B analogue of Fig 17: device-side stop-mask polling amortises
host<->device syncs in the serving engine (poll_every sweep) — plus the
co-residency panel: serving step rate vs GAPBS stall inflation when
Layer A and Layer B share one modelled PCIe link."""
from __future__ import annotations

import argparse

import jax.numpy as jnp

from .common import save_json
from repro.compile_cache import enable_compile_cache
from repro.configs import CONFIGS
from repro.models import core as M
from repro.serving.engine import Request, ServeEngine


def co_residency(quick=False):
    """Sweep the serving command-batch step rate against GAPBS BC on ONE
    shared PCIe link: Layer-B batches queue on the ``"serve"`` stream of
    the runtime's own session, so every serving byte and doorbell
    contends with Layer-A exception traffic.  Reports the GAPBS makespan
    inflation vs the serving-free baseline per step rate.

    Artifact: ``results/serving_coresidency.json``."""
    from repro.core.runtime import FaseRuntime
    from repro.core.target.cpu import CLOCK_HZ
    from repro.core.target.pysim import PySim
    from repro.core.workloads import build, graphgen
    from repro.serving.engine import SERVE_STREAM
    from repro.serving.htp import CommandBatch

    g = graphgen.rmat(4 if quick else 5, 8, weights=True)
    rates = (0, 2_000, 20_000) if quick else (0, 1_000, 10_000, 25_000)
    # a representative per-step command batch: a wide pod (32 slots,
    # 64-page block tables) — wire-heavy, but controller-sustainable at
    # every swept rate (no PageS churn: its 1.5k-cycle zeroing tail
    # would outrun the serve stream's controller slice at 25k steps/s
    # and the backlog would never drain)
    cb = CommandBatch.empty(slots=32, pages=64)
    cb.override[:] = 5
    serve_txn = cb.to_transaction()
    rows = []
    base = None
    for rate in rates:
        rt = FaseRuntime(PySim(2, 1 << 23), mode="fase", link="pcie")
        state = {"next_step": 0, "steps": 0}
        if rate:
            period = CLOCK_HZ // rate
            state["next_step"] = period

            def hook(now, rt=rt, state=state, period=period):
                # catch the serve schedule up to modelled time: one
                # command batch per step on the shared link
                while state["next_step"] <= now:
                    rt.session.submit(serve_txn, state["next_step"],
                                      stream=SERVE_STREAM)
                    state["steps"] += 1
                    state["next_step"] += period
            rt.traffic_hook = hook
        rt.load(build("bc"), ["bc", "g.bin", "2", "2"],
                files={"g.bin": g})
        rep = rt.run(max_ticks=1 << 36)
        if base is None:
            base = rep.ticks
        inflation = 100.0 * (rep.ticks - base) / base
        rows.append(dict(
            steps_per_s=rate, gapbs_ticks=rep.ticks,
            inflation_pct=inflation, serve_steps=state["steps"],
            serve_bytes=sum(rep.traffic.get(f"sys:{c}", 0)
                            for c in ("overrides", "block_tables",
                                      "page_cmds"))))
        print(f"serving_coresidency,rate={rate},{rep.ticks},"
              f"inflation={inflation:.3f}% over {state['steps']} "
              f"serve steps", flush=True)
    save_json("serving_coresidency.json", rows)
    return rows


def run(quick=False):
    cfg = CONFIGS["qwen3-8b"].smoke()
    params = M.init_params(cfg, 0)
    rows = []
    for poll in (1, 8):
        eng = ServeEngine(cfg, params, slots=2, max_seq=128,
                          poll_every=poll)
        for i in range(4):
            eng.submit(Request(rid=i, prompt=[3 + i, 9, 2], max_new=8,
                               eos=1))
        done = eng.run()
        rows.append(dict(poll_every=poll, steps=eng.steps,
                         d2h=eng.traffic.d2h_bytes,
                         h2d=eng.traffic.h2d_bytes,
                         finished=len(done)))
        print(f"serving_traffic,poll={poll},{eng.traffic.d2h_bytes},"
              f"d2h bytes over {eng.steps} steps", flush=True)
    save_json("serving_traffic.json", rows)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-poll", action="store_true",
                    help="co-residency panel only (no jitted serving)")
    a = ap.parse_args()
    enable_compile_cache()
    if not a.skip_poll:
        run(quick=a.quick)
    co_residency(quick=a.quick)
