"""The traffic generator, the units of work the window runs, and the
PySim reference.

A traffic mix is data (``bench/traffic/<name>.json``):

    workload   the guest program, a ``repro.core.workloads.build`` name
    argv       its arguments after argv[0]
    files      input files by name; ``{"rmat": {scale, degree, weights}}``
               is a GAPBS Kronecker graph (``fasebench.graphs``)
    link       registry keys of the host link (UART or PCIe) and its
               queue pair, laid over the configuration
    stdout     ``key value`` lines the guest must print (CoreMark's CRC)
    answers    keys of ``key value`` lines whose value
               ``bench/answers/<key>.py`` works out from the job's inputs
    max_jobs   the most units a window may run
    set_seed   (optional) the seed that draws every unit's inputs in place
               of ``--seed``: every seed gives a unit the same jobs, and
               so the same work, where a unit lasts as long as its
               slowest board

Every input file of a job is drawn from ``(--seed, phase, unit, board,
file)``, so the same seed gives the same inputs and every seed gives
the same sizes.

A unit is one whole job on a solo board (``FaseRuntime.load`` then
``run``, ``bench/units/solo.py``), or one lockstep round of a whole job
on each board of a fleet (``FleetRuntime.run_synchronous``,
``bench/units/fleet.py``).  Units call the program's own entry points
and re-implement neither.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

MAX_TICKS = 1 << 44
#: seed phases: the warm-up's inputs are never a window job's
WARMUP, WINDOW = 0, 1


@dataclass(frozen=True)
class JobInput:
    name: str
    argv: tuple
    files: tuple          # ((file name, bytes), ...)
    seeds: tuple          # the seed of each input file

    def key(self) -> tuple:
        """Jobs with equal keys have equal inputs, so one PySim run
        serves them all."""
        return (self.name, self.argv, self.seeds)


def target_config(config: dict, traffic: dict) -> dict:
    """The program's registry entry named by the configuration, with
    the configuration's deployment and the traffic's link laid over
    it."""
    from repro.configs import registry
    base = getattr(registry, config["registry"])
    over = {**config["deployment"], **traffic["link"]}
    unknown = sorted(set(over) - set(base))
    if unknown:
        raise KeyError(f"not keys of {config['registry']}: {unknown}")
    return {**base, **over}


def file_seed(seed: int, phase: int, unit: int, board: int,
              index: int) -> int:
    ss = np.random.SeedSequence([seed % (1 << 64), phase, unit, board,
                                 index])
    return int(ss.generate_state(1, np.uint64)[0])


def make_file(spec: dict, seed: int) -> bytes:
    (kind, args), = spec.items()
    if kind == "rmat":
        from .graphs import rmat
        return rmat(args["scale"], args["degree"], seed, args["weights"])
    raise KeyError(f"unknown input file kind {kind!r}")


def job_input(traffic: dict, seed: int, phase: int, unit: int,
              board: int) -> JobInput:
    files, seeds = [], []
    for i, (fname, spec) in enumerate(sorted(traffic["files"].items())):
        s = file_seed(seed, phase, unit, board, i)
        files.append((fname, make_file(spec, s)))
        seeds.append(s)
    return JobInput(traffic["workload"], tuple(traffic["argv"]),
                    tuple(files), tuple(seeds))


def unit_inputs(traffic: dict, seed: int, phase: int, unit: int,
                boards: int) -> list[JobInput]:
    """The board-jobs of one unit, in board order."""
    seed = traffic.get("set_seed", seed)
    return [job_input(traffic, seed, phase, unit, b)
            for b in range(boards)]


#: words a single batched read may carry: a guest page (512 words) and
#: every smaller power of two, which page-sized and smaller reads of
#: some jobs use and others do not
READ_WORDS = tuple(1 << k for k in range(10))


class Units:
    """How a cell runs its units: a file ``bench/units/<name>.py``, named
    by the configuration's ``units``, defines a subclass ``Units`` with
    ``boards``, ``unit(jobs, span)`` (one unit: one report per board, and
    a target of the unit's own to read from) and ``chunk_kernel``, the
    name of the function of ``repro.core.target.cpu`` that the unit
    launches every chunk through, looked up at each launch (the
    benchmark's tests break the timed path there)."""

    boards = 1

    def __init__(self, cfg: dict):
        self.cfg = cfg

    def run(self, jobs: list[JobInput], span) -> list:
        """One unit's reports, one a board, in board order."""
        return self.unit(jobs, span)[0]

    def warm_up(self, jobs: list[JobInput], span) -> None:
        """One whole unit, then on its own target a batched read of each
        size in ``READ_WORDS``: together they compile every program the
        window runs, whatever its jobs' inputs."""
        _, target = self.unit(jobs, span)
        for n in READ_WORDS:
            target.fetch_batch(words=[0] * n)


def reference(cfg: dict, job: JobInput, target_cls=None):
    """The job on ``PySim`` (or ``target_cls``), the program's own
    pure-Python target, through the same host runtime.  Against it a
    board-job's ticks, instret, traffic and stdout show whether the JAX
    target (chunk kernel and accessors) agrees with a second engine; the
    runtime, session and channel model are shared by both sides, so a
    fault there moves both alike (``fasebench.check``)."""
    from repro.configs.fase_rocket import runtime_kwargs
    from repro.core.runtime import FaseRuntime
    from repro.core.target.pysim import PySim
    from repro.core.workloads import build
    tgt = (target_cls or PySim)(cfg["n_cores"], cfg["mem_bytes"])
    rt = FaseRuntime(tgt, mode="fase", **runtime_kwargs(cfg))
    rt.load(build(job.name), [job.name, *job.argv], files=dict(job.files))
    return rt.run(max_ticks=MAX_TICKS)
