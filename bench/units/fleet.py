"""Four boards stacked on one chip (``FleetTarget``); a unit is one
``FleetRuntime.run_synchronous`` round, one whole job a board, every
global chunk one launch of ``cpu.run_chunk_fleet`` for all boards still
running, until the last guest exits, device-synced."""
from fasebench.jobs import MAX_TICKS, Units as _Units


class Units(_Units):
    boards = 4
    chunk_kernel = "run_chunk_fleet"

    def __init__(self, cfg: dict):
        from repro.configs.fase_rocket import fleet_kwargs
        super().__init__(cfg)
        if cfg["n_devices"] != self.boards:
            raise ValueError(f"the fleet units run {self.boards} boards, "
                             f"the configuration {cfg['n_devices']}")
        self.fkw = fleet_kwargs(cfg)

    def unit(self, jobs, span):
        import jax
        from repro.core.fleet import FleetRuntime, Job
        with span("load"):
            fleet = FleetRuntime(**self.fkw)
        with span("run"):
            res = fleet.run_synchronous(
                [Job(j.name, list(j.argv), files=dict(j.files),
                     max_ticks=MAX_TICKS) for j in jobs],
                max_ticks=MAX_TICKS)
            jax.block_until_ready(fleet.fleet_target.sts)
        reps = [r.report for r in res]
        print(f"fleet unit: {fleet.fleet_target.dispatch_count} global "
              f"chunks, board ticks {[r.ticks for r in reps]}, board "
              f"instr {[sum(r.instret) for r in reps]}", flush=True)
        return reps, fleet.fleet_target.view(0)
