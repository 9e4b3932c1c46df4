"""One board on ``JaxTarget``; a unit is one whole job: ``FaseRuntime``'s
``load`` then ``run``, to the guest's exit, device-synced."""
from fasebench.jobs import MAX_TICKS, Units as _Units


class Units(_Units):
    def __init__(self, cfg: dict):
        from repro.configs.fase_rocket import runtime_kwargs, \
            target_kwargs
        super().__init__(cfg)
        self.rkw = runtime_kwargs(cfg)
        self.tkw = target_kwargs(cfg)

    def read_target(self):
        from repro.core.interface import JaxTarget
        return JaxTarget(self.cfg["n_cores"], self.cfg["mem_bytes"],
                         **self.tkw)

    def run(self, jobs, span) -> list:
        import jax
        from repro.core.runtime import FaseRuntime
        from repro.core.workloads import build
        (job,) = jobs
        tgt = self.read_target()
        rt = FaseRuntime(tgt, mode="fase", **self.rkw)
        with span("load"):
            rt.load(build(job.name), [job.name, *job.argv],
                    files=dict(job.files))
        with span("run"):
            rep = rt.run(max_ticks=MAX_TICKS)
            jax.block_until_ready(tgt.st)
        return [rep]
