"""One board on ``JaxTarget``; a unit is one whole job: ``FaseRuntime``'s
``load`` then ``run``, to the guest's exit, device-synced."""
from fasebench.jobs import MAX_TICKS, Units as _Units


class Units(_Units):
    chunk_kernel = "run_chunk_fast"

    def __init__(self, cfg: dict):
        from repro.configs.fase_rocket import runtime_kwargs, \
            target_kwargs
        super().__init__(cfg)
        self.rkw = runtime_kwargs(cfg)
        self.tkw = target_kwargs(cfg)

    def unit(self, jobs, span):
        import jax
        from repro.core.interface import JaxTarget
        from repro.core.runtime import FaseRuntime
        from repro.core.workloads import build
        (job,) = jobs
        tgt = JaxTarget(self.cfg["n_cores"], self.cfg["mem_bytes"],
                        **self.tkw)
        rt = FaseRuntime(tgt, mode="fase", **self.rkw)
        with span("load"):
            rt.load(build(job.name), [job.name, *job.argv],
                    files=dict(job.files))
        with span("run"):
            rep = rt.run(max_ticks=MAX_TICKS)
            jax.block_until_ready(tgt.st)
        return [rep], tgt
