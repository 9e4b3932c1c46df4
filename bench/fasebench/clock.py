"""Compile clock, device check and peak memory of the benchmark process.

Copied from the repo's chip smoke test so that the yardstick does not
move when program code does: ``CompileClock`` sums the seconds JAX
spends tracing, lowering and compiling, and also counts compilations,
so that the harness can show that none happens inside the window.
"""
from __future__ import annotations

import jax


class NoAccelerator(SystemExit):
    """Raised when JAX finds no accelerator, or fewer chips than a cell
    asks for: the run exits non-zero and prints no result."""


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and the number
    of backend compilations.  A nested jit is traced inside its caller
    and never lowered alone, so only the trace of a function that is
    then lowered counts."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.names: list = []         # of each compiled program
        self._traced: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, fun_name="", **_):
        if event == self.TRACE:
            self._traced[fun_name] = secs
        elif event == self.LOWER:
            name = fun_name[4:-1] if fun_name.startswith("jit(") \
                else fun_name
            self.seconds += secs + self._traced.pop(name, 0.0)
        elif event == self.COMPILE:
            self.seconds += secs
            self.compiles += 1
            self.names.append(fun_name)

    def mark(self) -> tuple[float, int]:
        return self.seconds, self.compiles


def device_info(chips: int, require_accelerator: bool = True) -> dict:
    """The devices as JAX reports them.  Raises :class:`NoAccelerator`
    when there is no accelerator or fewer chips than ``chips``."""
    devs = jax.devices()
    info = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))
    if require_accelerator and info["platform"] == "cpu":
        raise NoAccelerator(f"bench: no accelerator, JAX found {info}")
    if len(devs) < chips:
        raise NoAccelerator(f"bench: the cell asks for {chips} chips, "
                            f"JAX found {len(devs)}")
    return info


def peak_bytes(chips: int) -> int | None:
    """``peak_bytes_in_use`` of the fullest of the first ``chips``
    devices, or None where the backend does not report it."""
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
