"""The comparison that decides ``correct``.

Every board-job the window completed is held, after the window, to two
references:

* what the guest printed, against answers that depend on no code of the
  program: a value the traffic states (CoreMark's CRC) or one that
  ``bench/answers/<key>.py`` works out from the job's inputs alone
  (GAPBS bc's ``bc_delta0``, by a plain Brandes pass over the same
  graph).  These cover every layer the job's output passes through: the
  chunk kernel and accessors that compute it, the runtime's syscalls
  that read the input file, spawn the threads and write the output, and
  the session that carries them;
* ``PySim``, the program's own pure-Python target, run on the same
  inputs through the same host runtime: ``stdout``, ``ticks``, per-core
  ``instret`` and ``traffic_total`` must be equal, which holds the JAX
  target (chunk kernel and accessors) to a second engine.  The runtime,
  session and channel model are shared by both sides, so ticks and
  traffic are checked for target agreement only, not for the link
  model's own correctness.

The state is 64-bit integers, so every comparison is exact and every
limit is 0.
"""
from __future__ import annotations

#: Report fields that must equal PySim's, bit for bit
COMPARED = ("stdout", "ticks", "instret", "traffic_total")


def stdout_values(stdout: bytes) -> dict:
    """``key value`` lines of a guest's stdout (CoreMark-lite's
    ``coremark_crc 16356``)."""
    out = {}
    for line in stdout.decode(errors="replace").splitlines():
        parts = line.split()
        if len(parts) == 2:
            out[parts[0]] = parts[1]
    return out


class Tally:
    """Mismatch counts per compared field, over board-jobs.

    ``expect`` maps a stdout key to its stated value; ``answers`` maps a
    stdout key to a function of the job's inputs that gives it."""

    def __init__(self, expect: dict, answers: dict):
        self.expect = {k: str(v) for k, v in expect.items()}
        self.answers = answers
        keys = [*COMPARED, *self.expect, *self.answers, "unfinished"]
        self.counts = dict.fromkeys(keys, 0)
        self.failed = 0

    def unfinished(self, n: int = 1) -> None:
        self.counts["unfinished"] += n
        self.failed += n

    def compare(self, got, ref, job) -> bool:
        """Count the fields in which ``got`` differs from ``ref``, and the
        stated or worked-out stdout values it lacks; True when none
        does."""
        bad = [f for f in COMPARED if getattr(got, f) != getattr(ref, f)]
        values = stdout_values(got.stdout)
        want = {**self.expect,
                **{k: str(f(job)) for k, f in self.answers.items()}}
        bad += [k for k, v in want.items() if values.get(k) != v]
        for f in bad:
            self.counts[f] += 1
        self.failed += bool(bad)
        return not bad

    def checks(self) -> dict:
        """Each compared number beside its limit (all limits are 0)."""
        return {name: {"value": n, "limit": 0}
                for name, n in self.counts.items()}
