"""between_chunks_ms_per_kinstr: wall milliseconds of the traced whole
unit in which the host is not waiting on a chunk (from the chunk's launch
to the end of the host's read of its result): host work of the runtime,
session and channel model, and the accessor programs it launches, per
thousand guest instructions."""

from fasebench.xtrace import covered


def read(trace):
    u = trace.unit
    if u is None or not u.chunks() or u.guest_instr <= 0:
        return None
    outside = u.wall_ns - covered((s, e) for _, s, e in u.chunks())
    return outside / 1e6 / u.kinstr
