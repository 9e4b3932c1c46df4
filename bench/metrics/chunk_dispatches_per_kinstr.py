"""chunk_dispatches_per_kinstr: launches of the chunk program per
thousand guest instructions of the traced whole unit."""


def read(trace):
    u = trace.unit
    if u is None or not u.chunks() or u.guest_instr <= 0:
        return None
    return len(u.chunks()) / u.kinstr
