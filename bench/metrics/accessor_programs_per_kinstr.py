"""accessor_programs_per_kinstr: launches of every program other than
the chunk program (``fetch_batch`` gathers, ``commit_batch`` scatters,
host micro-ops, page writes) per thousand guest instructions of the
traced whole unit."""


def read(trace):
    u = trace.unit
    if u is None or not u.chunks() or u.guest_instr <= 0:
        return None
    return len(u.others()) / u.kinstr
