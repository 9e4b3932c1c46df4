"""chunk_device_ms_per_dispatch: device milliseconds of one execution of
the chunk program (``run_chunk_fast`` on one board), the mean over the
device slice's executions.  Times ``chunk_dispatches_per_kinstr`` it
gives the chunk kernel's device time per thousand guest instructions."""


def read(trace):
    s = trace.slice
    chunks = s.chunks() if s is not None else []
    if not chunks:
        return None
    return sum(e - b for _, b, e in chunks) / len(chunks) / 1e6
