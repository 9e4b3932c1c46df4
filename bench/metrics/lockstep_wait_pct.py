"""lockstep_wait_pct: the share of the chips' chunk time spent waiting for
the slowest board of a global chunk, from the device slice.

A launch of the chunk program on a fleet sharded over chips runs once on
each chip, and the next cannot start before the slowest of them ends.
The slice's chunk executions are grouped into dispatches by overlap (an
execution that starts before the dispatch's latest end so far belongs to
it), and the metric is 100 * (1 - sum of execution times / sum over
dispatches of executions * the longest).  None where every dispatch has
one execution (one chip), or the slice holds no chunk."""


def dispatches(chunks) -> list:
    """``chunks`` (``(name, start_ns, end_ns)``) grouped into dispatches:
    lists of execution durations."""
    groups, end = [], None
    for _, s, e in sorted(chunks, key=lambda p: p[1]):
        if end is not None and s < end:
            groups[-1].append(e - s)
            end = max(end, e)
        else:
            groups.append([e - s])
            end = e
    return groups


def read(trace):
    s = trace.slice
    groups = dispatches(s.chunks()) if s is not None else []
    if all(len(g) == 1 for g in groups):
        return None
    span = sum(len(g) * max(g) for g in groups)
    return 100.0 * (1.0 - sum(sum(g) for g in groups) / span)
