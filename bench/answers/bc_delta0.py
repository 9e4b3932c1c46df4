"""bc_delta0: the number GAPBS bc prints, worked out here from the job's
graph file alone.

The guest runs Brandes' betweenness centrality from one source per trial
(vertex ``trial mod n``) and, after its last trial, prints ``bc_delta0``:
vertex 0's dependency on that source.  It keeps dependencies in Q32.32
fixed point on u64 words: a child ``v`` of ``u`` adds
``(2**32 + delta[v]) * sigma[u] // sigma[v]`` to ``delta[u]``, the
product wrapping at 2**64.  Shortest-path counts and dependencies do not
depend on the order in which the threads visit vertices, so one plain
serial pass gives the guest's number.
"""
from __future__ import annotations

from collections import deque

from fasebench.graphs import csr

MASK = (1 << 64) - 1
ONE = 1 << 32


def delta(rowptr, colidx, src: int) -> list[int]:
    """Every vertex's Q32.32 dependency on ``src``."""
    n = len(rowptr) - 1
    level = [-1] * n
    sigma = [0] * n
    level[src], sigma[src] = 0, 1
    order, queue = [], deque([src])
    while queue:                      # breadth first: levels and sigma
        u = queue.popleft()
        order.append(u)
        for v in colidx[rowptr[u]:rowptr[u + 1]]:
            if level[v] < 0:
                level[v] = level[u] + 1
                queue.append(v)
            if level[v] == level[u] + 1:
                sigma[v] = (sigma[v] + sigma[u]) & MASK
    dep = [0] * n
    for u in reversed(order):         # deepest level first
        acc = 0
        for v in colidx[rowptr[u]:rowptr[u + 1]]:
            if level[v] == level[u] + 1:
                acc += ((ONE + dep[v]) * sigma[u] & MASK) // sigma[v]
        dep[u] = acc & MASK
    return dep


def expected(job) -> int:
    """``job.argv`` is ``(graph file, threads, trials)``."""
    graph = dict(job.files)[job.argv[0]]
    rowptr, colidx = csr(graph)
    trials = int(job.argv[2])
    src = (trials - 1) % (len(rowptr) - 1)
    return delta(rowptr.tolist(), colidx.tolist(), src)[0]
