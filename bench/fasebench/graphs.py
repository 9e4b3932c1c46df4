"""GAPBS-style Kronecker (R-MAT) graphs, as the guest's graph file.

The file is little-endian u64 words: a header ``[n, m, has_weights]``,
then ``rowptr`` (n + 1), ``colidx`` (m) and, with weights, ``weights``
(m).  The graph is undirected (both directions stored), without self
loops or repeated edges, and each row is sorted.  A copy of the repo's
``graphgen.rmat``, kept here so that the benchmark's inputs do not move
when program code does.
"""
from __future__ import annotations

import numpy as np


def rmat(scale: int, degree: int, seed: int, weights: bool) -> bytes:
    """``2**scale`` vertices and about ``degree`` edges per vertex, drawn
    from ``seed`` with R-MAT's quadrant probabilities (0.57, 0.19, 0.19,
    0.05)."""
    n = 1 << scale
    m_dir = n * degree // 2
    rng = np.random.default_rng(seed)
    a, b, c = 0.57, 0.19, 0.19
    src = np.zeros(m_dir, dtype=np.int64)
    dst = np.zeros(m_dir, dtype=np.int64)
    for bit in range(scale):
        r1 = rng.random(m_dir)
        r2 = rng.random(m_dir)
        go_right = r1 > (a + b)
        right_top = r2 < c / (c + (1 - a - b - c))
        top = np.where(go_right, right_top, r2 < a / (a + b))
        src |= go_right.astype(np.int64) << bit
        dst |= (~top).astype(np.int64) << bit
    u = np.concatenate([src, dst])
    v = np.concatenate([dst, src])
    keep = u != v
    eid = np.unique(u[keep] * n + v[keep])        # sorted by (u, v)
    u, v = eid // n, eid % n
    m = len(u)
    rowptr = np.zeros(n + 1, dtype=np.uint64)
    np.add.at(rowptr, u + 1, 1)
    rowptr = np.cumsum(rowptr).astype(np.uint64)
    header = np.array([n, m, 1 if weights else 0], dtype=np.uint64)
    parts = [header.tobytes(), rowptr.tobytes(), v.astype(np.uint64).tobytes()]
    if weights:
        parts.append(rng.integers(1, 16, size=m).astype(np.uint64).tobytes())
    return b"".join(parts)


def csr(graph: bytes) -> tuple[np.ndarray, np.ndarray]:
    """``(rowptr, colidx)`` of a graph file, as int64."""
    n, m = (int(x) for x in np.frombuffer(graph, np.uint64, 2))
    rowptr = np.frombuffer(graph, np.uint64, n + 1, 24).astype(np.int64)
    colidx = np.frombuffer(graph, np.uint64, m, 24 + 8 * (n + 1))
    return rowptr, colidx.astype(np.int64)
