"""Plain reference of the PageRank cell, and the generator of its graph.

The graph is an LDBC Graphalytics ``graph500`` dataset made afresh from
the seed by the Graph500 specification's Kronecker generator: ``2^scale``
labels, ``edge_factor * 2^scale`` edges, each placed by ``scale`` draws
of a quadrant with probabilities A, B, C and 1 - A - B - C, then the
labels permuted at random. As Graphalytics stores these datasets, the
graph is undirected and simple (self-loops and repeated edges dropped)
and holds only the vertices that have an edge, numbered in label order.
PageRank reads each undirected edge as two directed ones.

The reference is the Graphalytics power iteration in float64 on the edge
list, from ranks 1/n:

    rank' = (1-d)/n + d * (sum_{u->v} rank_u / outdeg_u + dangling / n)

where ``dangling`` is the rank mass of vertices without out-edges. With
``bf16`` every rank and contribution is rounded to bfloat16, the step
below the configuration's float32: the control.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np


def kronecker_edges(scale: int, edge_factor: int, abc: Sequence[float],
                    rng):
    """(n, src, dst): the directed edges (both ways of every undirected
    one) as int64 arrays sorted by (dst, src), over vertices 0..n-1."""
    a, b, c = abc
    m = edge_factor << scale
    # one draw per level picks the quadrant: (0,0) below a, (0,1) below
    # a+b, (1,0) below a+b+c, else (1,1) (the specification's two draws
    # per level, ii then jj given ii, give the same distribution)
    i = np.zeros(m, np.int32)
    j = np.zeros(m, np.int32)
    for level in range(scale):
        u = rng.random(m, dtype=np.float32)
        ii = u >= a + b
        i |= ii.astype(np.int32) << level
        j |= (ii ^ (u >= a) ^ (u >= a + b + c)).astype(np.int32) << level
    perm = rng.permutation(1 << scale)
    i, j = perm[i].astype(np.int64), perm[j].astype(np.int64)
    keep = i != j
    lo, hi = np.minimum(i[keep], j[keep]), np.maximum(i[keep], j[keep])
    und = np.unique(lo << scale | hi)
    lo, hi = und >> scale, und & ((1 << scale) - 1)
    labels, ids = np.unique(np.concatenate([lo, hi]), return_inverse=True)
    lo, hi = ids[:len(lo)], ids[len(lo):]
    n = len(labels)
    key = np.sort(np.concatenate([hi * n + lo, lo * n + hi]))  # by (dst, src)
    return n, key % n, key // n


def _round_bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes
    return x.astype(ml_dtypes.bfloat16).astype(np.float64)


def pagerank(src, dst, n: int, damping: float, at: Sequence[int], *,
             bf16: bool = False) -> List[np.ndarray]:
    """Ranks after each iteration count in ``at`` (ascending)."""
    from scipy.sparse import csr_matrix
    outdeg = np.bincount(src, minlength=n).astype(np.float64)
    a = csr_matrix((np.ones(len(src)), (dst, src)), shape=(n, n))
    dangling = outdeg == 0
    inv = np.where(dangling, 0.0, 1.0 / np.maximum(outdeg, 1.0))
    rank = np.full(n, 1.0 / n)
    rnd = _round_bf16 if bf16 else (lambda x: x)
    out, it = [], 0
    for stop in at:
        while it < stop:
            contrib = rnd(rank * inv)
            pushed = rnd(a @ contrib)
            rank = rnd((1.0 - damping) / n
                       + damping * (pushed + rank[dangling].sum() / n))
            it += 1
        out.append(rank.copy())
    return out


def max_rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - ref) / np.abs(ref)))
