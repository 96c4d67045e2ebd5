"""Synthetic power-law graphs in CSR layout — the graph-mining workload's
input (the paper's third case-study application).

``powerlaw_graph`` draws out-degrees from a truncated power law and wires
destinations preferentially (popularity weights are themselves power-law
over a random node permutation), so both degree tails are heavy — the
web/social-graph shape that makes graph frameworks memory-bound. The CSR
stores **in-edges**: row ``v`` holds the sources of edges into ``v``,
which is exactly the order a pull/push SpMV consumes.

``graph_state`` expands the CSR into the device payload the Pallas kernels
(``repro.kernels.segsum``) read — tiled edge arrays plus node vectors —
grouped into the HRM regions of ``repro.core.policy``:

    graph/topology   src, dst (the tiled CSR expansion), outdeg, and the
                     per-tile block-dispatch tables of the node-blocked
                     layout — the pointer-heavy structure: corruption
                     rewires (or drops) edges
    graph/rank       the PageRank iterate (self-heals under convergence)
    graph/frontier   BFS frontier/visited/dist (transient per traversal)

With ``node_block=BN`` the state is built in the **node-blocked** layout
for graphs whose node vector does not fit one core's VMEM: edges are
bucketed by ``(dst_block, src_block)`` at build time (``bucket_edges``),
each bucket sentinel-padded to whole edge tiles, and per-tile block
coordinates stored under ``topology/blocks`` so
``edge_segment_push_blocked`` can steer its DMA per grid step. The block
size itself is carried as the *shape* of the ``bn_lanes`` marker leaf
(``node_block_of``), so recovering it never syncs the device and a struck
bit in the marker's payload cannot corrupt the layout.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.kernels.segsum import (EDGE_TILE, NODE_LANES, TILES_PER_STEP,
                                  _round_up, pad_edges)

# below this node count the O(n^2) legacy sampling loop is cheap and its
# exact edge stream is pinned by existing tests; above it the vectorized
# single-draw path keeps generation O(E log E)
_VECTORIZE_MIN_N = 4096


@dataclass(frozen=True)
class CSRGraph:
    """In-edge CSR: ``indices[indptr[v]:indptr[v+1]]`` = sources into v."""
    n: int
    indptr: np.ndarray        # (n+1,) int32
    indices: np.ndarray       # (nnz,) int32, row-sorted
    out_degree: np.ndarray    # (n,) int32

    @property
    def n_edges(self) -> int:
        return int(self.indices.shape[0])

    @property
    def max_in_degree(self) -> int:
        return int(np.diff(self.indptr).max()) if self.n else 0


def powerlaw_graph(n: int, *, avg_degree: float = 8.0, alpha: float = 2.1,
                   seed: int = 0,
                   vectorized: Optional[bool] = None) -> CSRGraph:
    """Deterministic power-law digraph: out-degrees follow a truncated
    ``k^{-alpha}`` law (configuration-model style), destinations are drawn
    preferentially, self-loops and duplicate edges are removed.

    ``vectorized=None`` keeps the legacy per-node sampling loop (and its
    exact edge stream) below ``_VECTORIZE_MIN_N`` nodes and switches to a
    single batched draw above it — same degree law and popularity
    weights, O(E log E) instead of O(n^2), but a different (still
    seed-deterministic) edge stream."""
    if vectorized is None:
        vectorized = n >= _VECTORIZE_MIN_N
    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    # out-degree targets: power-law weights over the permuted node ranks
    w = (np.arange(n, dtype=np.float64) + 1.0) ** (-1.0 / (alpha - 1.0))
    deg = np.maximum(1, np.round(avg_degree * w / w.mean())).astype(np.int64)
    deg = np.minimum(deg, max(1, n // 2))[order]
    # destination popularity: an independent permuted power law
    pop = w[rng.permutation(n)]
    p = pop / pop.sum()
    if vectorized:
        src_all = np.repeat(np.arange(n, dtype=np.int64), deg)
        dst_all = rng.choice(n, size=int(deg.sum()), p=p).astype(np.int64)
        keep = src_all != dst_all                  # no self loops
        pair = src_all[keep] * n + dst_all[keep]   # dedupe (u, v) pairs
        pair = np.unique(pair)
        src, dst = pair // n, pair % n
    else:
        srcs, dsts = [], []
        for u in range(n):
            d = rng.choice(n, size=int(deg[u]), p=p)   # with replacement;
            d = np.unique(d[d != u])                   # dedupe + no loops
            srcs.append(np.full(d.shape[0], u, np.int64))
            dsts.append(d)
        src = np.concatenate(srcs)
        dst = np.concatenate(dsts)
    order = np.lexsort((src, dst))                     # row-sorted (by dst)
    src, dst = src[order], dst[order]
    indptr = np.zeros(n + 1, np.int64)
    np.add.at(indptr, dst + 1, 1)
    indptr = np.cumsum(indptr)
    out_degree = np.bincount(src, minlength=n)
    return CSRGraph(n, indptr.astype(np.int32), src.astype(np.int32),
                    out_degree.astype(np.int32))


def bucket_edges(src, dst, n_pad: int, node_block: int, *,
                 edge_tile: int = EDGE_TILE
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Bucket (E,) edge arrays by ``(dst_block, src_block)`` for the
    node-blocked push kernel.

    Every bucket is sentinel-padded (id ``n_pad``: block-local out of
    range for *every* block) to whole ``edge_tile`` tiles, so each tile
    lives in exactly one bucket; buckets are laid out dst-block-major
    (the kernel's output-revisit contract), in whole grid steps of
    ``TILES_PER_STEP`` tiles. Returns
    ``(src, dst, tile_src_block, tile_dst_block)`` — edge arrays of shape
    (T*edge_tile,) plus the (T,) per-tile dispatch tables. Fully
    vectorized: O(E log E) at build time.
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    nb = n_pad // node_block
    if src.size == 0:                       # degenerate: one sentinel tile
        pad = np.full(edge_tile, n_pad, np.int32)
        return pad, pad.copy(), np.zeros(1, np.int32), np.zeros(1, np.int32)
    key = (dst // node_block) * nb + (src // node_block)
    order = np.argsort(key, kind="stable")
    src, dst, key = src[order], dst[order], key[order]
    uk, cnt = np.unique(key, return_counts=True)
    padded = np.maximum(edge_tile, -(-cnt // edge_tile) * edge_tile)
    starts = np.concatenate([[0], np.cumsum(padded)[:-1]])
    in_bucket = np.arange(src.size) - np.repeat(np.cumsum(cnt) - cnt, cnt)
    pos = np.repeat(starts, cnt) + in_bucket
    total = int(padded.sum())
    out_src = np.full(total, n_pad, np.int32)
    out_dst = np.full(total, n_pad, np.int32)
    out_src[pos] = src
    out_dst[pos] = dst
    tiles_per = padded // edge_tile
    tile_db = np.repeat(uk // nb, tiles_per).astype(np.int32)
    tile_sb = np.repeat(uk % nb, tiles_per).astype(np.int32)
    # whole grid steps of the push (TILES_PER_STEP tiles, one edge DMA),
    # which are also whole 1024-word tiles of a TPU's (E,) int32 layout
    # (else the push's (1, E) view re-lays both arrays out every call).
    # Extra tiles are all sentinel and repeat the last tile's blocks.
    extra = -tile_db.size % TILES_PER_STEP
    if extra:
        fill = np.full(extra * edge_tile, n_pad, np.int32)
        out_src = np.concatenate([out_src, fill])
        out_dst = np.concatenate([out_dst, fill])
        tile_sb = np.concatenate([tile_sb, np.repeat(tile_sb[-1:], extra)])
        tile_db = np.concatenate([tile_db, np.repeat(tile_db[-1:], extra)])
    return out_src, out_dst, tile_sb, tile_db


def graph_state(g: CSRGraph, *, with_bfs: bool = False, source: int = 0,
                edge_tile: int = EDGE_TILE,
                node_block: Optional[int] = None) -> dict:
    """Device payload for the kernels, classifiable by ``MemoryDomain``
    (wrap as ``{"graph": graph_state(g)}`` before ``protect``).

    ``dst`` is the CSR row expansion of ``indptr`` and ``src`` its
    ``indices`` column, tiled and sentinel-padded for the edge grid; the
    sentinel is ``n_pad`` (matches no node). With ``node_block`` set
    (a multiple of ``NODE_LANES``), the edge arrays are bucketed by
    ``(dst_block, src_block)`` and the per-tile dispatch tables are added
    under ``topology/blocks`` — the layout ``edge_segment_push_blocked``
    consumes for graphs that don't fit one core's VMEM.
    """
    dst_rows = np.repeat(np.arange(g.n, dtype=np.int64), np.diff(g.indptr))
    if node_block is None:
        n_pad = _round_up(max(g.n, 1), NODE_LANES)
        src, dst = pad_edges(jnp.asarray(g.indices),
                             jnp.asarray(dst_rows.astype(np.int32)), n_pad,
                             edge_tile=edge_tile)
        topology = {"src": src, "dst": dst}
    else:
        if node_block % NODE_LANES:
            raise ValueError(f"node_block {node_block} must be a multiple "
                             f"of NODE_LANES ({NODE_LANES})")
        n_pad = _round_up(max(g.n, 1), node_block)
        bsrc, bdst, tsb, tdb = bucket_edges(g.indices, dst_rows, n_pad,
                                            node_block,
                                            edge_tile=edge_tile)
        topology = {
            "src": jnp.asarray(bsrc), "dst": jnp.asarray(bdst),
            "blocks": {
                "src_block": jnp.asarray(tsb),
                "dst_block": jnp.asarray(tdb),
                # layout marker: the block size is this leaf's *shape*
                # (times NODE_LANES) — see node_block_of
                "bn_lanes": jnp.zeros((node_block // NODE_LANES,),
                                      jnp.int32),
            },
        }
    outdeg = jnp.zeros((1, n_pad), jnp.int32).at[0, :g.n].set(
        jnp.asarray(g.out_degree))
    topology["outdeg"] = outdeg
    real = jnp.arange(n_pad) < g.n
    rank = jnp.where(real, 1.0 / g.n, 0.0).reshape(1, n_pad)
    state = {
        "topology": topology,
        "rank": {"rank": rank.astype(jnp.float32)},
    }
    if with_bfs:
        onehot = (jnp.arange(n_pad) == source).astype(jnp.int32)
        state["frontier"] = {
            "frontier": onehot.reshape(1, n_pad),
            "visited": onehot.reshape(1, n_pad),
            "dist": jnp.where(onehot > 0, 0, -1).reshape(1, n_pad)
                       .astype(jnp.int32),
        }
    return state


def n_padded(state: dict) -> int:
    """Padded node-vector length of a ``graph_state`` payload."""
    return int(state["rank"]["rank"].shape[1])


def node_block_of(state: dict) -> Optional[int]:
    """Node-block size of a ``graph_state`` payload, or ``None`` for the
    dense (single-kernel) layout. Derived from the ``bn_lanes`` marker's
    shape — static, so it never syncs the device and never depends on
    (corruptible) payload bytes."""
    blocks = state["topology"].get("blocks")
    if blocks is None:
        return None
    return int(blocks["bn_lanes"].shape[0]) * NODE_LANES
