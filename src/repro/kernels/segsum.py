"""Pallas TPU kernels for tiled-CSR segment-sum SpMV and BFS frontier
updates — the compute core of the graph-mining workload (``repro.graph``).

Data layout: a CSR graph is expanded into edge arrays ``src``/``dst`` of
shape (E,) int32 (``dst`` is the CSR row expansion: edges arrive sorted by
destination), padded to a multiple of the edge tile with the sentinel id
``n_pad`` (matches no node, contributes nothing). Node vectors are (1, N)
with N a multiple of 128 lanes.

``edge_segment_push`` computes ``y[j] = sum_{e: dst[e]==j} x[src[e]]`` —
one grid step per edge tile; within a tile both the gather (``x[src]``)
and the scatter-add (segment sum by ``dst``) are realized as one-hot
matmuls, the TPU segment-sum idiom: the (N, TE) one-hot masks feed the MXU
and the accumulation across tiles rides the revisited output block. No
dynamic indexing touches the kernel, so the same body runs under
``interpret=True`` on CPU.

``frontier_update`` is the elementwise BFS step (threshold pushed mass,
mask visited, stamp the level into ``dist``), tiled over node blocks.

``*_oracle`` functions replay the identical tile/accumulation order in
plain jnp: the Pallas kernels agree with them to a few f32 ulp (the dots
accumulate in their own order; ``tests/test_graph.py``), and both are
allclose to the ``jax.ops.segment_sum`` reference (different summation
order). The one-hot matmuls run at ``HIGHEST`` precision: the TPU's
default would round the f32 values to bf16.

VMEM note: each grid step of ``edge_segment_push`` holds the full (1, N)
node vector plus two (N, TE) one-hot masks, so the single-kernel form
caps at N ~ a few thousand nodes on a 16 MiB-VMEM core (N = 4096 at the
default TE = 512 already needs 2 x 4096 x 512 x 4 B = 16.8 MiB of masks).
``edge_segment_push_blocked`` removes the cap: a node-block dimension is
added and edges are bucketed by ``(src_block, dst_block)`` at CSR build
time (``repro.graph.generate``), so each grid step touches only the
(1, BN) source slice its tile gathers from and the (1, BN) destination
slice it scatter-adds into — VMEM per step is O(BN x TE) independent of
N. Per-tile block coordinates arrive as scalar-prefetch arrays
(``PrefetchScalarGridSpec``): the index maps read ``src_block[i]`` /
``dst_block[i]`` to steer the DMA, the standard Pallas block-sparse
dispatch idiom. Tiles are sorted destination-block-major, so each output
block's accumulation chain runs over consecutive grid steps (one
zero-init at the first visit, revisited in place after).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

EDGE_TILE = 512          # edges per grid step; multiple of the 128-lane tile
NODE_LANES = 128         # node vectors padded to a multiple of this
# The one-hot gather/scatter must move f32 ranks without rounding them to
# bf16, which the TPU's default matmul precision would do.
_HIGHEST = jax.lax.Precision.HIGHEST


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.lru_cache(maxsize=None)
def fit_edge_tile(e: int, max_tile: int = EDGE_TILE) -> int:
    """Largest tile <= ``max_tile`` dividing the padded edge count ``e`` —
    lets consumers recover a valid grid for arrays padded with any
    ``edge_tile``.

    The padding contract (``pad_edges``) only ever produces multiples of
    the tile that padded them, so a divisor always exists; it is computed
    directly from ``e``'s factorization (O(sqrt e), not the old O(e)
    descending scan that walked every candidate on prime-ish counts) and
    memoized per (count, max_tile) shape."""
    if e <= 0:
        return 1
    if e <= max_tile:
        return e
    if e % max_tile == 0:
        return max_tile
    # largest divisor of e that is <= max_tile, via trial division: every
    # divisor d <= sqrt(e) also names its cofactor e // d
    best = 1
    d = 1
    while d * d <= e:
        if e % d == 0:
            for cand in (d, e // d):
                if best < cand <= max_tile:
                    best = cand
        d += 1
    return best


def pad_edges(src, dst, n_pad: int, *, edge_tile: int = EDGE_TILE):
    """Pad (E,) edge arrays to a multiple of ``edge_tile`` with the
    sentinel id ``n_pad`` (out of range: matches no node)."""
    e = src.shape[0]
    e_pad = max(edge_tile, _round_up(e, edge_tile))
    pad = e_pad - e
    if pad:
        src = jnp.pad(src, (0, pad), constant_values=n_pad)
        dst = jnp.pad(dst, (0, pad), constant_values=n_pad)
    return src.astype(jnp.int32), dst.astype(jnp.int32)


def _push_block(src, dst, x):
    """One edge tile: gather-by-src then segment-sum-by-dst, both as
    one-hot matmuls. src/dst: (1, TE); x: (1, N). Returns (1, N)."""
    n = x.shape[1]
    te = src.shape[1]
    node_ids = jax.lax.broadcasted_iota(jnp.int32, (n, te), 0)
    gather = (node_ids == src).astype(x.dtype)           # (N, TE)
    contrib = jnp.dot(x, gather, precision=_HIGHEST)     # (1, TE)
    edge_ids = jax.lax.broadcasted_iota(jnp.int32, (te, n), 1)
    scatter = (edge_ids == dst.reshape(te, 1)).astype(x.dtype)   # (TE, N)
    return jnp.dot(contrib, scatter, precision=_HIGHEST)  # (1, N)


def _push_kernel(src_ref, dst_ref, x_ref, y_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)
    y_ref[...] += _push_block(src_ref[...], dst_ref[...], x_ref[...])


@functools.partial(jax.jit, static_argnames=("edge_tile", "interpret"))
def edge_segment_push(src, dst, x, *, edge_tile: int = EDGE_TILE,
                      interpret=None):
    """src, dst: (E,) int32, E % edge_tile == 0, sentinel-padded; x: (1, N)
    float32, N % 128 == 0. Returns y (1, N) with
    ``y[j] = sum_{e: dst[e]==j} x[src[e]]``."""
    e = src.shape[0]
    _, n = x.shape
    assert e % edge_tile == 0, (e, edge_tile)
    assert n % NODE_LANES == 0, n
    edge_spec = pl.BlockSpec((1, edge_tile), lambda i: (0, i))
    node_spec = pl.BlockSpec((1, n), lambda i: (0, 0))
    return pl.pallas_call(
        _push_kernel,
        grid=(e // edge_tile,),
        in_specs=[edge_spec, edge_spec, node_spec],
        out_specs=node_spec,
        out_shape=jax.ShapeDtypeStruct((1, n), x.dtype),
        interpret=interpret_mode(interpret),
    )(src.reshape(1, e), dst.reshape(1, e), x)


def edge_segment_push_oracle(src, dst, x, *, edge_tile: int = EDGE_TILE):
    """jnp oracle replaying the kernel's exact tile math and accumulation
    order — the few-ulp reference for ``edge_segment_push``. Not jit'd:
    op-by-op dispatch keeps the per-tile accumulation chain as written."""
    e = src.shape[0]
    g = e // edge_tile
    y = jnp.zeros_like(x)
    for i in range(g):
        sl = slice(i * edge_tile, (i + 1) * edge_tile)
        y = y + _push_block(src[sl].reshape(1, -1),
                            dst[sl].reshape(1, -1), x)
    return y


def edge_segment_push_ref(src, dst, x):
    """Independent reference via ``jax.ops.segment_sum`` (different
    summation order: allclose, not bit-equal, to the kernel). Out-of-range
    ids — the sentinel padding, or corrupted (possibly negative) indices —
    drop their edge, matching the kernel's one-hot semantics."""
    n = x.shape[1]
    src_ok = (src >= 0) & (src < n)
    contrib = jnp.where(src_ok, x[0, jnp.clip(src, 0, n - 1)], 0.0)
    seg = jnp.where((dst >= 0) & (dst < n), dst, n)  # invalid -> segment n
    return jax.ops.segment_sum(contrib, seg,
                               num_segments=n + 1)[:n].reshape(1, n)


# --------------------------------------------- node-blocked push (scale)
def _push_block_local(src, dst, xb, bn: int):
    """One edge tile against one (src_block, dst_block) pair: gather from
    the (1, BN) source slice, scatter-add into a (1, BN) destination
    slice, both as one-hot matmuls over *block-local* ids. Ids outside
    [0, BN) — the sentinel, or edges whose stored id no longer lies in the
    tile's assigned block (corrupted topology) — match no one-hot column
    and drop."""
    te = src.shape[1]
    node_ids = jax.lax.broadcasted_iota(jnp.int32, (bn, te), 0)
    gather = (node_ids == src).astype(xb.dtype)              # (BN, TE)
    contrib = jnp.dot(xb, gather, precision=_HIGHEST)        # (1, TE)
    edge_ids = jax.lax.broadcasted_iota(jnp.int32, (te, bn), 1)
    scatter = (edge_ids == dst.reshape(te, 1)).astype(xb.dtype)  # (TE, BN)
    return jnp.dot(contrib, scatter, precision=_HIGHEST)     # (1, BN)


def _blocked_push_kernel(sb_ref, db_ref, first_ref, src_ref, dst_ref,
                         x_ref, y_ref, *, bn: int):
    i = pl.program_id(0)
    sb = sb_ref[i]
    db = db_ref[i]

    @pl.when(first_ref[i] == 1)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    src = src_ref[...] - sb * bn                 # (1, TE) block-local ids
    dst = dst_ref[...] - db * bn
    y_ref[...] += _push_block_local(src, dst, x_ref[...], bn)


def _first_visit(dst_block: jax.Array) -> jax.Array:
    """1 where a tile is the first (in grid order) to touch its
    destination block — requires the dst-block-major tile sort the CSR
    build guarantees (and tile subsetting preserves)."""
    if dst_block.shape[0] == 1:
        return jnp.ones((1,), jnp.int32)
    return jnp.concatenate([
        jnp.ones((1,), jnp.int32),
        (dst_block[1:] != dst_block[:-1]).astype(jnp.int32)])


def _visited_block_mask(dst_block: jax.Array, n_blocks: int,
                        bn: int) -> jax.Array:
    """(1, N) bool mask of node positions whose destination block is
    touched by at least one tile. Untouched output blocks are never
    initialized by the kernel — ``jnp.where`` forces them to exact zeros
    (a multiply would propagate NaN/Inf garbage instead)."""
    seen = jnp.zeros((n_blocks,), jnp.int32).at[dst_block].set(
        1, mode="drop")
    return (jnp.repeat(seen, bn).reshape(1, -1) > 0)


@functools.partial(jax.jit, static_argnames=("node_block", "interpret"))
def edge_segment_push_blocked(src, dst, src_block, dst_block, x, *,
                              node_block: int, interpret=None):
    """Node-blocked push: ``y[j] = sum_{e in-bucket: dst[e]==j} x[src[e]]``
    for graphs whose node vector does not fit one core's VMEM.

    src, dst: (T*TE,) int32 **global** node ids, bucketed by
    ``(dst_block, src_block)`` and sentinel-padded per bucket so every TE
    tile lives in exactly one bucket; src_block, dst_block: (T,) int32
    per-tile block coordinates (the scalar-prefetch dispatch tables);
    x: (1, N) with N % node_block == 0. Tiles must be sorted
    dst-block-major (``_first_visit`` contract).

    An edge contributes only when its stored id still lies inside its
    tile's assigned block — a corrupted id (or block coordinate) drops or
    reroutes the edge instead of gathering out of bounds; block
    coordinates are clipped to the valid range so a struck dispatch table
    can never address memory outside the node vector.
    """
    bn = node_block
    _, n = x.shape
    t = src_block.shape[0]
    assert n % bn == 0, (n, bn)
    assert src.shape[0] % t == 0, (src.shape[0], t)
    te = src.shape[0] // t
    n_blocks = n // bn
    sb = jnp.clip(src_block.astype(jnp.int32), 0, n_blocks - 1)
    db = jnp.clip(dst_block.astype(jnp.int32), 0, n_blocks - 1)
    first = _first_visit(db)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, te), lambda i, sbr, dbr, fr: (0, i)),
            pl.BlockSpec((1, te), lambda i, sbr, dbr, fr: (0, i)),
            pl.BlockSpec((1, bn), lambda i, sbr, dbr, fr: (0, sbr[i])),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i, sbr, dbr, fr: (0, dbr[i])),
    )
    y = pl.pallas_call(
        functools.partial(_blocked_push_kernel, bn=bn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, n), x.dtype),
        interpret=interpret_mode(interpret),
    )(sb, db, first, src.reshape(1, t * te), dst.reshape(1, t * te), x)
    return jnp.where(_visited_block_mask(db, n_blocks, bn), y, 0.0)


def edge_segment_push_blocked_oracle(src, dst, src_block, dst_block, x, *,
                                     node_block: int):
    """jnp oracle replaying the blocked kernel's exact per-tile math and
    dst-block accumulation order — the few-ulp reference. Not jit'd, for
    the same reason as ``edge_segment_push_oracle``."""
    bn = node_block
    _, n = x.shape
    t = src_block.shape[0]
    te = src.shape[0] // t
    n_blocks = n // bn
    src2 = src.reshape(t, te)
    dst2 = dst.reshape(t, te)
    sb_all = jnp.clip(src_block.astype(jnp.int32), 0, n_blocks - 1)
    db_all = jnp.clip(dst_block.astype(jnp.int32), 0, n_blocks - 1)
    y = jnp.zeros_like(x)
    for i in range(t):
        sb = sb_all[i]
        db = int(db_all[i])
        xb = jax.lax.dynamic_slice(x, (0, int(sb) * bn), (1, bn))
        tile = _push_block_local(src2[i:i + 1] - sb * bn,
                                 dst2[i:i + 1] - db_all[i] * bn, xb, bn)
        y = y.at[:, db * bn:(db + 1) * bn].add(tile)
    return jnp.where(_visited_block_mask(db_all, n_blocks, bn), y, 0.0)


def edge_segment_push_blocked_ref(src, dst, src_block, dst_block, x, *,
                                  node_block: int):
    """Independent ``jax.ops.segment_sum`` reference for the blocked
    semantics (allclose, not bit-equal): an edge contributes iff its
    stored src *and* dst ids lie inside the blocks its tile is assigned
    to — out-of-bucket ids (sentinel padding, corrupted/negative indices)
    drop the edge, matching the kernel's block-local one-hot."""
    bn = node_block
    n = x.shape[1]
    t = src_block.shape[0]
    te = src.shape[0] // t
    n_blocks = n // bn
    sb = jnp.repeat(jnp.clip(src_block.astype(jnp.int32), 0, n_blocks - 1),
                    te)
    db = jnp.repeat(jnp.clip(dst_block.astype(jnp.int32), 0, n_blocks - 1),
                    te)
    src_ok = (src >= sb * bn) & (src < (sb + 1) * bn)
    dst_ok = (dst >= db * bn) & (dst < (db + 1) * bn)
    contrib = jnp.where(src_ok, x[0, jnp.clip(src, 0, n - 1)], 0.0)
    seg = jnp.where(dst_ok, dst, n)              # out-of-bucket -> bin n
    return jax.ops.segment_sum(contrib, seg,
                               num_segments=n + 1)[:n].reshape(1, n)


# ------------------------------------------------------- BFS frontier step
def _frontier_kernel(pushed_ref, visited_ref, dist_ref, level_ref,
                     frontier_out, visited_out, dist_out):
    pushed = pushed_ref[...]
    visited = visited_ref[...]
    dist = dist_ref[...]
    level = level_ref[...]                       # (1, 1), broadcasts
    newly = ((pushed > 0) & (visited == 0)).astype(jnp.int32)
    frontier_out[...] = newly
    visited_out[...] = visited | newly
    dist_out[...] = jnp.where(newly > 0, level.astype(jnp.int32), dist)


@functools.partial(jax.jit, static_argnames=("block_nodes", "interpret"))
def frontier_update(pushed, visited, dist, level, *,
                    block_nodes: int = 1024, interpret=None):
    """BFS step: nodes reached by ``pushed`` frontier mass and not yet
    visited become the next frontier, stamped with ``level`` in ``dist``.

    pushed (1, N) f32; visited/dist (1, N) int32; level int32 scalar.
    Returns (frontier, visited, dist), all (1, N) int32.
    """
    _, n = pushed.shape
    assert n % NODE_LANES == 0, n
    # largest lane-multiple block <= block_nodes that divides n (NODE_LANES
    # always does, so this terminates)
    bn = max(NODE_LANES, min(block_nodes, n) // NODE_LANES * NODE_LANES)
    while n % bn:
        bn -= NODE_LANES
    node_spec = pl.BlockSpec((1, bn), lambda i: (0, i))
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    outs = tuple(jax.ShapeDtypeStruct((1, n), jnp.int32) for _ in range(3))
    return pl.pallas_call(
        _frontier_kernel,
        grid=(n // bn,),
        in_specs=[node_spec] * 3 + [scalar_spec],
        out_specs=(node_spec,) * 3,
        out_shape=outs,
        interpret=interpret_mode(interpret),
    )(pushed, visited.astype(jnp.int32), dist.astype(jnp.int32),
      jnp.asarray(level, jnp.int32).reshape(1, 1))


def frontier_update_oracle(pushed, visited, dist, level):
    """jnp oracle for ``frontier_update`` (bit-equivalence reference)."""
    visited = visited.astype(jnp.int32)
    dist = dist.astype(jnp.int32)
    newly = ((pushed > 0) & (visited == 0)).astype(jnp.int32)
    return (newly, visited | newly,
            jnp.where(newly > 0, jnp.int32(level), dist))
