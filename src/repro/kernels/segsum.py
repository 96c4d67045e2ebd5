"""Pallas TPU kernels for tiled-CSR segment-sum SpMV and BFS frontier
updates — the compute core of the graph-mining workload (``repro.graph``).

Data layout: a CSR graph is expanded into edge arrays ``src``/``dst`` of
shape (E,) int32 (``dst`` is the CSR row expansion: edges arrive sorted by
destination), padded to a multiple of the edge tile with the sentinel id
``n_pad`` (matches no node, contributes nothing). Node vectors are (1, N)
with N a multiple of 128 lanes.

``edge_segment_push`` computes ``y[j] = sum_{e: dst[e]==j} x[src[e]]`` for
small graphs — one grid step per edge tile; within a tile both the gather
(``x[src]``) and the scatter-add (segment sum by ``dst``) are flat one-hot
matmuls over all N nodes, at ``HIGHEST`` precision so the TPU does not
round the f32 values to bf16. Each step holds the full (1, N) vector plus
two (N, TE) masks, which caps it at N ~ a few thousand nodes on a
16 MiB-VMEM core (N = 4096 at TE = 512 already needs 16.8 MiB of masks).

``edge_segment_push_blocked`` is the push at scale. A node-block
dimension is added and edges are bucketed by ``(src_block, dst_block)``
at CSR build time (``repro.graph.generate``), so each edge tile gathers
from one BN-node source block and scatter-adds into one BN-node
destination block. Per-tile block coordinates arrive as scalar-prefetch
arrays (``PrefetchScalarGridSpec``): the index maps read
``src_block[i]`` to steer each source block's DMA, the standard Pallas
block-sparse dispatch idiom. Tiles are sorted destination-block-major, so
each output block's sum runs over consecutive tiles (zeroed at the first
visit, ``_first_visit``).

Within a tile the blocked push uses a **two-level one-hot**. A block-local
id l in [0, BN) is split into a row ``l >> 7`` in [0, R), R = BN/128, and
a lane ``l & 127``, and each block is held as an (R, 128) row-by-lane
tile — a view of the (1, N) vector, not a copy. The gather multiplies the
source block by the (128, TE) lane one-hot of the sources, which gives
every row's value at each edge's lane, (R, TE); the (R, TE) row match
picks each edge's row and a sum over rows leaves its value. The scatter
puts each value on its destination row, (R, TE), and multiplies by the
destination lane one-hot, contracting over the tile's edges into the
(R, 128) block. So the masks are O(TE·(R + 128)) elements per tile where
a flat one-hot over the block would be O(TE·BN) — 0.2 M against 8.4 M
at BN = 8192, TE = 512 — and the MXU sees a full operand of R or 128 rows
by TE, not a single (1, BN) row per weight tile. Both matmuls take bf16
operands: the one-hots are exact, and the float32 side is passed as three
bf16 parts whose sum is exact (``_split3``), so ranks stay float32 with
no rounding in the moves, at half the MXU passes of an f32 ``HIGHEST``
matmul. An id outside [0, BN) — the sentinel, a negative id, or an id
outside the tile's assigned block — has its row outside [0, R), matches
no row and drops.

Each grid step takes 8 consecutive edge tiles (fewer only where the tile
count is not a multiple of 8, which ``bucket_edges`` avoids), with one
edge DMA for all of them and one source block DMA each, and computes
them before summing any, so their matmul chains overlap. The
destination block being summed stays in a VMEM accumulator and is
copied out when the next block's first tile arrives. VMEM per step is
O(BN + TE·128) per tile it takes, independent of N: the node blocks are
32 KiB each at BN = 8192.

``frontier_update`` is the elementwise BFS step (threshold pushed mass,
mask visited, stamp the level into ``dist``), tiled over node blocks.

``*_oracle`` functions replay the identical tile/accumulation order in
plain jnp: the Pallas kernels agree with them to a few f32 ulp (the dots
accumulate in their own order; ``tests/test_graph.py``), and both are
allclose to the ``jax.ops.segment_sum`` reference (different summation
order). No dynamic indexing touches the one-hot math, so the same bodies
run under ``interpret=True`` on CPU.
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
# The flat push's one-hot gather/scatter must move f32 ranks without
# rounding them to bf16, which the TPU's default matmul precision would do.
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
_LANE_BITS = 7           # log2(NODE_LANES): row l >> 7, lane l & 127 of id l
TILES_PER_STEP = 8       # edge tiles a grid step of the blocked push takes
# dot_general contracting both operands' last axis: (M, TE) x (N, TE) -> (M, N)
_CONTRACT_EDGES = (((1,), (1,)), ((), ()))


def _lane_onehot(ids):
    """(128, TE) bf16 one-hot of each id's lane: [c, e] = (ids[e] & 127 == c).
    Every id has a lane, so this side never drops an edge."""
    te = ids.shape[1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (NODE_LANES, te), 0)
    return (lanes == (ids & (NODE_LANES - 1))).astype(jnp.bfloat16)


def _row_match(ids, rows: int):
    """(rows, TE) bool: [r, e] = (ids[e] >> 7 == r). An id below 0 or at or
    above ``rows * 128`` has its row outside [0, rows) and matches none."""
    te = ids.shape[1]
    return (jax.lax.broadcasted_iota(jnp.int32, (rows, te), 0)
            == (ids >> _LANE_BITS))


def _split3(v):
    """Three bf16 parts whose float32 sum is exactly ``v``: 8 significant
    bits each cover float32's 24, so a one-hot matmul of each part at the
    MXU's bf16 rate moves ``v`` without rounding it."""
    hi = v.astype(jnp.bfloat16)
    rest = v - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _push_block_local(src, dst, xb):
    """One edge tile against one (src_block, dst_block) pair, by the
    two-level one-hot: ids are *block-local*, l = 128 * row + lane, and the
    source and destination blocks are (BN/128, 128) row-by-lane tiles.

    src/dst: (1, TE) int32; xb: (R, 128) float32 with R = BN/128.
    Returns the tile's (R, 128) contribution to the destination block.

    Gather: ``z = xb @ lane_onehot(src)`` is (R, TE), z[r, e] = xb[r,
    lane(e)]; the row mask then keeps z[row(e), e] and a sum over rows
    gives each edge's value. Scatter: the values masked onto their
    destination rows, (R, TE), times the destination lane one-hot, summed
    over the tile's edges, is the (R, 128) block. Both matmuls take bf16
    operands: the one-hots are exact, and the float32 side goes through
    as its ``_split3`` parts, so ranks move without rounding. Ids outside
    [0, BN), the sentinel or an id that no longer lies in the tile's
    assigned block, match no row and drop."""
    rows = xb.shape[0]
    lanes_src = _lane_onehot(src)
    hi, mid, lo = (jnp.dot(p, lanes_src, preferred_element_type=jnp.float32)
                   for p in _split3(xb))
    z = hi + mid + lo                                        # (R, TE)
    contrib = jnp.sum(jnp.where(_row_match(src, rows), z, 0.0), axis=0,
                      keepdims=True)                         # (1, TE)
    rows_dst = _row_match(dst, rows)
    lanes_dst = _lane_onehot(dst)
    hi, mid, lo = (jax.lax.dot_general(
        jnp.where(rows_dst, p.astype(jnp.float32), 0.0).astype(jnp.bfloat16),
        lanes_dst, _CONTRACT_EDGES, preferred_element_type=jnp.float32)
        for p in _split3(contrib))
    return hi + mid + lo                                     # (R, 128)


def _tiles_per_step(t: int) -> int:
    """Edge tiles per grid step: the largest power of two up to
    ``TILES_PER_STEP`` that divides the tile count ``t``."""
    g = TILES_PER_STEP
    while t % g:
        g //= 2
    return g


def _blocked_push_kernel(sb_ref, db_ref, first_ref, src_ref, dst_ref,
                         *refs, bn: int, te: int, g: int):
    """One grid step: ``g`` consecutive edge tiles, each with its own
    source block (``refs[:g]``). The destination block being summed lives
    in the VMEM accumulator ``acc`` and is copied to ``y_hbm`` when the
    next block's first tile arrives, and after the last step."""
    x_refs, (y_hbm, acc, sem) = refs[:g], refs[g:]
    i = pl.program_id(0)

    def flush(block):
        copy = pltpu.make_async_copy(acc, y_hbm.at[pl.ds(block, 1)], sem)
        copy.start()
        copy.wait()

    # the step's tiles are independent until they are summed, so compute
    # them all first and their matmul chains overlap
    tiles = []
    for j in range(g):
        t = i * g + j
        edges = slice(j * te, (j + 1) * te)
        tiles.append(_push_block_local(                # block-local ids
            src_ref[:, edges] - sb_ref[t] * bn,
            dst_ref[:, edges] - db_ref[t] * bn, x_refs[j][0]))
    for j, tile in enumerate(tiles):
        t = i * g + j

        @pl.when(first_ref[t] == 1)
        def _init():
            @pl.when(t > 0)
            def _flush_previous():
                flush(db_ref[t - 1])
            acc[...] = jnp.zeros_like(acc)

        acc[0] += tile

    @pl.when(i == pl.num_programs(0) - 1)
    def _last():
        flush(db_ref[i * g + g - 1])


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
    assert n % bn == 0 and bn % NODE_LANES == 0, (n, bn)
    assert src.shape[0] % t == 0, (src.shape[0], t)
    te = src.shape[0] // t
    n_blocks = n // bn
    rows = bn // NODE_LANES
    sb = jnp.clip(src_block.astype(jnp.int32), 0, n_blocks - 1)
    db = jnp.clip(dst_block.astype(jnp.int32), 0, n_blocks - 1)
    first = _first_visit(db)
    g = _tiles_per_step(t)

    def x_spec(j):                                # tile j of each step
        return pl.BlockSpec((1, rows, NODE_LANES),
                            lambda i, sbr, dbr, fr: (sbr[i * g + j], 0, 0))

    edge_spec = pl.BlockSpec((1, g * te), lambda i, sbr, dbr, fr: (0, i))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(t // g,),
        in_specs=[edge_spec, edge_spec] + [x_spec(j) for j in range(g)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((1, rows, NODE_LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
    )
    # (n_blocks, rows, 128) is a view of the (1, N) vector in the same
    # order, whose last two axes are whole blocks for any node block
    xv = x.reshape(n_blocks, rows, NODE_LANES)
    y = pl.pallas_call(
        functools.partial(_blocked_push_kernel, bn=bn, te=te, g=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(xv.shape, x.dtype),
        interpret=interpret_mode(interpret),
    )(sb, db, first, src.reshape(1, t * te), dst.reshape(1, t * te),
      *[xv] * g)
    return jnp.where(_visited_block_mask(db, n_blocks, bn), y.reshape(1, n),
                     0.0)


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
    rows = bn // NODE_LANES
    y = jnp.zeros_like(x)
    for i in range(t):
        sb = sb_all[i]
        db = int(db_all[i])
        xb = jax.lax.dynamic_slice(x, (0, int(sb) * bn), (1, bn))
        tile = _push_block_local(src2[i:i + 1] - sb * bn,
                                 dst2[i:i + 1] - db_all[i] * bn,
                                 xb.reshape(rows, NODE_LANES))
        y = y.at[:, db * bn:(db + 1) * bn].add(tile.reshape(1, bn))
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
