"""Node-blocked graph plane: bucketed CSR layout, blocked push kernel
(property-tested against its jnp oracle and the segment_sum ref, including
sentinel padding and corrupted indices), frontier-sparse BFS equivalence,
the fori PageRank pin, fit_edge_tile, and the incremental scrub cursor."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import MemoryDomain, detect_recover_l, typical_server
from repro.graph import (bfs, bfs_reference, bfs_scrubbed, bucket_edges,
                         graph_state, node_block_of, pagerank,
                         pagerank_scrubbed, powerlaw_graph, top_k)
from repro.graph.bfs import active_src_blocks
from repro.graph.pagerank import _pagerank_fori, _region_paths, _step_math
from repro.kernels.segsum import (EDGE_TILE, NODE_LANES, TILES_PER_STEP,
                                  edge_segment_push_blocked,
                                  edge_segment_push_blocked_oracle,
                                  edge_segment_push_blocked_ref,
                                  fit_edge_tile)

# Kernel vs oracle: the same f32 tile math accumulated in different orders
# agrees to a few ulp (2 measured; 1e-6 is ~8), not bit for bit.
ORACLE_RTOL = 1e-6


@pytest.fixture(scope="module")
def graph():
    return powerlaw_graph(500, avg_degree=6, seed=2)


@pytest.fixture(scope="module")
def blocked_state(graph):
    return graph_state(graph, with_bfs=True, source=0, node_block=128,
                       edge_tile=128)


def _random_blocked(seed, n, e, bn, te, corrupt=False):
    """Random bucketed edge arrays (+ optional post-bucketing corruption
    of ids and dispatch tables — the struck-topology shape)."""
    rng = np.random.default_rng(seed)
    n_pad = ((max(n, 1) + bn - 1) // bn) * bn
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    bsrc, bdst, tsb, tdb = bucket_edges(src, dst, n_pad, bn, edge_tile=te)
    if corrupt:
        bsrc, bdst = bsrc.copy(), bdst.copy()
        tsb, tdb = tsb.copy(), tdb.copy()
        for _ in range(4):  # ids anywhere, incl. negative / far out
            bsrc[rng.integers(0, bsrc.size)] = rng.integers(-n_pad, 4 * n_pad)
            bdst[rng.integers(0, bdst.size)] = rng.integers(-n_pad, 4 * n_pad)
        tsb[rng.integers(0, tsb.size)] = rng.integers(-8, 8 + n_pad // bn)
    x = jnp.asarray(rng.random((1, n_pad)), jnp.float32)
    return (jnp.asarray(bsrc), jnp.asarray(bdst), jnp.asarray(tsb),
            jnp.asarray(tdb), x)


# ----------------------------------------------------- bucketed layout
def test_bucket_edges_preserves_and_sorts():
    rng = np.random.default_rng(0)
    n_pad, bn, te = 512, 128, 128
    src = rng.integers(0, 500, 1000)
    dst = rng.integers(0, 500, 1000)
    bsrc, bdst, tsb, tdb = bucket_edges(src, dst, n_pad, bn, edge_tile=te)
    assert bsrc.shape[0] == tsb.shape[0] * te
    assert np.all(np.diff(tdb) >= 0)             # dst-block-major
    real = bsrc < n_pad
    assert np.sum(real) == 1000                  # every edge kept once
    assert sorted(zip(bsrc[real], bdst[real])) == sorted(zip(src, dst))
    # every real edge lies in its tile's assigned blocks
    sb_e = np.repeat(tsb, te)
    db_e = np.repeat(tdb, te)
    assert np.all(bsrc[real] // bn == sb_e[real])
    assert np.all(bdst[real] // bn == db_e[real])
    # sentinel is block-local out of range for every block
    assert np.all(bsrc[~real] == n_pad)
    assert tsb.shape[0] % TILES_PER_STEP == 0     # whole push steps


def test_bucket_edges_whole_push_steps():
    """Three buckets of one 512-edge tile each round up to one grid step
    of the push, TILES_PER_STEP tiles: the added tiles are all sentinel,
    keep the dst-block-major order and repeat the last tile's blocks."""
    n_pad, bn = 384, 128
    src, dst = np.array([0, 130, 260]), np.array([0, 0, 300])
    bsrc, bdst, tsb, tdb = bucket_edges(src, dst, n_pad, bn, edge_tile=512)
    extra = TILES_PER_STEP - 3
    assert tsb.tolist() == [0, 1] + [2] * (1 + extra)
    assert tdb.tolist() == [0, 0] + [2] * (1 + extra)
    assert bsrc.shape[0] == TILES_PER_STEP * 512
    assert np.all(bsrc[3 * 512:] == n_pad) and np.all(bdst[3 * 512:] == n_pad)
    y = edge_segment_push_blocked(jnp.asarray(bsrc), jnp.asarray(bdst),
                                  jnp.asarray(tsb), jnp.asarray(tdb),
                                  jnp.ones((1, n_pad), jnp.float32),
                                  node_block=bn)
    assert float(y.sum()) == 3.0


def test_bucket_edges_degenerate_empty():
    bsrc, bdst, tsb, tdb = bucket_edges(np.array([], np.int64),
                                        np.array([], np.int64), 256, 128)
    assert bsrc.shape[0] % tsb.shape[0] == 0
    assert np.all(bsrc == 256)                   # one all-sentinel tile
    y = edge_segment_push_blocked(jnp.asarray(bsrc), jnp.asarray(bdst),
                                  jnp.asarray(tsb), jnp.asarray(tdb),
                                  jnp.ones((1, 256), jnp.float32),
                                  node_block=128)
    assert float(jnp.abs(y).sum()) == 0.0


def test_node_block_marker(graph, blocked_state):
    assert node_block_of(blocked_state) == 128
    assert node_block_of(graph_state(graph)) is None
    with pytest.raises(ValueError):
        graph_state(graph, node_block=100)       # not a lane multiple


# ------------------------------------------------------ blocked kernel
@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(3, 300),
       e=st.integers(1, 400), bni=st.sampled_from((128, 256)),
       te=st.sampled_from((128, 256)),
       corrupt=st.booleans())
def test_blocked_push_matches_oracle_and_ref(seed, n, e, bni, te, corrupt):
    """Property: the blocked Pallas kernel matches its jnp oracle to a few
    ulp and is allclose to the blocked segment_sum ref over random
    bucketed graphs — with and without post-bucketing corruption of edge
    ids and dispatch tables (drop/reroute semantics)."""
    args = _random_blocked(seed, n, e, bni, te, corrupt=corrupt)
    y = edge_segment_push_blocked(*args, node_block=bni)
    yo = edge_segment_push_blocked_oracle(*args, node_block=bni)
    yr = edge_segment_push_blocked_ref(*args, node_block=bni)
    np.testing.assert_allclose(y, yo, rtol=ORACLE_RTOL, atol=0)
    assert jnp.allclose(y, yr, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("bn", [1024, 8192])
def test_blocked_push_row_lane_id_edges(bn):
    """The kernel splits a block-local id l into row l >> 7 and lane
    l & 127. Edges on the id edges 0, 127, 128, BN-1 (kept) and BN, -1
    (outside the tile's block: dropped), among ids spread over every row,
    in groups of tiles that span up to three destination blocks and leave
    one block unvisited, match the oracle to a few ulp and the segment_sum
    ref."""
    rng = np.random.default_rng(bn)
    te, n_blocks = 256, 4
    edge_ids = np.array([0, 127, 128, bn - 1, bn, -1])
    db = np.array([0, 0, 1, 2, 2, 2, 2, 2, 2, 2, 2, 2], np.int32)
    sb = rng.integers(0, n_blocks, db.size).astype(np.int32)
    src = rng.integers(0, bn, (db.size, te))
    dst = rng.integers(0, bn, (db.size, te))
    src[:, :edge_ids.size] = edge_ids
    dst[:, :edge_ids.size] = np.roll(edge_ids, 1)
    src[:, edge_ids.size:2 * edge_ids.size] = edge_ids[::-1]
    dst[:, edge_ids.size:2 * edge_ids.size] = edge_ids
    src = (src + sb[:, None] * bn).astype(np.int32).reshape(-1)
    dst = (dst + db[:, None] * bn).astype(np.int32).reshape(-1)
    x = jnp.asarray(rng.random((1, n_blocks * bn)), jnp.float32)
    args = (jnp.asarray(src), jnp.asarray(dst), jnp.asarray(sb),
            jnp.asarray(db), x)
    y = edge_segment_push_blocked(*args, node_block=bn)
    yo = edge_segment_push_blocked_oracle(*args, node_block=bn)
    yr = edge_segment_push_blocked_ref(*args, node_block=bn)
    np.testing.assert_allclose(y, yo, rtol=ORACLE_RTOL, atol=0)
    assert jnp.allclose(y, yr, rtol=1e-5, atol=1e-6)
    assert bool(jnp.all(y[0, 3 * bn:] == 0))     # block 3 is never visited
    kept = (src % bn == src - np.repeat(sb, te) * bn) & \
        (dst % bn == dst - np.repeat(db, te) * bn)
    assert 0 < kept.sum() < src.size              # some edges drop
    np.testing.assert_allclose(float(y.sum()),
                               float(jnp.sum(x[0, src[kept]])), rtol=1e-5)


def test_blocked_push_matches_dense_push(graph):
    """Same graph, both layouts: the blocked kernel computes the same push
    as the dense single-kernel path (different summation order)."""
    from repro.graph.pagerank import _push
    dense = graph_state(graph)
    blocked = graph_state(graph, node_block=128, edge_tile=128)
    x = jnp.asarray(np.random.default_rng(5).random((1, 512)), jnp.float32)
    xb = x[:, :blocked["rank"]["rank"].shape[1]]
    yd = _push(dense["topology"], x[:, :dense["rank"]["rank"].shape[1]],
               "pallas")
    yb = _push(blocked["topology"], xb, "pallas")
    m = min(yd.shape[1], yb.shape[1])
    assert jnp.allclose(yd[:, :m], yb[:, :m], rtol=1e-5, atol=1e-6)


def test_blocked_sentinel_padding_inert():
    n_pad, bn = 256, 128
    bsrc, bdst, tsb, tdb = bucket_edges(np.array([0, 200]),
                                        np.array([200, 0]), n_pad, bn,
                                        edge_tile=128)
    x = jnp.ones((1, n_pad), jnp.float32)
    y = edge_segment_push_blocked(jnp.asarray(bsrc), jnp.asarray(bdst),
                                  jnp.asarray(tsb), jnp.asarray(tdb), x,
                                  node_block=bn)
    assert float(y.sum()) == 2.0                 # only the two real edges


# ------------------------------------------------- pagerank at scale
def test_blocked_pagerank_backends_agree(graph, blocked_state):
    _, rp, _ = pagerank(blocked_state, graph.n, iters=8, backend="pallas")
    _, ro, _ = pagerank(blocked_state, graph.n, iters=8, backend="oracle")
    _, rr, _ = pagerank(blocked_state, graph.n, iters=8,
                        backend="segment_sum")
    np.testing.assert_allclose(rp, ro, rtol=ORACLE_RTOL, atol=0)
    assert jnp.allclose(rp, rr, rtol=1e-5, atol=1e-7)


def test_blocked_pagerank_matches_dense(graph, blocked_state):
    dense = graph_state(graph)
    _, rb, _ = pagerank(blocked_state, graph.n, iters=10)
    _, rd, _ = pagerank(dense, graph.n, iters=10)
    assert jnp.allclose(rb[0, :graph.n], rd[0, :graph.n],
                        rtol=1e-5, atol=1e-7)
    golden = top_k(rd, graph.n, 8)
    assert bool(jnp.array_equal(top_k(rb, graph.n, 8), golden))


def test_fori_pagerank_pin(graph, blocked_state):
    """fori_loop hoisting adds no numeric change: bit-identical to
    iterating the jitted step program; allclose to the un-jitted eager
    loop (XLA fusion perturbs the epilogue ~1 ulp/step)."""
    for state in (blocked_state, graph_state(graph)):
        topo, r = state["topology"], state["rank"]["rank"]
        step = jax.jit(functools.partial(_step_math, n=graph.n,
                                         damping=0.85, backend="pallas"))
        for _ in range(6):
            r = step(topo, r)
        rf, _ = _pagerank_fori(topo, state["rank"]["rank"], n=graph.n,
                               iters=6, damping=0.85, backend="pallas")
        assert bool(jnp.all(r == rf))            # bit-identical
        _, re_, _ = pagerank(state, graph.n, iters=6)
        assert jnp.allclose(rf, re_, rtol=1e-6, atol=1e-8)


# ---------------------------------------------------------------- BFS
def test_bfs_sparse_equals_dense(graph, blocked_state):
    """Frontier-sparse dispatch is exact: skipped tiles would contribute
    exact zeros, so distances bit-match the dense blocked traversal and
    the CSR reference."""
    _, d_sparse = bfs(blocked_state, backend="pallas")       # sparse auto
    _, d_dense = bfs(blocked_state, backend="pallas", sparse=False)
    assert bool(jnp.all(d_sparse == d_dense))
    assert bool(jnp.array_equal(d_sparse[0, :graph.n],
                                bfs_reference(graph, 0)))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(4, 200),
       src=st.integers(0, 3))
def test_bfs_sparse_equals_dense_property(seed, n, src):
    g = powerlaw_graph(n, avg_degree=3, seed=seed)
    st_b = graph_state(g, with_bfs=True, source=src % g.n, node_block=128,
                       edge_tile=128)
    _, d1 = bfs(st_b, backend="pallas")
    _, d2 = bfs(st_b, backend="pallas", sparse=False)
    assert bool(jnp.all(d1 == d2))


def test_active_src_blocks_mask():
    f = jnp.zeros((1, 512), jnp.float32).at[0, 300].set(1.0)
    mask = active_src_blocks(f, 128)
    assert mask.tolist() == [False, False, True, False]


# ------------------------------------------------- incremental scrub
def test_scrub_partial_cycle_equals_full_scrub(blocked_state):
    """K consecutive scrub_partial slices == one monolithic scrub(), bit
    for bit, on payload and sidecar, with the same total corrections."""
    dom = MemoryDomain.protect({"graph": blocked_state}, typical_server())
    struck, _ = dom.inject(11, 5)
    full, rep_full = struck.scrub()
    part, total = struck, 0
    for c in range(5):
        part, rep = part.scrub_partial(c, slices=5)
        total += sum(int(v) for v in rep.corrected.values())
    for a, b in zip(jax.tree_util.tree_leaves(full.payload),
                    jax.tree_util.tree_leaves(part.payload)):
        assert bool(jnp.all(a == b))
    for a, b in zip(jax.tree_util.tree_leaves(full.sidecar),
                    jax.tree_util.tree_leaves(part.sidecar)):
        assert bool(jnp.all(a == b))
    assert total == sum(int(v) for v in rep_full.corrected.values())


def test_scrub_partial_subset_and_single_slice(blocked_state):
    dom = MemoryDomain.protect({"graph": blocked_state}, typical_server())
    paths = _region_paths(dom, ("graph/topology",))
    d1, rep = dom.scrub_partial(0, slices=4, paths=paths)
    assert set(rep.corrected) <= set(paths)
    # slices=1 degenerates to a full scrub of the selection: every
    # selected path is reported (corrected and/or detect-only counters)
    d2, rep2 = dom.scrub_partial(0, slices=1, paths=paths)
    assert set(rep2.corrected) | set(rep2.detected_uncorrectable) == \
        set(paths)


def test_scrubbed_drivers_reproduce_plain_results(graph, blocked_state):
    pol = detect_recover_l()
    dom = MemoryDomain.protect({"graph": blocked_state}, pol)
    dom, rank, _, _ = pagerank_scrubbed(dom, graph.n, iters=5,
                                        scrub_slices=3)
    _, r_plain, _ = pagerank(blocked_state, graph.n, iters=5)
    assert jnp.allclose(rank, r_plain, rtol=1e-6, atol=1e-8)
    dom2 = MemoryDomain.protect({"graph": blocked_state}, pol)
    dom2, dist, _ = bfs_scrubbed(dom2, scrub_slices=3)
    assert bool(jnp.array_equal(dist[0, :graph.n], bfs_reference(graph, 0)))


def test_scrub_partial_corrects_struck_topology(graph, blocked_state):
    """A struck dispatch table is healed once the cursor sweeps its rows —
    by the end of one cycle the blocked run matches the golden rank."""
    dom = MemoryDomain.protect({"graph": blocked_state}, detect_recover_l())
    _, golden, _ = pagerank(dom.payload["graph"], graph.n, iters=8)
    struck, _ = dom.inject(np.random.default_rng(13), 2,
                           paths=[p for p in dom.paths(True)
                                  if "topology" in p])
    part = struck
    for c in range(4):
        part, _ = part.scrub_partial(c, slices=4)
    _, rank, _ = pagerank(part.payload["graph"], graph.n, iters=8)
    assert bool(jnp.all(rank == golden))


# -------------------------------------------------------- fit_edge_tile
def test_fit_edge_tile_matches_descending_scan():
    def legacy(e, max_tile=EDGE_TILE):
        for t in range(min(max_tile, e), 0, -1):
            if e % t == 0:
                return t
        return 1
    for e in list(range(1, 600)) + [1024, 1536, 2048, 9973 * 2, 7919]:
        assert fit_edge_tile(e) == legacy(e), e
    assert fit_edge_tile(0) == 1
    # memoized: same object both calls (lru_cache)
    assert fit_edge_tile.cache_info().hits > 0


# ---------------------------------------------------- generator at scale
def test_vectorized_generator_valid_and_deterministic():
    a = powerlaw_graph(512, seed=4, vectorized=True)
    b = powerlaw_graph(512, seed=4, vectorized=True)
    assert np.array_equal(a.indices, b.indices)
    assert np.array_equal(a.indptr, b.indptr)
    assert a.indptr[0] == 0 and a.indptr[-1] == a.n_edges
    assert np.all((a.indices >= 0) & (a.indices < a.n))
    assert int(a.out_degree.sum()) == a.n_edges
    avg = a.n_edges / a.n
    assert a.max_in_degree > 5 * avg             # heavy tail preserved
    # no self loops survive the vectorized dedupe
    dst_rows = np.repeat(np.arange(a.n), np.diff(a.indptr))
    assert np.all(a.indices != dst_rows)


def test_small_graphs_keep_legacy_edge_stream():
    """Below the vectorization threshold the default path must reproduce
    the legacy per-node loop exactly (pinned explore/test graphs)."""
    d = powerlaw_graph(96, seed=7)
    legacy = powerlaw_graph(96, seed=7, vectorized=False)
    assert np.array_equal(d.indices, legacy.indices)
    assert np.array_equal(d.indptr, legacy.indptr)


# -------------------------------------------------------------- explore
def test_explore_graph_workload_node_block():
    from repro.launch.explore import graph_workload
    w = graph_workload(n_nodes=128, node_block=128)
    assert w.name == "graph"
    assert abs(sum(w.profile.fractions.values()) - 1.0) < 1e-9
