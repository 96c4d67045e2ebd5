"""Graph-mining workload: generator, Pallas segment-sum/BFS kernels
(bit-equivalence vs oracles), PageRank convergence under injection, and
MemoryDomain region wiring."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MemoryDomain, Tier, detect_recover, detect_recover_l
from repro.core.errormodel import InjectionPlan
from repro.graph import (bfs, bfs_reference, graph_state, n_padded,
                         pagerank, powerlaw_graph, top_k)
from repro.kernels.segsum import (edge_segment_push,
                                  edge_segment_push_oracle,
                                  edge_segment_push_ref, frontier_update,
                                  frontier_update_oracle, pad_edges)

# The push kernels and their oracles run the same f32 tile math, but the
# kernel's dot and the eager oracle's accumulate in different orders, so
# they agree to a few ulp, not bit for bit (2 ulp measured; 1e-6 is ~8).
# Index, frontier and ECC paths stay exact.
ORACLE_RTOL = 1e-6


@pytest.fixture(scope="module")
def graph():
    return powerlaw_graph(256, avg_degree=6, seed=1)


@pytest.fixture(scope="module")
def state(graph):
    return graph_state(graph, with_bfs=True, source=0)


# ----------------------------------------------------------- generator
def test_powerlaw_csr_valid(graph):
    g = graph
    assert g.indptr[0] == 0 and g.indptr[-1] == g.n_edges
    assert np.all(np.diff(g.indptr) >= 0)
    assert np.all((g.indices >= 0) & (g.indices < g.n))
    assert int(g.out_degree.sum()) == g.n_edges
    # no self loops: row v never contains v
    for v in (0, 1, g.n // 2, g.n - 1):
        row = g.indices[g.indptr[v]:g.indptr[v + 1]]
        assert v not in row


def test_powerlaw_heavy_tail(graph):
    avg = graph.n_edges / graph.n
    assert graph.max_in_degree > 5 * avg     # hubs exist
    assert int(np.diff(graph.indptr).min()) <= 1


def test_generator_deterministic():
    a = powerlaw_graph(64, seed=3)
    b = powerlaw_graph(64, seed=3)
    assert np.array_equal(a.indices, b.indices)
    assert np.array_equal(a.indptr, b.indptr)


# -------------------------------------------------------------- kernels
def test_spmv_bit_equal_oracle():
    rng = np.random.default_rng(0)
    n, e = 384, 1700
    src = jnp.asarray(rng.integers(0, n, e))
    dst = jnp.asarray(rng.integers(0, n, e))
    x = jnp.asarray(rng.random((1, n)), jnp.float32)
    s, d = pad_edges(src, dst, n)
    y = edge_segment_push(s, d, x)
    np.testing.assert_allclose(y, edge_segment_push_oracle(s, d, x),
                               rtol=ORACLE_RTOL, atol=0)


def test_spmv_allclose_segment_sum():
    rng = np.random.default_rng(1)
    n, e = 256, 900
    src = jnp.asarray(rng.integers(0, n, e))
    dst = jnp.asarray(rng.integers(0, n, e))
    x = jnp.asarray(rng.random((1, n)), jnp.float32)
    s, d = pad_edges(src, dst, n)
    y = edge_segment_push(s, d, x)
    assert jnp.allclose(y, edge_segment_push_ref(s, d, x),
                        rtol=1e-5, atol=1e-6)


def test_spmv_corrupted_indices_drop_edges_in_all_backends():
    """Negative / out-of-range ids (bit-flipped topology) drop the edge
    identically in the kernel, the oracle, and the segment_sum ref."""
    n = 128
    src = jnp.asarray([-5, 0, 3, 1 << 20], jnp.int32)
    dst = jnp.asarray([2, -7, 2, 2], jnp.int32)
    x = 10.0 * jnp.ones((1, n), jnp.float32)
    s, d = pad_edges(src, dst, n)
    y = edge_segment_push(s, d, x)
    assert float(y.sum()) == 10.0          # only edge (3 -> 2) survives
    assert bool(jnp.all(y == edge_segment_push_oracle(s, d, x)))
    assert bool(jnp.all(y == edge_segment_push_ref(s, d, x)))


def test_spmv_sentinel_padding_inert():
    n = 128
    src = jnp.asarray([0, 1], jnp.int32)
    dst = jnp.asarray([2, 2], jnp.int32)
    x = jnp.ones((1, n), jnp.float32)
    s, d = pad_edges(src, dst, n)          # pads with sentinel n
    y = edge_segment_push(s, d, x)
    assert float(y[0, 2]) == 2.0
    assert float(y.sum()) == 2.0           # padded slots contribute nothing


def test_nondefault_edge_tile_state_runs(graph):
    """graph_state exposes edge_tile; pagerank/bfs must recover a valid
    grid for whatever padding the state was built with."""
    st = graph_state(graph, with_bfs=True, source=0, edge_tile=256)
    st_def = graph_state(graph, with_bfs=True, source=0)
    _, rank, _ = pagerank(st, graph.n, iters=5)
    _, rank_def, _ = pagerank(st_def, graph.n, iters=5)
    assert jnp.allclose(rank, rank_def, rtol=1e-6, atol=1e-8)
    _, dist = bfs(st, backend="pallas")
    assert bool(jnp.array_equal(dist[0, :graph.n], bfs_reference(graph, 0)))


def test_frontier_kernel_bit_equal():
    rng = np.random.default_rng(2)
    n = 256
    pushed = jnp.asarray(rng.random((1, n)) > 0.7, jnp.float32)
    visited = jnp.asarray(rng.integers(0, 2, (1, n)), jnp.int32)
    dist = jnp.where(visited > 0, 1, -1).astype(jnp.int32)
    got = frontier_update(pushed, visited, dist, 2)
    want = frontier_update_oracle(pushed, visited, dist, 2)
    for a, b in zip(got, want):
        assert bool(jnp.all(a == b))


# ------------------------------------------------------------- pagerank
def test_pagerank_backends_agree(graph, state):
    _, r_pallas, _ = pagerank(state, graph.n, iters=10, backend="pallas")
    _, r_oracle, _ = pagerank(state, graph.n, iters=10, backend="oracle")
    _, r_ref, _ = pagerank(state, graph.n, iters=10, backend="segment_sum")
    np.testing.assert_allclose(r_pallas, r_oracle, rtol=ORACLE_RTOL,
                               atol=0)
    assert jnp.allclose(r_pallas, r_ref, rtol=1e-5, atol=1e-7)


def test_pagerank_is_a_distribution(graph, state):
    _, rank, delta = pagerank(state, graph.n, iters=25)
    assert abs(float(rank.sum()) - 1.0) < 1e-4
    assert float(delta) < 1e-4                      # converged
    assert bool(jnp.all(rank[0, graph.n:] == 0))    # padding stays empty


def test_pagerank_converges_under_injection(graph, state):
    """A soft mantissa flip in the rank iterate self-heals: the damped
    power iteration contracts the perturbation below top-k resolution."""
    _, golden_rank, _ = pagerank(state, graph.n, iters=25)
    golden = top_k(golden_rank, graph.n, 8)
    dom = MemoryDomain.protect({"graph": state}, detect_recover())
    plan = InjectionPlan(np.array([5], np.int32), np.array([18], np.int32),
                        hard=False)
    struck = dom.apply_plan("graph/rank/rank", plan)
    assert not bool(jnp.array_equal(struck.leaf("graph/rank/rank"),
                                    dom.leaf("graph/rank/rank")))
    _, rank2, _ = pagerank(struck.payload["graph"], graph.n, iters=25)
    assert bool(jnp.isfinite(rank2).all())
    assert bool(jnp.array_equal(top_k(rank2, graph.n, 8), golden))


def test_topology_strike_scrubbed_to_golden(graph, state):
    """Under D&R/L the CSR topology sits on SEC-DED: a single-bit strike
    is corrected before it can rewire edges."""
    dom = MemoryDomain.protect({"graph": state}, detect_recover_l())
    _, golden_rank, _ = pagerank(dom.payload["graph"], graph.n, iters=10)
    struck, _ = dom.inject(np.random.default_rng(7), 1,
                           paths=["graph/topology/src"])
    fixed, report = struck.scrub()
    assert report.totals()[0] >= 1
    _, rank, _ = pagerank(fixed.payload["graph"], graph.n, iters=10)
    assert bool(jnp.all(rank == golden_rank))


# ------------------------------------------------------------------ BFS
def test_bfs_matches_reference(graph, state):
    _, dist = bfs(state, backend="pallas")
    ref = bfs_reference(graph, 0)
    assert bool(jnp.array_equal(dist[0, :graph.n], ref))


def test_bfs_backends_agree(graph, state):
    _, d1 = bfs(state, backend="pallas")
    _, d2 = bfs(state, backend="oracle")
    assert bool(jnp.array_equal(d1, d2))


def test_bfs_padded_size_not_multiple_of_block():
    """n_pad=1408 is a lane multiple but not a multiple of the default
    1024-node frontier block — the kernel must pick a dividing block."""
    g = powerlaw_graph(1300, avg_degree=4, seed=9)
    st = graph_state(g, with_bfs=True, source=0)
    assert st["frontier"]["dist"].shape[1] % 1024 != 0
    _, dist = bfs(st, backend="pallas")
    ref = bfs_reference(g, 0)
    assert bool(jnp.array_equal(dist[0, :g.n], ref))


# --------------------------------------------------------------- domain
def test_graph_regions_and_tiers(graph, state):
    dom = MemoryDomain.protect({"graph": state}, detect_recover_l())
    assert dom.region_of("graph/topology/src") == "graph/topology"
    assert dom.region_of("graph/rank/rank") == "graph/rank"
    assert dom.region_of("graph/frontier/dist") == "graph/frontier"
    assert dom.tier_of("graph/topology/dst") is Tier.SECDED
    assert dom.tier_of("graph/rank/rank") is Tier.PARITY_R
    assert dom.tier_of("graph/frontier/visited") is Tier.PARITY_R
    frac = dom.region_profile().fractions
    assert abs(sum(frac.values()) - 1.0) < 1e-9
    assert frac["graph/topology"] > frac["graph/rank"]
    assert n_padded(state) % 128 == 0
