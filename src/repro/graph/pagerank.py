"""PageRank over the tiled-CSR payload — push SpMV per iteration via the
Pallas segment-sum kernels (``repro.kernels.segsum``), with a
``jax.ops.segment_sum`` reference path and an eager jnp oracle that
replays the kernel's tile math (agreement to a few f32 ulp).

The iterate is the classic damped power iteration

    rank' = (1-d)/n + d * (push(rank/outdeg) + dangling_mass/n)

restricted to the real (unpadded) nodes. The rank vector is linear in its
own perturbations and the damping factor contracts them by ``d`` per
iteration, so soft errors in ``graph/rank`` decay geometrically — the
paper's "iterative algorithms self-heal" observation, measurable here as
MASKED outcomes in the Fig.2 campaign. Errors in ``graph/topology``
(``src``/``dst``/``outdeg``/block-dispatch tables) rewire or drop edges
instead and push the stationary distribution itself: they surface as
INCORRECT top-k responses, which is why the explorer's HRM points put the
topology on a stronger tier.

States built with ``graph_state(..., node_block=BN)`` route through the
node-blocked kernel automatically (``node_block_of`` reads the layout
marker), so the same ``pagerank``/``bfs`` API runs graphs that don't fit
one core's VMEM. Two execution shapes ride on top:

  * ``fori=True`` moves the Python-level power-iteration loop onto
    ``jax.lax.fori_loop`` inside one jit program — one device dispatch
    for the whole run instead of O(iters) host round-trips. Pinned
    bit-identical to iterating the jitted step program (hoisting the
    loop adds no numeric change); the *un-jitted* eager loop can differ
    by ~1 ulp/step from XLA fusion, so it is compared allclose.
  * ``pagerank_scrubbed`` interleaves incremental scrub slices
    (``MemoryDomain.scrub_partial``) of the topology+rank regions between
    iterations, so a full protection pass completes every
    ``scrub_slices`` iterations without a monolithic scrub stall on the
    critical path.

``pagerank_eval_fn`` adapts the workload to ``run_campaign``: the "query
response" is the top-k node ranking (an int array, like the LM's greedy
tokens), with non-finite ranks flagged as a crash via the -1 marker.
"""
from __future__ import annotations

import functools
from typing import Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.graph.generate import node_block_of
from repro.kernels.segsum import (NODE_LANES, edge_segment_push,
                                  edge_segment_push_blocked,
                                  edge_segment_push_blocked_oracle,
                                  edge_segment_push_blocked_ref,
                                  edge_segment_push_oracle,
                                  edge_segment_push_ref, fit_edge_tile)

BACKENDS = ("pallas", "oracle", "segment_sum")


def _push(topo: dict, x, backend: str):
    """Push SpMV over a topology group, routing dense states through the
    single-kernel path and node-blocked states (a ``blocks`` dispatch
    table is present) through the blocked kernel — same backend names,
    same drop-on-corruption semantics per layout."""
    src, dst = topo["src"], topo["dst"]
    blocks = topo.get("blocks")
    if blocks is not None:
        bn = int(blocks["bn_lanes"].shape[0]) * NODE_LANES
        sb, db = blocks["src_block"], blocks["dst_block"]
        if backend == "pallas":
            return edge_segment_push_blocked(src, dst, sb, db, x,
                                             node_block=bn)
        if backend == "oracle":
            return edge_segment_push_blocked_oracle(src, dst, sb, db, x,
                                                    node_block=bn)
        if backend == "segment_sum":
            return edge_segment_push_blocked_ref(src, dst, sb, db, x,
                                                 node_block=bn)
        raise ValueError(f"backend {backend!r} not in {BACKENDS}")
    # the state's edge arrays may have been padded with any edge_tile;
    # recover a dividing tile rather than assuming the default
    tile = fit_edge_tile(src.shape[0])
    if backend == "pallas":
        return edge_segment_push(src, dst, x, edge_tile=tile)
    if backend == "oracle":
        return edge_segment_push_oracle(src, dst, x, edge_tile=tile)
    if backend == "segment_sum":
        return edge_segment_push_ref(src, dst, x)
    raise ValueError(f"backend {backend!r} not in {BACKENDS}")


def _step_math(topo: dict, rank, n: int, damping: float, backend: str):
    """One power iteration on the rank vector — the single definition both
    the eager loop and the fori path trace, so they stay bit-identical."""
    n_pad = rank.shape[1]
    real = (jnp.arange(n_pad) < n).reshape(1, n_pad)
    outdeg = topo["outdeg"].astype(jnp.float32)
    contrib = jnp.where(real & (outdeg > 0),
                        rank / jnp.maximum(outdeg, 1.0), 0.0)
    pushed = _push(topo, contrib, backend)
    dangling = jnp.sum(jnp.where(real & (outdeg <= 0), rank, 0.0))
    new = jnp.where(real,
                    (1.0 - damping) / n
                    + damping * (pushed + dangling / n), 0.0)
    return new.astype(jnp.float32)


def pagerank_step(state: dict, n: int, *, damping: float = 0.85,
                  backend: str = "pallas") -> dict:
    """One power iteration; returns the state with ``rank`` replaced."""
    new = _step_math(state["topology"], state["rank"]["rank"], n, damping,
                     backend)
    return {**state, "rank": {"rank": new}}


@functools.partial(jax.jit,
                   static_argnames=("n", "iters", "damping", "backend"))
def _pagerank_fori(topo: dict, rank0, *, n: int, iters: int,
                   damping: float, backend: str):
    """The whole power iteration as one ``jax.lax.fori_loop`` program:
    carries (rank, prev_rank) so the final L1 delta needs no extra step."""
    def body(_, carry):
        rank, _prev = carry
        return _step_math(topo, rank, n, damping, backend), rank

    return jax.lax.fori_loop(0, iters, body, (rank0, rank0))


def pagerank(state: dict, n: int, *, iters: int = 20,
             damping: float = 0.85, backend: str = "pallas",
             fori: bool = False) -> Tuple[dict, jax.Array, jax.Array]:
    """Run ``iters`` power iterations from the state's current rank.

    ``fori=True`` runs the loop as one jitted ``fori_loop`` program (no
    per-iteration host dispatch; bit-identical to iterating the jitted
    step, ~1 ulp/step from the un-jitted loop via XLA fusion); the
    default eager loop is kept as the op-by-op oracle.

    Returns (final state, rank (1, n_pad), L1 delta of the last step).
    """
    if fori:
        rank, prev = _pagerank_fori(state["topology"],
                                    state["rank"]["rank"], n=n,
                                    iters=iters, damping=damping,
                                    backend=backend)
        delta = jnp.sum(jnp.abs(rank - prev))
        return {**state, "rank": {"rank": rank}}, rank, delta
    prev = state["rank"]["rank"]
    for _ in range(iters):
        prev = state["rank"]["rank"]
        state = pagerank_step(state, n, damping=damping, backend=backend)
    delta = jnp.sum(jnp.abs(state["rank"]["rank"] - prev))
    return state, state["rank"]["rank"], delta


def _region_paths(domain, regions: Iterable[str]):
    want = set(regions)
    return [p for p in domain.paths(protected_only=True)
            if domain.region_of(p) in want]


def pagerank_scrubbed(domain, n: int, *, iters: int = 20,
                      damping: float = 0.85, backend: str = "pallas",
                      scrub_slices: int = 8,
                      regions: Iterable[str] = ("graph/topology",
                                                "graph/rank")):
    """Power iteration with protection overlapped off the critical path:
    after each iteration the rank sidecar is re-encoded (it was
    legitimately rewritten) and one incremental scrub slice
    (``MemoryDomain.scrub_partial``) of the topology+rank regions runs —
    a full scrub pass completes every ``scrub_slices`` iterations with
    only ~1/scrub_slices of a monolithic pass added per iteration.

    ``domain`` must protect a ``{"graph": graph_state(...)}`` payload.
    Each iteration is a ``graph.iteration`` span holding ``graph.step``,
    ``graph.rank_encode`` and ``graph.scrub_slice``.
    Returns (domain, rank (1, n_pad), L1 delta, merged ScrubReport).
    """
    from repro.core.sidecar import ScrubReport
    paths = _region_paths(domain, regions)
    corrected: dict = {}
    uncorrectable: dict = {}
    prev = domain.payload["graph"]["rank"]["rank"]
    for it in range(iters):
        with TraceAnnotation("graph.iteration", it=it):
            prev = domain.payload["graph"]["rank"]["rank"]
            with TraceAnnotation("graph.step"):
                state = pagerank_step(domain.payload["graph"], n,
                                      damping=damping, backend=backend)
            with TraceAnnotation("graph.rank_encode"):
                domain = domain.refresh({**domain.payload, "graph": state},
                                        paths=["graph/rank/rank"])
            with TraceAnnotation("graph.scrub_slice"):
                domain, rep = domain.scrub_partial(it, slices=scrub_slices,
                                                   paths=paths)
            for k, v in rep.corrected.items():
                corrected[k] = corrected.get(k, 0) + v
            for k, v in rep.detected_uncorrectable.items():
                uncorrectable[k] = uncorrectable.get(k, 0) + v
    rank = domain.payload["graph"]["rank"]["rank"]
    delta = jnp.sum(jnp.abs(rank - prev))
    return domain, rank, delta, ScrubReport(
        corrected=corrected, detected_uncorrectable=uncorrectable)


def top_k(rank: jax.Array, n: int, k: int) -> jax.Array:
    """Top-k node ids by rank (stable order; ties break by node id)."""
    return jnp.argsort(-rank[0, :n], stable=True)[:k].astype(jnp.int32)


def pagerank_eval_fn(n: int, *, iters: int = 20, k: int = 8,
                     damping: float = 0.85, backend: str = "pallas"):
    """Fig.2 ``eval_fn`` over a ``{"graph": graph_state}`` payload: run
    PageRank from the (possibly corrupted) state, answer with the top-k
    ranking. Non-finite ranks return the -1 crash marker. Healed rank
    strikes classify as MASKED_LOGIC: the converged rank returned in the
    final state never bit-equals the pre-strike iterate, so the masking is
    attributed to the algorithm's logic (convergence), not to an
    overwrite."""
    def eval_fn(payload):
        state, rank, _ = pagerank(payload["graph"], n, iters=iters,
                                  damping=damping, backend=backend)
        finite = jnp.isfinite(rank).all()
        toks = jnp.where(finite, top_k(rank, n, k), -1)
        return toks, {**payload, "graph": state}
    return eval_fn


__all__ = ["BACKENDS", "pagerank", "pagerank_step", "pagerank_scrubbed",
           "pagerank_eval_fn", "top_k", "node_block_of"]
