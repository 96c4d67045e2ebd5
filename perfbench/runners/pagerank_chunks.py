"""PageRank in chunks: ``pagerank_scrubbed`` called for one chunk of
iterations at a time (a chunk of ``scrub_slices`` iterations is one whole
scrub pass), blocking at each chunk's end, each chunk continuing from the
last one's ranks, until no further whole chunk fits the window.

The graph is the benchmark's input: a Graph500 Kronecker graph made from
the seed as Graphalytics stores its ``graph500`` datasets (the generator
is kept with the reference), handed to the program as an in-edge CSR,
which the program lays out in node blocks and protects under the cell's
policy.

Correctness: the ranks after the first chunk and after the window's last
chunk, against a float64 power iteration of the same edges run for as
many iterations, by the largest relative error over the vertices; and
the scrubs' counts of corrected and detected words, which a clean run
holds at 0.
"""
from __future__ import annotations

import gc
import time
from typing import List

import numpy as np

import bench

GRAPH_SALT = 21


def make_graph(cfg: dict, seed: int):
    """(CSR arrays of the program's input type, the edge list)."""
    from repro.graph.generate import CSRGraph
    ref = bench.load_module("reference", cfg["reference"])
    n, src, dst = ref.kronecker_edges(cfg["scale"], cfg["edge_factor"],
                                      cfg["initiator_abc"],
                                      bench.np_rng(seed, GRAPH_SALT))
    indptr = np.concatenate([[0], np.cumsum(np.bincount(dst, minlength=n))])
    g = CSRGraph(n, indptr.astype(np.int32),
                 src.astype(np.int32),
                 np.bincount(src, minlength=n).astype(np.int32))
    return g, src, dst


class Cell:
    def __init__(self, cfg: dict, traffic: dict, policy: dict, seed: int,
                 peaks: dict):
        self.cfg, self.traffic, self.policy, self.seed = (cfg, traffic,
                                                          policy, seed)
        self.peaks = peaks
        self.ref = bench.load_module("reference", cfg["reference"])
        self.work = bench.load_module("work", cfg["kind"])
        self.chunk = traffic["chunk_iterations"]
        self.slices = policy["scrub_slices"]

    def _protect(self):
        from repro.core import MemoryDomain
        from repro.core import policy as pol
        return MemoryDomain.protect({"graph": self.state},
                                    getattr(pol, self.policy[
                                        "params_policy"])())

    def _chunk(self, dom):
        from repro.graph import pagerank_scrubbed
        dom, rank, _, rep = pagerank_scrubbed(
            dom, self.n, iters=self.chunk,
            damping=self.cfg["damping_factor"], scrub_slices=self.slices)
        rank.block_until_ready()
        return dom, rank, rep

    def setup(self) -> None:
        import jax
        from repro.graph import graph_state
        g, self.src, self.dst = make_graph(self.cfg, self.seed)
        self.n = g.n
        self.state = graph_state(g, node_block=self.cfg["node_block"],
                                 edge_tile=self.cfg["edge_tile"])
        topo = self.state["topology"]
        self.topology_bytes = sum(a.size * a.dtype.itemsize for a in
                                  jax.tree_util.tree_leaves(topo))
        self.rank_bytes = self.state["rank"]["rank"].size * 4
        self.tiles = int(topo["blocks"]["src_block"].shape[0])
        with jax.profiler.TraceAnnotation("warmup"):
            self._chunk(self._protect())
        self.dom = self._protect()          # the window starts from 1/n
        bench.log(f"[graph] {self.n} vertices, {len(self.src)} edges, "
                  f"{self.tiles} edge tiles of node block "
                  f"{self.cfg['node_block']}")

    def window(self, seconds: float, tracer=None) -> bench.WindowResult:
        import jax
        chunks, longest = 0, 0.0
        flagged = []
        traced = {}
        t0 = time.perf_counter()
        while True:
            if chunks and time.perf_counter() - t0 + longest > seconds:
                break
            trace_this = tracer is not None and chunks < 2
            if trace_this and chunks == 0:
                tracer.start()
            c0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("pagerank_chunk"):
                self.dom, rank, rep = self._chunk(self.dom)
            longest = max(longest, time.perf_counter() - c0)
            if trace_this and chunks == 1:
                tracer.stop()
                traced = {"iterations": 2 * self.chunk}
            flagged.append(rep)
            if chunks == 0:
                self.first = rank
            chunks += 1
        window_s = time.perf_counter() - t0
        self.final, self.iters = rank, chunks * self.chunk
        self.reports = flagged
        if tracer is not None and not traced:
            tracer.stop()
            traced = {"iterations": chunks * self.chunk}
        if traced:
            traced.update(self._need())
        bench.log(f"[graph] window: {chunks} chunks of {self.chunk} "
                  f"iterations in {window_s:.6f} s")
        return bench.WindowResult(
            seconds=window_s,
            end_to_end={"pagerank_iter_ms":
                        window_s / self.iters * 1e3},
            attempted=chunks, failed=0,
            counters={"chunks": chunks, "iterations": self.iters},
            traced=traced)

    def _need(self) -> dict:
        w, tiers = self.work, self.policy["graph_tiers"]
        push = w.push_need(len(self.src), self.n)
        bw, fl = self.peaks["hbm_bytes_per_s"], self.peaks["flops_per_s"]
        return {
            "push_need_s": max(push["flops"] / fl, push["bytes"] / bw),
            "ecc_need_s": w.ecc_need_bytes(self.topology_bytes,
                                           self.rank_bytes, tiers,
                                           self.slices) / bw,
            "iter_need_s": w.iteration_need_seconds(
                len(self.src), self.n, self.topology_bytes, self.rank_bytes,
                tiers, self.slices, self.peaks)}

    def free(self) -> None:
        self.flagged = sum(sum(r.totals()) for r in self.reports)
        self.first = np.asarray(self.first)[0, :self.n]
        self.final = np.asarray(self.final)[0, :self.n]
        self.dom = self.state = None
        gc.collect()

    def check_readings(self, control: bool = False) -> dict:
        ranks = self.ref.pagerank(self.src, self.dst, self.n,
                                  self.cfg["damping_factor"],
                                  (self.chunk, self.iters),
                                  bf16=False)
        out = {"rank_rel_err_first_chunk":
               self.ref.max_rel_err(self.first, ranks[0]),
               "rank_rel_err_final": self.ref.max_rel_err(self.final,
                                                          ranks[1]),
               "scrub_words_flagged": float(self.flagged)}
        if control:
            low = self.ref.pagerank(self.src, self.dst, self.n,
                                    self.cfg["damping_factor"],
                                    (self.chunk, self.iters), bf16=True)
            out["control_rank_rel_err_first_chunk"] = \
                self.ref.max_rel_err(low[0], ranks[0])
            out["control_rank_rel_err_final"] = \
                self.ref.max_rel_err(low[1], ranks[1])
        return out

    def check(self) -> List[bench.Check]:
        return bench.checks(self.check_readings(),
                            self.traffic["check"]["limits"])


def make(cfg, traffic, policy, seed, peaks) -> Cell:
    return Cell(cfg, traffic, policy, seed, peaks)
