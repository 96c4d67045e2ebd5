"""A run with its timed path broken underneath comes out not correct,
once for each fault the cell can have. (One chip: no exchange between
chips to leave out.)"""
import numpy as np
import pytest

from conftest import run_cell


def _decode_fault(kind):
    def hook(cell):
        orig = cell.engine._decode

        def broken(params, pk, pv, table, tokens, pos):
            pk2, pv2, nxt, ok = orig(params, pk, pv, table, tokens, pos)
            nxt = np.array(nxt)
            if kind == "state_unchanged":       # KV writes dropped
                return pk, pv, nxt, ok
            if kind == "half_batch":            # odd slots copy even ones
                nxt[1::2] = nxt[0::2][:len(nxt[1::2])]
            if kind == "token_altered":
                nxt[0] = (nxt[0] + 1) % cell.cfg["vocab_size"]
            return pk2, pv2, nxt, ok
        cell.engine._decode = broken
    return hook


def _graph_fault(kind, monkeypatch):
    import importlib

    import jax.numpy as jnp
    pr = importlib.import_module("repro.graph.pagerank")

    def hook(cell):
        step, push = pr.pagerank_step, pr._push
        if kind == "state_unchanged":
            monkeypatch.setattr(pr, "pagerank_step",
                                lambda state, n, **kw: state)
        elif kind == "half_batch":             # half the edges, doubled
            def half(topo, x, backend):
                dst = topo["dst"]
                keep = jnp.arange(dst.shape[0]) < dst.shape[0] // 2
                sentinel = x.shape[1]
                return 2.0 * push({**topo, "dst": jnp.where(
                    keep, dst, sentinel)}, x, backend)
            monkeypatch.setattr(pr, "_push", half)
        elif kind == "answer_altered":
            def altered(state, n, **kw):
                out = step(state, n, **kw)
                r = out["rank"]["rank"]
                return {**out, "rank": {"rank": r.at[0, 0].mul(1.01)}}
            monkeypatch.setattr(pr, "pagerank_step", altered)
    return hook


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "token_altered"])
def test_serve_fault_is_not_correct(kind):
    res = run_cell("tiny.serve.hrm", seed=3, hooks=_decode_fault(kind))
    assert not res["correct"]
    gap = res["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"], gap


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered"])
def test_graph_fault_is_not_correct(kind, monkeypatch):
    import bench
    bench.add_program_path()
    res = run_cell("tiny.pagerank", seed=3,
                   hooks=_graph_fault(kind, monkeypatch))
    assert not res["correct"]
    err = res["checks"]["rank_rel_err_final"]
    assert err["value"] > err["limit"], err
