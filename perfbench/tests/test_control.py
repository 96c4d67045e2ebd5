"""The control, the plain reference one precision step below the
configuration's in the program's place, reads above every limit that the
program's runs keep, at the small test size."""
import pytest

from conftest import DATA
import bench
import control


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_control_fails_where_the_program_passes(seed):
    r = control.readings("tiny.serve.hrm", seed, 0.3, require_tpu=False,
                         root=DATA)
    lim = bench.load_json("traffic", "serve.tiny.hrm")["check"]["limits"]
    assert all(r[k] <= v for k, v in lim.items()), r
    # the control fails every number here, though one would do
    assert all(r["control_" + k] > v for k, v in lim.items()), r


@pytest.mark.parametrize("seed", [1, 2])
def test_graph_control_fails_where_the_program_passes(seed):
    r = control.readings("tiny.pagerank", seed, 0.3, require_tpu=False,
                         root=DATA)
    lim = bench.load_json("traffic", "pagerank.tiny")["check"]["limits"]
    for k in ("rank_rel_err_first_chunk", "rank_rel_err_final"):
        assert r[k] <= lim[k] < r["control_" + k], (k, r)
