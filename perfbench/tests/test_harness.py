"""A whole run of each small cell on the CPU (every step but the look for
a chip), and the look for a chip itself."""
import json

import pytest

import run
from conftest import DATA, run_cell


@pytest.mark.parametrize("workload", ["tiny.serve.hrm", "tiny.serve.none",
                                      "tiny.pagerank"])
def test_cell_runs_correct(workload):
    res = run_cell(workload, seed=2 ** 31 + 12345)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) >= {"setup_s", "peak_hbm_gib"}
    assert res["checks"]["compiles_in_window"]["value"] == 0
    json.dumps(res)


def test_same_seed_same_inputs():
    import bench
    sw = bench.load_module("runners", "serve_waves")
    t = bench.load_json("traffic", "serve.tiny.hrm")
    a, b = sw.make_wave(t, 99, 256), sw.make_wave(t, 99, 256)
    assert [r.prompt.tolist() for r in a] == [r.prompt.tolist() for r in b]
    # every seed serves the same sizes, in another pairing
    assert sorted(sw.wave_sizes(t, 1)) != [] and \
        sorted(p for p, _ in sw.wave_sizes(t, 1)) == \
        sorted(p for p, _ in sw.wave_sizes(t, 2))


def test_no_tpu_exits_nonzero_without_result(capsys):
    rc = run.main(["--workload", "granite16.serve.long.hrm", "--seed", "1",
                   "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""
