"""The trace reduction on a hand-made trace in a TPU run's layout
(``fixtures/make_synthetic.py``), whose numbers are known exactly."""
from pathlib import Path

import pytest

import bench
import reduce_trace as rt

FIXTURE = Path(__file__).resolve().parent.parent / "fixtures" / \
    "synthetic.xplane.pb"
US = 1e-6


def test_union_and_attribution():
    assert rt._union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    host = [(0.0, 100.0, "wave"), (10.0, 20.0, "PjitFunction(step)"),
            (12.0, 14.0, "inner")]
    idle = rt._attribute(sorted(host), [(12.5, 13.5), (15.0, 17.0),
                                        (30.0, 40.0)])
    assert idle == {"inner": 1e-9, "PjitFunction(step)": 2e-9,
                    "wave": 10e-9}


def test_synthetic_trace():
    s = rt.summarize(str(FIXTURE), groups=bench.kernel_groups())
    assert s.n_devices == 1 and s.spans == {"wave": 1, "pagerank_chunk": 1}
    assert s.window_s == pytest.approx(1600 * US)
    assert s.busy_s == pytest.approx(930 * US)
    assert s.group_seconds["ecc"] == pytest.approx(130 * US)
    assert s.group_counts == {"ecc": 3, "push": 2}
    assert s.group_seconds["push"] == pytest.approx(400 * US)
    assert s.op_seconds["jit_fn/edge_segment_push_blocked.6"] == pytest.approx(400 * US)
    want = {"PjitFunction(step)": 10, "PjitFunction(fn)": 10, "wave": 150,
            "block_until_ready": 450, "pagerank_chunk": 50}
    assert s.idle_by_host == pytest.approx({k: v * US
                                            for k, v in want.items()})
    b = s.breakdown(2)
    assert [n for n, _ in b["device_ops"]] == ["jit_fn/edge_segment_push_blocked.6",
                                               "jit_fn/convolution.5"]
    assert b["idle_gaps"][0][0] == "block_until_ready"


def test_needs_a_span():
    with pytest.raises(ValueError):
        rt.summarize(str(FIXTURE), ("no such span",))
