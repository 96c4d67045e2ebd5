"""The per-program reduction (``programs.py``) on a hand-made trace of two
engine iterations (``fixtures/make_programs.py``), whose numbers are
known exactly, and its metrics' readers on that trace and on one from a
program that names no programs and writes no program spans."""
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

import bench
import programs
import reduce_trace as rt

FIXTURES = Path(__file__).resolve().parent.parent / "fixtures"
PROGRAMS = FIXTURES / "programs.xplane.pb"
SYNTHETIC = FIXTURES / "synthetic.xplane.pb"
US = 1e-6
NEW_METRICS = ("serve.decode_ms", "serve.prefill_ms", "serve.kv_check_ms",
               "serve.kv_refresh_ms", "serve.params_scrub_ms",
               "serve.protect_share", "serve.kv_encode_amplification",
               "graph.protect_ms", "graph.step_idle_ms")


def _approx_us(d):
    return pytest.approx({k: v * US for k, v in d.items()})


def test_module_seconds():
    s = programs.summarize(str(PROGRAMS))
    assert s.n_devices == 1
    assert s.module_seconds == _approx_us({
        "jit_cache_scrub": 120, "jit_serve_decode": 400,
        "jit_cache_encode": 90, "jit_serve_prefill": 70,
        "jit_graph_scrub_slice": 5})                  # clipped at the end
    assert s.module_counts == {"jit_cache_scrub": 2, "jit_serve_decode": 2,
                               "jit_cache_encode": 2, "jit_serve_prefill": 1,
                               "jit_graph_scrub_slice": 1}
    # the module line holds the same device time as the operations line
    busy = rt.summarize(str(PROGRAMS)).busy_s
    assert sum(s.module_seconds.values()) == pytest.approx(busy)


def test_span_seconds_and_idle():
    s = programs.summarize(str(PROGRAMS))
    assert s.span_counts == {"serve.iteration": 2, "serve.kv_check": 2,
                             "serve.decode": 2, "serve.kv_refresh": 2,
                             "serve.prefill": 1}
    assert s.span_seconds == _approx_us({
        "serve.iteration": 960, "serve.kv_check": 170, "serve.decode": 440,
        "serve.kv_refresh": 130, "serve.prefill": 90})
    # idle by the innermost span; outside every program span, the wave's
    assert s.span_idle_seconds == _approx_us({
        "wave": 35, "serve.iteration": 130, "serve.kv_check": 50,
        "serve.decode": 40, "serve.kv_refresh": 40, "serve.prefill": 20})
    assert s.span_idle_each["serve.iteration"] == \
        pytest.approx([150 * US, 130 * US])
    assert s.span_stats["serve.kv_refresh"] == {"pages": 100}
    assert s.span_stats["serve.decode"] == {"active": 5}
    assert s.span_stats["serve.prefill"] == {"rid": 0, "prompt_len": 20,
                                             "pages": 3}


def test_innermost_idle_outer_first_at_a_shared_start():
    idle = programs._Idle([(0.0, 10.0)])
    spans = [(0.0, 4.0, "inner"), (0.0, 20.0, "outer")]
    assert programs._innermost_idle(spans, idle) == \
        pytest.approx({"inner": 4e-9, "outer": 6e-9})


def _ctx(trace_path, monkeypatch, tmp_path, iterations=2):
    shutil.copy(trace_path, tmp_path / "run.xplane.pb")
    monkeypatch.setattr(programs, "TRACE_DIR", tmp_path)
    summary = rt.summarize(str(trace_path), groups=bench.kernel_groups())
    return SimpleNamespace(trace=summary, window=SimpleNamespace(
        traced={"iterations": iterations}))


def _read(name, ctx):
    return bench.load_module("metrics", name).read(ctx)


def test_readers_on_named_programs(monkeypatch, tmp_path):
    ctx = _ctx(PROGRAMS, monkeypatch, tmp_path)
    got = {m: _read(m, ctx) for m in NEW_METRICS}
    assert got["serve.decode_ms"] == pytest.approx(0.2)
    assert got["serve.prefill_ms"] == pytest.approx(0.035)
    assert got["serve.kv_check_ms"] == pytest.approx(0.06)
    assert got["serve.kv_refresh_ms"] == pytest.approx(0.045)
    assert got["serve.params_scrub_ms"] is None       # no such program ran
    assert got["serve.protect_share"] == pytest.approx(100 * 215 / 685)
    assert got["serve.kv_encode_amplification"] == pytest.approx(100 / 8)
    assert got["graph.protect_ms"] == pytest.approx(0.0025)
    assert got["graph.step_idle_ms"] is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_give_nothing_without_names(name, monkeypatch, tmp_path):
    """A trace of a program that names no programs and writes no program
    spans (the synthetic trace's ``jit_fn``) gives no value, and no
    error."""
    assert _read(name, _ctx(SYNTHETIC, monkeypatch, tmp_path)) is None


def test_readers_without_a_trace(monkeypatch, tmp_path):
    monkeypatch.setattr(programs, "TRACE_DIR", tmp_path / "none")
    ctx = SimpleNamespace(trace=None, window=SimpleNamespace(traced={}))
    assert all(_read(m, ctx) is None for m in NEW_METRICS)
