"""The latent-attention serving runner at a CPU size (``dsv2_tiny``,
``serve.tiny.mla``): a whole set-up, window and check through the
runner, its control readings, and the decode's HBM roofline reader."""
import types

import pytest

import bench
import programs


@pytest.fixture(scope="module")
def cell():
    cfg = bench.load_json("configs", "dsv2_tiny")
    traffic = bench.load_json("traffic", "serve.tiny.mla")
    policy = bench.load_json("policies", traffic["policy"])
    bench.add_program_path()
    c = bench.load_module("runners", traffic["runner"]).make(
        cfg, traffic, policy, 2 ** 31 + 99, bench.peaks_for("TPU v5 lite"))
    c.setup()
    res = c.window(0.3)
    c.free()
    return c, res


def test_mla_cell_runs_correct(cell):
    c, res = cell
    assert res.failed == 0 and res.attempted > 0
    # the pool is whole blocks of 128 pages
    assert c.pages % 128 == 0
    checks = c.check()
    assert all(k.ok for k in checks), checks
    out = c.check_readings(control=True)
    # the float8 latent cache misses the reference by more than the
    # served bfloat16 one
    assert out["control_mean_logit_gap"] > out["mean_logit_gap"]


def test_decode_hbm_roofline_reads_ctx_tokens():
    cfg = bench.load_json("configs", "dsv2_tiny")
    work = bench.load_module("work", cfg["kind"])
    peaks = bench.peaks_for("TPU v5 lite")
    metric = bench.load_module("metrics", "serve.decode_hbm_roofline")
    steps, ctx_tokens = 10, 1000
    need = work.decode_hbm_bytes(cfg, steps, ctx_tokens) \
        / peaks["hbm_bytes_per_s"]
    assert work.kv_bytes_per_token(cfg) == 3 * (64 + 64) * 2

    def ctx(stats):
        summary = programs.ProgramSummary(
            n_devices=1, module_seconds={"jit_serve_decode": 2 * need},
            module_counts={}, span_seconds={},
            span_counts={"serve.decode": steps}, span_stats=stats,
            span_idle_seconds={}, span_idle_each={})
        return types.SimpleNamespace(trace=object(), config=cfg,
                                     peaks=peaks,
                                     program_summary=summary)

    assert metric.read(ctx({"serve.decode": {
        "active": 5, "ctx_tokens": ctx_tokens}})) == pytest.approx(50.0)
    # a program that stamps no ctx_tokens gives no value
    assert metric.read(ctx({"serve.decode": {"active": 5}})) is None
