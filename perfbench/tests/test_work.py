"""Work counts against hand counts for granite16 (16 layers, published
widths)."""
import bench

CFG = bench.load_json("configs", "granite16")
W = bench.load_module("work", "moe_transformer")


def test_params_and_bytes():
    n = W.param_count(CFG)
    # embed 49155 * 1536 (tied: also the head), attention 16 * 6.29M,
    # experts 16 * 40 * 3 * 1536 * 512, router 16 * 1536 * 40, norms
    # 33 * 1536
    hand = (49155 * 1536 + 16 * (2 * 1536 * 1536 + 2 * 1536 * 512)
            + 16 * 40 * 3 * 1536 * 512 + 16 * 1536 * 40 + 33 * 1536)
    assert n == hand
    assert abs(n / 1e9 - 1.687) < 0.001
    gib = sum(W.param_bytes(CFG).values()) / 2 ** 30
    assert abs(gib - 3.144) < 0.001


def test_kv_and_sidecars():
    assert W.kv_bytes_per_token(CFG) == 32 * 1024
    assert W.SIDECAR_PER_BYTE["secded"] == 8 / 64
    assert W.SIDECAR_PER_BYTE["parity_r"] == 1 / 64
    pol = bench.load_json("policies", "hrm.detect_recover_l.kv_parity")
    b = W.param_bytes(CFG)
    side = W.params_sidecar_bytes(CFG, pol["params_tiers"])
    assert side == (b["embed"] + b["attn"] + b["norm"]) / 8 + b["experts"] / 64


def test_active_params():
    # every parameter but the experts' unrouted share: the tied table
    # counts once, as the head; experts at 8 of 40
    exp = 16 * 40 * 3 * 1536 * 512
    hand = W.param_count(CFG) - exp + exp * 8 / 40
    assert W.active_params(CFG) == hand
    assert 0.47e9 < hand < 0.49e9


def test_wave_need_is_memory_bound_and_positive():
    peaks = bench.peaks_for("TPU v5 lite")
    pol = bench.load_json("policies", "hrm.detect_recover_l.kv_parity")
    need = W.wave_need_seconds(CFG, pol, [(3072, 256), (512, 64)], 256,
                               peaks)
    # 256 decode steps each read the 3.14 GiB of weights at least
    assert need["decode_s"] >= 256 * 3.14 * 2 ** 30 / 819e9
    assert need["decode_bytes"] / 819e9 >= need["decode_flops"] / 197e12


def test_push_need():
    P = bench.load_module("work", "pagerank")
    assert P.push_need(10, 4) == {"flops": 20.0, "bytes": 112.0}
    tiers = {"topology": "secded", "rank": "parity_r"}
    assert P.ecc_need_bytes(640, 64, tiers, 8) == \
        64 * (1 + 1 / 64) + (640 * 9 / 8 + 64 * 65 / 64) / 8


def test_unknown_device_is_an_error():
    import pytest
    with pytest.raises(KeyError):
        bench.peaks_for("TPU v4")
