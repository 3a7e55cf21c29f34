"""The roofline, utilization and byte arithmetic, against hand counts."""
import pytest

from perfbench import counts

V5E = counts.peaks("TPU v5 lite")


def test_peaks_table():
    assert V5E["bf16_flops_per_s"] == 197e12
    assert V5E["hbm_bytes_per_s"] == 819e9
    assert V5E["hbm_bytes"] == 16e9 and V5E["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError, match="no peaks for device kind"):
        counts.peaks("cpu")


def test_smollm_matmul_params_by_hand():
    # per layer: q 576*576, k and v 576*192 each, o 576*576, mlp 3*576*1536
    layer = 576 * 576 * 2 + 576 * 192 * 2 + 3 * 576 * 1536
    assert layer == 3_538_944
    n = counts.matmul_params(hidden=576, heads=9, kv_heads=3, head_dim=64,
                             ffn=1536, layers=30, vocab=49152)
    assert n == 30 * layer + 49152 * 576 == 134_479_872


def test_train_flops_per_token_and_mfu():
    f = counts.train_flops_per_token(n_matmul=1000, layers=2, heads=4,
                                     head_dim=8, seq=16)
    assert f == 6 * 1000 + 12 * 2 * 32 * 16
    # 1e5 tokens/s at 1.2e9 FLOP/token over 4 chips of 197 TFLOP/s
    assert counts.mfu(tokens_per_s=1e5, flops_per_token=1.2e9, chips=4,
                      peak_flops=197e12) == pytest.approx(1.2e14 / 7.88e14)


def test_sync_round_roofline():
    raw = 718_299_136  # 359,149,568 bf16 weights
    assert counts.sync_round_bytes(raw) == 2_154_897_408
    share = counts.roofline_share(hbm_bytes=counts.sync_round_bytes(raw),
                                  seconds=2.0, peak=V5E)
    assert share == pytest.approx(2_154_897_408 / 819e9 / 2.0)


def test_roofline_takes_the_binding_bound():
    # 197e9 FLOPs take 1 ms; 81.9e6 bytes take 0.1 ms: compute binds
    assert counts.roofline_share(flops=197e9, hbm_bytes=81.9e6,
                                 seconds=0.004, peak=V5E) == \
        pytest.approx(0.25)


def test_codec_bytes_by_hand():
    # RS: encode 100 + 20, decode-reduce 20 + 2 * 200; AG: encode 50 + 10,
    # decode 10 + 50
    assert counts.codec_bytes(rs_raw=100, rs_wire=20, acc_bytes=200,
                              ag_raw=50, ag_wire=10) == 660

