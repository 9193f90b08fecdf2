"""Work counters against hand counts, and the peaks table."""
import pytest

from _common import BENCH  # noqa: F401  (import paths)
import harness


def test_rf_work_hand_count():
    # 96 rows, 100 trees of depth 10, 6 features:
    # visits 96*100*10 = 96000, leaf adds 96*100 = 9600;
    # tables 100 * (1023 * 8 + 1024 * 4) = 1228000 bytes,
    # rows 96*6*4 = 2304, outputs 96*4 = 384
    w = harness.work("rf").per_launch(96, 100, 10, 6)
    assert w == {"ops": 105600, "bytes": 1228000 + 2304 + 384}


def test_fleet_tick_work_hand_count():
    # J=8 jobs on P=4 DCs, N=8: rows 8*4*3 = 96; RF ops 105600;
    # fills 3 * 64 pairs * 20 = 3840; bytes: inputs 2*64*8 = 1024,
    # outputs 8*36 + 13 = 301, tables 1228000 / (16*32)
    w = harness.work("fleet_tick").per_tick_step(
        jobs=8, slice=4, dcs=8, trees=100, depth=10, features=6,
        variants=16, ticks=32)
    assert w["ops"] == 105600 + 3840
    assert w["bytes"] == pytest.approx(1024 + 301 + 1228000 / 512)


def test_lm_train_work_hand_count():
    # h2o-danube-1.8b widths, 4 layers, 2048 tokens, window 4096:
    # attention projections 2560*2560*2 + 2560*640*2 = 16384000 and MLP
    # 3*2560*6912 = 53084160 a layer, x4 = 277872640, head 81920000:
    # 359792640 weights, 719585280 forward operations; attention
    # 4*32*80 = 10240 per key, 1024.5 keys on average, x4 = 41963520
    w = harness.work("lm_train").flops_per_token(
        layers=4, d_model=2560, heads=32, kv_heads=8, head_dim=80,
        d_ff=6912, vocab=32000, seq=2048, window=4096)
    assert w == 3 * (719585280 + 41963520)
    # a window shorter than the sequence caps the keys: 4 positions,
    # window 2 -> keys 1, 2, 2, 2 (mean 1.75)
    small = harness.work("lm_train").flops_per_token(
        layers=1, d_model=2, heads=1, kv_heads=1, head_dim=2, d_ff=1,
        vocab=1, seq=4, window=2)
    assert small == 3 * (2 * (4 * 2 * 2 + 3 * 2 + 2) + 4 * 2 * 1.75)


def test_peaks_known_and_unknown():
    p = harness.peak_for("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="no published peaks"):
        harness.peak_for("TPU v9 imaginary")


def test_roofline_share_and_bound():
    dev = harness.load_module(f"{BENCH}/metrics/_device.py")
    # 1.2272 MB at 819 GB/s = 1.498 us against 1 ms measured
    w = harness.work("rf").per_launch(96, 100, 10, 6)
    share = dev.roofline(w["ops"], w["bytes"], 1e-3, "TPU v5 lite")
    assert share == pytest.approx(100 * w["bytes"] / 819e9 / 1e-3)
    # bound by bytes: 1.5 us of table reads against 0.5 ns of visits
    assert w["bytes"] / 819e9 > 1000 * w["ops"] / 197e12
    with pytest.raises(KeyError):
        dev.roofline(1, 1, 1, "cpu")
