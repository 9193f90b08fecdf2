"""The training cell's work count and trace readers, on hand counts and
a hand-made trace."""
import pytest

from _common import BENCH  # noqa: F401  (import paths)
import harness
import tracereduce as tr_

CELL = "xpod4-danube.sync"
SHAPES = dict(layers=4, d_model=2560, heads=32, kv_heads=8, head_dim=80,
              d_ff=6912, vocab=32000, seq=4096, window=4096)


def test_lm_train_work_at_the_cells_shapes():
    # weights a layer: attention 2560*2560*2 + 2560*640*2 = 16384000,
    # MLP 3*2560*6912 = 53084160; x4 = 277872640, head 2560*32000 =
    # 81920000: 359792640 weights, 719585280 forward operations.
    # Attention 4*32*80 = 10240 per key a layer, 4097/2 = 2048.5 keys on
    # average (every key of the causal prefix, window = seq), x4 layers
    # = 83906560. Forward 803491840, training x3 = 2410475520.
    w = harness.work("lm_train").flops_per_token(**SHAPES)
    assert w == 3 * (719585280 + 83906560) == 2410475520


def _ev(name, start, dur):
    return tr_.Event(name, float(start), float(dur))


def _obs(steps=2, devices=2):
    """Two chips, a 1000-ns window from 1000, `steps` steps inside it and
    one outside; on each chip compute [1000, 1400) and [1600, 1900), and
    collective-permutes [1350, 1500) (50 ns under compute) and
    [1850, 1900) (all under compute)."""
    tr = tr_.Trace()
    for d in range(devices):
        tr.ops[f"/device:TPU:{d}"] = [
            _ev("%fusion.1 = f32[2]{0} fusion(...)", 1000, 400),
            _ev("%fusion.2 = f32[2]{0} fusion(...)", 1600, 300),
            _ev("%collective-permute-start.3 = (...)", 1350, 150),
            _ev("%collective-permute-done.4 = f32[2]{0} (...)", 1850, 50)]
    tr.host = [_ev("bench.window", 1000, 1000)]
    tr.host += [_ev("bench.step", 1000 + 400 * k, 400)
                for k in range(steps)]
    tr.host.append(_ev("bench.step", 1900, 400))     # runs past the window
    return {"trace": tr, "trace_window": (1000.0, 2000.0),
            "shapes": SHAPES, "tokens_per_step": 32768,
            "device_kind": "TPU v5 lite", "n_devices": devices}


def _read(name, obs):
    return harness.Benchmark.load().reader(name)(obs)


def test_sync_total_exposed_and_per_step():
    obs = _obs()
    # per chip: 150 + 50 = 200 ns, 100 of it under compute; all of it
    # counts, per step over the 2 steps inside the window, in ms
    assert _read("sync_ms.xpod", obs) == pytest.approx(200 / 2 * 1e-6)
    one = _obs(steps=1)
    assert _read("sync_ms.xpod", one) == pytest.approx(200e-6)


def test_train_mfu_is_work_over_window_chips_and_peak():
    obs = _obs(steps=2, devices=4)
    flops = 2410475520 * 32768 * 2
    want = 100 * flops / (1000e-9 * 4 * 197e12)
    assert _read("train_mfu.xpod", obs) == pytest.approx(want)


@pytest.mark.parametrize("name", ["train_mfu.xpod", "sync_ms.xpod"])
def test_readers_return_none_without_steps(name):
    assert _read(name, _obs(steps=0)) is None
    assert _read(name, {"trace": None, "trace_window": None}) is None


def test_idle_share_and_throughput():
    obs = _obs()
    # busy per chip: [1000, 1500) and [1600, 1900) = 800 of 1000 ns
    assert _read("idle_share.xpod", obs) == pytest.approx(20.0)
    assert _read("train_tokens_per_s", {"tokens": 65536,
                                        "window_s": 2.0}) == 32768.0
    assert _read("train_tokens_per_s", {}) is None


def test_the_cell_reports_its_metrics():
    bench = harness.Benchmark.load()
    assert [m["name"] for m in bench.metrics_for(CELL, False)] == [
        "train_tokens_per_s", "setup_s"]
    assert [m["name"] for m in bench.metrics_for(CELL, True)] == [
        "train_mfu.xpod", "sync_ms.xpod", "idle_share.xpod"]
    assert bench.cell(CELL)["chips"] == 4
