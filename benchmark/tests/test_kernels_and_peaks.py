"""Each ops-and-bytes function against hand-worked numbers; the peaks
table raising on a device it does not know."""

import pytest

from benchmark.harness import peaks
from benchmark.kernels import flash_train, paged_decode


def test_flash_train_needs_hand_worked():
    # one layer, hidden 4, batch 2, seq 8: forward = 2*2*2*8*8*4/2 = 1024
    # FLOPs, a step 3x; bytes: 12 arrays of 2*8*4 bf16 = 12*64*2 = 1536
    cfg = {"n_embd": 4, "n_layer": 1}
    assert flash_train.needs(cfg, {"batch": 2, "seq_len": 8}) == (3072, 1536)
    # three layers and two chips sharing the batch: x3, /2
    cfg = {"hidden_size": 4, "num_hidden_layers": 3}
    assert flash_train.needs(cfg, {"batch": 2, "seq_len": 8}, chips=2) \
        == (4608, 2304)


def test_paged_decode_needs_live_keys_only():
    # two layers, hidden 8, streams holding 3 and 5 live keys: 8 keys;
    # FLOPs = 2 * 8 * 2*2*8 = 512; bytes = 2 * 8 * 2*8*4 = 1024
    cfg = {"n_embd": 8, "n_layer": 2}
    assert paged_decode.needs(cfg, [3, 5]) == (512, 1024)
    # a bf16 pool halves the bytes, not the FLOPs
    assert paged_decode.needs(cfg, [3, 5], pool_bytes=2) == (512, 512)


def test_roofline_share():
    p = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    # 50 FLOPs need 0.5 s, 20 bytes need 2 s: bytes bound; took 4 s
    assert peaks.roofline_pct(50, 20, 4.0, p) == pytest.approx(50.0)
    assert peaks.roofline_pct(50, 20, 0.0, p) is None


def test_peaks_known_and_unknown():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks recorded"):
        peaks.peaks_for("TPU v9 imaginary")
