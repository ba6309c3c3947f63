import json
import os

import pytest

from kernels import scan as rooflines

PEAKS = json.load(open(os.path.join(os.path.dirname(__file__), "..", "peaks.json")))


def test_scan_bytes_hand_worked():
    # one z3 block: 16384 rows x 4 columns (tbin, toff, x, y) x 4 B, and
    # two bit planes of 16384 bits
    assert rooflines.scan_bytes(1, 16384, 4) == 16384 * 16 + 2 * 2048 == 266_240
    # 100 z2 blocks: x, y only
    assert rooflines.scan_bytes(100, 16384, 2) == 100 * (16384 * 8 + 4096)
    assert rooflines.scan_bytes(0, 16384, 4) == 0


def test_block_scan_is_bound_by_bytes_on_a_v5e():
    peaks = PEAKS["TPU v5 lite"]
    assert peaks["hbm_bytes_per_s"] == 819e9 and peaks["flops_per_s"] == 197e12
    calls = [{"blocks": 4096, "block_rows": 16384, "n_cols": 4}]
    got = rooflines.roofline(calls, peaks)
    assert got["bytes"] == 4096 * 266_240
    assert got["bound"] == "bytes"
    # the whole 2^26-row z3 table streams in 1.33 ms at the peak
    assert got["least_s"] == pytest.approx(4096 * 266_240 / 819e9)
    assert 1.3e-3 < got["least_s"] < 1.4e-3


def test_an_unknown_device_is_an_error():
    from harness import layers

    with pytest.raises(KeyError):
        layers.peaks_of("cpu")
    assert layers.peaks_of("TPU v5 lite")["hbm_bytes"] == 16e9
