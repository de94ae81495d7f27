"""Operation and byte counts of the policy kernel against hand counts."""
import pytest

import roofline

ACTOR = [8, 64, 32, 1]


def test_mlp_flops_hand_count():
    # per row: 8x64 MACs + 64 bias adds, 64x32 MACs + 32, 32x1 MACs + 1
    per_row = (2 * 8 * 64 + 64) + (2 * 64 * 32 + 32) + (2 * 32 + 1)
    assert per_row == 5281
    assert roofline.mlp_flops(3840, ACTOR) == 3840 * 5281


def test_mlp_bytes_hand_count():
    weights = (8 * 64 + 64) + (64 * 32 + 32) + (32 + 1)   # 2689 floats
    # each row: 8 inputs, 1 mask value, 1 logit, all float32
    assert roofline.mlp_bytes(3840, ACTOR) == 4 * (3840 * 10 + weights)
    assert roofline.mlp_bytes(3840, ACTOR, calls=3) == 4 * (
        3840 * 10 + 3 * weights)
    assert roofline.mlp_bytes(10, [21, 24, 12, 2], mask=False) == 4 * (
        10 * 23 + (21 * 24 + 24) + (24 * 12 + 12) + (12 * 2 + 2))


def test_roofline_takes_the_larger_bound():
    kind = "TPU v5 lite"
    # 3840 rows: 20.3 MFLOP (0.10 us at 197 TFLOP/s) against 164 KB
    # (0.20 us at 819 GB/s): memory bound
    f = roofline.mlp_flops(3840, ACTOR)
    b = roofline.mlp_bytes(3840, ACTOR)
    assert roofline.roofline_s(f, b, kind) == pytest.approx(b / 819e9)
    assert roofline.roofline_s(1e9, 1.0, kind) == pytest.approx(1e9 / 197e12)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
