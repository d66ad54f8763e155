"""The peaks table and the fused facility kernel's work count."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perfbench import peaks  # noqa: E402


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peak rates"):
        peaks.peak("TPU v9 imaginary")
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_fused_step_work_matches_hand_count():
    # 2 steps x 3 scenarios: 113 operations a step; bytes per scenario are
    # 2 steps x (8 dense rows + 4 trace rows) x 4 B plus the 128-word
    # accumulator row and 16 SMEM words
    ops, nbytes = peaks.fused_step_work(2, 3)
    assert ops == 113 * 2 * 3
    assert nbytes == 3 * (2 * (8 * 4 + 4 * 4) + (128 + 16) * 4)


@pytest.mark.parametrize("factor", [2, 7])
def test_fused_step_work_is_linear(factor):
    ops, _ = peaks.fused_step_work(1344, 5)
    ops_s, _ = peaks.fused_step_work(1344 * factor, 5)
    ops_n, b_n = peaks.fused_step_work(1344, 5 * factor)
    _, b = peaks.fused_step_work(1344, 5)
    assert ops_s == factor * ops and ops_n == factor * ops
    assert b_n == factor * b
    # bytes grow with steps at the per-step rate, plus a fixed row
    _, b1 = peaks.fused_step_work(100, 1)
    _, b2 = peaks.fused_step_work(100 * factor, 1)
    assert b2 - b1 == (factor - 1) * 100 * (8 * 4 + 4 * 4)


def test_roofline_names_its_bound():
    ops, nbytes = peaks.fused_step_work(1344, 632)
    pct, bound = peaks.roofline_pct(ops, nbytes, 1.0, "TPU v5 lite")
    assert bound == "memory"
    assert pct == pytest.approx(100 * nbytes / 819e9)
    pct, bound = peaks.roofline_pct(197e12, 1.0, 2.0, "TPU v5 lite")
    assert bound == "compute" and pct == pytest.approx(50.0)
