"""Byte counts of ``bench/work.py`` on hand-checked outputs."""
import pytest

from bench import work


def test_output_bytes_per_trajectory():
    # wall, work, io, down (f64) + failures, checkpoints (int32)
    # + truncated, exhausted (1 byte each)
    assert work.OUTPUT_BYTES == 42


@pytest.mark.parametrize("n_failures, expected", [
    ([0], 8 + 42),
    ([3], 4 * 8 + 42),
    ([[0, 1], [2, 5]], (1 + 2 + 3 + 6) * 8 + 4 * 42),
])
def test_trajectory_bytes(n_failures, expected):
    assert work.trajectory_bytes(n_failures) == expected


def test_roofline_pct():
    # 819 bytes in 1 ns at 819 GB/s is the whole roofline.
    assert work.roofline_pct(819.0, 1e-9, 819e9, 1) == pytest.approx(100.0)
    # Four chips share the bytes: a quarter of the least time.
    assert work.roofline_pct(819.0, 1e-9, 819e9, 4) == pytest.approx(25.0)
    assert work.roofline_pct(1.0, 0.0, 819e9, 1) is None
