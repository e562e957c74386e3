"""Bytes a unit of Monte-Carlo work has to move, counted from its outputs.

The count is the same for any engine or kernel that produces the same
trajectories, so a later change to the engine cannot make it stale: each
trajectory reads one 8-byte gap per failure plus the gap that its
completion outlives (``n_failures + 1``), and writes its output fields.
"""
from __future__ import annotations

import numpy as np

#: bytes of one f64 inter-failure gap.
GAP_BYTES = 8

#: bytes one trajectory writes: four f64 fields (wall time, work executed,
#: I/O time, downtime), two int32 counts (failures, checkpoints) and two
#: one-byte flags (truncated, schedule exhausted).  Energy is formed on
#: the host from those fields.
OUTPUT_BYTES = 4 * 8 + 2 * 4 + 2 * 1


def trajectory_bytes(n_failures) -> int:
    """Bytes moved by the trajectories whose failure counts are given."""
    n = np.asarray(n_failures, np.int64)
    return int(GAP_BYTES * (n + 1).sum() + OUTPUT_BYTES * n.size)


def roofline_pct(n_bytes: float, device_s: float, hbm_bytes_per_s: float,
                 chips: int):
    """Least time (bytes over the cell's HBM bandwidth) as a share of the
    measured device time, in per cent; None without device time."""
    if not device_s or device_s <= 0.0:
        return None
    return 100.0 * n_bytes / (hbm_bytes_per_s * chips) / device_s
