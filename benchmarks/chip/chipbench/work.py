"""Operations and bytes the ray-cast verify needs for one batch, counted
from the queries' own work rather than the kernel's padded grid, and the
least time the chip could take for them.

For query ``q`` with ``m_q`` real triangles over ``N`` users the work is
``N * m_q`` point-in-triangle tests, each three edge functions
``a*x + b*y + c`` of two multiplies and two adds.  The bytes are the users
read once (two float32 coordinates each), the real edge coefficients (nine
float32 per triangle) and the ``[Q, N]`` int32 counts the served API returns.
"""

from __future__ import annotations

import json
from pathlib import Path

__all__ = ["FLOPS_PER_TEST", "raycast_batch_work", "least_time", "peaks_for"]

FLOPS_PER_TEST = 3 * 4  # three edge functions of 2 multiplies and 2 adds
USER_BYTES = 2 * 4
TRIANGLE_BYTES = 9 * 4
COUNT_BYTES = 4

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def raycast_batch_work(n_users: int, tris: list[int]) -> tuple[float, float]:
    """``(flops, bytes)`` of one batch whose queries have ``tris`` real
    triangles each."""
    flops = float(n_users) * float(sum(tris)) * FLOPS_PER_TEST
    nbytes = (
        float(n_users) * USER_BYTES
        + float(sum(tris)) * TRIANGLE_BYTES
        + float(len(tris)) * n_users * COUNT_BYTES
    )
    return flops, nbytes


def peaks_for(device_kind: str) -> dict:
    """The peaks row of ``device_kind``; a device missing from the table is
    an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}")
    return table[device_kind]


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """``(seconds, bound)``: the larger of flops over peak FLOP/s and bytes
    over peak bandwidth, and which of the two it is."""
    t_flops = flops / float(peaks["flops_per_s"])
    t_bytes = nbytes / float(peaks["hbm_bytes_per_s"])
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
