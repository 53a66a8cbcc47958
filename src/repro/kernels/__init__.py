"""Pallas TPU kernels for the paper's compute hot-spots (+ jnp oracles).

* ``raycast``      — dense occluder hit counting (the ray-casting stage),
                     single-query and batched (``[Q]`` grid axis) variants
* ``rank_count``   — distance-rank counting (brute / "InfZone-GPU" baseline)
* ``grid_raycast`` — grid-culled counting (the TPU BVH analogue):
                     cell-bucketed scalar-prefetch ``(q, user-block)``
                     kernel, plus the host-side bucketing / plane-packing
                     helpers
* ``ops``          — jit'd public wrappers (padding, backend selection,
                     batched multi-query dispatch)
* ``ref``          — pure-jnp oracles used by the allclose sweeps
"""

from repro.kernels.ops import (
    grid_count_cells,
    grid_count_cells_batch,
    rank_count,
    raycast_count,
    raycast_count_batch,
)

__all__ = [
    "raycast_count",
    "rank_count",
    "raycast_count_batch",
    "grid_count_cells",
    "grid_count_cells_batch",
]
