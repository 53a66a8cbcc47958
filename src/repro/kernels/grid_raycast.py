"""Pallas TPU kernel: grid-culled hit counting (the BVH-analogue path).

For NON-pruned or conservatively-pruned scenes (paper §4.8, Table 3) the
occluder count is large enough that the dense sweep wastes work; the
paper's BVH bounds per-ray cost at ``O(k log m)``.  The TPU-native
equivalent (DESIGN.md §2) buckets users by grid cell and tests only the
cell's *partial-overlap* list, with fully-covering triangles absorbed into
a per-cell ``base`` counter (``repro.core.grid``).

Kernel layout: the host sorts users by cell id and pads each cell's user
run to a multiple of the block size; the kernel's grid iterates user
blocks with a **scalar-prefetch map** selecting, per step, which cell's
(padded) triangle-coefficient planes to stage into VMEM — predictable
block gathers instead of the BVH's pointer chasing.  Each program
instance evaluates ``[BU x L]`` edge functions.

The kernel (:func:`grid_raycast_cells_batch`; a single query is the
batch at ``Q = 1``) iterates ``(Q, user-block)``: the user→cell sort is
computed ONCE per batch (all stacked scenes share one domain rect) and
each program stages one query's planes for one cell — this replaces the
batched jnp path's gather-bound ``[Q, N, L, 3, 3]`` temporary with
``[BU x L]`` edge evaluations plus a ``base[q, cell]`` add.

Tiling on the compiled (Mosaic) path: a block's last two dimensions
must be multiples of ``(8, 128)`` or span the whole array, so the sorted
users travel as a ``[1, n_blocks*block]`` row read in ``(1, block)``
blocks and the counts as ``[Q, 1, n_blocks*block]`` written in
``(1, 1, block)`` blocks — ``block`` a multiple of the 128-lane tile
(:func:`min_cell_block`).  Interpret mode runs the same kernel code.

Validated against the ``core.grid`` jnp oracle in ``tests/test_kernels.py``
and ``tests/test_grid_pallas.py``; ``tests/test_tpu_compile.py`` compiles
it for a TPU v5e at serving widths.
"""

from __future__ import annotations

import functools
from typing import TYPE_CHECKING

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

if TYPE_CHECKING:  # repro.core imports this module
    from repro.core.grid import OccluderGrid

__all__ = [
    "auto_cell_block",
    "min_cell_block",
    "measured_pad_waste",
    "prepare_cell_buckets",
    "pack_cell_coeff_planes",
    "repack_cell_coeff_planes",
    "grid_raycast_cells_batch",
    "unsort_cell_counts",
]

#: Coordinate filler for padded user slots: far outside every domain rect,
#: and the rows are dropped by :func:`unsort_cell_counts` regardless.
_PAD_COORD = np.float32(2e9)


def min_cell_block() -> int:
    """Smallest per-cell user block the bucketed kernel runs with.

    The compiled kernel reads users and writes counts in lane-dimension
    blocks, which must be multiples of the 128-lane tile; interpret mode
    has no tiling rule, and a block of 8 keeps sparse cells' padding small.
    """
    from repro.kernels.ops import pallas_interpret_default

    return 8 if pallas_interpret_default() else 128


def auto_cell_block(n_users: int, n_occupied_cells: int) -> int:
    """Pick the per-cell user block size for a bucketing.

    Every occupied cell pads its user run up to a block multiple, so the
    padded total is ~``n + occupied * block``: a block near the mean cell
    occupancy keeps the waste bounded at ~2x.  A power of two clamped to
    ``[min_cell_block(), 256]``: [128, 256] on the compiled path, [8, 256]
    in interpret mode.
    """
    occ = max(int(n_occupied_cells), 1)
    mean = max(int(np.ceil(n_users / occ)), 1)
    return int(min(256, max(min_cell_block(), 1 << int(np.ceil(np.log2(mean))))))


def measured_pad_waste(xs, ys, rect, G: int) -> float:
    """Exact pad-waste ratio of :func:`prepare_cell_buckets` at
    ``block=None``: padded user rows / real user rows (≥ 1).

    The cell-bucketed kernels' verify cost tracks the *padded* total
    (``~ n + occupied · block``), not the raw user count — this ratio is
    the planner's occupancy feature (``log_pw``).  Computed from the same
    cell classification and :func:`auto_cell_block` choice as the real
    bucketing, without the sort or the scatter.
    """
    xs = np.asarray(xs, np.float32)
    ys = np.asarray(ys, np.float32)
    n = len(xs)
    if n == 0:
        return 1.0
    w = rect.width / G
    h = rect.height / G
    cx = np.clip(np.floor((xs - rect.xmin) / w), 0, G - 1).astype(np.int64)
    cy = np.clip(np.floor((ys - rect.ymin) / h), 0, G - 1).astype(np.int64)
    _uniq, lens = np.unique(cx * G + cy, return_counts=True)
    block = auto_cell_block(n, len(lens))
    padded = ((lens + block - 1) // block) * block
    return float(max(int(padded.sum()) / n, 1.0))


def prepare_cell_buckets(xs, ys, rect, G: int, block: int | None = 256):
    """Host-side bucketing: sort users by cell; pad each cell to ``block``.

    Returns ``(xs_s, ys_s, order, cell_map, n_blocks)`` where ``order``
    maps sorted rows back to original rows (−1 for padding) and
    ``cell_map[b]`` is the cell id of user block ``b``.  ``block=None``
    picks :func:`auto_cell_block` from the measured cell occupancy.

    Fully vectorized: run boundaries come from ``np.searchsorted`` on the
    sorted cell ids and every padded destination index is computed in one
    shot — O(N log N) for the sort, O(N + cells) after, replacing the old
    per-unique-cell rescan of the full cell array (O(U · cells) host time
    inside ``t_filter_s``).
    """
    xs = np.asarray(xs, np.float32)
    ys = np.asarray(ys, np.float32)
    n = len(xs)
    if n == 0:
        return (
            np.zeros(0, np.float32),
            np.zeros(0, np.float32),
            np.zeros(0, np.int64),
            np.zeros(0, np.int32),
            0,
        )
    w = rect.width / G
    h = rect.height / G
    cx = np.clip(np.floor((xs - rect.xmin) / w), 0, G - 1).astype(np.int64)
    cy = np.clip(np.floor((ys - rect.ymin) / h), 0, G - 1).astype(np.int64)
    cell = cx * G + cy
    order = np.argsort(cell, kind="stable")
    cell_sorted = cell[order]
    uniq = np.unique(cell)
    starts = np.searchsorted(cell_sorted, uniq, side="left")
    ends = np.searchsorted(cell_sorted, uniq, side="right")
    lens = ends - starts
    if block is None:
        block = auto_cell_block(n, len(uniq))
    block = int(block)
    padded = ((lens + block - 1) // block) * block
    offsets = np.concatenate([[0], np.cumsum(padded)[:-1]])
    total = int(padded.sum())
    xs_s = np.full(total, _PAD_COORD, np.float32)
    ys_s = np.full(total, _PAD_COORD, np.float32)
    ord_s = np.full(total, -1, np.int64)
    run_id = np.repeat(np.arange(len(uniq)), lens)
    dest = offsets[run_id] + (np.arange(n) - starts[run_id])
    xs_s[dest] = xs[order]
    ys_s[dest] = ys[order]
    ord_s[dest] = order
    cell_map = np.repeat(uniq, padded // block).astype(np.int32)
    return xs_s, ys_s, ord_s, cell_map, len(cell_map)


def _fill_cell_planes(planes: np.ndarray, grid: OccluderGrid, cells) -> None:
    """Write the ``[3, 3, L]`` coefficient planes of ``cells`` in place.

    List-slot positions are preserved (a ``-1`` hole left by
    ``refit_grid`` stays a degenerate plane in place), so an incremental
    re-pack is bit-identical to a fresh :func:`pack_cell_coeff_planes`.
    """
    cells = np.asarray(cells, np.int64)
    if not len(cells) or not len(grid.coeffs):
        return
    lists = grid.lists[cells]  # [C, L]
    valid = lists >= 0
    gathered = np.transpose(
        grid.coeffs[np.maximum(lists, 0)], (0, 2, 3, 1)
    )  # [C, 3, 3, L]
    deg = np.zeros((3, 3, 1), np.float32)
    deg[:, 2, :] = -1.0
    planes[cells, :, :, : lists.shape[1]] = np.where(
        valid[:, None, None, :], gathered, deg
    )


def pack_cell_coeff_planes(grid: OccluderGrid, lane_pad: int = 128):
    """``[G*G, 3(edges), 3(a,b,c), L]`` per-cell padded coefficient planes.

    Padding entries use the never-inside degenerate row (a=b=0, c=-1).
    ``lane_pad`` rounds ``L`` up to the TPU lane width for the compiled
    Mosaic kernel; the jnp reference execution passes ``lane_pad=1`` so
    its edge evaluations stop at the real max list length.
    """
    GG, L = grid.lists.shape
    L = max(lane_pad, ((L + lane_pad - 1) // lane_pad) * lane_pad, 1)
    planes = np.zeros((GG, 3, 3, L), np.float32)
    planes[:, :, 2, :] = -1.0  # degenerate default
    occupied = np.flatnonzero((grid.lists >= 0).any(axis=1))
    _fill_cell_planes(planes, grid, occupied)
    return planes


def repack_cell_coeff_planes(
    planes: np.ndarray, grid: OccluderGrid, cells: np.ndarray
) -> np.ndarray:
    """Incrementally re-pack only ``cells`` of a packed plane array.

    ``planes`` must have been packed from a grid with the same list width
    and lane padding (the refit contract: ``refit_grid`` preserves the
    padded list shape).  Returns a new array; the input is not mutated
    (cached indexes may still alias it).
    """
    out = planes.copy()
    _fill_cell_planes(out, grid, np.asarray(cells, np.int64))
    return out


def unsort_cell_counts(counts: np.ndarray, order: np.ndarray, n: int) -> np.ndarray:
    """Scatter bucketed counts ``[..., Ns]`` back to user order ``[..., n]``,
    dropping the ``order == -1`` padding rows."""
    counts = np.asarray(counts)
    ok = order >= 0
    out = np.zeros(counts.shape[:-1] + (n,), np.int32)
    out[..., order[ok]] = counts[..., ok]
    return out


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def grid_raycast_cells_batch(
    xs_sorted, ys_sorted, cell_map, planes, *, block: int, interpret: bool
):
    """Batched bucketed counting: one ``(q, user-block)`` grid dispatch.

    ``planes``: ``[Q, G*G, 3, 3, L]`` stacked per-query cell planes; the
    user sort (``xs_sorted``/``ys_sorted``/``cell_map``) is shared across
    queries (one domain rect per batch).  Each program instance stages one
    query's planes for one cell and evaluates ``[BU x L]`` edge functions.
    Returns partial-list hit counts ``[Q, n_blocks*block]`` int32 in
    sorted order — the caller adds ``base[q, cell]`` (kept out of SMEM:
    ``[Q, G*G]`` scalars would not fit the prefetch budget at serving Q).
    """
    n_blocks = int(cell_map.shape[0])
    n_sorted = n_blocks * block
    q_n = int(planes.shape[0])
    L = planes.shape[-1]

    def kernel(cell_map_ref, x_ref, y_ref, p_ref, o_ref):
        x = x_ref[0][:, None]  # [BU, 1]
        y = y_ref[0][:, None]
        p = p_ref[0, 0]  # [3, 3, L]
        inside = (x * p[0, 0][None, :] + y * p[0, 1][None, :] + p[0, 2][None, :]) >= 0.0
        inside &= (x * p[1, 0][None, :] + y * p[1, 1][None, :] + p[1, 2][None, :]) >= 0.0
        inside &= (x * p[2, 0][None, :] + y * p[2, 1][None, :] + p[2, 2][None, :]) >= 0.0
        o_ref[...] = jnp.sum(inside, axis=1, dtype=jnp.int32)[None, None, :]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # cell_map
        grid=(q_n, n_blocks),
        in_specs=[
            pl.BlockSpec((1, block), lambda i, j, cm: (0, j)),
            pl.BlockSpec((1, block), lambda i, j, cm: (0, j)),
            pl.BlockSpec((1, 1, 3, 3, L), lambda i, j, cm: (i, cm[j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block), lambda i, j, cm: (i, 0, j)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((q_n, 1, n_sorted), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(
        cell_map, xs_sorted.reshape(1, n_sorted), ys_sorted.reshape(1, n_sorted), planes
    ).reshape(q_n, n_sorted)
