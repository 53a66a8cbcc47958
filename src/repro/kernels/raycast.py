"""Pallas TPU kernel: dense occluder hit counting (the paper's hot spot).

This is the ray-casting stage of Algorithm 1 adapted to the TPU (DESIGN.md
§2): the vertical-ray / layered-triangle intersection collapses to a 2-D
edge-function test, so the kernel is a tiled ``[users x occluders]``
containment-count:

* users are tiled along the grid's first axis — each program instance holds
  a ``(BU,)`` block of x/y in VMEM,
* all three edge-coefficient planes (``A, B, C`` of shape ``[3, M]``) are
  tiled along the second grid axis in lane-aligned ``(3, BM)`` blocks,
* the body broadcasts to a ``[BU, BM]`` mask (6 FMA + 3 compares + 2 ANDs
  per pair on the VPU) and accumulates row sums into the int32 output
  block, which is revisited across the ``M`` grid axis (accumulator
  pattern; zeroed at ``j == 0``).

The batched variant (``raycast_count_batch_kernel_call``) prepends a
``[Q]`` query axis to the grid: each program instance additionally selects
one query's coefficient planes, so a whole multi-query batch is one kernel
dispatch over shared user blocks — the serving hot path
(``repro.core.rknn.rt_rknn_query_batch``).

Early ray termination (Alg. 2 line 16) has no SIMD analogue; after
InfZone-style pruning the scene is so small (``m`` ≈ 40–70) that the sweep
is *user-read bound*, not test bound — see EXPERIMENTS.md §Perf-RkNN for
the measured arithmetic-intensity argument.

Tiling rules of the compiled (Mosaic) kernels: a block's last two
dimensions must be multiples of ``(8, 128)`` or span the whole array.
The 1-D user blocks therefore take ``BU = 1024`` (one XLA ``T(1024)``
tile) or the whole padded user array (``_effective_blocks`` in
:mod:`repro.kernels.ops` picks one of the two); the batched kernel writes
a ``[Q, 1, Np]`` output through ``(1, 1, BU)`` blocks, whose unit middle
axis spans its whole dimension.  VMEM at the default tiles (BU=1024,
BM=512): x/y blocks 8 KiB, coefficient blocks 3·2·6 KiB, the
``[BU, BM]`` f32 broadcast temps ~2 MiB×3 live — under the ~16 MiB/core
budget with double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "raycast_count_kernel_call",
    "raycast_count_batch_kernel_call",
    "DEFAULT_BU",
    "DEFAULT_BM",
]

DEFAULT_BU = 1024  # users per block (one T(1024) tile of a 1-D array)
DEFAULT_BM = 512  # occluders per block (4 lanes of 128)


def _raycast_kernel(x_ref, y_ref, a_ref, b_ref, c_ref, o_ref):
    """One (user-block, occluder-block) tile of the containment count."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...][:, None]  # [BU, 1]
    y = y_ref[...][:, None]
    a = a_ref[...]  # [3, BM]
    b = b_ref[...]
    c = c_ref[...]
    inside = (x * a[0][None, :] + y * b[0][None, :] + c[0][None, :]) >= 0.0
    inside &= (x * a[1][None, :] + y * b[1][None, :] + c[1][None, :]) >= 0.0
    inside &= (x * a[2][None, :] + y * b[2][None, :] + c[2][None, :]) >= 0.0
    o_ref[...] += jnp.sum(inside, axis=1, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("bu", "bm", "interpret"))
def raycast_count_kernel_call(
    xs, ys, A, B, C, *, bu: int = DEFAULT_BU, bm: int = DEFAULT_BM, interpret: bool
):
    """Invoke the kernel on pre-padded inputs.

    ``xs, ys``: ``[Np]`` (``Np % bu == 0``); ``A, B, C``: ``[3, Mp]``
    (``Mp % bm == 0``) edge coefficients; padding slots must be degenerate
    (all-zero with ``c = -1``) so they contribute no hits.  Returns ``[Np]``
    int32 counts.  Padding/unpadding lives in :mod:`repro.kernels.ops`.
    """
    n_p = xs.shape[0]
    m_p = A.shape[1]
    grid = (n_p // bu, m_p // bm)
    return pl.pallas_call(
        _raycast_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bu,), lambda i, j: (i,)),
            pl.BlockSpec((bu,), lambda i, j: (i,)),
            pl.BlockSpec((3, bm), lambda i, j: (0, j)),
            pl.BlockSpec((3, bm), lambda i, j: (0, j)),
            pl.BlockSpec((3, bm), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bu,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_p,), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xs, ys, A, B, C)


def _raycast_batch_kernel(x_ref, y_ref, a_ref, b_ref, c_ref, o_ref):
    """One (query, user-block, occluder-block) tile of the batched count.

    Identical math to :func:`_raycast_kernel`; the leading grid axis selects
    the query's coefficient planes while the user blocks are shared across
    all queries (the serving layout: one resident user set, many scenes).
    """
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...][:, None]  # [BU, 1]
    y = y_ref[...][:, None]
    a = a_ref[0]  # [3, BM] — this query's coefficient planes
    b = b_ref[0]
    c = c_ref[0]
    inside = (x * a[0][None, :] + y * b[0][None, :] + c[0][None, :]) >= 0.0
    inside &= (x * a[1][None, :] + y * b[1][None, :] + c[1][None, :]) >= 0.0
    inside &= (x * a[2][None, :] + y * b[2][None, :] + c[2][None, :]) >= 0.0
    o_ref[...] += jnp.sum(inside, axis=1, dtype=jnp.int32)[None, None, :]


@functools.partial(jax.jit, static_argnames=("bu", "bm", "interpret"))
def raycast_count_batch_kernel_call(
    xs, ys, A, B, C, *, bu: int = DEFAULT_BU, bm: int = DEFAULT_BM, interpret: bool
):
    """Batched multi-query invoke on pre-padded inputs.

    ``xs, ys``: ``[Np]`` shared users (``Np % bu == 0``); ``A, B, C``:
    ``[Q, 3, Mp]`` per-query edge-coefficient planes (``Mp % bm == 0``,
    padding degenerate).  Returns ``[Q, Np]`` int32 counts — one kernel
    dispatch for the whole query batch instead of ``Q`` separate launches.
    The kernel writes ``[Q, 1, Np]`` (a ``(1, BU)`` block of a ``[Q, Np]``
    array breaks the tiling rule) and the unit axis is dropped here.
    """
    n_p = xs.shape[0]
    q_n, _, m_p = A.shape
    grid = (q_n, n_p // bu, m_p // bm)
    return pl.pallas_call(
        _raycast_batch_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bu,), lambda q, i, j: (i,)),
            pl.BlockSpec((bu,), lambda q, i, j: (i,)),
            pl.BlockSpec((1, 3, bm), lambda q, i, j: (q, 0, j)),
            pl.BlockSpec((1, 3, bm), lambda q, i, j: (q, 0, j)),
            pl.BlockSpec((1, 3, bm), lambda q, i, j: (q, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, bu), lambda q, i, j: (q, 0, i)),
        out_shape=jax.ShapeDtypeStruct((q_n, 1, n_p), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xs, ys, A, B, C).reshape(q_n, n_p)
