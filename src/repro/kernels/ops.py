"""Public jit'd wrappers around the Pallas kernels.

Handles padding to tile multiples, layout packing (``[M,3,3]`` coeffs →
``A/B/C`` planes), backend selection and unpadding.  Where JAX's default
backend is a TPU the kernels run compiled by Mosaic; on any other backend
they run in interpret mode (bit-faithful to the TPU lowering's
semantics) — :func:`pallas_interpret_default` decides, from
``jax.default_backend()`` alone.  ``backend="ref"`` routes to the
pure-jnp oracle — the fast path on CPU and the baseline the kernels are
benchmarked against.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.grid_raycast import grid_raycast_cells_batch
from repro.kernels.rank_count import rank_count_kernel_call
from repro.kernels.raycast import (
    raycast_count_batch_kernel_call,
    raycast_count_kernel_call,
)
from repro.obs.jitmon import track_jit

__all__ = [
    "raycast_count",
    "raycast_count_batch",
    "rank_count",
    "rank_count_batch",
    "grid_count_cells",
    "grid_count_cells_batch",
    "pallas_interpret_default",
]

_USER_CHUNK = 32_768  # bounds the [chunk, M, 3] broadcast temp (~40 MB f32)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _raycast_ref_chunked(xs, ys, coeffs, chunk: int = _USER_CHUNK):
    """Jitted + user-chunked oracle path (the fast CPU execution)."""
    n = xs.shape[0]
    pad = (-n) % chunk
    xs_p = jnp.pad(xs, (0, pad))
    ys_p = jnp.pad(ys, (0, pad))
    xc = xs_p.reshape(-1, chunk)
    yc = ys_p.reshape(-1, chunk)
    out = jax.lax.map(lambda xy: _ref.raycast_count_ref(xy[0], xy[1], coeffs), (xc, yc))
    return out.reshape(-1)[:n]


@jax.jit
def _rank_ref_jit(xs, ys, fx, fy, thr):
    return _ref.rank_count_ref(xs, ys, fx, fy, thr)


def pallas_interpret_default() -> bool:
    """True unless JAX's default backend is a TPU (compiled Mosaic kernels)."""
    return jax.default_backend() != "tpu"


def _pad1(x: jnp.ndarray, mult: int, value: float) -> jnp.ndarray:
    n = x.shape[0]
    p = (-n) % mult
    if p == 0:
        return x
    return jnp.concatenate([x, jnp.full((p,), value, x.dtype)])


def _effective_blocks(n: int, m: int, bu: int, bm: int) -> tuple[int, int]:
    """Shrink tile sizes to the pow2 envelope of the problem.

    Shared by the single-query and batched wrappers so their layouts can't
    drift apart.  A problem smaller than a tile becomes one whole-array
    block; a larger one keeps the requested tile, so the default
    ``bu = 1024`` satisfies the compiled kernels' 1-D tiling rule."""
    bu_eff = min(bu, max(8, 1 << max(int(np.ceil(np.log2(max(n, 1)))), 3)))
    bm_eff = min(bm, max(128, 1 << max(int(np.ceil(np.log2(max(m, 1)))), 7)))
    return bu_eff, bm_eff


def _coeff_planes(coeffs, bm_eff: int):
    """``[..., M, 3, 3]`` coeffs → ``(A, B, C)`` ``[..., 3, Mp]`` planes,
    lane-padded with never-inside rows (``a = b = 0, c = -1``)."""
    A = jnp.swapaxes(coeffs[..., 0], -1, -2)
    B = jnp.swapaxes(coeffs[..., 1], -1, -2)
    C = jnp.swapaxes(coeffs[..., 2], -1, -2)
    pm = (-A.shape[-1]) % bm_eff
    if pm:
        pad = A.shape[:-1] + (pm,)
        A = jnp.concatenate([A, jnp.zeros(pad, A.dtype)], axis=-1)
        B = jnp.concatenate([B, jnp.zeros(pad, B.dtype)], axis=-1)
        C = jnp.concatenate([C, jnp.full(pad, -1.0, C.dtype)], axis=-1)
    return A, B, C


def raycast_count(
    xs,
    ys,
    coeffs,
    *,
    backend: str = "pallas",
    bu: int = 1024,
    bm: int = 512,
    interpret: bool | None = None,
):
    """Hit counts of users against occluder edge functions.

    ``xs, ys``: ``[N]``; ``coeffs``: ``[M, 3, 3]``.  Returns ``[N]`` int32.
    Padding slots are degenerate (``a=b=0, c=-1``) and contribute nothing.
    """
    xs = jnp.asarray(xs, jnp.float32)
    ys = jnp.asarray(ys, jnp.float32)
    coeffs = jnp.asarray(coeffs, jnp.float32)
    if backend == "ref":
        if xs.shape[0] > _USER_CHUNK:
            return _raycast_ref_chunked(xs, ys, coeffs)
        return _ref.raycast_count_ref(xs, ys, coeffs)
    if backend != "pallas":
        raise ValueError(f"unknown backend {backend!r}")
    if interpret is None:
        interpret = pallas_interpret_default()
    n = xs.shape[0]
    bu_eff, bm_eff = _effective_blocks(n, coeffs.shape[0], bu, bm)
    xs_p = _pad1(xs, bu_eff, 0.0)
    ys_p = _pad1(ys, bu_eff, 0.0)
    A, B, C = _coeff_planes(coeffs, bm_eff)
    out = raycast_count_kernel_call(
        xs_p, ys_p, A, B, C, bu=bu_eff, bm=bm_eff, interpret=bool(interpret)
    )
    return out[:n]


@jax.jit
def _raycast_batch_ref_jit(xs, ys, coeffs):
    return _ref.raycast_count_batch_ref(xs, ys, coeffs)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _raycast_batch_ref_chunked(xs, ys, coeffs, chunk: int = _USER_CHUNK):
    """Jitted + user-chunked batched oracle: bounds the ``[Q, chunk, M, 3]``
    broadcast temp the same way the single-query path does, so large user
    sets don't blow the host heap under a big query batch."""
    n = xs.shape[0]
    pad = (-n) % chunk
    xs_p = jnp.pad(xs, (0, pad))
    ys_p = jnp.pad(ys, (0, pad))
    xc = xs_p.reshape(-1, chunk)
    yc = ys_p.reshape(-1, chunk)
    out = jax.lax.map(
        lambda xy: _ref.raycast_count_batch_ref(xy[0], xy[1], coeffs), (xc, yc)
    )  # [n_chunks, Q, chunk]
    return jnp.moveaxis(out, 1, 0).reshape(coeffs.shape[0], -1)[:, :n]


def raycast_count_batch(
    xs,
    ys,
    coeffs,
    *,
    backend: str = "pallas",
    bu: int = 1024,
    bm: int = 512,
    interpret: bool | None = None,
):
    """Batched multi-query hit counts: one dispatch for a whole query batch.

    ``xs, ys``: ``[N]`` shared users; ``coeffs``: ``[Q, Mp, 3, 3]`` stacked
    per-query edge functions (padded degenerate — see
    :func:`repro.core.scene.pad_scene_arrays`).  Returns ``[Q, N]`` int32.
    ``backend="ref"`` runs the jitted vmap oracle (the fast CPU path);
    ``backend="pallas"`` runs the ``[Q]``-grid-axis kernel.
    """
    xs = jnp.asarray(xs, jnp.float32)
    ys = jnp.asarray(ys, jnp.float32)
    coeffs = jnp.asarray(coeffs, jnp.float32)
    if coeffs.ndim != 4:
        raise ValueError(f"coeffs must be [Q, Mp, 3, 3], got {coeffs.shape}")
    if backend == "ref":
        # keep the [Q, chunk, M, 3] broadcast temp the same size as the
        # single-query path's [chunk, M, 3] by shrinking chunk with Q
        chunk = max(1024, _USER_CHUNK // max(int(coeffs.shape[0]), 1))
        if xs.shape[0] > chunk:
            return _raycast_batch_ref_chunked(xs, ys, coeffs, chunk=chunk)
        return _raycast_batch_ref_jit(xs, ys, coeffs)
    if backend != "pallas":
        raise ValueError(f"unknown backend {backend!r}")
    if interpret is None:
        interpret = pallas_interpret_default()
    n = xs.shape[0]
    bu_eff, bm_eff = _effective_blocks(n, coeffs.shape[1], bu, bm)
    xs_p = _pad1(xs, bu_eff, 0.0)
    ys_p = _pad1(ys, bu_eff, 0.0)
    A, B, C = _coeff_planes(coeffs, bm_eff)
    out = raycast_count_batch_kernel_call(
        xs_p, ys_p, A, B, C, bu=bu_eff, bm=bm_eff, interpret=bool(interpret)
    )
    return out[:, :n]


#: Element budget for one [Q, chunk, block, L] edge-evaluation temp of the
#: bucketed ref path (~16 MB f32) — mirrors _USER_CHUNK's role on the
#: dense path.
_CELL_CHUNK_ELEMS = 4_194_304


@functools.partial(jax.jit, static_argnames=("chunk",))
def _grid_cells_batch_ref_chunked(xs_b, ys_b, cell_map, planes, chunk: int):
    """Jitted + block-chunked bucketed oracle: bounds the
    ``[Q, chunk, block, L]`` edge-evaluation temp so large user sets don't
    blow the host heap under a big query batch (same convention as
    ``_raycast_batch_ref_chunked``)."""
    nb, block = xs_b.shape
    pad = (-nb) % chunk
    xs_p = jnp.pad(xs_b, ((0, pad), (0, 0)), constant_values=2e9)
    ys_p = jnp.pad(ys_b, ((0, pad), (0, 0)), constant_values=2e9)
    cm_p = jnp.pad(cell_map, (0, pad))
    xc = xs_p.reshape(-1, chunk, block)
    yc = ys_p.reshape(-1, chunk, block)
    cc = cm_p.reshape(-1, chunk)

    def one(args):
        x, y, cm = args
        return _ref.grid_cells_count_batch_ref(
            x.reshape(-1), y.reshape(-1), cm, planes
        )  # [Q, chunk*block]

    out = jax.lax.map(one, (xc, yc, cc))  # [n_chunks, Q, chunk*block]
    q_n = planes.shape[0]
    return jnp.moveaxis(out, 1, 0).reshape(q_n, -1)[:, : nb * block]


@jax.jit
def _grid_cells_batch_ref_jit(xs_s, ys_s, cell_map, planes):
    return _ref.grid_cells_count_batch_ref(xs_s, ys_s, cell_map, planes)


def grid_count_cells_batch(
    xs_sorted,
    ys_sorted,
    cell_map,
    base,
    planes,
    *,
    block: int,
    backend: str = "pallas",
    interpret: bool | None = None,
):
    """Batched cell-bucketed grid hit counts: ``[Q, n_sorted]`` int32.

    ``xs_sorted/ys_sorted``: ``[n_blocks*block]`` cell-sorted padded users
    (from :func:`repro.kernels.grid_raycast.prepare_cell_buckets` — the
    sort is shared across the batch's queries, one domain rect);
    ``cell_map``: ``[n_blocks]``; ``base``: ``[Q, G*G]``; ``planes``:
    ``[Q, G*G, 3, 3, L]`` stacked per-query cell coefficient planes.
    Counts stay in sorted order — unscatter with
    :func:`repro.kernels.grid_raycast.unsort_cell_counts`.
    """
    xs_sorted = jnp.asarray(xs_sorted, jnp.float32)
    ys_sorted = jnp.asarray(ys_sorted, jnp.float32)
    base = jnp.asarray(base, jnp.int32)
    planes = jnp.asarray(planes, jnp.float32)
    q_n = planes.shape[0]
    nb = int(cell_map.shape[0])
    if nb == 0:
        return jnp.zeros((q_n, 0), jnp.int32)
    cell_map = jnp.asarray(cell_map, jnp.int32)
    if backend == "ref":
        L = int(planes.shape[-1])
        chunk = max(int(_CELL_CHUNK_ELEMS) // max(q_n * block * L, 1), 1)
        if chunk < nb:
            chunk = max(1 << int(np.log2(chunk)), 1)  # sticky pow2: fewer retraces
            counts = _grid_cells_batch_ref_chunked(
                xs_sorted.reshape(nb, block),
                ys_sorted.reshape(nb, block),
                cell_map,
                planes,
                chunk=chunk,
            )
        else:
            counts = _grid_cells_batch_ref_jit(xs_sorted, ys_sorted, cell_map, planes)
    elif backend == "pallas":
        if interpret is None:
            interpret = pallas_interpret_default()
        counts = grid_raycast_cells_batch(
            xs_sorted, ys_sorted, cell_map, planes,
            block=block, interpret=bool(interpret),
        )
    else:
        raise ValueError(f"unknown backend {backend!r}")
    # base[q, cell] is added outside the kernel: a [Q, G*G] scalar table
    # has no place in the prefetch SMEM budget at serving Q
    cells_u = jnp.repeat(cell_map, block)  # [n_sorted]
    return counts + base[:, cells_u]


def grid_count_cells(
    xs_sorted,
    ys_sorted,
    cell_map,
    base,
    planes,
    *,
    block: int,
    backend: str = "pallas",
    interpret: bool | None = None,
):
    """Single-query bucketed grid hit counts: ``[n_sorted]`` int32.

    ``base``: ``[G*G]``; ``planes``: ``[G*G, 3, 3, L]``.  Same contract as
    :func:`grid_count_cells_batch` at ``Q = 1``.
    """
    return grid_count_cells_batch(
        xs_sorted,
        ys_sorted,
        cell_map,
        jnp.asarray(base, jnp.int32)[None],
        jnp.asarray(planes, jnp.float32)[None],
        block=block,
        backend=backend,
        interpret=interpret,
    )[0]


def rank_count(
    users,
    facilities,
    q,
    *,
    exclude: int | None = None,
    backend: str = "pallas",
    bu: int = 1024,
    bm: int = 1024,
    interpret: bool | None = None,
):
    """#facilities strictly closer than ``q`` per user (``[N]`` int32).

    ``users``: ``[N, 2]``; ``facilities``: ``[M, 2]``; ``q``: ``[2]``.
    ``exclude`` masks one facility row (the query itself for in-set
    queries) by pushing it to infinity.
    """
    users = jnp.asarray(users, jnp.float32)
    facilities = jnp.asarray(facilities, jnp.float32)
    q = jnp.asarray(q, jnp.float32)
    xs, ys = users[:, 0], users[:, 1]
    fx, fy = facilities[:, 0], facilities[:, 1]
    if exclude is not None:
        fx = fx.at[exclude].set(jnp.inf)
        fy = fy.at[exclude].set(jnp.inf)
    thr = (xs - q[0]) ** 2 + (ys - q[1]) ** 2
    if backend == "ref":
        return _rank_ref_jit(xs, ys, fx, fy, thr)
    if backend != "pallas":
        raise ValueError(f"unknown backend {backend!r}")
    if interpret is None:
        interpret = pallas_interpret_default()
    n = xs.shape[0]
    bu_eff, bm_eff = _effective_blocks(n, fx.shape[0], bu, bm)
    xs_p = _pad1(xs, bu_eff, 0.0)
    ys_p = _pad1(ys, bu_eff, 0.0)
    thr_p = _pad1(thr, bu_eff, 0.0)
    fx_p = _pad1(fx, bm_eff, jnp.inf)
    fy_p = _pad1(fy, bm_eff, jnp.inf)
    out = rank_count_kernel_call(
        xs_p, ys_p, fx_p, fy_p, thr_p, bu=bu_eff, bm=bm_eff, interpret=bool(interpret)
    )
    return out[:n]


@jax.jit
def _rank_batch_ref_jit(xs, ys, fx, fy, thr):
    return _ref.rank_count_batch_ref(xs, ys, fx, fy, thr)


def rank_count_batch(users, facilities, q_pts, *, exclude=None):
    """Batched distance-rank counting: ``[Q, N]`` int32 in one dispatch.

    ``users``: ``[N, 2]``; ``facilities``: ``[M, 2]``; ``q_pts``: ``[Q, 2]``
    query points.  ``exclude`` is an optional length-``Q`` sequence of
    facility rows to mask per query (``-1`` / ``None`` entries mask
    nothing) — the batched analogue of :func:`rank_count`'s ``exclude``.
    """
    users = jnp.asarray(users, jnp.float32)
    facilities = jnp.asarray(facilities, jnp.float32)
    q_pts = jnp.asarray(q_pts, jnp.float32)
    xs, ys = users[:, 0], users[:, 1]
    q_n = q_pts.shape[0]
    fx = jnp.broadcast_to(facilities[None, :, 0], (q_n, facilities.shape[0]))
    fy = jnp.broadcast_to(facilities[None, :, 1], (q_n, facilities.shape[0]))
    if exclude is not None:
        excl = np.asarray(
            [-1 if e is None else int(e) for e in exclude], dtype=np.int32
        )
        rows = np.flatnonzero(excl >= 0)
        if len(rows):
            fx = fx.at[rows, excl[rows]].set(jnp.inf)
            fy = fy.at[rows, excl[rows]].set(jnp.inf)
    thr = (xs[None, :] - q_pts[:, 0, None]) ** 2 + (ys[None, :] - q_pts[:, 1, None]) ** 2
    return _rank_batch_ref_jit(xs, ys, fx, fy, thr)


# ---------------------------------------------------------------------------
# compile accounting: every jitted kernel and reference entry point is
# wrapped so an unexpected retrace (a pad-bucket miss storm reshaping the
# dense oracle, a chunk-size change, a new cell-block count) surfaces as
# ``compile.count{fn=...}`` in the process metrics registry instead of a
# mystery latency spike.
# ---------------------------------------------------------------------------
raycast_count_kernel_call = track_jit(raycast_count_kernel_call, "raycast_kernel")
raycast_count_batch_kernel_call = track_jit(
    raycast_count_batch_kernel_call, "raycast_batch_kernel"
)
rank_count_kernel_call = track_jit(rank_count_kernel_call, "rank_kernel")
grid_raycast_cells_batch = track_jit(grid_raycast_cells_batch, "grid_cells_batch_kernel")
_raycast_ref_chunked = track_jit(_raycast_ref_chunked, "raycast_ref")
_rank_ref_jit = track_jit(_rank_ref_jit, "rank_ref")
_raycast_batch_ref_jit = track_jit(_raycast_batch_ref_jit, "raycast_batch_ref")
_raycast_batch_ref_chunked = track_jit(
    _raycast_batch_ref_chunked, "raycast_batch_ref_chunked"
)
_grid_cells_batch_ref_chunked = track_jit(
    _grid_cells_batch_ref_chunked, "grid_cells_batch_ref_chunked"
)
_grid_cells_batch_ref_jit = track_jit(
    _grid_cells_batch_ref_jit, "grid_cells_batch_ref"
)
_rank_batch_ref_jit = track_jit(_rank_batch_ref_jit, "rank_batch_ref")
