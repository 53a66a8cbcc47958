"""Pallas kernel validation: interpret-mode vs pure-jnp oracle, swept over
shapes and dtypes (the per-kernel allclose requirement)."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.brute import rank_counts_np
from repro.core.geometry import Rect, points_in_tris_np
from repro.core.scene import build_scene
from repro.kernels import ops
from repro.kernels.ref import rank_count_ref, raycast_count_ref

RECT = Rect(0.0, 0.0, 1.0, 1.0)


def _scene(seed, M, k=5):
    rng = np.random.default_rng(seed)
    F = rng.random((max(M, 2), 2))
    sc = build_scene(F, 0, k, RECT, strategy="none")
    return sc, rng


@pytest.mark.parametrize("n_users", [1, 7, 128, 1000, 4096])
@pytest.mark.parametrize("n_fac", [2, 3, 40, 130])
def test_raycast_kernel_shape_sweep(n_users, n_fac):
    sc, rng = _scene(n_users * 1000 + n_fac, n_fac)
    U = rng.random((n_users, 2)).astype(np.float32)
    got = np.asarray(
        ops.raycast_count(U[:, 0], U[:, 1], sc.coeffs, backend="pallas", interpret=True)
    )
    want = np.asarray(raycast_count_ref(U[:, 0], U[:, 1], sc.coeffs))
    np.testing.assert_array_equal(got, want)
    # and the ref itself equals the fp64 host oracle
    host = points_in_tris_np(U.astype(np.float64), sc.coeffs.astype(np.float64)).sum(1)
    np.testing.assert_array_equal(want, host)


@pytest.mark.parametrize("block", [(8, 128), (64, 128), (256, 256)])
def test_raycast_kernel_block_shapes(block):
    bu, bm = block
    sc, rng = _scene(77, 60)
    U = rng.random((500, 2)).astype(np.float32)
    got = np.asarray(
        ops.raycast_count(
            U[:, 0], U[:, 1], sc.coeffs, backend="pallas", bu=bu, bm=bm, interpret=True
        )
    )
    want = np.asarray(raycast_count_ref(U[:, 0], U[:, 1], sc.coeffs))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_raycast_kernel_dtypes(dtype):
    """Inputs of either dtype agree after the kernel's f32 cast (scenes are
    constructed in f64 and handed to devices in f32)."""
    sc, rng = _scene(5, 40)
    U = rng.random((256, 2)).astype(dtype)
    got = np.asarray(
        ops.raycast_count(U[:, 0], U[:, 1], sc.coeffs, backend="pallas", interpret=True)
    )
    want = np.asarray(raycast_count_ref(U[:, 0], U[:, 1], sc.coeffs))
    np.testing.assert_array_equal(got, want)


def _non_tie_mask(U, F, q, eps=1e-6):
    """Users with no facility at a near-tie distance to q (strict-< flips at
    1-ulp boundaries are semantically arbitrary; exclude them)."""
    d2 = np.sum((U[:, None, :] - F[None, :, :]) ** 2, axis=-1)
    d2q = np.sum((U - q) ** 2, axis=1)
    return ~np.any(np.abs(d2 - d2q[:, None]) < eps * (1.0 + d2q[:, None]), axis=1)


@pytest.mark.parametrize("n_users,n_fac", [(1, 1), (33, 9), (700, 80), (2048, 1000)])
def test_rank_count_kernel_sweep(n_users, n_fac):
    rng = np.random.default_rng(n_users + n_fac)
    U = rng.random((n_users, 2))
    F = rng.random((n_fac, 2))
    qi = int(rng.integers(0, n_fac))
    got = np.asarray(
        ops.rank_count(U, F, F[qi], exclude=qi, backend="pallas", interpret=True)
    )
    want = rank_counts_np(U, F, F[qi], exclude=qi)
    ok = _non_tie_mask(U, F, F[qi])
    np.testing.assert_array_equal(got[ok], want[ok])
    assert np.all(np.abs(got - want) <= 1)  # ties move counts by at most 1


def test_rank_count_ref_matches_kernel_padding_semantics():
    rng = np.random.default_rng(0)
    U = rng.random((100, 2)).astype(np.float32)
    F = rng.random((37, 2)).astype(np.float32)
    q = F[3]
    thr = np.sum((U - q) ** 2, axis=1).astype(np.float32)
    ref = np.asarray(rank_count_ref(U[:, 0], U[:, 1], F[:, 0], F[:, 1], thr))
    krn = np.asarray(
        ops.rank_count(U, F, q, exclude=None, backend="pallas", interpret=True)
    )
    ok = _non_tie_mask(U.astype(np.float64), F.astype(np.float64), q.astype(np.float64))
    np.testing.assert_array_equal(ref[ok], krn[ok])


# ---- grid-culled kernel (BVH analogue) --------------------------------------

def _nonpruned_scene(seed, n_fac=200):
    rng = np.random.default_rng(seed)
    F = rng.random((n_fac, 2))
    sc = build_scene(F, 0, 10, RECT, strategy="none")
    U = rng.random((2000, 2))
    return sc, U


@pytest.mark.parametrize("G,block", [(8, 128), (16, 128), (16, 256), (32, 128)])
def test_grid_raycast_kernel_matches_f32_reference(G, block):
    """Grid Pallas kernel == dense f32 reference.  (Comparison is f32-to-f32:
    the f64 host oracle can differ by measure-zero edge-test ties.)"""
    from repro.core.grid import build_grid
    from repro.kernels.grid_raycast import pack_cell_coeff_planes, prepare_cell_buckets

    sc, U = _nonpruned_scene(G * 1000 + block)
    ref32 = np.asarray(
        raycast_count_ref(U[:, 0].astype(np.float32), U[:, 1].astype(np.float32), sc.coeffs)
    )
    g = build_grid(sc.tris[: sc.n_tris], sc.coeffs[: sc.n_tris], RECT, G=G)
    xs_s, ys_s, order, cell_map, nb = prepare_cell_buckets(U[:, 0], U[:, 1], RECT, G, block=block)
    planes = pack_cell_coeff_planes(g)
    counts = np.asarray(
        ops.grid_count_cells(
            xs_s, ys_s, cell_map, g.base, planes, block=block, interpret=True
        )
    )
    ok = order >= 0
    got = np.zeros(len(U), np.int64)
    got[order[ok]] = counts[ok]
    np.testing.assert_array_equal(got, ref32)


def _buckets_reference(xs, ys, rect, G, block):
    """The pre-vectorization bucketing (per-unique-cell rescan) as oracle."""
    xs = np.asarray(xs, np.float32)
    ys = np.asarray(ys, np.float32)
    w, h = rect.width / G, rect.height / G
    cx = np.clip(np.floor((xs - rect.xmin) / w), 0, G - 1).astype(np.int64)
    cy = np.clip(np.floor((ys - rect.ymin) / h), 0, G - 1).astype(np.int64)
    cell = cx * G + cy
    order = np.argsort(cell, kind="stable")
    xs_parts, ys_parts, ord_parts, cells = [], [], [], []
    for c in np.unique(cell):
        rows = order[cell[order] == c]
        pad = (-len(rows)) % block
        xs_parts.append(np.concatenate([xs[rows], np.full(pad, 2e9, np.float32)]))
        ys_parts.append(np.concatenate([ys[rows], np.full(pad, 2e9, np.float32)]))
        ord_parts.append(np.concatenate([rows, np.full(pad, -1, np.int64)]))
        cells.extend([int(c)] * ((len(rows) + pad) // block))
    return (
        np.concatenate(xs_parts),
        np.concatenate(ys_parts),
        np.concatenate(ord_parts),
        np.asarray(cells, np.int32),
        len(cells),
    )


@pytest.mark.parametrize("n,G,block", [(1, 8, 8), (97, 8, 16), (2000, 32, 8),
                                       (500, 64, 256)])
def test_prepare_cell_buckets_matches_reference(n, G, block):
    """The searchsorted-run-boundary bucketing is bit-identical to the old
    per-unique-cell rescan."""
    from repro.kernels.grid_raycast import prepare_cell_buckets

    rng = np.random.default_rng(n + G)
    U = rng.random((n, 2))
    got = prepare_cell_buckets(U[:, 0], U[:, 1], RECT, G, block=block)
    want = _buckets_reference(U[:, 0], U[:, 1], RECT, G, block)
    for g, w in zip(got[:4], want[:4]):
        np.testing.assert_array_equal(g, w)
    assert got[4] == want[4]


def test_prepare_cell_buckets_perf_shape():
    """Perf-shape regression (the old implementation rescanned the full
    cell array once per unique cell — O(U · cells) host time inside
    ``t_filter_s``): a many-unique-cells bucketing must run in linearithmic
    time.  The budget is ~50x above the vectorized implementation's
    measured cost and ~10x below the rescan's."""
    import time

    from repro.kernels.grid_raycast import prepare_cell_buckets

    rng = np.random.default_rng(0)
    U = rng.random((300_000, 2))
    prepare_cell_buckets(U[:1000, 0], U[:1000, 1], RECT, 64, block=8)  # warm
    t0 = time.perf_counter()
    xs_s, ys_s, order, cell_map, nb = prepare_cell_buckets(
        U[:, 0], U[:, 1], RECT, 64, block=8
    )
    dt = time.perf_counter() - t0
    assert nb > 3000  # actually a many-cells shape
    assert dt < 2.0, f"bucketing took {dt:.2f}s — host rescan regression?"


def test_auto_cell_block_tracks_occupancy(monkeypatch):
    from repro.kernels.grid_raycast import auto_cell_block

    assert auto_cell_block(100, 100) == 8  # sparse cells: minimal block
    assert auto_cell_block(4096, 16) == 256  # dense cells: capped at 256
    assert auto_cell_block(1000, 30) == 64  # mean 34 -> next pow2, clamped
    assert auto_cell_block(0, 0) == 8
    # the compiled kernel's lane-dimension blocks start at one 128-lane tile
    monkeypatch.setattr(ops, "pallas_interpret_default", lambda: False)
    assert auto_cell_block(100, 100) == 128
    assert auto_cell_block(1000, 30) == 128
    assert auto_cell_block(4096, 16) == 256


@pytest.mark.parametrize("Q", [1, 3])
def test_grid_raycast_batch_kernel_matches_batch_oracle(Q):
    """Batched (q, cell-block) kernel + ref execution == the batched jnp
    grid oracle, through the shared user sort and the unsort scatter."""
    from repro.core.grid import build_grid, grid_hit_counts_batch_jnp, stack_grids
    from repro.kernels.grid_raycast import (
        pack_cell_coeff_planes,
        prepare_cell_buckets,
        unsort_cell_counts,
    )

    G = 16
    rng = np.random.default_rng(Q)
    F = rng.random((150, 2))
    U = rng.random((1200, 2))
    scenes = [build_scene(F, q, 8, RECT, strategy="none") for q in range(Q)]
    grids = [build_grid(s.tris[: s.n_tris], s.coeffs[: s.n_tris], RECT, G=G)
             for s in scenes]
    base_s, lists_s, coeffs_s = stack_grids(grids)
    want = np.asarray(
        grid_hit_counts_batch_jnp(
            U[:, 0].astype(np.float32), U[:, 1].astype(np.float32),
            base_s, lists_s, coeffs_s, RECT, G,
        )
    )
    xs_s, ys_s, order, cell_map, nb = prepare_cell_buckets(
        U[:, 0], U[:, 1], RECT, G, block=None
    )
    block = xs_s.shape[0] // nb
    for lane_pad, backend in ((1, "ref"), (8, "pallas")):
        packs = [pack_cell_coeff_planes(g, lane_pad=lane_pad) for g in grids]
        L = max(p.shape[-1] for p in packs)
        planes = np.zeros((Q,) + packs[0].shape[:-1] + (L,), np.float32)
        planes[:, :, :, 2, :] = -1.0
        for i, p in enumerate(packs):
            planes[i, ..., : p.shape[-1]] = p
        base_q = np.stack([g.base for g in grids])
        counts = np.asarray(
            ops.grid_count_cells_batch(
                xs_s, ys_s, cell_map, base_q, planes,
                block=block, backend=backend, interpret=True,
            )
        )
        got = unsort_cell_counts(counts, order, len(U))
        np.testing.assert_array_equal(got, want, err_msg=backend)


def test_grid_raycast_cells_interpret_autodetect():
    """``interpret=None`` resolves via ``pallas_interpret_default()`` (so a
    real TPU would run the compiled Mosaic kernel) and matches the
    explicit interpret=True result on this CPU container."""
    from repro.core.grid import build_grid
    from repro.kernels.grid_raycast import pack_cell_coeff_planes, prepare_cell_buckets

    assert ops.pallas_interpret_default()  # CPU container: interpret is on
    sc, U = _nonpruned_scene(3, n_fac=80)
    g = build_grid(sc.tris[: sc.n_tris], sc.coeffs[: sc.n_tris], RECT, G=8)
    xs_s, ys_s, order, cell_map, nb = prepare_cell_buckets(
        U[:, 0], U[:, 1], RECT, 8, block=128
    )
    planes = pack_cell_coeff_planes(g)
    auto = np.asarray(
        ops.grid_count_cells(xs_s, ys_s, cell_map, g.base, planes, block=128)
    )
    explicit = np.asarray(
        ops.grid_count_cells(
            xs_s, ys_s, cell_map, g.base, planes, block=128, interpret=True
        )
    )
    np.testing.assert_array_equal(auto, explicit)


def test_grid_base_absorbs_fully_covering_triangles():
    """The per-cell base counter is the batched early-exit: most hits in a
    non-pruned scene come from fully-covering triangles, absorbed at zero
    per-user cost."""
    from repro.core.grid import build_grid

    sc, U = _nonpruned_scene(7)
    g = build_grid(sc.tris[: sc.n_tris], sc.coeffs[: sc.n_tris], RECT, G=16)
    assert g.base.max() > 0
    assert g.max_list < sc.n_tris  # partial lists are a strict subset
