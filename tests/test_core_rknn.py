"""End-to-end correctness of the RT-RkNN formulation (Lemma 3.4 etc.).

* Equivalence: ``hit-count < k  ⟺  brute-force rank < k`` for every
  backend (dense kernel, dense ref, grid, BVH-with-early-exit, brute).
* Pruning neutrality: InfZone-style and conservative pruning never change
  the answer set vs non-pruned scenes.
* Backend agreement on raw counts (where early exit doesn't saturate).
* Monochromatic reduction.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.brute import rknn_brute_np, rknn_mono_brute_np
from repro.core.bvh import build_bvh, bvh_hit_counts
from repro.core.geometry import Rect, points_in_tris_np
from repro.core.grid import build_grid, grid_hit_counts_jnp
from repro.core.rknn import BACKENDS, rknn_mono_query, rt_rknn_query
from repro.core.scene import build_scene

RECT = Rect(0.0, 0.0, 1.0, 1.0)


def _instance(seed, M=60, N=400):
    rng = np.random.default_rng(seed)
    return rng.random((M, 2)), rng.random((N, 2)), rng


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("seed,k", [(0, 1), (1, 3), (2, 10), (3, 25)])
def test_backends_match_brute(backend, seed, k):
    F, U, rng = _instance(seed)
    qi = int(rng.integers(0, len(F)))
    res = rt_rknn_query(F, U, qi, k, backend=backend)
    truth = rknn_brute_np(U, F, qi, k)
    np.testing.assert_array_equal(res.mask, truth)


@pytest.mark.parametrize("strategy", ["infzone", "conservative", "none"])
def test_pruning_neutrality(strategy):
    for seed in range(8):
        F, U, rng = _instance(seed, M=100, N=500)
        k = int(rng.integers(1, 12))
        qi = int(rng.integers(0, len(F)))
        res = rt_rknn_query(F, U, qi, k, backend="dense-ref", strategy=strategy)
        np.testing.assert_array_equal(res.mask, rknn_brute_np(U, F, qi, k))


def test_pruning_reduces_occluders():
    F, U, rng = _instance(11, M=1000, N=100)
    qi = 0
    pruned = build_scene(F, qi, 10, RECT, strategy="infzone")
    full = build_scene(F, qi, 10, RECT, strategy="none")
    assert pruned.n_occluders < full.n_occluders / 5  # paper Table 3 regime


@given(st.integers(0, 10_000), st.integers(1, 12))
@settings(max_examples=40, deadline=None)
def test_equivalence_property(seed, k):
    """Lemma 3.4 as a hypothesis property over random instances."""
    rng = np.random.default_rng(seed)
    F = rng.random((int(rng.integers(5, 80)), 2))
    U = rng.random((200, 2))
    qi = int(rng.integers(0, len(F)))
    sc = build_scene(F, qi, k, RECT, strategy="none")
    hits = points_in_tris_np(U, sc.coeffs.astype(np.float64)).sum(axis=1)
    np.testing.assert_array_equal(hits < k, rknn_brute_np(U, F, qi, k))


def test_grid_and_bvh_counts_equal_dense():
    F, U, rng = _instance(5, M=120, N=600)
    qi = 7
    sc = build_scene(F, qi, 6, RECT, strategy="infzone")
    dense = points_in_tris_np(U, sc.coeffs.astype(np.float64)).sum(axis=1)
    g = build_grid(sc.tris[: sc.n_tris], sc.coeffs[: sc.n_tris], RECT, G=48)
    gc = np.asarray(
        grid_hit_counts_jnp(U[:, 0], U[:, 1], g.base, g.lists, g.coeffs, RECT, 48)
    )
    np.testing.assert_array_equal(gc, dense)
    bvh = build_bvh(sc.tris[: sc.n_tris])
    bc = np.asarray(
        bvh_hit_counts(
            U[:, 0], U[:, 1], bvh.left, bvh.right, bvh.bbox, sc.coeffs[: sc.n_tris]
        )
    )
    np.testing.assert_array_equal(bc, dense)


def test_bvh_early_exit_saturates_at_k():
    F, U, rng = _instance(6, M=80, N=300)
    qi = 2
    k = 4
    sc = build_scene(F, qi, k, RECT, strategy="none")
    bvh = build_bvh(sc.tris[: sc.n_tris])
    counts = np.asarray(
        bvh_hit_counts(
            U[:, 0], U[:, 1], bvh.left, bvh.right, bvh.bbox, sc.coeffs[: sc.n_tris], k=k
        )
    )
    assert counts.max() <= k
    np.testing.assert_array_equal(counts < k, rknn_brute_np(U, F, qi, k))


@pytest.mark.parametrize("backend", ["dense-ref", "brute", "grid", "bvh"])
def test_monochromatic(backend):
    rng = np.random.default_rng(9)
    for _ in range(5):
        P = rng.random((70, 2))
        qi = int(rng.integers(0, 70))
        k = int(rng.integers(1, 6))
        res = rknn_mono_query(P, qi, k, backend=backend)
        np.testing.assert_array_equal(res.mask, rknn_mono_brute_np(P, qi, k))


@pytest.mark.parametrize("backend", ["dense-ref", "grid", "bvh"])
def test_mono_counts_self_hit_corrected(backend):
    """Regression for the mono off-by-one: ``counts`` must be self-hit
    corrected (number of OTHER points strictly closer than q), so that
    ``mask == counts < k`` and ``counts[mask]`` equal the mono brute ranks
    exactly (outside the mask they may sit at a saturated lower bound)."""
    rng = np.random.default_rng(21)
    P = rng.random((60, 2))
    qi, k = 5, 4
    res = rknn_mono_query(P, qi, k, backend=backend)
    # brute rank oracle: #others strictly closer to p than q is (a != p, q)
    q = P[qi]
    d2q = np.sum((P - q) ** 2, axis=1)
    d2 = np.sum((P[:, None, :] - P[None, :, :]) ** 2, axis=-1)
    closer = d2 < d2q[:, None]
    np.fill_diagonal(closer, False)
    closer[:, qi] = False
    want = closer.sum(axis=1)
    np.testing.assert_array_equal(res.mask, rknn_mono_brute_np(P, qi, k))
    np.testing.assert_array_equal(res.counts[res.mask], want[res.mask])
    mask_from_counts = res.counts < k
    mask_from_counts[qi] = False
    np.testing.assert_array_equal(res.mask, mask_from_counts)
    assert np.all(res.counts[~res.mask] >= 0)  # never negative after correction


def test_query_point_not_in_facility_set():
    """q may be an arbitrary point (bichromatic with external query)."""
    F, U, rng = _instance(12)
    q = np.array([0.37, 0.61])
    res = rt_rknn_query(F, U, q, 5, backend="dense-ref")
    truth = rknn_brute_np(U, F, q, 5)
    np.testing.assert_array_equal(res.mask, truth)


def test_k_one_and_k_huge():
    F, U, rng = _instance(13, M=30)
    qi = 3
    res1 = rt_rknn_query(F, U, qi, 1, backend="dense-ref")
    np.testing.assert_array_equal(res1.mask, rknn_brute_np(U, F, qi, 1))
    res2 = rt_rknn_query(F, U, qi, len(F) + 5, backend="dense-ref")
    assert res2.mask.all()  # k >= |F| accepts everyone
