"""Property tests for the geometric core (hypothesis-driven).

The central invariant (paper Def. 3.1): for any facility pair, the occluder
triangles' coverage *inside the domain* equals the bisector's invalid
half-plane.  Plus edge-function/clip/area unit checks.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.geometry import (
    Rect,
    bisector,
    clip_polygon_halfplane,
    edge_coeffs,
    ensure_ccw,
    points_in_tris_np,
    polygon_area,
    signed_area,
)
from repro.core.occluders import occluder_triangles

RECT = Rect(0.0, 0.0, 1.0, 1.0)
coord = st.floats(0.01, 0.99, allow_nan=False, allow_infinity=False)


@st.composite
def facility_pair(draw):
    ax, ay = draw(coord), draw(coord)
    qx, qy = draw(coord), draw(coord)
    # keep the pair separated so the bisector is well-conditioned
    if abs(ax - qx) + abs(ay - qy) < 1e-3:
        qx = min(0.99, qx + 0.1)
    return np.array([ax, ay]), np.array([qx, qy])


@given(facility_pair(), st.integers(0, 10_000))
@settings(max_examples=200, deadline=None)
def test_occluder_equals_invalid_halfplane(pair, seed):
    a, q = pair
    tris = occluder_triangles(a, q, RECT)
    rng = np.random.default_rng(seed)
    pts = RECT.sample(rng, 256)
    n, c = bisector(a, q)
    margin = 1e-9 * (1 + abs(c))
    strict_invalid = pts @ n - c < -margin
    strict_valid = pts @ n - c > margin
    if len(tris):
        inside = points_in_tris_np(pts, edge_coeffs(tris)).any(axis=1)
    else:
        inside = np.zeros(len(pts), bool)
    assert not np.any(strict_invalid & ~inside), "invalid-side point not covered"
    assert not np.any(strict_valid & inside), "valid-side point wrongly covered"


@pytest.mark.parametrize(
    "a,q",
    [
        ((0.2, 0.5), (0.8, 0.5)),  # vertical bisector (case c)
        ((0.5, 0.1), (0.5, 0.9)),  # horizontal bisector (case d)
        ((0.3, 0.3), (0.3, 0.8)),
        ((0.1, 0.1), (0.9, 0.9)),  # diagonal, extended case likely
        ((0.45, 0.5), (0.55, 0.5)),
    ],
)
def test_axis_aligned_and_diagonal_cases(a, q):
    a, q = np.asarray(a, float), np.asarray(q, float)
    tris = occluder_triangles(a, q, RECT)
    assert 1 <= len(tris) <= 2
    rng = np.random.default_rng(0)
    pts = RECT.sample(rng, 4096)
    n, c = bisector(a, q)
    inv = pts @ n - c < -1e-12
    val = pts @ n - c > 1e-12
    inside = points_in_tris_np(pts, edge_coeffs(tris)).any(axis=1)
    assert not np.any(inv & ~inside) and not np.any(val & inside)


def test_degenerate_pair_empty():
    a = np.array([0.5, 0.5])
    assert len(occluder_triangles(a, a, RECT)) == 0


def test_edge_coeffs_orientation_invariance():
    tri = np.array([[[0.1, 0.1], [0.9, 0.2], [0.4, 0.8]]])
    rng = np.random.default_rng(3)
    pts = RECT.sample(rng, 512)
    inside_ccw = points_in_tris_np(pts, edge_coeffs(ensure_ccw(tri)))
    flipped = tri[:, ::-1, :]
    inside_flip = points_in_tris_np(pts, edge_coeffs(ensure_ccw(flipped)))
    np.testing.assert_array_equal(inside_ccw, inside_flip)
    assert signed_area(ensure_ccw(flipped))[0] > 0


@given(facility_pair())
@settings(max_examples=100, deadline=None)
def test_clip_area_consistency(pair):
    """Shoelace area of the clipped invalid polygon equals MC estimate."""
    a, q = pair
    n, c = bisector(a, q)
    poly = clip_polygon_halfplane(RECT.as_polygon(), n, c)  # p.n <= c side
    area = abs(polygon_area(poly))
    rng = np.random.default_rng(0)
    pts = RECT.sample(rng, 20_000)
    mc = float(np.mean(pts @ n - c < 0))
    assert abs(area - mc) < 0.02


def test_degenerate_triangle_coeffs_never_inside():
    tri = np.array([[[0.5, 0.5], [0.5, 0.5], [0.5, 0.5]]])
    cf = edge_coeffs(tri)
    pts = np.array([[0.5, 0.5], [0.1, 0.9]])
    assert not points_in_tris_np(pts, cf).any()
