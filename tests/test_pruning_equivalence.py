"""``prune_facilities`` against the full-grid loop it replaced.

The coverage test now evaluates each chunk's bisectors once, at the
corners of live (possibly-zone) cells only, and reuses those signs for
the survivors' coverage update.  The scene, the dynamic subsystem's
``safe_radius`` certificate and persisted scenes all depend on the result
being unchanged, so ``keep`` and every ``PruneStats`` field are compared
with ``==`` against the previous implementation, kept here verbatim:
``_FullGrid`` evaluates every bisector on all (G+1)² corners.

The ``prune.cells{kind}`` counters say how many cells the cover test
evaluated against the whole grid.
"""

import time

import numpy as np
import pytest

from repro.core.geometry import Rect, bisector
from repro.core.grid import build_sleep, build_yield_ratio
from repro.core.pruning import (
    STRATEGIES,
    PruneStats,
    adaptive_grid,
    prune_facilities,
)
from repro.data.spatial import road_network_points, uniform_points
from repro.obs import process_registry, render_registries

# ---------------------------------------------------------------------------
# Reference: the full-grid pruning loop, verbatim
# ---------------------------------------------------------------------------


class _FullGrid:
    """Full-containment coverage counts over a G x G cell grid."""

    def __init__(self, rect: Rect, grid: int):
        self.rect = rect
        self.G = grid
        xs = np.linspace(rect.xmin, rect.xmax, grid + 1)
        ys = np.linspace(rect.ymin, rect.ymax, grid + 1)
        cx, cy = np.meshgrid(xs, ys, indexing="ij")  # corner lattice [G+1, G+1]
        self._corners = np.stack([cx, cy], axis=-1)
        self.counts = np.zeros((grid, grid), dtype=np.int32)

    def corner_signed_batch(self, n: np.ndarray, c: np.ndarray) -> np.ndarray:
        """[B, G+1, G+1] signed values for a batch of half-planes."""
        v = np.einsum("xyk,bk->bxy", self._corners, np.asarray(n, dtype=np.float64))
        return v - np.asarray(c, dtype=np.float64)[:, None, None]

    def possibly_zone(self, k: int) -> np.ndarray:
        """Cells that may still contain influence-zone points: ``[G, G]``."""
        return self.counts < k

    def zone_radius(self, k: int, q: np.ndarray) -> float:
        """max over possibly-zone cell corners of dist(corner, q).

        dist(., q) is convex so the per-cell max is attained at a corner;
        taking all corners of possibly-zone cells upper-bounds the zone's
        max distance (Eq. (1) soundness).
        """
        pz = self.possibly_zone(k)
        if not pz.any():
            return 0.0
        mask = np.zeros((self.G + 1, self.G + 1), dtype=bool)
        mask[:-1, :-1] |= pz
        mask[1:, :-1] |= pz
        mask[:-1, 1:] |= pz
        mask[1:, 1:] |= pz
        d = np.linalg.norm(self._corners - np.asarray(q, dtype=np.float64), axis=-1)
        return float(d[mask].max())


def _reference_prune(
    facilities: np.ndarray,
    q: np.ndarray,
    k: int,
    rect: Rect,
    *,
    strategy: str = "infzone",
    grid: int | None = None,
    warmup: int = 20,
    exclude: int | None = None,
) -> tuple[np.ndarray, PruneStats]:
    """Keep-mask over ``facilities`` for query point ``q``.

    ``exclude`` optionally names a facility row to skip entirely (the query
    itself for in-set queries).  Returns ``(keep_mask [M] bool, stats)``.
    ``grid=None`` picks the resolution adaptively: dense facility sets have
    tiny influence zones, so the coverage grid must be finer to certify
    coverage (measured: G=256 halves kept occluders at |F|=10^4).
    """
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown pruning strategy {strategy!r}")
    if grid is None:
        grid = adaptive_grid(len(facilities))
    facilities = np.asarray(facilities, dtype=np.float64)
    q = np.asarray(q, dtype=np.float64)
    M = len(facilities)
    keep = np.zeros(M, dtype=bool)
    alive = np.ones(M, dtype=bool)
    if exclude is not None:
        alive[exclude] = False
    # facilities coincident with q carry no bisector: drop them
    coincident = np.linalg.norm(facilities - q, axis=1) < 1e-12
    alive &= ~coincident

    if strategy == "none":
        keep = alive.copy()
        return keep, PruneStats(M, int(keep.sum()), 0, 0, strategy)

    dist_q = np.linalg.norm(facilities - q, axis=1)
    order = order_all = np.argsort(dist_q, kind="stable")
    order = order[alive[order]]
    cov = _FullGrid(rect, grid)
    n_eq1 = 0
    n_cover = 0
    radius = np.inf  # zone radius upper bound; tightened as occluders land
    processed = 0
    max_processed = 0.0  # farthest facility any chunk examined

    # Facilities are processed in distance order in CHUNKS: the discard test
    # for a chunk is evaluated against the current kept set only, and every
    # survivor of the chunk is kept at once.  Keeping an occluder that a
    # strictly sequential pass would have discarded is always SOUND (hit
    # counts only move toward the true closer-facility counts; see module
    # docstring) — the chunk width trades a few extra occluders for a ~64x
    # smaller host loop.  Near ``q`` pruning quality matters most (those
    # facilities define the zone), so chunks start small and grow.
    pos = 0
    # background maintenance threads (MVCC prewarm) run this loop
    # deprioritized: each iteration is a few ms of solid C-level work, so
    # yielding ratio x the iteration's own time keeps foreground readers
    # at well over the fair-scheduling half of a contended core
    while pos < len(order):
        yield_ratio = build_yield_ratio()  # per iteration: may be dynamic
        t_iter = time.perf_counter() if yield_ratio else 0.0
        chunk = 8 if keep.sum() < 4 * k + 8 else 64
        # ---- Eq. (1) bulk reject of everything beyond 2*radius ----------
        if radius < np.inf:
            cut = np.searchsorted(dist_q[order], 2.0 * radius, side="right")
            if cut <= pos:
                n_eq1 += len(order) - pos
                break
            if cut < len(order):
                n_eq1 += len(order) - cut
                order = order[:cut]
        batch = order[pos : pos + chunk]
        pos += len(batch)
        processed_batch = processed
        processed += len(batch)
        max_processed = max(max_processed, float(dist_q[batch[-1]]))
        n_b, c_b = bisector(facilities[batch], q)  # [B, 2], [B]
        full_test = strategy == "infzone" or processed_batch < warmup
        if full_test:
            pz = cov.possibly_zone(k)
            if not pz.any():
                n_cover += len(batch) + (len(order) - pos)
                break
            # vectorized: cell fully-valid per batch facility  [B, G, G]
            sgn = cov.corner_signed_batch(n_b, c_b) >= 0.0  # [B, G+1, G+1]
            fv = sgn[:, :-1, :-1] & sgn[:, 1:, :-1] & sgn[:, :-1, 1:] & sgn[:, 1:, 1:]
            covered = (~pz[None] | fv).all(axis=(1, 2))  # [B]
            survivors = batch[~covered]
            n_cover += int(covered.sum())
        else:
            survivors = batch
        if len(survivors):
            keep[survivors] = True
            ns, cs = bisector(facilities[survivors], q)
            inv = cov.corner_signed_batch(ns, cs) < 0.0
            full_inv = (
                inv[:, :-1, :-1] & inv[:, 1:, :-1] & inv[:, :-1, 1:] & inv[:, 1:, 1:]
            )
            cov.counts += full_inv.sum(axis=0).astype(np.int32)
            radius = cov.zone_radius(k, q)
        if yield_ratio:
            build_sleep((time.perf_counter() - t_iter) * yield_ratio)

    safe_radius = (
        max(2.0 * float(radius), max_processed) if np.isfinite(radius) else np.inf
    )
    stats = PruneStats(M, int(keep.sum()), n_eq1, n_cover, strategy, safe_radius)
    return keep, stats


# ---------------------------------------------------------------------------
# Equivalence
# ---------------------------------------------------------------------------

N_USERS = 4000
GENERATORS = ("road", "uniform", "lattice")

CASES = [
    (gen, n_fac, k, strategy)
    for gen in GENERATORS
    for n_fac in (50, 1000, 2500)  # 2500 crosses to the fine adaptive grid
    for k in (1, 10, 100)
    for strategy in ("infzone", "conservative")
]


def _inputs(gen: str, n_fac: int):
    """Facilities (the last row duplicates row 0) and the domain.

    ``lattice`` puts facilities on a 1/64 lattice of the unit square:
    their bisectors then run exactly through coverage-grid corners, where
    a corner's signed value is exactly 0 (valid)."""
    if gen == "lattice":
        rng = np.random.default_rng(n_fac)
        F = rng.integers(1, 64, (n_fac, 2)) / 64.0
        rect = Rect(0.0, 0.0, 1.0, 1.0)
    else:
        make = road_network_points if gen == "road" else uniform_points
        pts = make(n_fac + N_USERS, seed=n_fac + 17)
        F, rect = pts[:n_fac], Rect.from_points(pts)
    return np.concatenate([F, F[:1]]), rect


def _queries(F: np.ndarray, rect: Rect):
    """(q, exclude) pairs: in-set queries (row 0 has a coincident
    duplicate), an explicit point, and an explicit point on a facility."""
    rng = np.random.default_rng(len(F))
    lo = np.array([rect.xmin, rect.ymin])
    hi = np.array([rect.xmax, rect.ymax])
    j = len(F) // 2
    return [
        (F[0], 0),
        (F[j + 1], j + 1),
        (lo + rng.random(2) * (hi - lo), None),
        (F[j].copy(), None),
    ]


@pytest.mark.parametrize(
    "gen,n_fac,k,strategy",
    CASES,
    ids=[f"{g}-F{n}-k{k}-{s}" for g, n, k, s in CASES],
)
def test_prune_matches_full_grid_loop(gen, n_fac, k, strategy):
    F, rect = _inputs(gen, n_fac)
    for q, exclude in _queries(F, rect):
        keep, stats = prune_facilities(
            F, q, k, rect, strategy=strategy, exclude=exclude
        )
        keep_ref, stats_ref = _reference_prune(
            F, q, k, rect, strategy=strategy, exclude=exclude
        )
        assert np.array_equal(keep, keep_ref), (q, exclude)
        assert stats == stats_ref, (q, exclude)


# ---------------------------------------------------------------------------
# prune.cells{kind=tested|grid}
# ---------------------------------------------------------------------------


def _cells():
    snap = process_registry().snapshot()
    return snap["prune.cells{kind=tested}"], snap["prune.cells{kind=grid}"]


def test_prune_cells_counts_live_cells_against_the_grid():
    F, rect = _inputs("road", 1000)
    tested0, grid0 = _cells()
    prune_facilities(F, F[1], 10, rect, exclude=1)
    tested1, grid1 = _cells()
    assert 0 < tested1 - tested0 < grid1 - grid0
    assert (grid1 - grid0) % adaptive_grid(len(F)) ** 2 == 0

    prune_facilities(F, F[1], 10, rect, strategy="none", exclude=1)
    assert _cells() == (tested1, grid1)


def test_prune_cells_are_on_metrics():
    text = render_registries(process_registry())
    assert 'prune_cells{kind="tested"}' in text
    assert 'prune_cells{kind="grid"}' in text
