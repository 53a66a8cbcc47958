"""Scene-construction pruning — the paper's InfZone-style facility filter.

Algorithm 1 line 2: a facility's occluder is discarded when it is *fully
covered* by ``k`` previously-kept occluders — no ray can then change its
verdict by hitting it (any user inside it already counts >= k hits).  The
paper drives this with InfZone's influence-zone machinery; we implement a
**sound conservative variant** on a coverage grid:

* the domain is divided into ``G x G`` cells; for every kept occluder
  (an invalid half-plane) we track which cells it *fully strictly* contains
  (all 4 cell corners strictly invalid ⇒ the whole convex cell is strictly
  invalid — linear functionals attain extrema at corners);
* a cell whose full-containment count is ``>= k`` provably contains no point
  of the influence zone (every point in it has >= k closer facilities);
* a new facility is discarded iff **every** possibly-zone cell lies entirely
  on its valid side (all 4 corners ``p.n >= c`` ⇒ no strictly-invalid point
  in the cell).  Discarding is therefore never wrong; coarse grids only keep
  extra occluders (performance, not correctness).

The cheap InfZone filters are kept verbatim:
* Eq. (1) bulk reject:  ``dist(f, q) > 2 * max_{v in Z} dist(v, q)`` — with
  the max taken over corners of possibly-zone cells (a superset of the zone,
  so the rejection stays sound);
* facilities are processed in increasing distance from ``q`` (as in both
  InfZone and TPL), which shrinks the zone fastest.

Three strategies from paper §4.8 are exposed: ``"infzone"``,
``"conservative"`` (full test for the first ``warmup`` facilities, Eq. (1)
only afterwards) and ``"none"``.

**Live-cell compaction.**  Only possibly-zone (*live*) cells can change a
verdict, so only they are tested.  Each chunk's bisectors are evaluated
once, at the corners of live cells (``x·n0 + y·n1 - c`` in float64, the
same arithmetic per corner as a full-grid evaluation, so a corner lying
on a bisector resolves the same way).  Every live corner belongs to a
live cell, so "every live cell fully valid" is "every live corner
valid"; the survivors' coverage update reuses the same signs.  Corner
distances to ``q`` are computed once per query.  A cell that reaches
``k`` is dropped from the live set and its count is never updated again:
counts only grow, so a dead cell stays at or above ``k`` whatever its
stale count, and nothing reads it (not the cover test, not the zone
radius, not the early exit).  The result — keep mask and every
``PruneStats`` field — equals the full-grid loop's exactly;
``prune.cells{kind=tested|grid}`` counts the cells tested against G² per
full-test chunk.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.core.geometry import Rect, bisector
from repro.core.grid import build_sleep, build_yield_ratio
from repro.obs.metrics import process_registry

__all__ = ["PruneStats", "prune_facilities", "STRATEGIES", "adaptive_grid"]

STRATEGIES = ("infzone", "conservative", "none")

#: Per full-test chunk: the live cells it evaluated, and the whole grid
#: (G²).  Their ratio is how far the live-cell compaction engages.
_TESTED = process_registry().counter("prune.cells", kind="tested")
_GRID = process_registry().counter("prune.cells", kind="grid")

#: Bit ``s % 8`` for occluder row ``s``: packs 8 rows of flags into a byte.
_BIT_WEIGHTS = np.tile(np.left_shift(1, np.arange(8)).astype(np.uint8), 8)

#: Adaptive coverage-grid resolution: facility sets below the threshold
#: prune at the coarse resolution, denser ones at the fine one (measured:
#: G=256 halves kept occluders at |F|=10^4).  The dynamic subsystem's
#: cold-equivalence contract depends on detecting when an update crosses
#: the threshold — always read it from here.
ADAPTIVE_GRID_THRESHOLD = 2000
ADAPTIVE_GRID_COARSE = 128
ADAPTIVE_GRID_FINE = 256


def adaptive_grid(n_facilities: int) -> int:
    """The coverage-grid resolution ``prune_facilities`` picks for
    ``grid=None`` at this facility count."""
    return (
        ADAPTIVE_GRID_COARSE
        if n_facilities < ADAPTIVE_GRID_THRESHOLD
        else ADAPTIVE_GRID_FINE
    )


@dataclasses.dataclass
class PruneStats:
    """Bookkeeping for benchmarks (paper Table 3 / Fig 16).

    ``safe_radius`` is the *update-stability certificate* consumed by the
    dynamic subsystem (:mod:`repro.dynamic`): any facility change (insert,
    delete, or either endpoint of a move) strictly farther than this from
    the query point provably leaves a cold re-prune — and therefore the
    whole scene — bit-identical.  It is ``max(2·radius_final, d_max)``
    where ``radius_final`` is the final influence-zone radius bound and
    ``d_max`` the farthest facility the chunked pass ever examined: a
    strictly-farther row sorts after every examined one (chunk boundaries
    are unchanged) and is Eq. (1)-rejected by the final radius before it
    can be processed.  ``inf`` means no change is provably safe (strategy
    ``"none"`` keeps everything; an empty kept set never bounded the zone).
    """

    n_facilities: int
    n_kept: int
    n_eq1_rejected: int
    n_cover_rejected: int
    strategy: str
    safe_radius: float = float("inf")


class _CoverageGrid:
    """Full-containment coverage counts of the *live* cells of a G x G grid.

    A cell is live while its count is below ``k`` (possibly-zone).  Only
    live cells are kept, as the flat lattice index of their low corner
    (``cells``) beside their counts; the corners any live cell touches
    are gathered once per live-set change (``px``/``py``, and ``quad``:
    each live cell's four corners as slots of that list).  A cell that
    reaches ``k`` is dropped for good: counts only grow.
    """

    def __init__(self, rect: Rect, grid: int, q: np.ndarray, k: int):
        self.G = grid
        W = grid + 1
        self._xs = np.linspace(rect.xmin, rect.xmax, W)
        self._ys = np.linspace(rect.ymin, rect.ymax, W)
        # squared corner-to-q distances, fixed for the whole query
        self._d2 = (
            np.square(self._xs - q[0])[:, None] + np.square(self._ys - q[1])[None]
        ).ravel()
        first = np.arange(grid if k > 0 else 0)
        self.cells = (first[:, None] * W + first[None]).ravel()
        self.counts = np.zeros(len(self.cells), dtype=np.int32)
        self._slot = np.empty(W * W, dtype=np.intp)
        self._index()

    def _index(self) -> None:
        """Gather the live cells' corners and each cell's four slots."""
        W = self.G + 1
        f = self.cells
        quad_flat = (f, f + W, f + 1, f + W + 1)
        used = np.zeros(W * W, dtype=bool)
        for idx in quad_flat:
            used[idx] = True
        self._corner_idx = np.flatnonzero(used)
        self.px = self._xs[self._corner_idx // W]
        self.py = self._ys[self._corner_idx % W]
        self._slot[self._corner_idx] = np.arange(len(self._corner_idx))
        self.quad = tuple(self._slot[idx] for idx in quad_flat)

    def signed(self, n: np.ndarray, c: np.ndarray) -> np.ndarray:
        """``[B, C]`` values ``x·n0 + y·n1 - c`` at the live corners."""
        v = np.multiply.outer(n[:, 0], self.px)
        v += np.multiply.outer(n[:, 1], self.py)
        v -= c[:, None]
        return v

    def add_invalid(self, valid: np.ndarray, k: int) -> None:
        """Count each live cell whose 4 corners are strictly invalid for a
        kept occluder (``valid``: ``[S, C]`` corner flags ``p.n >= c``),
        then drop the cells that reached ``k``."""
        # 8 occluders a byte: AND the 4 corners' packed invalid bits per
        # cell, then count the set bits (pad rows are 0 and stay 0)
        S, C = valid.shape
        nb = -(-S // 8)
        bits = np.zeros((nb * 8, C), dtype=np.uint8)
        bits[:S] = ~valid
        bits *= _BIT_WEIGHTS[: nb * 8, None]
        inv = bits.reshape(nb, 8, C).sum(axis=1, dtype=np.uint8)  # [nb, C]
        a, b, c, d = (np.take(inv, idx, axis=1) for idx in self.quad)
        self.counts += np.bitwise_count(a & b & c & d).sum(axis=0, dtype=np.uint8)
        live = self.counts < k
        if not live.all():
            self.cells = self.cells[live]
            self.counts = self.counts[live]
            self._index()

    def zone_radius(self) -> float:
        """max over live cells' corners of dist(corner, q).

        dist(., q) is convex so the per-cell max is attained at a corner;
        taking all corners of possibly-zone cells upper-bounds the zone's
        max distance (Eq. (1) soundness).
        """
        if not len(self.cells):
            return 0.0
        # sqrt is monotone: the max distance is the sqrt of the max square
        return float(np.sqrt(self._d2[self._corner_idx].max()))


def prune_facilities(
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
    order = np.argsort(dist_q, kind="stable")
    order = order[alive[order]]
    cov = _CoverageGrid(rect, grid, q, k)
    n_kept = 0
    n_eq1 = 0
    n_cover = 0
    n_tested = 0  # live cells the full-test chunks evaluated
    n_full = 0  # full-test chunks
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
        chunk = 8 if n_kept < 4 * k + 8 else 64
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
        full_test = strategy == "infzone" or processed_batch < warmup
        if full_test and not len(cov.cells):
            n_cover += len(batch) + (len(order) - pos)
            break
        n_b, c_b = bisector(facilities[batch], q)  # [B, 2], [B]
        valid = cov.signed(n_b, c_b) >= 0.0  # [B, live corners]
        if full_test:
            # every live cell fully valid <=> every live corner valid
            covered = valid.all(axis=1)  # [B]
            n_cover += int(covered.sum())
            n_tested += len(cov.cells)
            n_full += 1
            survivors = batch[~covered]
            valid = valid[~covered]
        else:
            survivors = batch
        if len(survivors):
            keep[survivors] = True
            n_kept += len(survivors)
            cov.add_invalid(valid, k)
            radius = cov.zone_radius()
        if yield_ratio:
            build_sleep((time.perf_counter() - t_iter) * yield_ratio)

    if n_full:
        _TESTED.inc(n_tested)
        _GRID.inc(n_full * grid * grid)
    safe_radius = (
        max(2.0 * float(radius), max_processed) if np.isfinite(radius) else np.inf
    )
    stats = PruneStats(M, n_kept, n_eq1, n_cover, strategy, safe_radius)
    return keep, stats
