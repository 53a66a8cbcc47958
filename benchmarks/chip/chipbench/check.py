"""The comparison that decides ``correct``.

The window's answers are sampled (a reservoir drawn from the seed over all
the window's batches) and kept on the host.  Once the window has closed and
the system under test is freed, each sampled query is recomputed by
:mod:`chipbench.reference` and compared, user by user:

* ``wrong_members``: users whose membership the reference decides (outside
  the rounding band of every deciding bisector) and the served mask gets
  wrong.  The guarantee is exact answers, so the limit is 0.
* ``wrong_counts``: members whose exact rank the reference knows (no
  bisector within the band) and whose served count differs from it.  A
  member's occluder count is the number of facilities closer than the query,
  so it equals the rank exactly; the limit is 0.  Only entry points that
  return counts are compared.
"""

from __future__ import annotations

import numpy as np

__all__ = ["LIMITS", "Reservoir", "compare"]

#: Each compared number and its limit (PERF.md gives the readings they
#: were set from).
LIMITS = {"wrong_members": 0, "wrong_counts": 0}


class Reservoir:
    """A uniform sample of ``size`` items from a stream of unknown length,
    drawn with ``rng`` (so the same seed and the same number of batches keep
    the same ones)."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size = int(size)
        self.rng = rng
        self.items: list = []
        self.seen = 0

    def offer(self, item) -> None:
        if self.seen < self.size:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.size:
                self.items[j] = item
        self.seen += 1


def _query_point(facilities: np.ndarray, q):
    arr = np.asarray(q)
    if arr.ndim == 0:
        return facilities[int(arr)], int(arr)
    return np.asarray(q, np.float64).reshape(2), None


def compare(sample, ux, uy, facilities, k: int, ranks) -> dict:
    """Compare sampled answers with the reference.

    ``sample``: ``(queries, masks [Q, N], counts [Q, N] or None)`` per batch.
    ``ranks(ux, uy, facilities, q_pt, exclude) -> (r_lo, r_hi)`` is the
    reference.  Returns the compared numbers plus what was checked.
    """
    out = dict(
        wrong_members=0, wrong_counts=0, queries=0, wrong_queries=0,
        users_checked=0, undecided=0, counts_checked=0,
    )
    for queries, masks, counts in sample:
        for i, q in enumerate(queries):
            q_pt, exclude = _query_point(facilities, q)
            lo, hi = ranks(ux, uy, facilities, q_pt, exclude)
            member = hi < k
            undecided = (lo < k) & ~member
            wrong = int(np.count_nonzero((np.asarray(masks[i]) != member) & ~undecided))
            out["wrong_members"] += wrong
            out["undecided"] += int(np.count_nonzero(undecided))
            out["users_checked"] += int(lo.size - np.count_nonzero(undecided))
            bad_counts = 0
            if counts is not None:
                known = member & (lo == hi)
                c = np.asarray(counts[i])
                bad_counts = int(np.count_nonzero(c[known] != lo[known]))
                out["wrong_counts"] += bad_counts
                out["counts_checked"] += int(np.count_nonzero(known))
            out["queries"] += 1
            out["wrong_queries"] += int(wrong > 0 or bad_counts > 0)
    return out
