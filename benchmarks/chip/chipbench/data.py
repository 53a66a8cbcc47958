"""Road-network point sets and the paper's facility/user split, from a seed.

The paper runs on the DIMACS 9th Challenge road networks (NY ... USA).  No
such file is in the repository, so the points are generated: a random planar
graph of hubs, each joined to its three nearest, with points sampled along
the edges (weighted by length) and jittered by GPS-sized noise.  That keeps
the clustered, linear structure of road vertices at the published
cardinality.  The same seed gives the same points.

:class:`RoadNetwork` also draws further points along the same roads, for
candidate sites that are neither users nor facilities.
"""

from __future__ import annotations

import numpy as np

__all__ = ["PAPER_DATASETS", "RoadNetwork", "facility_user_split"]

#: Paper Table 1 cardinalities (DIMACS 9th Challenge road networks).
PAPER_DATASETS = {
    "NY": 264_346,
    "FLA": 1_070_376,
    "CAL": 1_890_815,
    "E": 3_598_623,
    "CTR": 14_081_816,
    "USA": 23_947_347,
}

#: Standard deviation of the jitter around each road, in domain units.
JITTER = 0.002


class RoadNetwork:
    """A seeded hub graph and its ``n`` road points (``points``, ``[n, 2]``
    float64 in ``[0, 1]``)."""

    def __init__(self, n: int, seed: int):
        rng = np.random.default_rng(seed)
        n_hubs = max(16, int(np.sqrt(n) / 4))
        hubs = rng.random((n_hubs, 2))
        d2 = np.sum((hubs[:, None] - hubs[None, :]) ** 2, axis=-1)
        np.fill_diagonal(d2, np.inf)
        edges = [
            (i, int(j))
            for i in range(n_hubs)
            for j in np.argsort(d2[i])[:3]
            if i < j
        ]
        self.hubs = hubs
        self.edges = np.asarray(edges)
        lengths = np.linalg.norm(
            hubs[self.edges[:, 0]] - hubs[self.edges[:, 1]], axis=1
        )
        self.probs = lengths / lengths.sum()
        counts = rng.multinomial(n, self.probs)
        pts = [
            self._along(rng, a, b, c) for (a, b), c in zip(self.edges, counts) if c
        ]
        out = np.concatenate(pts) if pts else np.zeros((0, 2))
        if len(out) < n:  # multinomial rounding
            out = np.concatenate([out, rng.random((n - len(out), 2))])
        self.points = np.clip(out[:n], 0.0, 1.0)

    def _along(self, rng, a: int, b: int, c: int) -> np.ndarray:
        t = rng.random(c)[:, None]
        p = self.hubs[a][None] * (1 - t) + self.hubs[b][None] * t
        return p + rng.normal(0.0, JITTER, p.shape)

    def sample(self, rng: np.random.Generator, n: int) -> np.ndarray:
        """``n`` further points along the same roads, ``[n, 2]`` float64."""
        edge = rng.choice(len(self.edges), size=n, p=self.probs)
        t = rng.random(n)[:, None]
        a = self.hubs[self.edges[edge, 0]]
        b = self.hubs[self.edges[edge, 1]]
        p = a * (1 - t) + b * t + rng.normal(0.0, JITTER, (n, 2))
        return np.clip(p, 0.0, 1.0)


def facility_user_split(points: np.ndarray, n_facilities: int, seed: int):
    """Paper protocol: ``n_facilities`` random points are the facilities, the
    rest the users.  Returns ``(facilities, users)``."""
    idx = np.random.default_rng(seed).permutation(len(points))
    return points[idx[:n_facilities]], points[idx[n_facilities:]]
