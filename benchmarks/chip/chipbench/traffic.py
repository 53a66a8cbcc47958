"""The one traffic generator: it reads a mix's parameters from
``traffic/<mix>.json`` and the deployment's sizes from its configuration.

A mix names the entry point it drives and where its queries come from:

* ``"queries": "facilities"`` -- each batch holds ``q`` distinct facilities
  drawn from all of them, with weights ``1 / rank ** zipf_s`` over a seeded
  popularity order.  ``zipf_s`` 0 is the paper's protocol: queries drawn
  uniformly from the facilities.  No batch repeats, so the engine's
  prepared-batch cache never hits; a scene is found in the scene cache only
  where its facility was drawn before and not evicted.
* ``"queries": "road_points"`` -- each batch holds ``q`` fresh candidate
  sites drawn along the deployment's roads, never repeated, so every scene
  is built inside the window.

Set-up serves ``warmup_batches`` batches of the same kind, drawn from a
stream the window never uses.

Every stream of draws has its own generator seeded from ``(seed, stream)``,
so the same seed gives the same batches in the same order.
"""

from __future__ import annotations

from typing import Iterator

import numpy as np

__all__ = ["Traffic", "seed_rng"]

#: Independent draw streams per seed.
STREAM_WINDOW, STREAM_WARMUP, STREAM_POPULARITY, STREAM_SAMPLE = 1, 2, 3, 4


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one stream of one run's seed (any whole number)."""
    return np.random.default_rng([seed % (1 << 64), stream])


class Traffic:
    """Batches of one mix for one deployment and seed."""

    def __init__(self, spec: dict, cfg: dict, seed: int, facilities, network):
        self.spec = spec
        self.entry = spec["entry"]
        self.kind = spec["queries"]
        self.q = int(cfg["q"])
        self.seed = seed
        self.n_facilities = len(facilities)
        self.network = network
        if self.kind == "facilities":
            if self.n_facilities < self.q:
                raise ValueError(f"{self.n_facilities} facilities, batches of {self.q}")
            ranked = seed_rng(seed, STREAM_POPULARITY).permutation(self.n_facilities)
            w = np.empty(self.n_facilities)
            w[ranked] = 1.0 / np.arange(1, self.n_facilities + 1) ** float(spec["zipf_s"])
            self.weights = w / w.sum()
        elif self.kind != "road_points":
            raise ValueError(f"unknown query source {self.kind!r}")

    def warmup_batches(self) -> list[list]:
        """Set-up batches, drawn from a stream the window never uses."""
        draw = self._draw(seed_rng(self.seed, STREAM_WARMUP))
        return [next(draw) for _ in range(int(self.spec["warmup_batches"]))]

    def batches(self) -> Iterator[list]:
        """The window's batches, without end."""
        return self._draw(seed_rng(self.seed, STREAM_WINDOW))

    def _draw(self, rng) -> Iterator[list]:
        if self.kind == "road_points":
            while True:
                yield list(self.network.sample(rng, self.q))
        seen: set[tuple] = set()
        while True:
            idx = rng.choice(self.n_facilities, self.q, replace=False, p=self.weights)
            batch = [int(i) for i in idx]
            key = tuple(sorted(batch))
            if key in seen:
                continue
            seen.add(key)
            yield batch
