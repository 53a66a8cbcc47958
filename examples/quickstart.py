"""Quickstart: one RkNN query end-to-end, every backend, verified exact.

Builds a stateful :class:`RkNNEngine` once (users uploaded once, shared
domain rect, scene cache) and queries it per backend — the amortized path.
The legacy one-shot free functions (``rt_rknn_query`` …) remain available
as shims; see docs/API.md for the migration table.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import RkNNEngine, available_backends
from repro.core.brute import rknn_brute_np
from repro.data.spatial import facility_user_split, road_network_points


def main() -> None:
    enable_compile_cache()
    # a road-network-like city: 100k points, 1000 facilities, rest users
    points = road_network_points(100_000, seed=7)
    facilities, users = facility_user_split(points, 1_000, seed=7)
    q, k = 42, 10

    print(f"|F|={len(facilities)}  |U|={len(users)}  query=facility#{q}  k={k}\n")

    engine = RkNNEngine(facilities, users)  # build once, query many
    truth = rknn_brute_np(users, facilities, q, k)
    for backend in available_backends():
        res = engine.query(q, k, backend=backend)
        ok = np.array_equal(res.mask, truth)
        extra = ""
        if res.scene is not None:
            extra = (f"  occluders={res.scene.n_occluders}/{len(facilities)} "
                     f"(InfZone-style pruning)")
        print(
            f"{backend:10s}  |RkNN|={res.mask.sum():5d}  exact={ok}  "
            f"filter={res.t_filter_s*1e3:7.1f}ms  verify={res.t_verify_s*1e3:7.1f}ms{extra}"
        )
        assert ok, backend

    # the scene cache makes the repeat query nearly free on the filter side
    repeat = engine.query(q, k, backend="dense-ref")
    print(
        f"\nrepeat query (scene cache hit): filter={repeat.t_filter_s*1e3:.2f}ms  "
        f"cache hits={engine.scene_cache.hits}"
    )

    # monochromatic variant (paper §2.1): facilities querying facilities
    mono = engine.query_mono(q, k)
    print(f"monochromatic RkNN of facility #{q}: {mono.mask.sum()} results")
    print("\nAll backends agree with the exact oracle — Lemma 3.4 in action.")


if __name__ == "__main__":
    main()
