"""End-to-end driver: serve batched RkNN queries against a user fleet.

The paper's deployment story (docs/API.md): users uploaded once, scenes
built per query on the host (double-buffered by ``RkNNEngine.stream``
against the device dispatch of the previous batch), and the ray-cast
executed as one batched device step.  Run with more hosts/devices and pass
a mesh — the same code shards users over the data axes and queries over
``'model'``.

    PYTHONPATH=src python examples/rknn_serving.py [--users 500000] [--queries 64]
"""

import argparse
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import RkNNConfig, RkNNEngine
from repro.core.brute import rknn_brute_np
from repro.data.spatial import facility_user_split, road_network_points


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--users", type=int, default=200_000)
    ap.add_argument("--facilities", type=int, default=1_000)
    ap.add_argument("--queries", type=int, default=32)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--k", type=int, default=10)
    args = ap.parse_args()

    pts = road_network_points(args.users + args.facilities, seed=3)
    F, U = facility_user_split(pts, args.facilities, seed=3)

    t0 = time.perf_counter()
    # "plain GPU transfer" of Table 2 + scene cache for hot facilities
    engine = RkNNEngine(F, U, RkNNConfig(scene_cache=256))
    engine.xs  # materialize the device upload inside the timed window
    t_up = time.perf_counter() - t0
    print(f"user upload (+engine wiring): {t_up*1e3:.1f} ms for |U|={len(U)}")

    rng = np.random.default_rng(0)
    queries = rng.integers(0, len(F), args.queries)
    batches = [queries[i : i + args.batch] for i in range(0, len(queries), args.batch)]

    t0 = time.perf_counter()
    n_results = 0
    masks_by_query = {}
    for qbatch, masks in engine.stream(batches, args.k):
        n_results += int(masks.sum())
        for qi, m in zip(qbatch, masks):
            masks_by_query[int(qi)] = m
    wall = time.perf_counter() - t0

    s = engine.stats
    print(
        f"served {s.n_queries} queries in {wall*1e3:.1f} ms "
        f"({wall/s.n_queries*1e3:.2f} ms/query) — "
        f"scene(host,overlapped)={s.t_filter_s*1e3:.0f}ms "
        f"raycast(device)={s.t_verify_s*1e3:.0f}ms  max_occluders={s.m_max}"
    )
    print(f"total influence-set size: {n_results}")

    # spot-verify three queries against the exact oracle
    for qi in list(masks_by_query)[:3]:
        truth = rknn_brute_np(U, F, qi, args.k)
        assert np.array_equal(masks_by_query[qi], truth), qi
    print("spot-checked 3 queries against the exact oracle: OK")


if __name__ == "__main__":
    main()
