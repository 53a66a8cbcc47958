"""Dynamic-data walkthrough: updates, scene refit, continuous RkNN.

Builds a :class:`repro.dynamic.DynamicEngine`, streams user drift and
facility churn through it, and shows the three things the subsystem
buys over rebuilding from scratch:

1. versioned snapshots — ``apply_updates`` returns per-update reports of
   what survived, was refit, or dropped;
2. scene-cache survival under churn (the filter phase collapses on
   repeat queries even as the data moves);
3. continuous queries — standing RkNN handles that re-evaluate only when
   an update can change them, streaming ``(version, result)`` events.

Every step is verified against a cold engine built from the same
snapshot.

    PYTHONPATH=src python examples/rknn_dynamic.py [--users 20000]
"""

import argparse
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import RkNNConfig, RkNNEngine
from repro.data.spatial import facility_user_split, road_network_points
from repro.dynamic import DynamicEngine, UpdateBatch
from repro.workloads import drifting_users, facility_churn


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--users", type=int, default=20_000)
    ap.add_argument("--facilities", type=int, default=400)
    ap.add_argument("--queries", type=int, default=8)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args()

    pts = road_network_points(args.users + args.facilities, seed=7)
    F, U = facility_user_split(pts, args.facilities, seed=7)
    rng = np.random.default_rng(0)
    qs = [int(q) for q in rng.integers(0, len(F), args.queries)]

    engine = DynamicEngine(F, U, RkNNConfig(backend="grid"))
    engine.query_batch(qs, args.k)  # warm: jit + scene cache
    handle = engine.register_continuous(qs[0], args.k)
    print(f"engine v{engine.version}: |F|={len(F)} |U|={len(U)} Q={len(qs)}")

    stream = drifting_users(U, steps=args.steps, frac=0.02, seed=1) + facility_churn(
        F, steps=1, rate=0.01, seed=2, protect=np.asarray(qs)
    )
    for batch in stream:
        rep = engine.apply_updates(batch)
        t0 = time.perf_counter()
        res = engine.query_batch(qs, args.k)
        t_q = time.perf_counter() - t0
        kind = "users" if batch.touches_users else "facilities"
        print(
            f"v{rep.version} [{kind:10s}] update={rep.t_update_s*1e3:6.1f}ms "
            f"query={t_q*1e3:6.1f}ms scenes: survived={rep.scenes_survived} "
            f"refit={rep.scenes_refit} dropped={rep.scenes_dropped} "
            f"scatter={rep.users_scattered}"
        )
        # verify against a cold engine built from the final snapshot
        cold = RkNNEngine(
            engine.facilities, engine.users, RkNNConfig(backend="grid")
        )
        assert np.array_equal(res.masks, cold.query_batch(qs, args.k).masks)

    events = handle.poll()
    # deletions shift rows: the handle tracks its facility through the
    # remap, so the cold comparison must use handle.q_idx, not the old id
    exact = np.array_equal(handle.mask, cold.query(handle.q_idx, args.k).mask)
    print(
        f"continuous q={qs[0]}->{handle.q_idx}: {len(events)} change event(s), "
        f"{handle.n_skipped} update(s) skipped outside the influence zone; "
        f"exact vs cold: {exact}"
    )
    assert exact
    st = engine.update_stats
    print(
        f"totals over {st.n_updates} updates: survived={st.scenes_survived} "
        f"refit={st.scenes_refit} dropped={st.scenes_dropped} "
        f"scatters={st.user_scatters} update_time={st.t_update_s*1e3:.0f}ms"
    )
    print("all steps verified against cold rebuilds: OK")


if __name__ == "__main__":
    main()
