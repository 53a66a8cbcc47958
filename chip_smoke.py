"""Smoke run of the RkNN serving path on a TPU, checked end to end.

Drives the public API once at the paper's default deployment: the CAL
road network at its published cardinality (1,890,815 points), 1,000 of
them split off as facilities, the other 1,889,815 users resident on the
device, k = 10, batches of Q = 16 queries.

One chip (the default) runs, in one process:

* ``RkNNEngine.query_batch`` on the ``grid-pallas`` and ``dense`` backends
  (the compiled Pallas kernels), cold and warm.  Every query's counts
  must be bit-identical to ``dense-ref`` run on the same chip, and the
  masks of the first queries must equal the exact brute-force oracle over
  all users.  The warm window must compile nothing.
* ``RkNNEngine.stream`` over a few more batches, checked against the
  ``dense`` backend.
* ``DynamicEngine.apply_updates`` of drifting users, then a re-query that
  must equal a cold ``DynamicEngine`` built on the updated users.

``--chips 4`` runs only the sharded path: ``ShardedEngine`` over a
four-chip ``user_mesh`` against a one-chip ``RkNNEngine`` in the same
process, masks and counts bit for bit, and each shard's kernel output on
its own chip.

Any mismatch or error exits non-zero; so does a host where JAX finds no
TPU.  The last line of standard output is, only on success::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Usage::

    python chip_smoke.py [--chips 4] [--seed 0]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

K = 10
Q = 16
N_FACILITIES = 1_000
N_BRUTE = 4  # queries checked against the brute-force oracle over all users
N_WARM = 5  # warm repeats of one batch per backend
N_STREAM = 3  # batches driven through stream()
KERNEL_BACKENDS = ("grid-pallas", "dense")


_T0 = time.perf_counter()


def log(*parts) -> None:
    print(f"[{time.perf_counter() - _T0:9.3f} s]", *parts, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAIL: {what}")


class CompileWatch:
    """Counts every program JAX lowers and every XLA compile, plus the
    engine's ``compile.count{fn}`` counters, between two reads."""

    def __init__(self):
        import jax

        self.events: dict[str, int] = {}

        def listener(event, _duration, **_kw):
            if event.startswith("/jax/core/compile/"):
                self.events[event] = self.events.get(event, 0) + 1

        jax.monitoring.register_event_duration_secs_listener(listener)

    def read(self) -> dict:
        from repro.obs import process_registry

        counts = {
            f"compile.count{{fn={labels['fn']}}}": c.value
            for labels, c in process_registry().find("compile.count")
        }
        counts.update(self.events)
        return counts

    @staticmethod
    def delta(before: dict, after: dict) -> dict:
        return {k: v - before.get(k, 0) for k, v in after.items() if v != before.get(k, 0)}


def cal_deployment(seed: int, n_points: int | None = None):
    """``(facilities, users)`` of the CAL road network (published size
    unless ``n_points`` is given), split by the paper's protocol."""
    from repro.data.spatial import PAPER_DATASETS, facility_user_split, road_network_points

    points = road_network_points(n_points or PAPER_DATASETS["CAL"], seed=seed)
    return facility_user_split(points, N_FACILITIES, seed=seed)


def query_batches(n_facilities: int, n_batches: int, seed: int) -> list[list[int]]:
    rng = np.random.default_rng(seed)
    return [
        [int(q) for q in rng.choice(n_facilities, Q, replace=False)]
        for _ in range(n_batches)
    ]


def mosaic_lowering(n_users: int, interpret: bool) -> dict[str, bool]:
    """Whether each served kernel lowers to a Mosaic custom call with the
    interpret flag the engine runs it with, at the deployment's widths
    (the cell count of the bucketed kernel does not change its code)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.grid_raycast import grid_raycast_cells_batch
    from repro.kernels.raycast import raycast_count_batch_kernel_call

    def spec(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    users = spec(-(-n_users // 1024) * 1024)
    coeffs = spec(Q, 3, 128)
    cells, block = 64, 256
    sorted_users = spec(cells * block)
    lowered = {
        "dense": raycast_count_batch_kernel_call.lower(
            users, users, coeffs, coeffs, coeffs, bu=1024, bm=128, interpret=interpret
        ),
        "grid-pallas": grid_raycast_cells_batch.lower(
            sorted_users,
            sorted_users,
            spec(cells, dtype=jnp.int32),
            spec(Q, cells, 3, 3, 128),
            block=block,
            interpret=interpret,
        ),
    }
    return {name: "tpu_custom_call" in low.as_text() for name, low in lowered.items()}


def timed_batch(eng, qs, backend):
    t0 = time.perf_counter()
    res = eng.query_batch(qs, K, backend=backend)
    np.asarray(res.counts)  # results are host arrays: the device work is done
    return res, time.perf_counter() - t0


def same(a, b) -> bool:
    return a.shape == b.shape and bool(np.array_equal(a, b))


def run_one_chip(facilities, users, seed: int, watch: CompileWatch) -> None:
    from repro.core import RkNNEngine
    from repro.core.brute import rknn_brute_np
    from repro.dynamic import DynamicEngine
    from repro.workloads import drifting_users

    batches = query_batches(len(facilities), 1 + N_STREAM, seed)
    first = batches[0]
    eng = RkNNEngine(facilities, users)

    ref, t_ref = timed_batch(eng, first, "dense-ref")
    log(f"dense-ref: cold {t_ref:.6f} s/batch (scene builds + compile included)")
    brute = {
        qi: rknn_brute_np(eng.users, eng.facilities, qi, K) for qi in first[:N_BRUTE]
    }
    for backend in KERNEL_BACKENDS:
        res, t_cold = timed_batch(eng, first, backend)
        log(f"{backend}: cold {t_cold:.6f} s/batch (compile included)")
        before = watch.read()
        warm = [timed_batch(eng, first, backend)[1] for _ in range(N_WARM)]
        after = watch.read()
        log(f"{backend}: warm s/batch {' '.join(f'{t:.6f}' for t in warm)}")
        log(f"{backend}: compile counters before warm window {json.dumps(before)}")
        log(f"{backend}: compile counters after warm window {json.dumps(after)}")
        compiled = CompileWatch.delta(before, after)
        check(not compiled, f"{backend}: compiled inside the warm window: {compiled}")
        n_same = sum(same(res.counts[i], ref.counts[i]) for i in range(Q))
        log(f"{backend}: {n_same}/{Q} queries' counts bit-identical to dense-ref")
        check(n_same == Q, f"{backend}: counts differ from dense-ref")
        check(same(res.masks, ref.masks), f"{backend}: masks differ from dense-ref")
        n_brute = 0
        for i, qi in enumerate(first[:N_BRUTE]):
            diff = int(np.count_nonzero(res.masks[i] != brute[qi]))
            if diff:
                log(f"{backend}: query {qi}: {diff}/{len(users)} users differ from brute")
            n_brute += diff == 0
        log(f"{backend}: {n_brute}/{N_BRUTE} queries' masks equal brute on all users")
        check(n_brute == N_BRUTE, f"{backend}: masks differ from the brute oracle")

    streamed = 0
    for qb, masks in eng.stream(batches[1:], K, backend="grid-pallas"):
        check(
            same(masks, eng.query_batch(qb, K, backend="dense").masks),
            f"stream batch {streamed}: grid-pallas masks differ from dense",
        )
        streamed += 1
    log(f"stream: {streamed}/{N_STREAM} batches on grid-pallas equal dense")
    check(streamed == N_STREAM, "stream ended early")
    del eng

    dyn = DynamicEngine(facilities, users)
    dyn.query_batch(first, K, backend="grid-pallas")
    moves = drifting_users(users, steps=1, seed=seed)[0]
    report = dyn.apply_updates(moves)
    log(f"update: moved {len(moves.user_move[0])} users, version {report.version}")
    cold = DynamicEngine(facilities, dyn.users)
    for backend in KERNEL_BACKENDS:
        got = dyn.query_batch(first, K, backend=backend)
        want = cold.query_batch(first, K, backend=backend)
        ok = same(got.counts, want.counts) and same(got.masks, want.masks)
        log(f"update: {backend} re-query equals a cold engine on the updated users: {ok}")
        check(ok, f"update: {backend} re-query differs from a cold engine")


def run_four_chips(facilities, users, seed: int) -> None:
    from repro.core import RkNNEngine
    from repro.shard import ShardedEngine, user_mesh

    first = query_batches(len(facilities), 1, seed)[0]
    single = RkNNEngine(facilities, users)
    sharded = ShardedEngine(facilities, users, mesh=user_mesh(4))
    for backend in KERNEL_BACKENDS:
        want, t_one = timed_batch(single, first, backend)
        got, t_cold = timed_batch(sharded, first, backend)
        _, t_warm = timed_batch(sharded, first, backend)
        log(
            f"{backend}: one chip cold {t_one:.6f} s/batch; four shards cold "
            f"{t_cold:.6f} s/batch, warm {t_warm:.6f} s/batch"
        )
        n_same = sum(same(got.counts[i], want.counts[i]) for i in range(Q))
        log(f"{backend}: {n_same}/{Q} queries' counts bit-identical to the one-chip engine")
        check(n_same == Q, f"{backend}: sharded counts differ from one chip")
        check(same(got.masks, want.masks), f"{backend}: sharded masks differ from one chip")
        record = [e for e in sharded.explain() if e.get("mode") == "shard-batch"][-1]
        devices = record["per_shard_device"]
        log(f"{backend}: shard output devices {devices}")
        check(len(set(devices)) == 4, f"{backend}: shard outputs share a device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: FAIL: no TPU found; JAX's devices are {platform!r} "
            "— this smoke run needs a TPU chip"
        )
    check(len(devices) >= args.chips, f"{args.chips} chips needed, {len(devices)} visible")

    from repro.compile_cache import enable_compile_cache
    from repro.kernels.ops import pallas_interpret_default

    log(f"compile cache: {enable_compile_cache()}")
    kind = devices[0].device_kind
    log(f"platform={platform} device_kind={kind} devices={len(devices)}")
    interpret = pallas_interpret_default()
    log(f"interpret={interpret}")
    check(not interpret, "Pallas kernels would run in interpret mode")
    mosaic = mosaic_lowering(1_889_815, interpret)
    log(f"Mosaic custom call in the lowered kernels: {mosaic}")
    check(all(mosaic.values()), "a served kernel does not lower to Mosaic")
    watch = CompileWatch()

    t0 = time.perf_counter()
    facilities, users = cal_deployment(args.seed)
    log(
        f"users={len(users)} facilities={len(facilities)} k={K} Q={Q} "
        f"(generated in {time.perf_counter() - t0:.3f} s)"
    )
    if args.chips == 4:
        run_four_chips(facilities, users, args.seed)
    else:
        run_one_chip(facilities, users, args.seed, watch)
    stats = devices[0].memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} (device 0)")
    device = {"platform": platform, "kind": kind, "count": len(devices)}
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
