"""User-axis sharded serving: bit-identity to the single-process oracle.

The sharding contract (docs/API.md "Sharded serving"): per-user hit
counts are per-user independent, so partitioning the user population
over shards and scattering the per-shard slabs back through the
partition permutation must reproduce the single-process engine's counts
and masks **bit-identically** — for every registered backend, every
shard count, and across an ``apply_updates`` stream (where the COW
shard-state carry and the version-lockstep rule are what is actually
under test).
"""

import numpy as np
import pytest

from repro.core.backends import available_backends, concrete_backends
from repro.core.engine import RkNNEngine
from repro.dynamic import UpdateBatch
from repro.distributed.sharding import user_shard_bounds
from repro.shard import (
    ShardedEngine,
    assemble_counts,
    mesh_shards,
    result_sizes,
    shard_devices,
    tree_psum,
    user_mesh,
)

SHARD_COUNTS = (1, 2, 4)
K = 4


def _instance(seed, M=36, N=420):
    rng = np.random.default_rng(seed)
    F = rng.random((M, 2))
    F[:4] = [[0.0, 0.0], [0.0, 1.0], [1.0, 0.0], [1.0, 1.0]]  # pin the hull
    U = rng.random((N, 2))
    # mixed facility-index and point queries
    qs = [0, 7, np.array([0.5, 0.5]), 13, np.array([0.21, 0.77]), 5]
    return F, U, qs, rng


# ---------------------------------------------------------------------------
# the core property: bit-identity across backends x shard counts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shards", SHARD_COUNTS)
@pytest.mark.parametrize("backend", available_backends())
def test_sharded_matches_single_process(backend, shards):
    F, U, qs, _ = _instance(11)
    oracle = RkNNEngine(F, U, backend=backend).query_batch(qs, K)
    got = ShardedEngine(F, U, backend=backend, shards=shards).query_batch(qs, K)
    assert np.array_equal(oracle.masks, got.masks)
    if backend in concrete_backends():
        # the planner may legitimately split a batch differently on a
        # sharded engine (the log_s feature reprices verify), and count
        # *semantics* differ per backend — masks are the invariant there
        assert np.array_equal(
            np.asarray(oracle.counts), np.asarray(got.counts)
        )


@pytest.mark.parametrize("shards", SHARD_COUNTS)
@pytest.mark.parametrize("backend", available_backends())
def test_sharded_matches_after_update_stream(backend, shards):
    F, U, qs, rng = _instance(23)
    eng = ShardedEngine(F, U, backend=backend, shards=shards)
    eng.query_batch(qs, K)  # warm caches so the COW carry has work to do
    # moves, churn (interior inserts: hull-stable), and facility jitter —
    # every COW path: scatter, partition rebuild, restamp
    mv = 100 + rng.choice(len(U) - 100, 25, replace=False)
    eng.apply_updates(user_move=(mv, rng.random((25, 2))))
    eng.query_batch(qs, K)
    eng.apply_updates(
        UpdateBatch(
            user_insert=rng.uniform(0.2, 0.8, (12, 2)),
            user_delete=np.arange(8),
        )
    )
    fb = np.array([17, 23, 29])
    eng.apply_updates(
        facility_move=(fb, np.clip(F[fb] + 0.03, 0, 1))
    )
    got = eng.query_batch(qs, K)
    oracle = RkNNEngine(eng.facilities, eng.users, backend=backend).query_batch(
        qs, K
    )
    assert np.array_equal(oracle.masks, got.masks)
    if backend in concrete_backends():
        assert np.array_equal(
            np.asarray(oracle.counts), np.asarray(got.counts)
        )


def test_single_query_and_stream_paths_match(shards=3):
    F, U, qs, _ = _instance(5)
    oracle = RkNNEngine(F, U, backend="grid-pallas-ref")
    eng = ShardedEngine(F, U, backend="grid-pallas-ref", shards=shards)
    for q in qs:
        assert np.array_equal(oracle.query(q, K).mask, eng.query(q, K).mask)
    batches = [qs[:3], qs[3:], qs]
    ref = [m for _, m in oracle.stream(batches, K)]
    got = [m for _, m in eng.stream(batches, K)]
    for r, g in zip(ref, got):
        assert np.array_equal(r, g)


# ---------------------------------------------------------------------------
# version lockstep + COW shard-state carry
# ---------------------------------------------------------------------------


def test_shard_state_version_lockstep():
    F, U, qs, rng = _instance(7)
    eng = ShardedEngine(F, U, backend="dense-ref", shards=4)
    eng.query_batch(qs, K)
    st = eng._snap.shard_state
    assert st is not None and st.version == eng.version
    assert all(v.version == st.version for v in st.views)
    assert sum(v.n_users for v in st.views) == len(U)

    # pure move: functional scatter, same partition, new version stamp
    mv = rng.choice(len(U), 10, replace=False)
    eng.apply_updates(user_move=(mv, rng.random((10, 2))))
    st2 = eng._snap.shard_state
    assert st2 is not None and st2.version == eng.version
    assert st2.perm is st.perm  # partition carried, not rebuilt
    assert all(v.version == st2.version for v in st2.views)

    # facility-only delta: user arrays carried by reference, re-stamped
    eng.apply_updates(facility_move=(np.array([9]), np.array([[0.4, 0.4]])))
    st3 = eng._snap.shard_state
    assert st3 is not None and st3.version == eng.version
    assert st3.views[0].xs is st2.views[0].xs

    # shape change: partition is stale — rebuilt lazily on next query
    eng.apply_updates(user_insert=rng.uniform(0.3, 0.7, (6, 2)))
    assert eng._snap.shard_state is None
    eng.query_batch(qs, K)
    st4 = eng._snap.shard_state
    assert st4 is not None and st4.n_users == len(U) + 6
    assert st4.version == eng.version


def test_per_shard_stats_and_explain():
    F, U, qs, _ = _instance(3)
    eng = ShardedEngine(F, U, backend="grid-pallas-ref", shards=4)
    eng.query_batch(qs, K)
    assert len(eng.stats.shard_verify_s) == 4
    assert len(eng.stats.shard_filter_s) == 4
    assert any(t > 0 for t in eng.stats.shard_verify_s)
    assert eng.stats.shard_imbalance >= 1.0
    recs = [e for e in eng.explain() if e.get("mode") == "shard-batch"]
    assert recs, "explain() must surface shard batch records"
    rec = recs[-1]
    assert rec["shards"] == 4
    assert sum(rec["per_shard_users"]) == len(U)
    assert len(rec["per_shard_verify_s"]) == 4
    # the device each shard's kernel output landed on
    assert rec["per_shard_device"] == [
        str(v.device) for v in eng._snap.shard_state.views
    ]
    # psum-reduced result sizes match the actual masks
    got = eng.query_batch(qs, K)
    recs2 = [e for e in eng.explain() if e.get("mode") == "shard-batch"]
    assert recs2[-1]["result_sizes"] == [int(m.sum()) for m in got.masks]


def test_batch_cache_carry_across_user_churn():
    """Satellite: the prepared-batch LRU survives user insert/delete for
    backends whose prepared state is scene-only."""
    F, U, qs, rng = _instance(13)
    for backend in ("dense-ref", "grid", "bvh"):
        eng = ShardedEngine(F, U, backend=backend, shards=2)
        eng.query_batch(qs, K)
        h0 = eng.stats.batch_cache_hits
        rep = eng.apply_updates(user_insert=rng.uniform(0.2, 0.8, (9, 2)))
        assert rep.batches_carried > 0, backend
        got = eng.query_batch(qs, K)
        assert eng.stats.batch_cache_hits > h0, backend
        oracle = RkNNEngine(eng.facilities, eng.users, backend=backend)
        assert np.array_equal(oracle.query_batch(qs, K).masks, got.masks)


# ---------------------------------------------------------------------------
# mesh + reduction units
# ---------------------------------------------------------------------------


def test_user_shard_bounds_invariants():
    for n in (0, 1, 5, 97, 1000):
        for s in (1, 2, 3, 4, 7):
            b = user_shard_bounds(n, s)
            assert b[0] == 0 and b[-1] == n and len(b) == s + 1
            sizes = np.diff(b)
            assert (sizes >= 0).all() and sizes.max() - sizes.min() <= 1


def test_tree_psum_deterministic_and_exact():
    rng = np.random.default_rng(0)
    parts = [rng.integers(0, 100, 17).astype(np.int64) for _ in range(5)]
    assert np.array_equal(tree_psum(parts), np.sum(parts, axis=0))
    with pytest.raises(ValueError):
        tree_psum([])


def test_assemble_counts_roundtrip():
    rng = np.random.default_rng(1)
    n, q, s = 103, 3, 4
    full = rng.integers(0, 9, (q, n)).astype(np.int32)
    perm = rng.permutation(n)
    bounds = user_shard_bounds(n, s)
    slabs = [full[:, perm[bounds[i] : bounds[i + 1]]] for i in range(s)]
    assert np.array_equal(assemble_counts(slabs, perm, bounds, n), full)
    sizes = result_sizes(slabs, 5)
    assert np.array_equal(sizes, (full < 5).sum(axis=1))


def test_user_mesh_and_devices():
    import jax

    n_dev = len(jax.devices())
    mesh = user_mesh(n_dev)
    assert mesh_shards(mesh) == n_dev
    assert shard_devices(n_dev, mesh) == list(jax.devices())
    # oversubscription cycles CPU devices without a mesh, errors with one
    devs = shard_devices(n_dev + 3)
    assert len(devs) == n_dev + 3
    with pytest.raises(ValueError):
        user_mesh(n_dev + 1)
    # the engine accepts a mesh and locks its shard count to it
    F, U, qs, _ = _instance(2, M=20, N=64)
    eng = ShardedEngine(F, U, backend="dense-ref", mesh=mesh)
    assert eng.n_shards == n_dev
    with pytest.raises(ValueError):
        ShardedEngine(F, U, backend="dense-ref", mesh=mesh, shards=n_dev + 1)


def test_shard_devices_never_cycle_accelerators(monkeypatch):
    """Asking for more shards than chips raises instead of stacking two
    shards on one accelerator; exactly as many shards as chips is fine."""
    import jax

    import repro.shard.mesh as shard_mesh

    class _Chip:
        platform = "tpu"

    chips = [_Chip(), _Chip()]
    monkeypatch.setattr(shard_mesh.jax, "devices", lambda: chips)
    assert shard_devices(2) == chips
    with pytest.raises(ValueError, match="2 tpu device"):
        shard_devices(3)
    monkeypatch.undo()
    assert len(shard_devices(len(jax.devices()) + 1)) == len(jax.devices()) + 1
