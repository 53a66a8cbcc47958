"""The persistence layer (ISSUE 10): ``rknn-store/1`` save / warm-restore.

Covers the PR acceptance surface:

* **crash-mid-write recovery** (satellite bugfix): stranded ``step_*.tmp``
  leftovers and manifests listing lost leaf files are *skipped* by the
  newest-complete-step fallback, and an explicitly requested incomplete
  step raises a clear ``FileNotFoundError`` instead of a bare np.load
  crash;
* **round-trip property**: random scenarios × every registered concrete
  backend × shards {1, 4} — save → restore → query is bit-identical to
  the cold engine (masks, counts, mono), including after an
  ``apply_updates`` stream on top of the restored snapshot;
* **cross-process restore**: a fresh interpreter (different hash salt —
  the in-memory ``SceneCache.fingerprint`` is *not* portable) restores
  the store and serves identical masks without rebuilding a scene;
* **partial invalidation**: per-category fingerprints — a user-set
  change invalidates dataset/scenes but the (data-independent,
  hardware-keyed) planner profile survives; a store is never trusted
  across a schema change;
* **MVCC hot-adopt**: ``engine.restore(dir)`` on a live engine publishes
  the store as version N+1 via the atomic swap;
* **observability**: restore emits ``persist.restore_s`` /
  ``persist.bytes`` metrics and ``/snapshot`` reports the active store.
"""

import http.client
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from repro.checkpoint.store import (
    latest_step,
    restore_checkpoint,
    save_checkpoint,
    save_state,
)
from repro.core.backends import concrete_backends
from repro.core.engine import RkNNConfig, RkNNEngine
from repro.dynamic import DynamicEngine
from repro.persist import SCHEMA, expected_fingerprints
from repro.planner.profiles import (
    PlannerProfile,
    get_active_profile,
    hardware_fingerprint,
    set_active_profile,
)
from repro.shard.engine import ShardedEngine


def _instance(seed, M=40, N=250):
    rng = np.random.default_rng(seed)
    F = rng.uniform(0.0, 100.0, (M, 2))
    U = rng.uniform(0.0, 100.0, (N, 2))
    return F, U, rng


def _results(eng, queries, k):
    return [eng.query(q, k) for q in queries]


def _same(a, b):
    return bool(
        np.array_equal(np.asarray(a.mask), np.asarray(b.mask))
        and np.array_equal(np.asarray(a.counts), np.asarray(b.counts))
    )


@pytest.fixture(autouse=True)
def _no_active_profile():
    """Persist tests manipulate the process-global planner profile."""
    prev = get_active_profile()
    set_active_profile(None)
    yield
    set_active_profile(prev)


# ------------------------------------------------------- crash-mid-write
def test_crash_mid_write_recovery(tmp_path):
    d = str(tmp_path / "ckpt")
    tree = {"w": np.arange(12.0).reshape(3, 4), "b": np.zeros(3)}
    save_checkpoint(d, 0, tree)
    tree2 = {"w": tree["w"] + 1, "b": tree["b"] + 1}
    save_checkpoint(d, 1, tree2)

    # crash scenario A: a stranded .tmp dir from a save that died mid-write
    os.makedirs(os.path.join(d, "step_000000000002.tmp"))
    # crash scenario B: step 3's manifest exists but a leaf was lost
    save_checkpoint(d, 3, tree2)
    victim = os.path.join(d, "step_000000000003")
    leaf = json.load(open(os.path.join(victim, "manifest.json")))["leaves"]["w"]["file"]
    os.remove(os.path.join(victim, leaf))

    # newest *complete* step wins; neither leftover trips the reader
    assert latest_step(d) == 1
    restored, manifest = restore_checkpoint(d, tree)
    assert manifest["step"] == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]), tree2["w"])

    # explicitly asking for the incomplete step names the missing leaf
    with pytest.raises(FileNotFoundError, match="incomplete"):
        restore_checkpoint(d, tree, step=3)

    # the state-store reader obeys the same completeness contract
    save_state(d, 5, {"c": {"fingerprint": "x", "meta": {},
                            "arrays": {"a": np.ones(4)}}}, schema=SCHEMA)
    folder = os.path.join(d, "step_000000000005")
    os.remove(os.path.join(folder, "c__a.npy"))
    assert latest_step(d) == 1


# ------------------------------------------------- round-trip property
@pytest.mark.parametrize("backend", concrete_backends())
@pytest.mark.parametrize("n_shards", [1, 4])
def test_roundtrip_bit_identical(tmp_path, backend, n_shards):
    """save → restore → query ≡ cold, per backend × shard count,
    including after an update stream on the restored snapshot."""
    F, U, rng = _instance(seed=7 + n_shards)
    cfg = RkNNConfig(backend=backend, grid_g=16)
    queries, k = [0, 3, 11], 6

    cold = ShardedEngine(F, U, cfg, shards=n_shards)
    want = _results(cold, queries, k)
    d = str(tmp_path / "store")
    cold.save_state(d)

    warm = ShardedEngine(
        F, U, RkNNConfig(backend=backend, grid_g=16, warm_store=d),
        shards=n_shards,
    )
    cats = warm.persist_info["categories"]
    assert cats["dataset"]["status"] == "restored"
    from repro.core.backends import get_backend

    if get_backend(backend).uses_scene:
        assert cats["scenes"]["status"] == "restored"
    got = _results(warm, queries, k)
    assert all(_same(c, w) for c, w in zip(want, got))
    # the cached working set really was adopted: zero scene rebuilds
    assert warm._snap.scene_cache.misses == 0

    # mono path rides the same restored state
    assert _same(cold.query_mono(queries[0], k), warm.query_mono(queries[0], k))

    # updates on top of the restored snapshot stay cold-equivalent
    ins = rng.uniform(0.0, 100.0, (3, 2))
    mv = rng.choice(len(U), 10, replace=False)
    pts = rng.uniform(0.0, 100.0, (10, 2))
    for eng in (cold, warm):
        eng.apply_updates(facility_insert=ins, user_move=(mv, pts))
    assert all(_same(c, w) for c, w in zip(
        _results(cold, queries, k), _results(warm, queries, k)))


# --------------------------------------------------- cross-process restore
def test_cross_process_restore(tmp_path):
    """A fresh interpreter (fresh hash salt) restores the store and
    serves identical masks with zero scene rebuilds — proves no salted
    in-memory fingerprint leaked into the manifest."""
    F, U, _ = _instance(seed=11)
    d = str(tmp_path / "store")
    eng = RkNNEngine(F, U, RkNNConfig(backend="grid", grid_g=16))
    want = np.stack([np.asarray(r.mask) for r in _results(eng, [0, 2, 5], 8)])
    eng.save_state(d)
    np.save(tmp_path / "F.npy", F)
    np.save(tmp_path / "U.npy", U)

    prog = f"""
import numpy as np
from repro.core.engine import RkNNConfig, RkNNEngine
F = np.load({str(tmp_path / 'F.npy')!r}); U = np.load({str(tmp_path / 'U.npy')!r})
eng = RkNNEngine(F, U, RkNNConfig(backend="grid", grid_g=16, warm_store={d!r}))
cats = eng.persist_info["categories"]
assert cats["scenes"]["status"] == "restored", cats
masks = np.stack([np.asarray(eng.query(q, 8).mask) for q in (0, 2, 5)])
assert eng._snap.scene_cache.misses == 0, "restored working set was rebuilt"
np.save({str(tmp_path / 'warm_masks.npy')!r}, masks)
"""
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    env.pop("PYTHONHASHSEED", None)  # a fresh random salt is the point
    r = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True, env=env
    )
    assert r.returncode == 0, r.stderr[-2000:]
    got = np.load(tmp_path / "warm_masks.npy")
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------- partial invalidation
def test_partial_invalidation_user_change(tmp_path):
    """Per-category fingerprints: a user-set change invalidates the
    data-keyed categories but the hardware-keyed planner profile is
    adopted untouched."""
    F, U, rng = _instance(seed=13)
    set_active_profile(
        PlannerProfile(hardware=hardware_fingerprint(), source="test", models={})
    )
    eng = RkNNEngine(F, U, RkNNConfig(backend="grid", grid_g=16))
    _results(eng, [0, 1], 6)
    d = str(tmp_path / "store")
    eng.save_state(d)
    assert "planner" in eng.persist_info["categories"]

    set_active_profile(None)
    U2 = rng.uniform(0.0, 150.0, (len(U) + 40, 2))  # moves the hull rect too
    warm = RkNNEngine(F, U2, RkNNConfig(backend="grid", grid_g=16, warm_store=d))
    cats = warm.persist_info["categories"]
    assert cats["planner"]["status"] == "restored"
    assert get_active_profile() is not None
    assert cats["dataset"]["status"] == "stale"
    assert cats["scenes"]["status"] == "stale"
    # stale scene category really was NOT adopted
    assert len(warm._snap.scene_cache) == 0

    # an installed profile is never clobbered by a restore
    marker = PlannerProfile(
        hardware=hardware_fingerprint(), source="operator", models={}
    )
    set_active_profile(marker)
    warm2 = RkNNEngine(F, U, RkNNConfig(backend="grid", grid_g=16, warm_store=d))
    assert warm2.persist_info["categories"]["planner"]["status"] == "skipped"
    assert get_active_profile() is marker


def test_schema_mismatch_rejected(tmp_path):
    F, U, _ = _instance(seed=17)
    d = str(tmp_path / "store")
    eng = RkNNEngine(F, U, RkNNConfig(backend="grid", grid_g=16))
    eng.query(0, 6)
    eng.save_state(d)
    folder = os.path.join(d, f"step_{0:012d}")
    m = json.load(open(os.path.join(folder, "manifest.json")))
    m["schema"] = "rknn-store/999"
    json.dump(m, open(os.path.join(folder, "manifest.json"), "w"))
    warm = RkNNEngine(F, U, RkNNConfig(backend="grid", grid_g=16, warm_store=d))
    assert "error" in warm.persist_info  # refused wholesale, engine still cold
    assert warm.query(0, 6) is not None


def test_expected_fingerprints_move_with_data():
    F, U, rng = _instance(seed=19)
    eng = RkNNEngine(F, U, RkNNConfig(backend="grid", grid_g=16))
    base = expected_fingerprints(eng, eng._snap)
    eng2 = RkNNEngine(F, rng.uniform(0, 100, U.shape), RkNNConfig(backend="grid", grid_g=16))
    moved = expected_fingerprints(eng2, eng2._snap)
    assert moved["dataset"] != base["dataset"]
    assert moved["kernel"] != base["kernel"]
    assert moved["planner"] == base["planner"]  # data-independent


def test_interpret_mode_bucketing_not_adopted_by_compiled_kernel(
    tmp_path, monkeypatch
):
    """A store bucketed in interpret mode (8-user cell blocks) is restored
    on a runtime whose compiled kernel needs 128-user blocks: the stale
    bucketing is skipped and rebuilt, and answers stay identical."""
    import repro.kernels.grid_raycast as gr

    F, U, _ = _instance(seed=31)
    cfg = RkNNConfig(backend="grid-pallas-ref", grid_g=16)
    eng = RkNNEngine(F, U, cfg)
    want = eng.query_batch([0, 3], 6)
    d = str(tmp_path / "store")
    eng.save_state(d)

    monkeypatch.setattr(gr, "min_cell_block", lambda: 128)
    warm = RkNNEngine(F, U, RkNNConfig(backend="grid-pallas-ref", grid_g=16, warm_store=d))
    assert not any(
        key[0] == "gp-buckets" for key, _ in warm._snap.kernel_memo.items()
    )
    got = warm.query_batch([0, 3], 6)
    np.testing.assert_array_equal(got.counts, want.counts)
    blocks = [
        value[1][5]
        for key, value in warm._snap.kernel_memo.items()
        if key[0] == "gp-buckets"
    ]
    assert blocks and all(b % 128 == 0 for b in blocks)


# --------------------------------------------------------- MVCC hot-adopt
def test_hot_adopt_publishes_next_version(tmp_path):
    F, U, rng = _instance(seed=23)
    d = str(tmp_path / "store")
    src = DynamicEngine(F, U, RkNNConfig(backend="grid", grid_g=16))
    want = _results(src, [0, 4], 6)
    src.save_state(d)

    live = DynamicEngine(
        rng.uniform(0, 100, (20, 2)), rng.uniform(0, 100, (80, 2)),
        RkNNConfig(backend="grid", grid_g=16),
    )
    live.query(0, 4)
    v0 = live._snap.version
    info = live.restore(d)
    assert info["mode"] == "hot-adopt"
    assert live._snap.version == v0 + 1  # published as MVCC N+1
    got = _results(live, [0, 4], 6)
    assert all(_same(c, w) for c, w in zip(want, got))


# ----------------------------------------------------------- observability
def test_persist_metrics_and_snapshot_endpoint(tmp_path):
    F, U, _ = _instance(seed=29)
    d = str(tmp_path / "store")
    eng = RkNNEngine(F, U, RkNNConfig(backend="grid", grid_g=16))
    _results(eng, [0, 1], 6)
    eng.save_state(d)
    assert eng.metrics.find("persist.bytes")

    warm = DynamicEngine(F, U, RkNNConfig(backend="grid", grid_g=16, warm_store=d))
    assert warm.metrics.find("persist.restore_s")
    srv = warm.serve_obs(port=0)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=5)
        conn.request("GET", "/snapshot")
        payload = json.loads(conn.getresponse().read())
        assert payload["persist"]["schema"] == SCHEMA
        assert payload["persist"]["store"] == os.path.abspath(d)
        assert payload["persist"]["categories"]["scenes"]["status"] == "restored"
    finally:
        srv.close()


# ------------------------------------------------------------------- CLI
def test_cli_inspect_and_verify(tmp_path, capsys):
    from repro.persist.__main__ import main

    F, U, _ = _instance(seed=31)
    d = str(tmp_path / "store")
    eng = RkNNEngine(F, U, RkNNConfig(backend="grid", grid_g=16))
    _results(eng, [0, 1, 2], 6)
    eng.save_state(d)

    assert main(["--inspect", d]) == 0
    out = capsys.readouterr().out
    assert SCHEMA in out and "scenes" in out and "fresh" in out

    assert main(["--verify", d]) == 0
    out = capsys.readouterr().out
    assert "bit-identical" in out

    # a mutated store fails verification (exit 1, mismatch reported)
    folder = os.path.join(d, f"step_{0:012d}")
    m = json.load(open(os.path.join(folder, "manifest.json")))
    # invert every stored edge test — scene rows AND the packed grid
    # planes the backend actually casts against
    victims = [m["categories"]["scenes"]["arrays"]["coeffs"]["file"]] + [
        v["file"]
        for key, v in m["categories"].get("indexes", {}).get("arrays", {}).items()
        if key.endswith("coeffs")
    ]
    for fn in victims:
        arr = np.load(os.path.join(folder, fn))
        np.save(os.path.join(folder, fn), -arr)
    rc = main(["--verify", d])
    out = capsys.readouterr().out
    assert rc == 1 and "MISMATCH" in out
