"""CPU tests of the comparison that decides ``correct``: the reference
against a float64 brute force, the control (bfloat16) rejected, and runs of
the harness, with the look for a chip skipped, that come out false when the
timed path is broken underneath."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import control  # noqa: E402
import run  # noqa: E402
from chipbench.data import RoadNetwork, facility_user_split  # noqa: E402
from chipbench.reference import BAND, reference_ranks  # noqa: E402

#: Small sizes the CPU can hold (the kernels run in interpret mode here).
SMALL = {
    "cal_f1000_k10.uniform": dict(points=8000, facilities=200, check_batches=2),
    "cal_f1000_k10.siting": dict(points=8000, check_batches=2),
    "usa_f1000_k100.uniform": dict(points=8000, check_batches=2),
    "usa_f1000_k100_x4.uniform": dict(points=8000, check_batches=2),
}
SEED = 2**31 + 7


def _run(workload, seed=SEED):
    return run.run_cell(
        workload, seed, 0.3, False, require_chip=False,
        overrides=SMALL[workload], log_lines=lambda _s: None,
    )


def _brute_rank(users, facilities, q, exclude):
    d2q = np.sum((users - q) ** 2, axis=1)
    d2f = np.sum((users[:, None, :] - facilities[None, :, :]) ** 2, axis=2)
    closer = d2f < d2q[:, None]
    if exclude is not None:
        closer[:, exclude] = False
    return closer.sum(axis=1)


@pytest.mark.parametrize("k", [1, 10, 100])
def test_reference_brackets_the_exact_rank(k):
    import jax.numpy as jnp

    net = RoadNetwork(3000, 5)
    facilities, users = facility_user_split(net.points, 300, 5)
    ux, uy = jnp.asarray(users[:, 0], jnp.float32), jnp.asarray(users[:, 1], jnp.float32)
    for q in (0, 17, 299):
        exact = _brute_rank(users, facilities, facilities[q], q)
        lo, hi = reference_ranks(ux, uy, facilities, facilities[q], q)
        assert np.all(lo <= exact) and np.all(exact <= hi)
        assert np.mean(lo == hi) > 0.99  # the band leaves few users undecided
        decided = (hi < k) | (lo >= k)
        assert np.array_equal((exact < k)[decided], (hi < k)[decided])
    point = np.array([0.4, 0.6])
    lo, hi = reference_ranks(ux, uy, facilities, point, None)
    exact = _brute_rank(users, facilities, point, None)
    assert np.all(lo <= exact) and np.all(exact <= hi)
    assert BAND < 1e-5


@pytest.mark.parametrize("workload", ["cal_f1000_k10.uniform", "cal_f1000_k10.siting"])
def test_control_in_bfloat16_is_rejected(workload):
    out = control.run_control(workload, SEED, overrides=SMALL[workload])
    assert out["queries"] > 0
    assert out["wrong_members"] > 0


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_sound_run_is_correct(workload):
    result = _run(workload)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert result["checks"]["wrong_members"] == {"value": 0, "limit": 0}
    assert set(result["metrics"]) == {"queries_per_s", "setup_s"}


def _faulty(monkeypatch, fault):
    from repro.kernels import ops

    real = ops.raycast_count_batch

    def broken(xs, ys, coeffs, **kw):
        return fault(real, xs, ys, coeffs, **kw)

    monkeypatch.setattr(ops, "raycast_count_batch", broken)


def _alter_first_answer(real, xs, ys, coeffs, **kw):
    return real(xs, ys, coeffs, **kw).at[0].add(1000)


def _half_batch(real, xs, ys, coeffs, **kw):
    import jax.numpy as jnp

    half = max(coeffs.shape[0] // 2, 1)
    out = real(xs, ys, coeffs[:half], **kw)
    return jnp.concatenate([out, out])[: coeffs.shape[0]]


@pytest.mark.parametrize("fault", [_alter_first_answer, _half_batch], ids=["altered", "half"])
@pytest.mark.parametrize(
    "workload",
    ["cal_f1000_k10.uniform", "cal_f1000_k10.siting", "usa_f1000_k100_x4.uniform"],
)
def test_broken_timed_path_is_not_correct(monkeypatch, workload, fault):
    _faulty(monkeypatch, fault)
    result = _run(workload)
    assert result["correct"] is False
    assert result["failed"] > 0
    assert result["checks"]["wrong_members"]["value"] > 0


def test_lost_shard_is_not_correct(monkeypatch):
    """The exchange between chips left out: the last shard's counts never
    reach the reassembled answer (its users read 0)."""
    from repro.shard import engine as shard_engine

    real = shard_engine.ShardDispatch.__call__

    def lose_last_shard(self, prepared):
        out = np.array(real(self, prepared))
        last = self.state.views[-1]
        out[:, self.state.perm[last.lo:last.hi]] = 0
        return out

    monkeypatch.setattr(shard_engine.ShardDispatch, "__call__", lose_last_shard)
    result = _run("usa_f1000_k100_x4.uniform")
    assert result["correct"] is False
    assert result["checks"]["wrong_members"]["value"] > 0


def test_sharded_engine_needs_one_shard_a_chip():
    net = RoadNetwork(2000, 3)
    facilities, users = facility_user_split(net.points, 100, 3)
    cfg = {"engine": "ShardedEngine", "engine_options": {"backend": "dense", "shards": 2},
           "chips": 4}
    with pytest.raises(run.RunError, match="shards=2 differs from chips=4"):
        run.make_engine(cfg, facilities, users)
    with pytest.raises(run.RunError, match="unknown engine"):
        run.make_engine({**cfg, "engine": "OtherEngine"}, facilities, users)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", "cal_f1000_k10.uniform",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
