"""CPU tests of the benchmark's yardstick: traffic, work function, reference,
reservoir and the interval arithmetic of the trace reduction."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench.check import Reservoir  # noqa: E402
from chipbench.data import RoadNetwork, facility_user_split  # noqa: E402
from chipbench.traffic import Traffic  # noqa: E402
from chipbench.work import least_time, peaks_for, raycast_batch_work  # noqa: E402
from chipbench.xplane import merge  # noqa: E402

HERE = Path(__file__).resolve().parent
BIG_SEED = 2**31 + 12345


def _mix(name):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def _deployment(seed, n=5000):
    net = RoadNetwork(n, seed)
    facilities, users = facility_user_split(net.points, 200, seed)
    return net, facilities, users


# ---- data ------------------------------------------------------------------
def test_road_network_is_deterministic_and_in_the_unit_square():
    a, b = RoadNetwork(4000, BIG_SEED), RoadNetwork(4000, BIG_SEED)
    assert a.points.shape == (4000, 2)
    assert np.array_equal(a.points, b.points)
    assert a.points.min() >= 0.0 and a.points.max() <= 1.0
    assert not np.array_equal(a.points, RoadNetwork(4000, BIG_SEED + 1).points)


def test_split_keeps_every_point_once():
    net, facilities, users = _deployment(7)
    both = np.concatenate([facilities, users])
    assert len(facilities) == 200 and len(both) == len(net.points)
    assert np.array_equal(
        np.unique(both, axis=0), np.unique(net.points, axis=0)
    )


# ---- traffic ---------------------------------------------------------------
def _take(gen, n):
    return [next(gen) for _ in range(n)]


@pytest.mark.parametrize("seed", [0, 3, BIG_SEED])
def test_uniform_traffic_is_deterministic_distinct_and_covers_all_facilities(seed):
    net, facilities, _ = _deployment(seed)
    cfg = {"q": 16}
    t1 = Traffic(_mix("uniform"), cfg, seed, facilities, net)
    t2 = Traffic(_mix("uniform"), cfg, seed, facilities, net)
    b1, b2 = _take(t1.batches(), 100), _take(t2.batches(), 100)
    assert b1 == b2
    for batch in b1:
        assert len(batch) == 16 and len(set(batch)) == 16
        assert all(0 <= f < len(facilities) for f in batch)
    assert len({tuple(sorted(b)) for b in b1}) == len(b1)  # no batch repeats
    counts = np.bincount([f for b in b1 for f in b], minlength=len(facilities))
    assert counts.min() > 0 and counts.max() < 4 * counts.mean()
    warm = t1.warmup_batches()
    assert len(warm) == _mix("uniform")["warmup_batches"]
    assert all(len(set(b)) == 16 for b in warm) and warm[0] != b1[0]


def test_zipf_traffic_follows_popularity():
    net, facilities, _ = _deployment(11)
    spec = {**_mix("uniform"), "zipf_s": 1.0}
    t = Traffic(spec, {"q": 4}, 11, facilities, net)
    counts = np.bincount([f for b in _take(t.batches(), 400) for f in b],
                         minlength=len(facilities))
    order = np.argsort(-t.weights)
    assert counts[order[0]] > 3 * counts[order[50]]


@pytest.mark.parametrize("seed", [1, BIG_SEED])
def test_siting_traffic_never_repeats_and_stays_on_the_domain(seed):
    net, facilities, users = _deployment(seed)
    t = Traffic(_mix("siting"), {"q": 16}, seed, facilities, net)
    batches = _take(t.batches(), 40)
    again = _take(Traffic(_mix("siting"), {"q": 16}, seed, facilities, net).batches(), 40)
    pts = np.array([p for b in batches for p in b])
    assert np.array_equal(pts, np.array([p for b in again for p in b]))
    assert pts.shape == (640, 2)
    assert len(np.unique(pts, axis=0)) == 640
    assert pts.min() >= 0.0 and pts.max() <= 1.0
    warm = np.array([p for b in t.warmup_batches() for p in b])
    assert not (set(map(tuple, warm)) & set(map(tuple, pts)))
    assert not (set(map(tuple, users)) & set(map(tuple, pts)))


# ---- work function -----------------------------------------------------------
def test_raycast_work_counts_real_triangles_and_the_served_counts():
    flops, nbytes = raycast_batch_work(1000, [10, 30])
    assert flops == 1000 * 40 * 12
    assert nbytes == 1000 * 8 + 40 * 36 + 2 * 1000 * 4


def test_least_time_names_its_bound():
    peaks = peaks_for("TPU v5 lite")
    t, bound = least_time(*raycast_batch_work(1_889_815, [70] * 16), peaks)
    assert bound == "memory"
    assert t == pytest.approx((1_889_815 * 8 + 70 * 16 * 36 + 16 * 1_889_815 * 4) / 819e9)
    t, bound = least_time(197e12, 1.0, peaks)
    assert bound == "compute" and t == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("cpu")


# ---- reservoir -----------------------------------------------------------------
def test_reservoir_is_a_seeded_sample_of_the_whole_stream():
    def sample(seed, n):
        r = Reservoir(4, np.random.default_rng(seed))
        for i in range(n):
            r.offer(i)
        return sorted(r.items)

    assert sample(5, 3) == [0, 1, 2]
    assert sample(5, 200) == sample(5, 200)
    assert max(sample(5, 200)) > 3
    assert len(set(sample(5, 200))) == 4


# ---- trace interval arithmetic -------------------------------------------------
def test_merge_unions_overlapping_intervals():
    assert merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
