"""CPU tests of the trace reduction on a small trace recorded on the chip:
the CAL configuration at 200,000 points, batches of 16 facilities whose
scenes were all cached, the profiler on for a short window
(``testdata/cal_tiny.xplane.pb.gz``); and of the reduction over several
chips on synthetic two-chip traces."""

from __future__ import annotations

import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402
from chipbench.kernels import RAYCAST_BATCH  # noqa: E402
from chipbench.work import peaks_for  # noqa: E402
from chipbench.xplane import OUTSIDE, Event, Trace, label_gaps  # noqa: E402

TRACE = HERE / "testdata" / "cal_tiny.xplane.pb.gz"
#: What the recorded run reported: its batches, and its users.
BATCHES, USERS = 11, 199_000


@pytest.fixture(scope="module")
def trace():
    return Trace.load(TRACE)


def test_window_and_devices(trace):
    lo, hi = trace.window()
    assert list(trace.devices) == ["/device:TPU:0"]
    assert 0.2 < (hi - lo) / 1e9 < 2.0


def test_kernel_is_found_once_per_batch(trace):
    lo, hi = trace.window()
    seconds, n = trace.kernel_seconds(RAYCAST_BATCH, lo, hi)
    assert n == BATCHES
    ops = trace.op_seconds(lo, hi)
    assert ops[RAYCAST_BATCH] == pytest.approx(seconds)
    assert max(ops, key=ops.get) == RAYCAST_BATCH
    assert 0.0 < seconds <= trace.busy_s(lo, hi)


def test_busy_and_idle_gaps_tile_the_window(trace):
    lo, hi = trace.window()
    busy = trace.busy_s(lo, hi)
    gaps = trace.idle_gaps(lo, hi)
    assert 0.0 < busy < (hi - lo) / 1e9
    assert busy + sum(e - s for s, e in gaps) / 1e9 == pytest.approx((hi - lo) / 1e9)
    assert all(lo <= s < e <= hi for s, e in gaps)


def _ctx(trace, tris):
    lo, hi = trace.window()
    return types.SimpleNamespace(
        batches=BATCHES, queries=16 * BATCHES, window_s=(hi - lo) / 1e9,
        spans=[("filter", 0.0, 0.002, 1, {}), ("verify", 0.002, 0.010, 1, {})],
        compiles={}, scene_cache=(10, 0), trace=trace, trace_window=(lo, hi),
        tris=tris, n_users=USERS, peaks=peaks_for("TPU v5 lite"),
    )


def test_readers_on_the_recorded_trace(trace):
    ctx = _ctx(trace, [[100] * 16] * BATCHES)
    lo, hi = ctx.trace_window
    seconds, _ = trace.kernel_seconds(RAYCAST_BATCH, lo, hi)
    assert run.read_metric("kernel_ms_per_batch", ctx) == pytest.approx(1e3 * seconds / BATCHES)
    idle = run.read_metric("device_idle_pct", ctx)
    assert idle == pytest.approx(100 * (1 - trace.busy_s(lo, hi) / ctx.window_s))
    roofline = run.read_metric("raycast_batch_roofline", ctx)
    assert 0.0 < roofline < 100.0
    assert run.read_metric("filter_ms_per_batch", ctx) == pytest.approx(2.0 / BATCHES)
    assert run.read_metric("verify_ms_per_batch", ctx) == pytest.approx(8.0 / BATCHES)
    assert run.read_metric("scene_cache_hit_pct", ctx) == 100.0
    assert run.read_metric("compiles_in_window", ctx) == 0.0


def test_readers_find_nothing_to_read():
    empty = Trace([Event("chipbench_window", 0.0, 1e9)], {})
    ctx = _ctx(empty, None)
    ctx.spans, ctx.scene_cache = [], (0, 0)
    for name in ("kernel_ms_per_batch", "raycast_batch_roofline", "device_idle_pct",
                 "filter_ms_per_batch", "verify_ms_per_batch", "scene_cache_hit_pct"):
        assert run.read_metric(name, ctx) is None, name


def test_gaps_are_labelled_by_the_deepest_open_span():
    spans = [("batch", 0.0, 100.0, 0), ("verify", 50.0, 90.0, 1)]
    gaps = [(60.0, 80.0), (10.0, 20.0), (95.0, 99.0), (120.0, 130.0)]
    assert label_gaps(gaps, spans) == [
        ("verify", 20e-9), ("batch", 10e-9), ("batch", 4e-9), (OUTSIDE, 10e-9),
    ]


def _first_device_gaps(trace, lo, hi):
    """The gaps as the reduction took them before it read several chips:
    those of the first device with operations."""
    for d in sorted(trace.devices):
        busy = trace.busy(d, lo, hi)
        if busy:
            edges = [lo] + [t for iv in busy for t in iv] + [hi]
            return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    return [(lo, hi)]


def test_one_chip_idle_gaps_are_the_first_devices(trace):
    lo, hi = trace.window()
    assert trace.idle_gaps(lo, hi) == _first_device_gaps(trace, lo, hi)
    assert trace.busy_union(lo, hi) == trace.busy("/device:TPU:0", lo, hi)


def _two_chips(ops0, ops1):
    return Trace(
        [Event("chipbench_window", 0.0, 1000.0)],
        {"/device:TPU:0": [Event("%k.1 = custom-call", s, e) for s, e in ops0],
         "/device:TPU:1": [Event("%k.2 = custom-call", s, e) for s, e in ops1]},
    )


def test_idle_gaps_are_where_no_chip_runs():
    # chip 0 idles over [300, 600), which chip 1 covers from 250 to 650
    tr = _two_chips([(100.0, 300.0), (600.0, 700.0)], [(250.0, 650.0)])
    assert tr.idle_gaps(0.0, 1000.0) == [(0.0, 100.0), (700.0, 1000.0)]
    assert tr.busy_union(0.0, 1000.0) == [(100.0, 700.0)]
    assert _first_device_gaps(tr, 0.0, 1000.0) == [(0.0, 100.0), (300.0, 600.0), (700.0, 1000.0)]


def _concurrency(trace):
    ctx = types.SimpleNamespace(trace=trace, trace_window=(0.0, 1000.0))
    return run.read_metric("chip_concurrency", ctx)


def test_chip_concurrency_counts_chips_busy_together():
    assert _concurrency(_two_chips([(100.0, 300.0)], [(300.0, 500.0)])) == pytest.approx(1.0)
    assert _concurrency(_two_chips([(100.0, 300.0)], [(100.0, 300.0)])) == pytest.approx(2.0)
    # half of chip 1's 200 ns overlaps chip 0: 400 ns of work in 300 ns
    assert _concurrency(_two_chips([(100.0, 300.0)], [(200.0, 400.0)])) == pytest.approx(4 / 3)
    assert _concurrency(Trace([Event("chipbench_window", 0.0, 1000.0)], {})) is None
