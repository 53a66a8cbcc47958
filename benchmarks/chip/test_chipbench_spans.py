"""CPU tests of the readers of the system's spans inside filter and verify
(``scene.build``, ``verify.d2h``, ``mask``, ``stream.wait``) and of the
``repro/<span>`` annotations it writes into the profiler's trace, on
synthetic contexts; the kernel's trace name pinned; and traced runs of the
harness at a small size that report every one of them."""

from __future__ import annotations

import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import run  # noqa: E402
from chipbench.kernels import RAYCAST_BATCH  # noqa: E402
from chipbench.xplane import WINDOW_MARK, Event, Trace  # noqa: E402

SPAN_READERS = {
    "scene_build_ms_per_batch": "scene.build",
    "d2h_ms_per_batch": "verify.d2h",
    "mask_ms_per_batch": "mask",
    "stream_wait_ms_per_batch": "stream.wait",
}
#: Readers that read a number from a CPU run (no device planes there).
NEW = [*SPAN_READERS, "idle_unattributed_pct", "shard_host_ms_per_batch"]


def _ctx(spans=(), host=(), ops=(), batches=4):
    """A window of 0..1000 ns with the given ring spans, host events and
    device operations."""
    trace = Trace(
        [Event(WINDOW_MARK, 0.0, 1000.0), *host],
        {"/device:TPU:0": list(ops)} if ops else {},
    )
    return types.SimpleNamespace(
        batches=batches, spans=list(spans), trace=trace, trace_window=(0.0, 1000.0),
    )


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_span_reader_sums_its_spans_over_batches(metric):
    name = SPAN_READERS[metric]
    spans = [
        (name, 1.0, 1.5, 2, {}),
        (name, 2.0, 2.25, 1, {"bytes": 8}),
        ("verify", 0.0, 3.0, 1, {}),
        ("filter", 0.0, 3.0, 1, {}),
    ]
    assert run.read_metric(metric, _ctx(spans, batches=5)) == pytest.approx(150.0)


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_span_reader_finds_nothing_to_read(metric):
    others = [("filter", 0.0, 1.0, 1, {}), ("verify", 1.0, 2.0, 1, {})]
    assert run.read_metric(metric, _ctx(others)) is None  # the parent's spans
    assert run.read_metric(metric, _ctx([(SPAN_READERS[metric], 0.0, 1.0, 0, {})],
                                        batches=0)) is None


def test_idle_unattributed_is_the_idle_time_no_annotation_covers():
    ops = [Event("%k.1 = custom-call", 100.0, 300.0), Event("%k.2 = custom-call", 600.0, 700.0)]
    # idle: [0,100) [300,600) [700,1000] = 700 ns; annotations cover
    # 50..100 and 300..400 (one nested in another), and 900..1200
    host = [
        Event("repro/batch", 50.0, 400.0),
        Event("repro/verify.d2h", 320.0, 380.0),
        Event("repro/filter", 900.0, 1200.0),
        Event("python_other", 400.0, 600.0),  # not the system's
    ]
    covered = 50.0 + 100.0 + 100.0
    got = run.read_metric("idle_unattributed_pct", _ctx(host=host, ops=ops))
    assert got == pytest.approx(100.0 * (700.0 - covered) / 700.0)


def test_idle_unattributed_reads_zero_when_spans_cover_every_gap():
    ops = [Event("%k.1 = custom-call", 100.0, 300.0)]
    host = [Event("repro/filter", 0.0, 100.0), Event("repro/verify", 300.0, 1000.0)]
    assert run.read_metric("idle_unattributed_pct", _ctx(host=host, ops=ops)) == 0.0


def test_idle_unattributed_is_none_without_annotations():
    ops = [Event("%k.1 = custom-call", 100.0, 300.0)]
    host = [Event("python_other", 0.0, 1000.0), Event("repro/late", 1000.0, 2000.0)]
    assert run.read_metric("idle_unattributed_pct", _ctx(host=host, ops=ops)) is None


def test_kernel_trace_name_is_pinned():
    """The device trace names the kernel after its jitted wrapper, which
    ``kernel_ms_per_batch`` and ``raycast_batch_roofline`` look for."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    fn = getattr(ops.raycast_count_batch_kernel_call, "__wrapped_jit__",
                 ops.raycast_count_batch_kernel_call)
    users = jax.ShapeDtypeStruct((8,), jnp.float32)
    planes = jax.ShapeDtypeStruct((1, 3, 128), jnp.float32)
    text = fn.lower(users, users, planes, planes, planes, bu=8, bm=128,
                    interpret=True).as_text()
    assert f"module @jit_{RAYCAST_BATCH} " in text


SMALL = {
    "cal_f1000_k10.uniform": dict(points=8000, facilities=200, check_batches=2),
    "cal_f1000_k10.siting": dict(points=8000, check_batches=2),
    "usa_f1000_k100_x4.uniform": dict(points=8000, check_batches=2),
}


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_traced_run_reports_the_new_metrics(workload):
    """A ``--trace 1`` run, the look for a chip skipped: every new metric
    that lists the cell reads a number."""
    result = run.run_cell(
        workload, 2**31 + 11, 0.5, True, require_chip=False,
        overrides=SMALL[workload], log_lines=lambda _s: None,
    )
    bench, *_ = run.load_cell(workload)
    listed = {m["name"] for m in run.metrics_for(bench, "per_layer", workload)}
    assert ("stream_wait_ms_per_batch" in listed) == workload.endswith("siting")
    for name in NEW:
        if name in listed:
            assert result["metrics"][name]["value"] is not None, name
    assert 0.0 <= result["metrics"]["idle_unattributed_pct"]["value"] <= 100.0
    for name in ("filter_ms_per_batch", "verify_ms_per_batch"):
        assert result["metrics"][name]["value"] > 0.0


def test_shard_host_is_shard_verify_time_with_every_chip_idle():
    # two shards a batch, two batches; shard-verify spans [0,300) [300,600)
    # and [600,800) [800,1000); kernels run [100,250) on chip 0 and
    # [350,550) on chip 1; the second batch has no kernel in the window
    trace = Trace(
        [Event(WINDOW_MARK, 0.0, 1000.0),
         Event("repro/verify", 0.0, 1000.0),
         *(Event("repro/shard-verify", s, e)
           for s, e in [(0.0, 300.0), (300.0, 600.0), (600.0, 800.0), (800.0, 1200.0)])],
        {"/device:TPU:0": [Event("%k.1 = custom-call", 100.0, 250.0)],
         "/device:TPU:1": [Event("%k.2 = custom-call", 350.0, 550.0)]},
    )
    ctx = types.SimpleNamespace(batches=2, trace=trace, trace_window=(0.0, 1000.0))
    idle_ns = 1000.0 - 150.0 - 200.0
    assert run.read_metric("shard_host_ms_per_batch", ctx) == pytest.approx(idle_ns / 1e6 / 2)


def test_shard_host_finds_nothing_without_shards():
    ops = [Event("%k.1 = custom-call", 100.0, 300.0)]
    host = [Event("repro/verify", 0.0, 1000.0), Event("repro/shard-verify", 1000.0, 2000.0)]
    assert run.read_metric("shard_host_ms_per_batch", _ctx(host=host, ops=ops)) is None
    host = [Event("repro/shard-verify", 0.0, 500.0)]
    assert run.read_metric("shard_host_ms_per_batch", _ctx(host=host, ops=ops, batches=0)) is None
