"""The engine's spans inside filter and verify, and their batch numbers.

What is under test:

* ``query_batch`` builds one tree per batch: ``batch`` over ``filter``
  (``scene.build`` with ``scene.prune`` / ``scene.occluders``, then
  ``filter.stack``), ``verify`` (``verify.h2d``, ``verify.wait``,
  ``verify.d2h``) and ``mask`` after it; every span carries the batch's
  number, also in a pool of scene workers.
* ``stream`` gives the producer's ``filter`` and the consumer's
  ``stream.wait`` / ``verify`` / ``mask`` one number per batch across the
  two threads.
* ``filter`` and ``verify`` still time what the result reports.
* With tracing off nothing is recorded and no profiler annotation opens;
  under a ``jax.profiler`` trace every ring span has a ``repro/<name>``
  host event with the same nesting.
* The verify phase counts its copies in ``copy.bytes{dir}``.
"""

import glob
import os

import numpy as np
import pytest

from repro.core import RkNNEngine
from repro.obs import Tracer, chrome_trace, set_tracer, span, spans
from repro.obs.trace import ANNOTATION_PREFIX

K = 4


@pytest.fixture
def tracer():
    """A fresh enabled tracer installed as the global one."""
    t = Tracer(capacity=1 << 12)
    prev = set_tracer(t)
    t.enable()
    yield t
    set_tracer(prev)


@pytest.fixture(scope="module")
def points():
    rng = np.random.default_rng(13)
    return rng.random((60, 2)), rng.random((700, 2))


def _engine(points, **kw):
    facilities, users = points
    return RkNNEngine(facilities, users, backend="dense-ref", **kw)


def _parent(rec, recs):
    """The innermost span of ``rec``'s thread, one level up, that contains it."""
    up = [
        r for r in recs
        if r["tid"] == rec["tid"] and r["depth"] == rec["depth"] - 1
        and r["t0"] <= rec["t0"] and rec["t1"] <= r["t1"]
    ]
    assert len(up) == 1, (rec, up)
    return up[0]


def _shape(recs):
    """``(name, parent name)`` in time order."""
    return [
        (r["name"], _parent(r, recs)["name"] if r["depth"] else None) for r in recs
    ]


SCENE = [
    ("scene.build", "filter"),
    ("scene.prune", "scene.build"),
    ("scene.occluders", "scene.build"),
]
VERIFY = [
    ("verify", "batch"),
    ("verify.h2d", "verify"),
    ("verify.wait", "verify"),
    ("verify.d2h", "verify"),
]


def test_query_batch_span_tree(tracer, points):
    eng = _engine(points)
    res = eng.query_batch([0, 1, 2], K)
    recs = spans(tracer)
    assert _shape(recs) == (
        [("batch", None), ("filter", "batch")]
        + SCENE * 3
        + [("filter.stack", "filter")]
        + VERIFY
        + [("mask", "batch")]
    )
    assert {r["batch"] for r in recs} == {0}
    by = {r["name"]: r for r in recs}
    assert by["mask"]["t0"] >= by["verify"]["t1"]  # mask is outside verify
    np.testing.assert_array_equal(res.masks, res.counts < K)

    # a repeat hits the prepared-batch cache: no scene build, no stacking,
    # and the next number
    tracer.clear()
    eng.query_batch([0, 1, 2], K)
    recs = spans(tracer)
    assert _shape(recs) == [("batch", None), ("filter", "batch")] + VERIFY + [
        ("mask", "batch")
    ]
    assert {r["batch"] for r in recs} == {1}


def test_scene_workers_join_the_batch(tracer, points):
    eng = _engine(points, scene_workers=2)
    eng.query_batch([3, 4, 5, 6], K)
    builds = [r for r in spans(tracer) if r["name"].startswith("scene.")]
    assert len(builds) == 12
    assert {r["batch"] for r in builds} == {0}


def test_filter_and_verify_time_what_the_result_reports(tracer, points):
    eng = _engine(points)
    res = eng.query_batch([7, 8], K)
    by = {r["name"]: r for r in spans(tracer)}
    assert res.t_filter_s == pytest.approx(by["filter"]["t1"] - by["filter"]["t0"])
    assert res.t_verify_s == pytest.approx(by["verify"]["t1"] - by["verify"]["t0"])
    assert by["filter"]["t1"] <= by["verify"]["t0"]


def test_stream_links_producer_and_consumer_spans(tracer, points):
    eng = _engine(points)
    batches = [[0, 1], [2, 3], [4, 5]]
    served = list(eng.stream(batches, K))
    assert [b for b, _ in served] == batches
    recs = spans(tracer)
    numbers = sorted({r["batch"] for r in recs if r["name"] == "filter"})
    assert len(numbers) == 3
    for n in numbers:
        mine = [r for r in recs if r["batch"] == n]
        names = {r["name"] for r in mine}
        assert {"filter", "scene.build", "filter.stack", "stream.wait",
                "verify", "verify.d2h", "mask"} <= names
        (producer,) = {r["tid"] for r in mine if r["name"] == "filter"}
        (consumer,) = {r["tid"] for r in mine if r["name"] == "verify"}
        assert producer != consumer
        assert {r["tid"] for r in mine if r["name"] in ("mask", "stream.wait")} == {consumer}
        assert all(r["depth"] == 0 for r in mine
                   if r["name"] in ("filter", "verify", "mask", "stream.wait"))
    # the last wait returns the end of the stream: no batch
    assert [r["batch"] for r in recs if r["name"] == "stream.wait"][-1] == -1


def test_tracing_off_records_nothing_and_opens_no_annotation(tracer, points):
    opened = []

    class Counting:
        def __init__(self, *a, **kw):
            opened.append(a)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    tracer._annotation = Counting
    tracer.disable()
    eng = _engine(points)
    res = eng.query_batch([0, 1], K)
    assert res.t_filter_s > 0.0 and res.t_verify_s > 0.0  # spans still time
    assert list(tracer.records()) == []
    assert opened == []
    with span("x") as sp:
        pass
    assert sp._ann is None and sp.seq == -1

    tracer.enable()
    eng.query_batch([2, 3], K)
    assert len(opened) == len(list(tracer.records())) > 0
    assert all(a[0].startswith(ANNOTATION_PREFIX) for a in opened)


def test_copy_bytes_counts_the_verify_copies(points):
    eng = _engine(points, pad_to=128)
    eng.query_batch([0, 1, 2], K)
    snap = eng.metrics.snapshot()
    n_users = len(points[1])
    assert snap["copy.bytes{dir=d2h}"] == 3 * n_users * 4  # [Q, N] int32
    assert snap["copy.bytes{dir=h2d}"] == 3 * 128 * 9 * 4  # [Q, Mp, 3, 3] f32


def test_chrome_trace_carries_the_batch_number(tracer, points):
    eng = _engine(points)
    eng.query_batch([0, 1], K)
    with span("outside"):
        pass
    events = [e for e in chrome_trace(tracer)["traceEvents"] if e["ph"] == "X"]
    assert {e["args"]["batch"] for e in events if e["name"] != "outside"} == {0}
    assert "batch" not in next(e for e in events if e["name"] == "outside")["args"]


def test_spans_land_in_the_profiler_trace(tracer, points, tmp_path):
    import jax
    from jax.profiler import ProfileData

    eng = _engine(points)
    eng.query_batch([9, 10], K)  # compile outside the trace
    tracer.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.query_batch([11, 12, 13], K)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    events = [
        (e.name[len(ANNOTATION_PREFIX):], e.start_ns, e.start_ns + e.duration_ns,
         dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:CPU")
        for line in plane.lines
        for e in line.events
        if e.name.startswith(ANNOTATION_PREFIX)
    ]
    recs = spans(tracer)
    assert sorted(n for n, *_ in events) == sorted(r["name"] for r in recs)
    # the i-th ring span of a name is the i-th host event of that name
    events.sort(key=lambda ev: ev[1])
    match = {}
    for name in {r["name"] for r in recs}:
        ring = [r for r in recs if r["name"] == name]
        host = [ev for ev in events if ev[0] == name]
        for r, ev in zip(ring, host):
            match[id(r)] = ev
    for r in recs:
        ev = match[id(r)]
        assert ev[3].get("batch") == r["batch"] == 1  # the second batch
        if r["depth"]:
            up = match[id(_parent(r, recs))]
            assert up[1] <= ev[1] and ev[2] <= up[2], (r["name"], up[0])


def test_obs_imports_jax_only_when_a_span_is_traced():
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from repro.obs import enable_tracing, span\n"
        "with span('off'): pass\n"
        "enable_tracing()\n"
        "print('jax' in sys.modules)\n"
        "with span('on'): pass\n"
        "print('jax' in sys.modules)\n"
    )
    env = {**os.environ, "PYTHONPATH": os.pathsep.join(sys.path)}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         env=env, timeout=120, check=True).stdout.split()
    assert out == ["False", "True"]
