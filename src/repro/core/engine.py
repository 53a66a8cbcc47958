"""Stateful RkNN query engine: build once, serve many query waves.

The paper's performance story is amortization — construct geometry once,
cast many rays (RT-kNNS Unbound and RTNN make the same point for RT-core
kNN: the wins come from reusing the built acceleration structure across
query batches).  :class:`RkNNEngine` is the long-lived object that state
hangs off:

* the shared domain :class:`~repro.core.geometry.Rect` and the device-
  resident user coordinate arrays (uploaded once, like the paper's
  "plain GPU transfer" of Table 2);
* a :class:`~repro.core.hybrid.SceneCache` so hot queries skip InfZone
  pruning + occluder construction entirely (cache hits show up directly
  as a collapsed ``t_filter_s``);
* a batch-level LRU of prepared backend state (stacked coeffs / stacked
  grid / stacked BVH), so a repeated query workload skips the whole host
  filter phase;
* persistent jitted dispatches: scene pads are bucketed to sticky powers
  of two, so repeat workloads re-enter the same XLA executable instead of
  re-tracing;
* an optional ``jax.sharding.Mesh`` — the dense-ref batch dispatch is then
  pjit'd with users sharded over the data axes and queries over
  ``'model'`` (the serving layout previously trapped in ``launch/serve``).

Verification backends are pluggable via :mod:`repro.core.backends`; the
legacy free functions (``rt_rknn_query`` etc.) are one-shot shims over a
throwaway engine.  Lifecycle, config knobs, and the migration table from
the free functions live in ``docs/API.md``.
"""

from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import itertools
import math
import queue
import threading
import time
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.backends import (
    Backend,
    BatchRequest,
    QueryRequest,
    get_backend,
)
from repro.core.geometry import Rect
from repro.core.hybrid import SceneCache, _q_key
from repro.core.results import RkNNBatchResult, RkNNResult
from repro.core.scene import Scene, build_scene
from repro.core.snapshot import EngineSnapshot
from repro.obs import (
    Histogram,
    MetricsRegistry,
    batch_scope,
    current_batch,
    span,
    track_jit,
)
from repro.planner.models import WorkloadShape

__all__ = ["RkNNConfig", "EngineStats", "RkNNEngine", "serve_shardings"]


def serve_shardings(mesh):
    """The serving partition layout: ``(user_sh, scene_sh, out_sh)``.

    Users sharded over the data-parallel axes, per-query scenes replicated
    (they are tiny — ~64 triangles · 36 B), queries sharded over
    ``'model'``.  Single source of truth for the engine's live dispatch
    and ``launch.serve.lower_rknn_serve``'s dry-run lowering.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.meshctx import dp_axes

    dp = dp_axes(mesh)
    dp_spec = dp if len(dp) > 1 else dp[0]
    user_sh = NamedSharding(mesh, P(dp_spec))
    scene_sh = NamedSharding(mesh, P("model", None, None, None))
    out_sh = NamedSharding(mesh, P("model", dp_spec))
    return user_sh, scene_sh, out_sh


def _auto_axes(mesh):
    """``mesh`` with every axis of type ``Auto``.

    The sharded serving steps below are written for sharding propagation:
    they pin only their inputs and outputs (``in_shardings`` /
    ``out_shardings``).  On a mesh with ``Explicit`` axes — what
    ``jax.make_mesh`` builds by default — every intermediate carries a
    sharding type instead, and the steps' gathers, which declare none, are
    refused.  The devices and axis names are unchanged.
    """
    from jax.sharding import AxisType, Mesh

    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(
        mesh.devices, mesh.axis_names, axis_types=(AxisType.Auto,) * mesh.devices.ndim
    )


@dataclasses.dataclass(frozen=True)
class RkNNConfig:
    """Construction-time knobs of :class:`RkNNEngine` (see docs/API.md).

    ``scene_cache`` / ``batch_cache`` are LRU capacities (0 disables).
    ``pad_scene_to`` seeds the sticky power-of-two triangle pad bucket;
    ``pad_to`` pins it exactly (overriding bucketing) when not ``None``.
    """

    backend: str = "dense-ref"
    strategy: str = "infzone"
    grid_g: int = 64
    prune_grid: int | None = None
    pad_to: int | None = None
    scene_workers: int = 0
    scene_cache: int = 256
    batch_cache: int = 8
    pad_scene_to: int = 128
    #: Feed the planner's observed-vs-predicted residuals back into the
    #: active profile's coefficients (damped; ``auto`` backend only).
    online_recalibration: bool = False
    #: Arm a :class:`repro.obs.FlightRecorder` at construction: any
    #: reader/writer exception (and sentinel trips) dumps a postmortem
    #: bundle under ``flight_dir``.
    flight_recorder: bool = False
    flight_dir: str = "flight"
    #: Warm-start from a ``rknn-store/1`` directory (:mod:`repro.persist`):
    #: at construction, every fingerprint-matching state category (scenes,
    #: indexes, kernel bucketing, shards, planner profile) is adopted into
    #: the fresh snapshot.  Best-effort — a missing or stale store leaves a
    #: fully functional cold engine.
    warm_store: str | None = None


class EngineStats:
    """The legacy cumulative-stats surface, as live **views** over the
    engine's :class:`~repro.obs.MetricsRegistry`.

    Every field that used to be a mutated dataclass attribute is now a
    property reading the underlying counters/gauges/histograms, so the
    public shape is unchanged while the same telemetry also carries full
    per-``(phase, backend, shard)`` distributions (``engine.metrics
    .snapshot()`` exposes those, including p50/p90/p99).

    The ``planner_*`` fields only move when queries route through the
    ``auto`` backend: per-backend dispatch counts and the running
    predicted-vs-observed cost totals (the planner's calibration error is
    ``planner_obs_s / planner_pred_s`` drifting from 1).

    The ``shard_*`` fields only move on a sharded engine
    (:class:`repro.shard.ShardedEngine`): cumulative per-shard filter
    (per-shard bucketing/stacking) and verify (per-shard dispatch) time,
    indexed by shard, and the lifetime imbalance ratio
    ``max(shard_verify) / mean(shard_verify)`` — 1.0 is perfectly
    balanced; clustered user distributions drift above it.

    ``events_dropped`` / ``continuous_pruned`` surface the dynamic
    engine's standing-query bookkeeping: events lost to saturated
    :class:`~repro.dynamic.continuous.ContinuousQuery` buffers and dead
    handles pruned on the update path.
    """

    def __init__(self, metrics: MetricsRegistry):
        self.metrics = metrics

    def _phase_sum(self, name: str, phase: str) -> float:
        return sum(
            h.sum
            for labels, h in self.metrics.find(name)
            if labels.get("phase") == phase
        )

    def _shard_list(self, phase: str) -> list[float]:
        per = {
            int(labels["shard"]): h.sum
            for labels, h in self.metrics.find("shard.phase_s")
            if labels.get("phase") == phase
        }
        if not per:
            return []
        return [per.get(i, 0.0) for i in range(max(per) + 1)]

    @property
    def n_queries(self) -> int:
        return self.metrics.counter("queries").value

    @property
    def n_batches(self) -> int:
        return self.metrics.counter("batches").value

    @property
    def t_filter_s(self) -> float:
        return self._phase_sum("phase_s", "filter")

    @property
    def t_verify_s(self) -> float:
        return self._phase_sum("phase_s", "verify")

    @property
    def m_max(self) -> int:
        return int(self.metrics.gauge("m_max").value)

    @property
    def batch_cache_hits(self) -> int:
        return self.metrics.counter("batch_cache.hits").value

    @property
    def planner_decisions(self) -> dict:
        return {
            labels["backend"]: c.value
            for labels, c in self.metrics.find("planner.decisions")
        }

    @property
    def planner_pred_s(self) -> float:
        return sum(
            h.sum
            for labels, h in self.metrics.find("planner.plan_s")
            if labels.get("kind") == "pred"
        )

    @property
    def planner_obs_s(self) -> float:
        return sum(
            h.sum
            for labels, h in self.metrics.find("planner.plan_s")
            if labels.get("kind") == "obs"
        )

    @property
    def planner_recal_nudges(self) -> int:
        return self.metrics.counter("planner.recal_nudges").value

    @property
    def shard_filter_s(self) -> list[float]:
        return self._shard_list("filter")

    @property
    def shard_verify_s(self) -> list[float]:
        return self._shard_list("verify")

    @property
    def shard_imbalance(self) -> float:
        found = self.metrics.find("shard.imbalance")
        return found[0][1].value if found else 1.0

    @property
    def events_dropped(self) -> int:
        return self.metrics.counter("continuous.events_dropped").value

    @property
    def continuous_pruned(self) -> int:
        return self.metrics.counter("continuous.pruned").value

    def __repr__(self) -> str:  # debugging parity with the old dataclass
        fields = (
            "n_queries", "n_batches", "t_filter_s", "t_verify_s", "m_max",
            "batch_cache_hits", "planner_decisions", "planner_pred_s",
            "planner_obs_s", "planner_recal_nudges", "shard_filter_s",
            "shard_verify_s", "shard_imbalance", "events_dropped",
            "continuous_pruned",
        )
        inner = ", ".join(f"{f}={getattr(self, f)!r}" for f in fields)
        return f"EngineStats({inner})"


def _next_pow2(n: int) -> int:
    return 1 << max(int(np.ceil(np.log2(max(n, 1)))), 0)


def _normalize_queries(
    facilities: np.ndarray, qs
) -> tuple[list[int | np.ndarray], np.ndarray, list[int | None]]:
    """Split a query batch into per-query build args, points, and excludes."""
    queries: list[int | np.ndarray] = []
    q_pts = np.zeros((len(qs), 2), np.float64)
    excludes: list[int | None] = []
    for i, q in enumerate(qs):
        arr = np.asarray(q)
        if arr.ndim == 0 and np.issubdtype(arr.dtype, np.integer):
            qi = int(arr)
            queries.append(qi)
            q_pts[i] = facilities[qi]
            excludes.append(qi)
        else:
            pt = np.asarray(q, np.float64).reshape(2)
            queries.append(pt)
            q_pts[i] = pt
            excludes.append(None)
    return queries, q_pts, excludes


class RkNNEngine:
    """Build once from ``(facilities, users, RkNNConfig)``; query many times.

    Exposes :meth:`query`, :meth:`query_batch`, :meth:`query_mono`, and
    :meth:`stream` (double-buffered host scene builds overlapping device
    dispatch).  Backend selection defaults to ``config.backend`` and can be
    overridden per call with any name in the backend registry.
    """

    def __init__(
        self,
        facilities: np.ndarray,
        users: np.ndarray,
        config: RkNNConfig | None = None,
        *,
        mesh=None,
        rect: Rect | None = None,
        **overrides,
    ):
        config = config or RkNNConfig()
        if overrides:
            config = dataclasses.replace(config, **overrides)
        get_backend(config.backend)  # validate eagerly
        self.config = config
        self.mesh = None if mesh is None else _auto_axes(mesh)
        self.metrics = MetricsRegistry()
        self.stats = EngineStats(self.metrics)
        self._init_metrics()
        # numbers each served batch; every span of a batch carries it
        self._batch_ids = itertools.count()
        self._snap = self._make_snapshot(
            0,
            np.asarray(facilities, dtype=np.float64),
            np.asarray(users, dtype=np.float64),
            rect=rect,
            explicit_rect=rect is not None,
        )
        self._pad_bucket = max(int(config.pad_scene_to), 1)
        #: Lock-free read-activity clock: query entry points bump it, the
        #: dynamic writer samples it to decide whether prewarm should run
        #: deprioritized.  Races just lose a tick — it is a heuristic, so
        #: no lock touches the read path.
        self._read_clock = 0
        self._mesh_steps: dict = {}  # (backend, statics) -> jitted dispatch
        self._plan_log: "collections.deque[dict]" = collections.deque(maxlen=128)
        #: Health layer (all optional, never on the hot path): a flight
        #: recorder armed by config, a lazily-built sentinel, any live
        #: introspection servers, and the device-bytes scrape memo.
        self.flight = None
        self._sentinel = None
        self._obs_servers: list = []
        self._devbytes_cache: tuple | None = None
        #: Last persist operation's report (:mod:`repro.persist`): store
        #: path, schema, and per-category restored/stale/absent statuses.
        self.persist_info: dict | None = None
        if config.flight_recorder:
            from repro.obs.flight import FlightRecorder

            self.flight = FlightRecorder(self, dir=config.flight_dir)
        if self.mesh is not None:
            self._init_mesh(self._snap, self.mesh)
        if config.warm_store:
            from repro.persist import warm_start

            warm_start(self, config.warm_store)

    def _make_snapshot(
        self,
        version: int,
        facilities: np.ndarray,
        users: np.ndarray,
        *,
        rect: Rect | None = None,
        explicit_rect: bool = False,
        scene_cache: SceneCache | None | str = "new",
    ) -> EngineSnapshot:
        """A fresh :class:`EngineSnapshot` sized from the engine config.
        ``scene_cache="new"`` allocates one (respecting the capacity
        knob); the COW update path passes its migrated cache instead."""
        if scene_cache == "new":
            scene_cache = (
                SceneCache(capacity=self.config.scene_cache)
                if self.config.scene_cache > 0
                else None
            )
        return EngineSnapshot(
            version,
            facilities,
            users,
            rect=rect,
            explicit_rect=explicit_rect,
            scene_cache=scene_cache,
            batch_capacity=self.config.batch_cache,
        )

    # ------------------------------------------------------------------
    # observability (the engine's metrics registry; EngineStats is a view)
    # ------------------------------------------------------------------
    def _init_metrics(self) -> None:
        """Eager scalar metrics + derived gauges.  Per-(phase, backend)
        histograms are created lazily through the handle cache so the
        steady-state query cost is one dict hit + one observe."""
        m = self.metrics
        self._m_queries = m.counter("queries")
        self._m_batches = m.counter("batches")
        self._m_cache_hits = m.counter("batch_cache.hits")
        self._m_mmax = m.gauge("m_max")
        self._m_lag = m.gauge("mvcc.version_lag")
        self._m_pred = m.histogram("planner.plan_s", kind="pred")
        self._m_obs = m.histogram("planner.plan_s", kind="obs")
        self._m_nudges = m.counter("planner.recal_nudges")
        for direction in ("h2d", "d2h"):  # filled by the verify phase's copies
            m.counter("copy.bytes", dir=direction)
        self._metric_cache: dict = {}
        m.derived("scene_cache.hit_ratio", self._scene_cache_hit_ratio)
        m.derived("batch_cache.hit_ratio", self._batch_cache_hit_ratio)
        m.derived("mvcc.version", lambda: float(self._snap.version))
        m.derived("pad_waste", self._pad_waste_ratio)
        # Device-memory accounting of the *served* snapshot version, by
        # category (evaluated only at scrape/snapshot time; one memoized
        # walk serves all categories — see _device_bytes_cached).
        for cat in ("users", "shards", "indexes", "kernel", "batches",
                    "scenes", "total"):
            m.derived(
                "mem.bytes",
                (lambda cat=cat: float(
                    self._device_bytes_cached(self._snap).get(cat, 0)
                )),
                category=cat,
            )

    def _scene_cache_hit_ratio(self) -> float | None:
        sc = self._snap.scene_cache
        if sc is None:
            return None
        total = sc.hits + sc.misses
        return sc.hits / total if total else None

    def _batch_cache_hit_ratio(self) -> float | None:
        n = self._m_batches.value
        return self._m_cache_hits.value / n if n else None

    def _pad_waste_ratio(self) -> float | None:
        try:
            return float(self._snap.pad_waste(self._snap.rect, self.config.grid_g))
        except Exception:
            return None

    # ------------------------------------------------------------------
    # persistence (repro.persist — versioned warm-start state store)
    # ------------------------------------------------------------------
    def save_state(self, directory: str, *, keep: int = 3) -> str:
        """Export the served snapshot's amortized state (scenes, packed
        indexes, kernel bucketing, planner profile, shard partition) as
        the next ``rknn-store/1`` step under ``directory``.  Atomic:
        readers of the store always see a complete step.  Returns the
        published step folder."""
        from repro.persist import save_engine_state

        return save_engine_state(self, directory, keep=keep)

    def restore(self, directory: str) -> dict:
        """Hot-adopt a ``rknn-store/1`` store into this **live** engine:
        builds a snapshot around the store's dataset, adopts every
        fingerprint-matching category, and publishes it as MVCC version
        N+1 via the atomic swap — in-flight readers keep serving N.
        Returns the per-category status report (also on
        ``self.persist_info``)."""
        from repro.persist import restore_engine

        return restore_engine(self, directory)

    def _persist_note(self, op: str, category: str, nbytes: int, seconds) -> None:
        """Record one category's persist traffic (registry dedupes by
        label, so these are stable per-category instruments)."""
        self.metrics.gauge("persist.bytes", category=category, op=op).set(
            float(nbytes)
        )
        if seconds is not None:
            self.metrics.histogram(f"persist.{op}_s", category=category).observe(
                float(seconds)
            )

    def _persist_extra_fingerprints(self, snap: EngineSnapshot) -> dict:
        """Subclass hook: expected fingerprints for engine-specific
        categories (ShardedEngine adds ``shards``)."""
        return {}

    def _persist_extra_categories(self, snap: EngineSnapshot) -> dict:
        """Subclass hook: extra ``{name: {fingerprint, meta, arrays}}``
        categories to persist."""
        return {}

    def _persist_adopt_extra(self, snap: EngineSnapshot, name: str, entry, arrays):
        """Subclass hook: adopt one engine-specific category (fingerprint
        already matched).  Return the adopted item count, or ``None`` if
        the category is not recognized."""
        return None

    def _phase_hist(self, phase: str, backend: str) -> Histogram:
        key = (phase, backend)
        h = self._metric_cache.get(key)
        if h is None:
            h = self._metric_cache[key] = self.metrics.histogram(
                "phase_s", phase=phase, backend=backend
            )
        return h

    def _decision_counter(self, backend: str):
        key = ("dec", backend)
        c = self._metric_cache.get(key)
        if c is None:
            c = self._metric_cache[key] = self.metrics.counter(
                "planner.decisions", backend=backend
            )
        return c

    def _residual_hist(self, backend: str) -> Histogram:
        key = ("res", backend)
        h = self._metric_cache.get(key)
        if h is None:
            h = self._metric_cache[key] = self.metrics.histogram(
                "planner.residual", signed=True, backend=backend
            )
        return h

    # ------------------------------------------------------------------
    # health layer (live introspection, SLO sentinel, flight recorder)
    # ------------------------------------------------------------------
    def serve_obs(self, port: int = 0, host: str = "127.0.0.1"):
        """Boot the live introspection endpoint for this engine
        (``/metrics``, ``/spans``, ``/explain``, ``/snapshot``,
        ``/healthz``) on a daemon thread.  ``port=0`` binds an ephemeral
        port — read it back from the returned server's ``.port``/``.url``.
        Read-only and lock-free; see :mod:`repro.obs.health.server`."""
        from repro.obs.health import ObsServer

        srv = ObsServer(self, port=port, host=host)
        self._obs_servers.append(srv)
        return srv

    @property
    def sentinel(self):
        """The engine's SLO sentinel (built on first touch with the
        default rule families — see :func:`repro.obs.engine_rules`).
        Drives ``/healthz``; a sustained breach dumps a flight bundle
        when a recorder is armed."""
        s = self._sentinel
        if s is None:
            from repro.obs.sentinel import Sentinel, engine_rules

            rules, discover = engine_rules(self)

            def on_trip(st) -> None:
                fr = self.flight
                if fr is not None:
                    fr.dump(f"slo:{st.rule.name}")

            # benign first-touch race: two racing builders produce
            # equivalent sentinels, last assignment wins
            s = self._sentinel = Sentinel(
                rules, on_trip=on_trip, discover=discover
            )
        return s

    def _flight_exception(self, where: str, exc: BaseException) -> None:
        """Dump a postmortem bundle when a recorder is armed (never
        raises; never runs when flight is off — the common case costs
        one attribute read on the exception path only)."""
        fr = self.flight
        if fr is not None:
            fr.record_exception(where, exc)

    def _device_bytes_cached(self, snap: EngineSnapshot) -> dict[str, int]:
        """Memoized :meth:`EngineSnapshot.device_bytes` — one walk per
        snapshot version per ~250ms, so a scrape hitting all seven
        ``mem.bytes`` gauges (or `/snapshot` plus `/metrics`) pays once."""
        now = time.monotonic()
        hit = self._devbytes_cache
        if hit is not None and hit[0] is snap and now - hit[1] < 0.25:
            return hit[2]
        out = snap.device_bytes()
        self._devbytes_cache = (snap, now, out)
        return out

    # ------------------------------------------------------------------
    # snapshot delegation (compat surface; query paths resolve _snap once)
    # ------------------------------------------------------------------
    @property
    def facilities(self) -> np.ndarray:
        return self._snap.facilities

    @property
    def users(self) -> np.ndarray:
        return self._snap.users

    @property
    def scene_cache(self) -> SceneCache | None:
        return self._snap.scene_cache

    @property
    def rect(self) -> Rect:
        """The shared domain rectangle (facilities ∪ users, padded)."""
        return self._snap.rect

    @property
    def xs(self) -> jnp.ndarray:
        return self._snap.xs

    @property
    def ys(self) -> jnp.ndarray:
        return self._snap.ys

    def _hull_bounds(self) -> tuple[np.ndarray, np.ndarray]:
        return self._snap.hull_bounds()

    def _fingerprint(self) -> int:
        return self._snap.fingerprint()

    def _rect_for(self, snap: EngineSnapshot, q_pts: np.ndarray) -> Rect:
        """Snapshot rect, extended only when a query point falls outside
        the facility∪user hull (keeps one-shot shims bit-compatible with
        the old per-call ``Rect.from_points(F, q, U)``)."""
        if snap.explicit_rect:
            return snap.rect
        lo, hi = snap.hull_bounds()
        if np.all(q_pts >= lo) and np.all(q_pts <= hi):
            return snap.rect
        return Rect.from_points(snap.facilities, q_pts, snap.users)

    # ------------------------------------------------------------------
    # mesh-sharded batch dispatches (absorbed from launch/serve.py)
    # ------------------------------------------------------------------
    def _init_mesh(self, snap: EngineSnapshot, mesh) -> None:
        """Upload the snapshot's (DP-padded) user coordinates, sharded over
        the data axes; per-backend jitted dispatches are built lazily (the
        jitted steps are version-independent and stay on the engine)."""
        from repro.distributed.meshctx import dp_axes

        dp = dp_axes(mesh)
        user_sh, _scene_sh, _out_sh = serve_shardings(mesh)
        xs = snap.users[:, 0].astype(np.float32)
        ys = snap.users[:, 1].astype(np.float32)
        n = len(xs)
        dpn = int(np.prod([mesh.shape[a] for a in dp]))
        padn = (-n) % dpn
        if padn:  # sentinel users far outside every scene; sliced off below
            xs = np.concatenate([xs, np.full(padn, 2e9, np.float32)])
            ys = np.concatenate([ys, np.full(padn, 2e9, np.float32)])
        snap.mesh_xs = jax.device_put(xs, user_sh)
        snap.mesh_ys = jax.device_put(ys, user_sh)
        snap.mesh_n = n

    def _mesh_q_sharding(self, ndim: int):
        """NamedSharding for a per-query stacked array: queries over
        ``'model'``, trailing dims replicated."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P("model", *([None] * (ndim - 1))))

    def _mesh_dispatch_for(
        self, snap: EngineSnapshot, backend: Backend, *, rect: Rect, k: int
    ):
        """Engine-held device-dispatch override for ``count_batch``.

        The dense-ref, grid, and bvh batched paths all shard the same way
        (users over the data axes, queries over ``'model'``; the per-query
        stacked index state is tiny).  The jitted step is cached per
        backend and per the statics its math closes over — the domain rect
        and G for the grid, ``k`` for the bvh early exit — while the
        returned dispatch closure captures the *snapshot's* sharded user
        arrays, so steps survive updates and only the cheap closure is
        rebuilt per version.  ``dense`` (interpret-mode Pallas) and
        ``brute`` stay single-device.
        Returns ``dispatch(prepared) -> [Q, N] np.int32`` or ``None``.
        """
        if self.mesh is None or snap.mesh_xs is None:
            return None
        user_sh, _scene_sh, out_sh = serve_shardings(self.mesh)
        mesh_xs, mesh_ys, n = snap.mesh_xs, snap.mesh_ys, snap.mesh_n

        if backend.name == "dense-ref":
            key = ("dense-ref",)
            step = self._mesh_steps.get(key)
            if step is None:
                from repro.kernels.ref import raycast_count_batch_ref

                step = track_jit(
                    jax.jit(
                        raycast_count_batch_ref,
                        in_shardings=(user_sh, user_sh, self._mesh_q_sharding(4)),
                        out_shardings=out_sh,
                    ),
                    "mesh.dense-ref",
                )
                self._mesh_steps[key] = step
            return lambda prepared: np.asarray(
                step(mesh_xs, mesh_ys, jnp.asarray(prepared))
            )[:, :n]

        if backend.name == "grid":
            from repro.core.grid import grid_hit_counts_batch_jnp

            # the grid math closes over the domain rect; only the
            # snapshot's shared rect gets a cached sharded step.  A
            # transient rect (out-of-hull point query) would mean one XLA
            # compile per batch and an ever-growing step cache — fall back
            # to the single-device dispatch for those instead.  The rect
            # participates in the key (updates can move the hull), capped
            # like the bvh k-cache below.
            if rect != snap.rect:
                return None
            key = ("grid", self.config.grid_g, rect)
            step = self._mesh_steps.get(key)
            if step is None:
                if sum(1 for kk in self._mesh_steps if kk[0] == "grid") >= 16:
                    return None  # pathological rect churn: stop compiling
                G = self.config.grid_g

                def _grid_fn(xs, ys, base, lists, coeffs, rect=rect, G=G):
                    return grid_hit_counts_batch_jnp(
                        xs, ys, base, lists, coeffs, rect, G
                    )

                step = track_jit(
                    jax.jit(
                        _grid_fn,
                        in_shardings=(
                            user_sh,
                            user_sh,
                            self._mesh_q_sharding(2),
                            self._mesh_q_sharding(3),
                            self._mesh_q_sharding(4),
                        ),
                        out_shardings=out_sh,
                    ),
                    "mesh.grid",
                )
                self._mesh_steps[key] = step
            return lambda prepared: np.asarray(
                step(mesh_xs, mesh_ys, *(jnp.asarray(p) for p in prepared))
            )[:, :n]

        if backend.name == "bvh":
            from repro.core.bvh import bvh_hit_counts_batch

            key = ("bvh", k)
            step = self._mesh_steps.get(key)
            if step is None:
                if sum(1 for kk in self._mesh_steps if kk[0] == "bvh") >= 16:
                    return None  # pathological many-k workload: stop compiling

                def _bvh_fn(xs, ys, left, right, bbox, coeffs, k=k):
                    return bvh_hit_counts_batch(
                        xs, ys, left, right, bbox, coeffs, k=k
                    )

                step = track_jit(
                    jax.jit(
                        _bvh_fn,
                        in_shardings=(
                            user_sh,
                            user_sh,
                            self._mesh_q_sharding(2),
                            self._mesh_q_sharding(2),
                            self._mesh_q_sharding(3),
                            self._mesh_q_sharding(4),
                        ),
                        out_shardings=out_sh,
                    ),
                    "mesh.bvh",
                )
                self._mesh_steps[key] = step
            return lambda prepared: np.asarray(
                step(mesh_xs, mesh_ys, *(jnp.asarray(p) for p in prepared))
            )[:, :n]

        return None

    # ------------------------------------------------------------------
    # filter phase helpers (host)
    # ------------------------------------------------------------------
    def _build_scene(
        self, snap: EngineSnapshot, q, k: int, rect: Rect, *, pad_to: int | None = None
    ):
        if snap.scene_cache is not None and pad_to is None:
            scene, _hit = snap.scene_cache.get_or_build(
                snap.facilities,
                q,
                k,
                rect,
                fp=snap.fingerprint(),
                strategy=self.config.strategy,
                grid=self.config.prune_grid,
                users_hint=snap.users,
            )
            return scene
        return build_scene(
            snap.facilities,
            q,
            k,
            rect,
            strategy=self.config.strategy,
            grid=self.config.prune_grid,
            pad_to=pad_to,
            users_hint=snap.users,
        )

    def _index_for(self, snap: EngineSnapshot, backend: Backend, scene: Scene) -> Any:
        """Per-scene index from the snapshot's memo, so cached scenes carry
        their grid/BVH across repeated queries (and across updates, via
        the COW migration)."""
        store = snap.index_memo.store_for(scene)
        key = (backend.name, self.config.grid_g)
        if key not in store:
            # the backend's own build memo shares the store: grid and
            # grid-pallas dedupe their underlying grid build through it
            store[key] = backend.build_index(
                scene, grid_g=self.config.grid_g, memo=store
            )
        return store[key]

    def _workload_shards(self) -> int:
        """Shard count the planner prices workloads at (the ``log_s``
        feature).  1 on single-process engines; ``ShardedEngine``
        overrides with its mesh size."""
        return 1

    def _prepare_batch(self, backend: Backend, req: BatchRequest):
        """Backend stacking for one batch, honoring a dispatch that owns
        its own prepare step (``req.dispatch.prepare``): the sharded
        dispatch builds *per-shard* prepared state (cell buckets, lane-
        compacted planes) that the plain ``Backend.prepare_batch`` —
        which sees no partition — cannot."""
        prep = getattr(req.dispatch, "prepare", None)
        if prep is not None:
            return prep(backend, req)
        return backend.prepare_batch(req)

    def _batch_cache_get(self, snap: EngineSnapshot, key):
        """Prepared-batch lookup (None key → miss); counts a hit in the
        stats.  Lock-free — see :class:`~repro.core.snapshot.LruCache`."""
        if key is None:
            return None
        hit = snap.batch_cache.get(key)
        if hit is not None:
            self._m_cache_hits.inc()
        return hit

    def _batch_cache_put(self, snap: EngineSnapshot, key, value) -> None:
        if key is None:
            return
        snap.batch_cache.put(key, value)

    def _build_scenes(
        self, snap: EngineSnapshot, queries: list, k: int, rect: Rect, workers: int
    ):
        """Cache-aware host scene builds, optionally thread-pooled."""

        def one(q):
            return self._build_scene(snap, q, k, rect)

        if workers > 0 and len(queries) > 1:
            batch = current_batch()

            def pooled(q):
                with batch_scope(batch):  # the worker's spans join the batch
                    return one(q)

            with concurrent.futures.ThreadPoolExecutor(workers) as pool:
                return list(pool.map(pooled, queries))
        return [one(q) for q in queries]

    def _mp_bucket(self, scenes: list[Scene]) -> int:
        if self.config.pad_to is not None:
            return self.config.pad_to
        mmax = max(s.tris.shape[0] for s in scenes)
        # lock-free monotone max: concurrent batches may briefly lose an
        # update, costing at most one extra retrace — never a wrong pad
        bucket = max(self._pad_bucket, _next_pow2(mmax))
        self._pad_bucket = bucket
        return bucket

    def _filter_batch(
        self,
        snap: EngineSnapshot,
        backend: Backend,
        queries: list,
        q_pts: np.ndarray,
        excludes: list,
        k: int,
        rect: Rect,
        scene_workers: int,
    ) -> tuple[BatchRequest, Any, list[Scene]]:
        """Host filter phase for one batch: scenes + stacked backend state,
        LRU-cached by (backend, k, queries, rect) so a repeated workload
        collapses to a dictionary lookup."""
        cache_key = None
        if self.config.batch_cache > 0:
            cache_key = (
                backend.name,
                k,
                tuple(_q_key(q) for q in queries),
                rect,
            )
            hit = self._batch_cache_get(snap, cache_key)
            if hit is not None:
                req, prepared, scenes = hit
                return req, prepared, scenes

        scenes = self._build_scenes(snap, queries, k, rect, scene_workers)
        dispatch = self._mesh_dispatch_for(snap, backend, rect=rect, k=k)
        # the mesh dispatch closes over its own sharded user arrays — don't
        # materialize a second, replicated device copy it would never read
        req = BatchRequest(
            xs=None if dispatch is not None else snap.xs,
            ys=None if dispatch is not None else snap.ys,
            k=k,
            rect=rect,
            grid_g=self.config.grid_g,
            scenes=scenes,
            # per-scene index memo: scene-cache hits reuse their grid/BVH
            # instead of rebuilding it on every new batch composition
            indexes=[self._index_for(snap, backend, s) for s in scenes],
            users=snap.users,
            facilities=snap.facilities,
            q_pts=q_pts,
            excludes=excludes,
            mp=self._mp_bucket(scenes),
            dispatch=dispatch,
            metrics=self.metrics,
            memo=snap.kernel_memo,
        )
        with span("filter.stack"):
            prepared = self._prepare_batch(backend, req)
        self._batch_cache_put(snap, cache_key, (req, prepared, scenes))
        return req, prepared, scenes

    # ------------------------------------------------------------------
    # planner (the "auto" meta-backend)
    # ------------------------------------------------------------------
    def _scene_cached(self, snap: EngineSnapshot, q, k: int, rect: Rect) -> bool:
        if snap.scene_cache is None:
            return False
        return snap.scene_cache.contains(
            snap.facilities, q, k, rect, fp=snap.fingerprint()
        )

    def _record_plan(self, planner, plan: dict, observed_s: float) -> None:
        """Close out one plan: observed cost, engine log, metrics, planner.

        ``observed_s`` comes from the query path's spans (filter + verify
        elapsed), so the planner's recalibration signal and the exported
        trace are the same measurement.  Per-dispatched-backend log-
        residuals ``log(obs/pred)`` land in signed histograms — the drift
        gate's raw material."""
        plan["observed_s"] = observed_s
        self._plan_log.append(plan)
        for name, n in plan.get("decisions", {}).items():
            self._decision_counter(name).inc(n)
        self._m_pred.observe(plan.get("predicted_s", 0.0))
        self._m_obs.observe(observed_s)
        planner.record(plan)
        for name, pred, obs, _verify_only in planner._pred_obs_pairs(plan):
            if pred > 0.0 and obs > 0.0:
                self._residual_hist(name).observe(math.log(obs / pred))
        if self.config.online_recalibration:
            self._m_nudges.inc(planner.observe(plan))

    def explain(self) -> list[dict]:
        """Recent ``auto`` plans, oldest first: each entry carries the
        chosen backend(s), predicted cost, candidate costs, and — once the
        dispatch ran — observed cost."""
        return list(self._plan_log)

    def _plan_amortized(self, snap: EngineSnapshot) -> bool:
        """Whether the planner prices geometric backends at steady-state
        (verify-only) cost.  True on engines with a scene cache: they are
        long-lived serving objects, so a scene build is an *investment*
        the cache repays on every repeat — the planner should pick the
        backend that is cheapest once hot, not the one that is cheapest
        for exactly one cold call.  One-shot shims disable the cache and
        get the strict per-call comparison.
        """
        return snap.scene_cache is not None

    def _plan_single(
        self, snap: EngineSnapshot, planner, q_build, k: int, q_pt: np.ndarray
    ):
        """Pre-scene routing of one query.  Returns (backend, plan)."""
        rect = self._rect_for(snap, q_pt[None])
        amortized = self._plan_amortized(snap)
        shape = WorkloadShape(
            len(snap.facilities),
            len(snap.users),
            k,
            1,
            cache_hit=amortized or self._scene_cached(snap, q_build, k, rect),
            pad_waste=snap.pad_waste(rect, self.config.grid_g),
            shards=self._workload_shards(),
        )
        choice, pred, costs = planner.select(shape)
        plan = {
            "mode": "single",
            "backend": choice,
            "predicted_s": pred,
            "candidates": costs,
            "cache_hit": shape.cache_hit,
            "amortized": amortized,
            "decisions": {choice: 1},
        }
        return get_backend(choice), plan

    # ------------------------------------------------------------------
    # public query surface
    # ------------------------------------------------------------------
    def query(self, q, k: int, *, backend: str | None = None) -> RkNNResult:
        """Bichromatic RkNN of one query (facility index or ``[2]`` point).

        With the ``auto`` backend the planner picks the concrete backend
        *before* any scene is built (a brute decision skips the filter
        phase entirely); the result's ``backend`` field reports the
        concrete choice and :meth:`explain` the full plan.
        """
        self._read_clock += 1
        try:
            return self._query(self._snap, q, k, backend=backend)
        except Exception as e:
            self._flight_exception("query", e)
            raise

    def _query(
        self, snap: EngineSnapshot, q, k: int, *, backend: str | None = None
    ) -> RkNNResult:
        b = get_backend(backend or self.config.backend)
        arr = np.asarray(q)
        if arr.ndim == 0 and np.issubdtype(arr.dtype, np.integer):
            q_build: int | np.ndarray = int(arr)
            q_pt, exclude = snap.facilities[int(arr)], int(arr)
        else:
            q_pt = np.asarray(q, np.float64).reshape(2)
            q_build, exclude = q_pt, None

        plan = planner = None
        if b.is_meta:
            planner = b
            b, plan = self._plan_single(snap, planner, q_build, k, q_pt)

        if not b.uses_scene:
            # geometry-free: never materialize the device user arrays
            with span("query", backend=b.name, version=snap.version):
                with span("verify", backend=b.name) as sv:
                    counts = b.count(
                        QueryRequest(
                            xs=None,
                            ys=None,
                            k=k,
                            users=snap.users,
                            facilities=snap.facilities,
                            q_pt=q_pt,
                            exclude=exclude,
                        )
                    )
            t_verify = sv.elapsed_s
            self._m_queries.inc()
            self._phase_hist("verify", b.name).observe(t_verify)
            self._m_lag.set(float(self._snap.version - snap.version))
            if plan is not None:
                self._record_plan(planner, plan, t_verify)
            return RkNNResult(
                counts < k, counts, None, 0.0, t_verify, b.name, snap.version
            )

        with span("query", backend=b.name, version=snap.version):
            with span("filter", backend=b.name) as sf:
                rect = self._rect_for(snap, q_pt[None])
                scene = self._build_scene(
                    snap, q_build, k, rect, pad_to=self.config.pad_to
                )
                index = self._index_for(snap, b, scene)
            with span("verify", backend=b.name) as sv:
                counts = b.count(
                    QueryRequest(
                        xs=snap.xs,
                        ys=snap.ys,
                        k=k,
                        grid_g=self.config.grid_g,
                        scene=scene,
                        index=index,
                        memo=snap.kernel_memo,
                    )
                )
        t_filter, t_verify = sf.elapsed_s, sv.elapsed_s
        self._m_queries.inc()
        self._phase_hist("filter", b.name).observe(t_filter)
        self._phase_hist("verify", b.name).observe(t_verify)
        self._m_mmax.set_max(scene.n_tris)
        self._m_lag.set(float(self._snap.version - snap.version))
        if plan is not None:
            self._record_plan(planner, plan, t_filter + t_verify)
        return RkNNResult(
            counts < k, counts, scene, t_filter, t_verify, b.name, snap.version
        )

    def query_batch(
        self,
        qs,
        k: int,
        *,
        backend: str | None = None,
        scene_workers: int | None = None,
    ) -> RkNNBatchResult:
        """Batched bichromatic RkNN: all of ``qs`` against the shared users.

        One host filter phase (scene builds — cache-aware — plus backend
        stacking) and ONE batched device dispatch.  Masks are bit-identical
        to looping :meth:`query` per query (equivalence-tested across all
        backends).
        """
        self._read_clock += 1
        try:
            return self._query_batch(
                self._snap, qs, k, backend=backend, scene_workers=scene_workers
            )
        except Exception as e:
            self._flight_exception("query_batch", e)
            raise

    def _query_batch(
        self,
        snap: EngineSnapshot,
        qs,
        k: int,
        *,
        backend: str | None = None,
        scene_workers: int | None = None,
    ) -> RkNNBatchResult:
        b = get_backend(backend or self.config.backend)
        workers = (
            self.config.scene_workers if scene_workers is None else scene_workers
        )
        qs = list(qs)
        n_users = len(snap.users)
        if not qs:
            return RkNNBatchResult(
                masks=np.zeros((0, n_users), bool),
                counts=np.zeros((0, n_users), np.int32),
                scenes=None if not b.uses_scene else [],
                t_filter_s=0.0,
                t_verify_s=0.0,
                backend=b.name,
                k=k,
                version=snap.version,
            )
        if b.is_meta:
            return self._query_batch_planner(snap, b, qs, k, workers)
        queries, q_pts, excludes = _normalize_queries(snap.facilities, qs)

        batch = next(self._batch_ids)
        if not b.uses_scene:
            with span("batch", backend=b.name, q=len(qs), version=snap.version,
                      batch=batch):
                with span("verify", backend=b.name) as sv:
                    counts = b.count_batch(
                        BatchRequest(
                            xs=None,
                            ys=None,
                            k=k,
                            users=snap.users,
                            facilities=snap.facilities,
                            q_pts=q_pts,
                            excludes=excludes,
                            metrics=self.metrics,
                        ),
                        None,
                    )
                with span("mask"):
                    masks = counts < k
            t_verify = sv.elapsed_s
            self._m_queries.inc(len(qs))
            self._m_batches.inc()
            self._phase_hist("verify", b.name).observe(t_verify)
            self._m_lag.set(float(self._snap.version - snap.version))
            return RkNNBatchResult(
                masks, counts, None, 0.0, t_verify, b.name, k, snap.version
            )

        with span("batch", backend=b.name, q=len(qs), version=snap.version,
                  batch=batch):
            with span("filter", backend=b.name) as sf:
                rect = self._rect_for(snap, q_pts)
                req, prepared, scenes = self._filter_batch(
                    snap, b, queries, q_pts, excludes, k, rect, workers
                )
            with span("verify", backend=b.name) as sv:
                counts = b.count_batch(req, prepared)
            with span("mask"):
                masks = counts < k
        t_filter, t_verify = sf.elapsed_s, sv.elapsed_s
        self._m_queries.inc(len(qs))
        self._m_batches.inc()
        self._phase_hist("filter", b.name).observe(t_filter)
        self._phase_hist("verify", b.name).observe(t_verify)
        self._m_mmax.set_max(max(s.n_tris for s in scenes))
        self._m_lag.set(float(self._snap.version - snap.version))
        return RkNNBatchResult(
            masks, counts, scenes, t_filter, t_verify, b.name, k, snap.version
        )

    def _dispatch_group(
        self,
        snap: EngineSnapshot,
        b: Backend,
        idxs: list[int],
        scenes: list[Scene] | None,
        q_pts: np.ndarray,
        excludes: list,
        k: int,
        rect: Rect | None,
    ) -> tuple[np.ndarray, float, float]:
        """Prepare + count one planner group.  Returns ``(counts [|idxs|, N],
        t_prepare_s, t_count_s)`` — prepare is host (filter), count device
        (verify).  Prepared geometric groups are LRU-cached alongside the
        fixed-backend batches, so a repeated ``auto`` workload skips the
        re-stacking just like a repeated fixed-backend one.
        """
        sf = span("filter", backend=b.name, group=1)
        with sf:
            if not b.uses_scene:
                req = BatchRequest(
                    xs=None,
                    ys=None,
                    k=k,
                    users=snap.users,
                    facilities=snap.facilities,
                    q_pts=q_pts[idxs],
                    excludes=[excludes[i] for i in idxs],
                    metrics=self.metrics,
                )
                prepared = None
            else:
                cache_key = None
                if self.config.batch_cache > 0:
                    # excludes participate in the key: a facility-index query
                    # (exclude=i) and a point query at that facility's exact
                    # coordinates (exclude=None) build different scenes
                    cache_key = (
                        "auto",
                        b.name,
                        k,
                        tuple((_q_key(q_pts[i]), excludes[i]) for i in idxs),
                        rect,
                    )
                    hit = self._batch_cache_get(snap, cache_key)
                    if hit is not None:
                        req, prepared, _sub = hit
                        sf.__exit__(None, None, None)
                        with span("verify", backend=b.name, group=1) as sv:
                            counts = b.count_batch(req, prepared)
                        return np.asarray(counts), sf.elapsed_s, sv.elapsed_s
                sub = [scenes[i] for i in idxs]
                dispatch = self._mesh_dispatch_for(snap, b, rect=rect, k=k)
                req = BatchRequest(
                    xs=None if dispatch is not None else snap.xs,
                    ys=None if dispatch is not None else snap.ys,
                    k=k,
                    rect=rect,
                    grid_g=self.config.grid_g,
                    scenes=sub,
                    indexes=[self._index_for(snap, b, s) for s in sub],
                    users=snap.users,
                    facilities=snap.facilities,
                    q_pts=q_pts[idxs],
                    excludes=[excludes[i] for i in idxs],
                    mp=self._mp_bucket(sub),
                    dispatch=dispatch,
                    metrics=self.metrics,
                    memo=snap.kernel_memo,
                )
                with span("filter.stack"):
                    prepared = self._prepare_batch(b, req)
                self._batch_cache_put(snap, cache_key, (req, prepared, sub))
        with span("verify", backend=b.name, group=1) as sv:
            counts = b.count_batch(req, prepared)
        return np.asarray(counts), sf.elapsed_s, sv.elapsed_s

    def _query_batch_planner(
        self, snap: EngineSnapshot, planner, qs: list, k: int, workers: int
    ) -> RkNNBatchResult:
        """The ``auto`` batched path: price, (maybe) filter, split, recombine.

        Two-stage decision:

        1. *Pre-scene*: the whole batch is priced with the estimated scene
           size.  If brute wins outright, no scene is ever built.
        2. *Post-scene*: scenes are built (cache-aware), each query is
           re-priced with its **actual** triangle count (filter cost now
           sunk → ``cache_hit=True``), and the batch is partitioned into
           per-backend groups dispatched independently; counts recombine
           in query order.  Count *semantics* may differ per row (bvh
           saturates at ``k``, brute counts distance ranks) — masks are
           the invariant, as everywhere else.

        The whole decision (assignments + scenes) is memoized in the batch
        LRU: a repeated workload goes straight to its group dispatches
        (which hit their own prepared-group LRU) without re-planning.
        """
        queries, q_pts, excludes = _normalize_queries(snap.facilities, qs)
        n_f, n_u, q_n = len(snap.facilities), len(snap.users), len(qs)
        batch = next(self._batch_ids)
        sb = span("batch", backend="auto", q=q_n, version=snap.version, batch=batch)
        with sb:
            counts, plan, per_q, groups, scenes, t_count_total = (
                self._plan_and_dispatch(
                    snap, planner, queries, q_pts, excludes, k, rect_workers=workers,
                    n_f=n_f, n_u=n_u, q_n=q_n,
                )
            )
        # filter = everything in the batch wall that was not a group's
        # device count dispatch (planning, scene builds, group stacking) —
        # same accounting as the old inline perf_counter arithmetic
        t_filter = sb.elapsed_s - t_count_total
        with span("mask", batch=batch):  # outside `batch`: its wall is filter's
            masks = counts < k

        self._m_queries.inc(q_n)
        self._m_batches.inc()
        self._phase_hist("filter", "auto").observe(t_filter)
        self._m_lag.set(float(self._snap.version - snap.version))
        if scenes:
            self._m_mmax.set_max(max(s.n_tris for s in scenes))
        self._record_plan(planner, plan, sb.elapsed_s)
        return RkNNBatchResult(
            masks,
            counts,
            scenes,
            t_filter,
            t_count_total,
            "auto",
            k,
            snap.version,
        )

    def _plan_and_dispatch(
        self, snap, planner, queries, q_pts, excludes, k,
        *, rect_workers, n_f, n_u, q_n,
    ):
        """Body of the ``auto`` batch (inside its ``batch`` span): plan
        (or reuse a memoized decision), build scenes, dispatch groups."""
        workers = rect_workers
        rect = self._rect_for(snap, q_pts)
        pad_w = snap.pad_waste(rect, self.config.grid_g)

        plan_key = cached_decision = None
        if self.config.batch_cache > 0:
            from repro.planner.profiles import profile_epoch

            # the epoch invalidates memoized decisions when the operator
            # activates a new (re)calibrated profile
            plan_key = (
                "auto-plan",
                profile_epoch(),
                k,
                tuple(_q_key(q) for q in queries),
                rect,
            )
            cached_decision = self._batch_cache_get(snap, plan_key)

        if cached_decision is not None:
            per_q, groups, scenes = cached_decision
            plan: dict = {
                "mode": "batch",
                "predicted_s": sum(cost for _, cost in per_q),
                "plan_cache_hit": True,
                "k": k,
                "q": q_n,
            }
        else:
            # price geometric backends at verify-only cost when the filter
            # phase is already amortized (scenes cached) — or *will* be (see
            # _plan_amortized: a cache-carrying engine invests in scene
            # builds because every repeat of a hot query rides them for free)
            amortized = self._plan_amortized(snap) or all(
                self._scene_cached(snap, q, k, rect) for q in queries
            )
            batch_shape = WorkloadShape(
                n_f, n_u, k, q_n, cache_hit=amortized, pad_waste=pad_w,
                shards=self._workload_shards(),
            )
            ranked = planner.rank(batch_shape)
            plan = {
                "mode": "batch",
                "predicted_s": ranked[0][1],
                "candidates": dict(ranked),
                "amortized": amortized,
                "k": k,
                "q": q_n,
            }
            if not get_backend(ranked[0][0]).uses_scene:
                # brute wins on the estimate: never build a scene
                name = ranked[0][0]
                per_q = [(name, ranked[0][1] / max(q_n, 1))] * q_n
                groups = {name: list(range(q_n))}
                scenes = None
            else:
                scenes = self._build_scenes(snap, queries, k, rect, workers)
                # re-price per query with the actual scene size; the filter
                # cost is sunk now
                per_q = planner.assign_batch(
                    [
                        WorkloadShape(
                            n_f,
                            n_u,
                            k,
                            1,
                            m_tris=s.n_tris,
                            cache_hit=True,
                            pad_waste=pad_w,
                            shards=self._workload_shards(),
                        )
                        for s in scenes
                    ]
                )
                groups = {}
                for i, (name, _cost) in enumerate(per_q):
                    groups.setdefault(name, []).append(i)
            self._batch_cache_put(snap, plan_key, (per_q, groups, scenes))

        counts = np.zeros((q_n, n_u), np.int32)
        t_count_total = 0.0
        observed_group: dict[str, float] = {}
        for name, idxs in groups.items():
            gcounts, t_prep, t_count = self._dispatch_group(
                snap, get_backend(name), idxs, scenes, q_pts, excludes, k, rect
            )
            counts[idxs] = gcounts
            t_count_total += t_count
            # the group's device count time lands under ITS backend; the
            # host-side remainder lands under "auto" in the caller
            self._phase_hist("verify", name).observe(t_count)
            observed_group[name] = t_prep + t_count

        plan.update(
            assignments=[name for name, _ in per_q],
            predicted_per_query=[cost for _, cost in per_q],
            split=len(groups) > 1,
            groups={name: len(idxs) for name, idxs in groups.items()},
            observed_group_s=observed_group,
            decisions={name: len(idxs) for name, idxs in groups.items()},
        )
        return counts, plan, per_q, groups, scenes, t_count_total

    def query_mono(self, q_idx: int, k: int, *, backend: str | None = None) -> RkNNResult:
        """Monochromatic RkNN over the facility set (paper §2.1 / §4.5).

        Reduces to the bichromatic machinery with ``F = U = facilities`` at
        threshold ``k + 1`` (every point's ray hits its own occluder), then
        self-hit-corrects the counts — see docs/API.md for the derivation.
        """
        self._read_clock += 1
        try:
            return self._query_mono(int(q_idx), k, backend=backend)
        except Exception as e:
            self._flight_exception("query_mono", e)
            raise

    def _query_mono(self, q_idx: int, k: int, *, backend: str | None) -> RkNNResult:
        snap = self._snap
        if snap._is_mono is None:
            snap._is_mono = snap.users is snap.facilities or (
                snap.users.shape == snap.facilities.shape
                and np.array_equal(snap.users, snap.facilities)
            )
        if snap._is_mono:
            res = self._query(snap, int(q_idx), k + 1, backend=backend)
        else:
            if snap._mono is None:
                # mesh is deliberately not forwarded: the single-query path
                # never routes through the sharded batch dispatch.  The
                # sub-engine is pinned to this snapshot's facilities, so it
                # rides the snapshot (benign first-touch race: two racing
                # builders produce equal engines, last assignment wins).
                snap._mono = RkNNEngine(
                    snap.facilities,
                    snap.facilities,
                    self.config,
                    rect=snap._rect if snap.explicit_rect else None,
                )
            res = snap._mono.query(int(q_idx), k + 1, backend=backend)
            # mirror the sub-engine's work into our metrics
            self._m_queries.inc()
            self._phase_hist("filter", res.backend).observe(res.t_filter_s)
            self._phase_hist("verify", res.backend).observe(res.t_verify_s)
        counts = np.asarray(res.counts, np.int32).copy()
        # self-hit correction: every point except q hits its own occluder
        # (q's occluder is excluded from the scene, so its count is already
        # "others")
        counts[np.arange(len(counts)) != q_idx] -= 1
        np.maximum(counts, 0, out=counts)
        mask = counts < k
        mask[q_idx] = False
        return RkNNResult(
            mask,
            counts,
            res.scene,
            res.t_filter_s,
            res.t_verify_s,
            res.backend,
            snap.version,
        )

    def stream(self, batches, k: int, *, backend: str | None = None):
        """Double-buffered batch stream: the host filter phase of batch
        ``i+1`` (scene builds + stacking, in a producer thread) overlaps the
        device dispatch of batch ``i``.  Yields ``(batch, masks[Q, N])``.

        Producer exceptions are re-raised in the consumer — the generator
        never hangs on a failed build.

        With the ``auto`` backend the planner re-routes each batch as a
        whole (pre-scene, estimated cost — no per-query splitting on the
        streaming path, which would defeat the double buffering).
        """
        b = get_backend(backend or self.config.backend)
        buf: "queue.Queue" = queue.Queue(maxsize=2)

        def producer():
            try:
                for batch in batches:
                    # one snapshot per batch: each yielded mask set is a
                    # consistent view of exactly one version, and a stream
                    # naturally picks up concurrent updates batch to batch
                    snap = self._snap
                    qs = list(batch)
                    n = next(self._batch_ids)
                    sf = span("filter", backend=b.name, stream=1,
                              version=snap.version, batch=n)
                    sf.__enter__()
                    queries, q_pts, excludes = _normalize_queries(
                        snap.facilities, qs
                    )
                    b_eff, plan = b, None
                    if b.is_meta:
                        shape = WorkloadShape(
                            len(snap.facilities),
                            len(snap.users),
                            k,
                            len(qs),
                            cache_hit=self._plan_amortized(snap),
                            pad_waste=snap.pad_waste(
                                snap.rect, self.config.grid_g
                            ),
                            shards=self._workload_shards(),
                        )
                        choice, pred, costs = b.select(shape)
                        plan = {
                            "mode": "stream-batch",
                            "backend": choice,
                            "predicted_s": pred,
                            "candidates": costs,
                            "cache_hit": shape.cache_hit,
                            "decisions": {choice: len(qs)},
                        }
                        b_eff = get_backend(choice)
                    if b_eff.uses_scene:
                        rect = self._rect_for(snap, q_pts)
                        built = self._filter_batch(
                            snap, b_eff, queries, q_pts, excludes, k, rect,
                            self.config.scene_workers,
                        )
                    else:
                        req = BatchRequest(
                            xs=None,
                            ys=None,
                            k=k,
                            users=snap.users,
                            facilities=snap.facilities,
                            q_pts=q_pts,
                            excludes=excludes,
                            metrics=self.metrics,
                        )
                        built = (req, None, None)
                    sf.__exit__(None, None, None)
                    t_filter = sf.elapsed_s
                    self._phase_hist("filter", b.name).observe(t_filter)
                    buf.put((n, batch, len(qs), b_eff, plan, t_filter, built))
                buf.put(None)
            except BaseException as e:  # surface in the consumer, no deadlock
                buf.put(e)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        while True:
            with span("stream.wait") as sw:
                item = buf.get()
                if isinstance(item, tuple):
                    sw.batch = item[0]
            if item is None:
                return
            if isinstance(item, BaseException):
                if isinstance(item, Exception):
                    self._flight_exception("stream", item)
                raise item
            n, batch, q_n, b_eff, plan, t_filter, (req, prepared, scenes) = item
            with span("verify", backend=b_eff.name, stream=1, batch=n) as sv:
                counts = b_eff.count_batch(req, prepared)
            t_verify = sv.elapsed_s
            self._phase_hist("verify", b_eff.name).observe(t_verify)
            self._m_queries.inc(q_n)
            self._m_batches.inc()
            if scenes:
                self._m_mmax.set_max(max(s.n_tris for s in scenes))
            if plan is not None:
                # observed = this batch's own filter + verify work — NOT the
                # wall-clock since the producer started, which would include
                # time spent waiting in the double buffer and corrupt the
                # planner's pred-vs-obs calibration signal
                self._record_plan(b, plan, t_filter + t_verify)
            with span("mask", batch=n):
                masks = counts < k
            yield batch, masks
