"""User-axis SPMD sharded serving: :class:`ShardedEngine`.

The paper's scaling axis is the *user* population — RT-RkNN casts one
ray per user, so users are where the parallel work lives, while
facilities (and the per-query occluder scenes built from them) are tiny.
The sharded engine encodes that asymmetry directly:

* **replicated** per shard: facilities, scenes, grid/BVH indexes, packed
  per-cell coefficient planes — all host-built once and shared;
* **sharded** over the ``'users'`` mesh axis: the user coordinate
  arrays, the per-shard cell buckets feeding the grid-pallas kernels,
  and the per-shard hit-count slabs.

The partition is *spatial*: users are sorted by grid cell (the same
cell id the bucketed kernels use) and cut into ``shards`` contiguous
runs (:func:`repro.distributed.sharding.user_shard_bounds`), so each
shard covers a compact region of the domain.  That is what makes
sharding a *throughput* lever even on one core: a shard only ships the
coefficient planes of cells **its** users occupy and only pads the
plane list axis to the longest live list in **its** region — strictly
less device work than the global dispatch, on top of whatever physical
parallelism the mesh provides.

Counts are per-user independent, so the per-shard slabs scatter back
through the partition permutation bit-identically to the single-process
oracle (:mod:`repro.shard.reduce`; property-tested across every
registered backend).  Per-query aggregates cross shards through the
``psum``-style tree reduction.

MVCC integration: the per-shard replicas live on the
:class:`~repro.core.snapshot.EngineSnapshot` (``snap.shard_state``) as
ONE immutable :class:`ShardState` swapped atomically — every view in a
state carries the snapshot's version, so a batch resolved against one
snapshot can never mix shard views from two versions (the
version-lockstep rule).  ``DynamicEngine`` user-move deltas scatter
functionally into the owning shard's device arrays along the same axis;
shape-changing deltas rebuild the partition lazily.
"""

from __future__ import annotations

import collections

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.backends import Backend, stack_cell_planes
from repro.core.engine import RkNNConfig
from repro.core.geometry import Rect
from repro.core.snapshot import EngineSnapshot, LruCache
from repro.distributed.sharding import user_shard_bounds
from repro.dynamic.engine import DynamicEngine
from repro.kernels import ops as _ops
from repro.obs import span
from repro.shard.mesh import mesh_shards, shard_devices
from repro.shard.reduce import tree_psum

__all__ = ["ShardedEngine", "ShardState", "ShardView", "ShardDispatch"]

#: Backend-name groups routed to each per-shard dispatch flavor.  The
#: grid-pallas family gets per-shard bucketing + compaction; the others
#: share one replicated prepared state and slice users per shard.
_GP_BACKENDS = frozenset({"grid-pallas", "grid-pallas-ref"})
_DENSE_BACKENDS = frozenset({"dense", "dense-ref"})
_GRID_BACKENDS = frozenset({"grid"})
_BVH_BACKENDS = frozenset({"bvh"})
_SHARDABLE = _GP_BACKENDS | _DENSE_BACKENDS | _GRID_BACKENDS | _BVH_BACKENDS


class ShardView:
    """One shard's replica view of one snapshot version.

    Owns the shard's user coordinates as device-resident ``f32`` arrays
    (pinned to ``device``) plus a private kernel memo for the per-shard
    cell bucketing — private so S shards cannot thrash the snapshot's
    small shared :class:`~repro.core.snapshot.LruCache`.
    """

    __slots__ = ("index", "device", "version", "lo", "hi", "xs", "ys", "memo")

    def __init__(self, index, device, version, lo, hi, xs, ys, memo=None):
        self.index = int(index)
        self.device = device
        self.version = int(version)
        self.lo = int(lo)
        self.hi = int(hi)
        self.xs = xs
        self.ys = ys
        self.memo = memo if memo is not None else LruCache(4)

    @property
    def n_users(self) -> int:
        return self.hi - self.lo


class ShardState:
    """The full shard partition of one snapshot version — swapped as ONE
    object (``snap.shard_state = state``), never mutated in place, so a
    reader resolves either all of version N's views or all of N+1's."""

    __slots__ = ("version", "n_shards", "perm", "pos", "bounds", "views", "n_users")

    def __init__(self, version, n_shards, perm, pos, bounds, views):
        self.version = int(version)
        self.n_shards = int(n_shards)
        self.perm = perm  # [N] spatial sort of user rows
        self.pos = pos  # [N] inverse: original row -> position in perm
        self.bounds = bounds  # [S+1] cut points into perm
        self.views = views  # tuple[ShardView], len S
        self.n_users = int(len(perm))

    def restamp(self, version: int) -> "ShardState":
        """The same partition re-stamped for a new snapshot version
        (facility-only deltas: user arrays carried by reference)."""
        views = tuple(
            ShardView(v.index, v.device, version, v.lo, v.hi, v.xs, v.ys, v.memo)
            for v in self.views
        )
        return ShardState(
            version, self.n_shards, self.perm, self.pos, self.bounds, views
        )

    def summary(self) -> dict:
        """JSON-able description of the partition for the introspection
        endpoint: per-shard row ranges, devices, and user counts (plus
        the imbalance ratio the sentinel watches).  Pure reads of
        immutable fields — safe against concurrent publication."""
        counts = [v.n_users for v in self.views]
        mean = (sum(counts) / len(counts)) if counts else 0.0
        return dict(
            version=self.version,
            n_shards=self.n_shards,
            n_users=self.n_users,
            imbalance=(max(counts) / mean) if mean else 1.0,
            shards=[
                dict(
                    index=v.index,
                    device=str(v.device),
                    lo=v.lo,
                    hi=v.hi,
                    n_users=v.n_users,
                )
                for v in self.views
            ],
        )


def _device_of(out: jax.Array) -> str:
    """The device a shard's kernel output landed on."""
    return str(next(iter(out.devices())))


def _spatial_perm(users: np.ndarray, rect: Rect, grid_g: int) -> np.ndarray:
    """Stable sort of user rows by grid cell id — the same ``cx*G + cy``
    the bucketed kernels use, so each contiguous cut covers a compact
    cell range."""
    xs = users[:, 0].astype(np.float32)
    ys = users[:, 1].astype(np.float32)
    g = max(int(grid_g), 1)
    w = rect.width / g
    h = rect.height / g
    cx = np.clip(np.floor((xs - rect.xmin) / w), 0, g - 1).astype(np.int64)
    cy = np.clip(np.floor((ys - rect.ymin) / h), 0, g - 1).astype(np.int64)
    return np.argsort(cx * g + cy, kind="stable")


class ShardDispatch:
    """The per-batch sharded verify dispatch, injected as
    ``BatchRequest.dispatch``.

    The engine's filter phase calls :meth:`prepare` (via
    ``RkNNEngine._prepare_batch``) instead of the backend's own
    ``prepare_batch`` and the backend's ``count_batch`` calls the
    instance itself — so every batched path (fixed-backend batches,
    planner groups, ``stream()``) shards without knowing it.
    ``carries_users`` marks the per-shard prepared state as
    user-coordinate-bearing for the COW batch-cache carry.
    """

    carries_users = True

    def __init__(self, engine: "ShardedEngine", state: ShardState,
                 backend: Backend, rect: Rect, k: int):
        self.engine = engine
        self.state = state
        self.backend = backend
        self.rect = rect
        self.k = int(k)

    # ---- filter phase: per-shard (or replicated) prepared state --------
    def prepare(self, backend: Backend, req):
        name = backend.name
        state = self.state
        t_filter = [0.0] * state.n_shards
        if name in _GP_BACKENDS:
            indexes = req.indexes
            if indexes is None:
                indexes = [
                    backend.build_index(s, grid_g=req.grid_g) for s in req.scenes
                ]
            full_planes = [backend._planes_for(g) for g in indexes]
            per_shard = []
            for view in state.views:
                if view.n_users == 0:
                    per_shard.append(None)
                    continue
                with span("shard-filter", shard=view.index, backend=name) as sf:
                    xs_s, ys_s, order, ranks, occ, block = backend._buckets_for(
                        view.xs, view.ys, self.rect, req.grid_g, memo=view.memo
                    )
                    # the shard-local compaction double-whammy: only the cells
                    # THIS shard's users occupy ship, and the plane list axis
                    # pads to the longest live list in THIS region, not the
                    # global max
                    planes_q = stack_cell_planes(
                        [p[occ] for p in full_planes],
                        lane_pad=backend.lane_pad,
                        compact=True,
                    )
                    base_q = np.stack([g.base[occ] for g in indexes]).astype(np.int32)
                    xs_s = jax.device_put(xs_s, view.device)
                    ys_s = jax.device_put(ys_s, view.device)
                    # fuse the shard-local unsort with the global reassembly:
                    # kernel lane j's user sits at ``perm[lo + order[j]]`` in
                    # the original row order, so the dispatch can scatter the
                    # kernel output straight into the final array — one pass
                    # over [Q, N] instead of two.  Padding lanes route to the
                    # trash row ``n_users``.
                    ok = np.asarray(order) >= 0
                    dest = np.where(
                        ok,
                        state.perm[view.lo + np.clip(order, 0, None)],
                        state.n_users,
                    ).astype(np.int64)
                    per_shard.append(
                        (xs_s, ys_s, dest, ok, ranks, block, base_q, planes_q)
                    )
                t_filter[view.index] = sf.elapsed_s
            self.engine._note_shard_filter(t_filter)
            return ("shard", per_shard)
        # dense / grid / bvh: prepared state is a pure function of the
        # replicated scenes — build it once, slice users per shard at
        # dispatch time
        with span("shard-filter", shard=-1, backend=name, shared=1) as sf:
            shared = backend.prepare_batch(req)
        t_filter = [sf.elapsed_s / state.n_shards] * state.n_shards
        self.engine._note_shard_filter(t_filter)
        return ("shared", shared)

    # ---- verify phase: one dispatch per shard + fused reassembly -------
    def __call__(self, prepared) -> np.ndarray:
        kind, payload = prepared
        state = self.state
        backend = self.backend
        name = backend.name
        # The reassembly target is TRANSPOSED — ``[N + 1, Q]`` — so each
        # shard's scatter writes contiguous Q-wide rows at random offsets
        # (one cache line per user) instead of strided columns of a
        # ``[Q, N]`` array; at 10^6 users that is ~5x cheaper and it is
        # the only full-population pass the warm path makes.  Row ``N``
        # is the trash row the kernels' padding lanes land in.  The
        # returned ``[Q, N]`` transpose-view carries identical values to
        # :func:`repro.shard.reduce.assemble_counts` (the property-tested
        # reference composition).
        out_t: np.ndarray | None = None
        t_verify = [0.0] * state.n_shards
        partials: list[np.ndarray | None] = [None] * state.n_shards
        out_devices: list[str | None] = [None] * state.n_shards
        for i, view in enumerate(state.views):
            if view.n_users == 0:
                continue
            sv = span("shard-verify", shard=view.index, backend=name)
            sv.__enter__()
            if kind == "shard":
                xs_s, ys_s, dest, ok, ranks, block, base_q, planes_q = payload[i]
                counts = _ops.grid_count_cells_batch(
                    xs_s, ys_s, ranks, base_q, planes_q,
                    block=block, backend=backend.kernel_backend,
                )
                out_devices[view.index] = _device_of(counts)
                counts = np.asarray(counts)
                if out_t is None:
                    out_t = np.zeros(
                        (state.n_users + 1, counts.shape[0]), np.int32
                    )
                out_t[dest] = counts.T
                part = ((counts < self.k) & ok).sum(axis=1).astype(np.int64)
            else:
                if name in _DENSE_BACKENDS:
                    slab = _ops.raycast_count_batch(
                        view.xs, view.ys, payload,
                        backend=backend.kernel_backend,
                    )
                elif name in _GRID_BACKENDS:
                    from repro.core.grid import grid_hit_counts_batch_jnp

                    base, lists, coeffs = payload
                    slab = grid_hit_counts_batch_jnp(
                        view.xs, view.ys, base, lists, coeffs,
                        self.rect, self.engine.config.grid_g,
                    )
                elif name in _BVH_BACKENDS:
                    from repro.core.bvh import bvh_hit_counts_batch

                    left, right, bbox, coeffs = payload
                    slab = bvh_hit_counts_batch(
                        view.xs, view.ys, left, right, bbox, coeffs, k=self.k
                    )
                else:  # pragma: no cover — _mesh_dispatch_for gates the names
                    raise ValueError(f"unshardable backend {name!r}")
                out_devices[view.index] = _device_of(slab)
                slab = np.asarray(slab)
                if out_t is None:
                    out_t = np.zeros(
                        (state.n_users + 1, slab.shape[0]), np.int32
                    )
                out_t[state.perm[view.lo:view.hi]] = slab.T
                part = (slab < self.k).sum(axis=1).astype(np.int64)
            partials[view.index] = part
            sv.__exit__(None, None, None)
            t_verify[view.index] = sv.elapsed_s
        if out_t is None:  # pragma: no cover — n_users == 0 never dispatches
            return np.zeros((0, state.n_users), np.int32)
        n_q = out_t.shape[1]
        sizes = tree_psum(
            [p if p is not None else np.zeros(n_q, np.int64) for p in partials]
        )
        self.engine._note_shard_verify(
            t_verify,
            backend=name,
            version=state.version,
            per_shard_users=[v.n_users for v in state.views],
            per_shard_device=out_devices,
            sizes=sizes,
        )
        return out_t[: state.n_users].T


class ShardedEngine(DynamicEngine):
    """A :class:`~repro.dynamic.engine.DynamicEngine` whose verify phase
    is partitioned over a user-axis device mesh.

    Construction adds the mesh knobs; every query/update surface is
    inherited.  Pass ``mesh=user_mesh(n)`` for a strict one-device-per-
    shard layout.  ``shards`` alone takes one visible device per shard;
    CPU devices are cycled when there are fewer (the partition and
    compaction are preserved; only physical parallelism collapses), while
    accelerators raise — a shard never silently shares a chip.  Masks and
    counts are bit-identical to the single-process engine for every
    concrete backend.
    """

    def __init__(
        self,
        facilities,
        users,
        config: RkNNConfig | None = None,
        *,
        shards: int | None = None,
        mesh=None,
        devices=None,
        rect: Rect | None = None,
        **overrides,
    ):
        if mesh is not None:
            n = mesh_shards(mesh)
            if shards is not None and int(shards) != n:
                raise ValueError(
                    f"shards={shards} disagrees with the mesh's users axis ({n})"
                )
            shards = n
            devices = shard_devices(n, mesh)
        if shards is None:
            shards = len(jax.devices())
        self.n_shards = max(int(shards), 1)
        self.shard_mesh = mesh
        self._shard_devices = (
            list(devices) if devices is not None else shard_devices(self.n_shards)
        )
        if len(self._shard_devices) != self.n_shards:
            raise ValueError(
                f"{self.n_shards} shards need {self.n_shards} devices, "
                f"got {len(self._shard_devices)}"
            )
        self._shard_log: "collections.deque[dict]" = collections.deque(maxlen=128)
        # base engine's `mesh=` kwarg is the training-style serve mesh —
        # deliberately NOT forwarded; the users mesh is this class's own
        super().__init__(facilities, users, config, rect=rect, **overrides)
        self.metrics.gauge("shard.imbalance").set(1.0)

    # ------------------------------------------------------------------
    # the shard partition (lazy per snapshot; one atomic install)
    # ------------------------------------------------------------------
    def _workload_shards(self) -> int:
        return self.n_shards

    def _shard_state_for(self, snap: EngineSnapshot) -> ShardState:
        st = snap.shard_state
        if (
            st is not None
            and st.version == snap.version
            and st.n_shards == self.n_shards
        ):
            return st
        users = snap.users
        n = len(users)
        perm = _spatial_perm(users, snap.rect, self.config.grid_g)
        pos = np.empty(n, np.int64)
        pos[perm] = np.arange(n)
        bounds = user_shard_bounds(n, self.n_shards)
        xs = users[:, 0].astype(np.float32)
        ys = users[:, 1].astype(np.float32)
        views = []
        for s in range(self.n_shards):
            lo, hi = int(bounds[s]), int(bounds[s + 1])
            sl = perm[lo:hi]
            dev = self._shard_devices[s]
            views.append(
                ShardView(
                    s, dev, snap.version, lo, hi,
                    jax.device_put(xs[sl], dev),
                    jax.device_put(ys[sl], dev),
                )
            )
        st = ShardState(snap.version, self.n_shards, perm, pos, bounds, tuple(views))
        # benign first-touch race: two racing builders produce equal
        # states; one atomic assignment wins (never a mixed-version set)
        snap.shard_state = st
        return st

    # ------------------------------------------------------------------
    # persistence hooks (repro.persist: the ``shards`` category)
    # ------------------------------------------------------------------
    def _persist_extra_fingerprints(self, snap: EngineSnapshot) -> dict:
        from repro.persist.store import _rect_parts, content_digest

        return {
            "shards": content_digest(
                "shards",
                snap.users,
                _rect_parts(snap.rect),
                int(self.config.grid_g),
                int(self.n_shards),
            )
        }

    def _persist_extra_categories(self, snap: EngineSnapshot) -> dict:
        st = self._shard_state_for(snap)
        return {
            "shards": {
                "meta": {"n_shards": int(st.n_shards)},
                "arrays": {"perm": st.perm, "pos": st.pos, "bounds": st.bounds},
            }
        }

    def _persist_adopt_extra(self, snap: EngineSnapshot, name: str, entry, arrays):
        if name != "shards":
            return None
        # the partition arrays come from the store; the per-shard device
        # views are re-placed locally (device topology is host state, not
        # store state)
        perm = np.ascontiguousarray(arrays["perm"], np.int64)
        pos = np.ascontiguousarray(arrays["pos"], np.int64)
        bounds = np.ascontiguousarray(arrays["bounds"], np.int64)
        users = snap.users
        xs = users[:, 0].astype(np.float32)
        ys = users[:, 1].astype(np.float32)
        views = []
        for s in range(self.n_shards):
            lo, hi = int(bounds[s]), int(bounds[s + 1])
            sl = perm[lo:hi]
            dev = self._shard_devices[s]
            views.append(
                ShardView(
                    s, dev, snap.version, lo, hi,
                    jax.device_put(xs[sl], dev),
                    jax.device_put(ys[sl], dev),
                )
            )
        snap.shard_state = ShardState(
            snap.version, self.n_shards, perm, pos, bounds, tuple(views)
        )
        return self.n_shards

    # ------------------------------------------------------------------
    # the dispatch injection point (covers batches, groups, stream)
    # ------------------------------------------------------------------
    def _mesh_dispatch_for(
        self, snap: EngineSnapshot, backend: Backend, *, rect: Rect, k: int
    ):
        if backend.name not in _SHARDABLE:
            return super()._mesh_dispatch_for(snap, backend, rect=rect, k=k)
        state = self._shard_state_for(snap)
        if state.n_users == 0:
            return None  # nothing to partition; single dispatch is exact
        return ShardDispatch(self, state, backend, rect, k)

    # ------------------------------------------------------------------
    # per-shard stats (metrics registry views; EngineStats + explain())
    # ------------------------------------------------------------------
    def _shard_hist(self, phase: str, i: int):
        key = ("shard", phase, i)
        h = self._metric_cache.get(key)
        if h is None:
            h = self._metric_cache[key] = self.metrics.histogram(
                "shard.phase_s", phase=phase, shard=i
            )
        return h

    def _note_shard_filter(self, times: list[float]) -> None:
        # every shard observes (zeros included) so the per-shard view
        # lists always span all n_shards entries
        for i, t in enumerate(times):
            self._shard_hist("filter", i).observe(t)

    def _note_shard_verify(
        self, times, *, backend, version, per_shard_users, per_shard_device, sizes
    ) -> None:
        tot = [0.0] * self.n_shards
        for i, t in enumerate(times):
            self._shard_hist("verify", i).observe(t)
        for labels, h in self.metrics.find("shard.phase_s"):
            if labels.get("phase") == "verify":
                i = int(labels["shard"])
                if 0 <= i < self.n_shards:
                    tot[i] += h.sum
        mean = sum(tot) / max(len(tot), 1)
        imbalance = (max(tot) / mean) if mean > 0 else 1.0
        self.metrics.gauge("shard.imbalance").set(imbalance)
        self._shard_log.append(
            {
                "mode": "shard-batch",
                "backend": backend,
                "version": version,
                "shards": self.n_shards,
                "per_shard_users": list(per_shard_users),
                "per_shard_device": list(per_shard_device),
                "per_shard_verify_s": [float(t) for t in times],
                "imbalance": imbalance,
                "result_sizes": [int(x) for x in np.asarray(sizes)],
            }
        )

    def explain(self) -> list[dict]:
        """Planner plans (inherited) followed by the per-batch shard
        records: per-shard user counts and verify timings, the running
        imbalance ratio, and the ``psum``-reduced result sizes."""
        return super().explain() + list(self._shard_log)

    # ------------------------------------------------------------------
    # COW update integration (scatter along the same axis)
    # ------------------------------------------------------------------
    def _cow_user_arrays(self, old, new, batch, report) -> None:
        super()._cow_user_arrays(old, new, batch, report)
        st = old.shard_state
        if st is None or st.n_shards != self.n_shards:
            return
        mv_ids, mv_pts = batch.user_move
        moves_only = (
            len(mv_ids) > 0
            and not len(batch.user_insert)
            and not len(batch.user_delete)
        )
        if not moves_only:
            return  # |U| changed: the partition itself is stale — rebuild lazily
        # functional scatter into the owning shards (old views untouched);
        # moved users keep their shard assignment until the next rebuild —
        # spatial purity degrades, correctness never does (any partition
        # is a valid partition)
        pos = st.pos[np.asarray(mv_ids, np.int64)]
        shard_of = np.searchsorted(st.bounds, pos, side="right") - 1
        views = []
        for s, view in enumerate(st.views):
            sel = shard_of == s
            if sel.any():
                local = jnp.asarray(pos[sel] - int(st.bounds[s]))
                xs = view.xs.at[local].set(
                    jnp.asarray(mv_pts[sel, 0], jnp.float32)
                )
                ys = view.ys.at[local].set(
                    jnp.asarray(mv_pts[sel, 1], jnp.float32)
                )
                views.append(
                    ShardView(s, view.device, new.version, view.lo, view.hi, xs, ys)
                )
            else:
                views.append(
                    ShardView(
                        s, view.device, new.version, view.lo, view.hi,
                        view.xs, view.ys, view.memo,
                    )
                )
        new.shard_state = ShardState(
            new.version, st.n_shards, st.perm, st.pos, st.bounds, tuple(views)
        )

    def _apply_updates_locked(self, batch):
        old = self._snap
        report = super()._apply_updates_locked(batch)
        new = self._snap
        st = old.shard_state
        if (
            new.shard_state is None
            and st is not None
            and st.n_shards == self.n_shards
            and not batch.touches_users
        ):
            # facility-only delta: the user partition is untouched — carry
            # every shard's device arrays by reference, re-stamped to the
            # new version in one atomic install (lockstep preserved)
            new.shard_state = st.restamp(new.version)
        return report
