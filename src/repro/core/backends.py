"""Pluggable verification backends behind one registry.

Before this module existed, every query path in :mod:`repro.core.rknn`
carried its own if/elif ladder over the backend name — three copies
(`_build_index`, `_verify_counts`, and the batched dispatch) that each new
backend (the planned Pallas grid-batch kernel, hybrid auto-selection) would
have had to thread through.  Now a backend is ONE class implementing

* :meth:`Backend.build_index`    — host-side index build (filter phase),
* :meth:`Backend.count`          — single-query device count (verify phase),
* :meth:`Backend.prepare_batch`  — host-side batch stacking (filter phase),
* :meth:`Backend.count_batch`    — one batched device dispatch (verify phase),

registered with :func:`register_backend` and resolved with
:func:`get_backend`.  The split between ``prepare_batch`` and
``count_batch`` exists so callers can keep the paper's two-stage timing
convention honest: everything host-side lands in ``t_filter_s``, only the
device dispatch in ``t_verify_s``.

Built-in backends (all produce identical verdict sets — property-tested):

* ``"dense"``    — Pallas ray-cast kernel (interpret mode on CPU), the
                   TPU-native execution of the paper's ray-casting stage.
* ``"dense-ref"``— pure-jnp oracle (fast on CPU; same math).
* ``"grid"``     — uniform-grid culled counting (TPU BVH analogue).
* ``"grid-pallas"`` — cell-bucketed grid counting via the scalar-prefetch
                   Pallas kernel (``repro.kernels.grid_raycast``): users
                   sorted by cell once per batch, per-cell coefficient
                   planes staged into VMEM per program instance.
* ``"grid-pallas-ref"`` — pure-jnp execution of the same bucketed math
                   (the fast CPU path, mirroring dense/dense-ref).
* ``"bvh"``      — paper-faithful LBVH traversal with early termination.
* ``"brute"``    — exact distance-rank counting (no geometry; baseline).
* ``"auto"``     — the query planner (:mod:`repro.planner.backend`): a
                   *meta* backend (``is_meta = True``) that cost-dispatches
                   every request to the predicted-cheapest concrete backend
                   using the active calibration profile.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import weakref
from typing import Any, Callable, ClassVar

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.bvh import (
    BVH,
    build_bvh,
    bvh_hit_counts,
    bvh_hit_counts_batch,
    refit_bvh,
    stack_bvhs,
)
from repro.core.geometry import Rect
from repro.core.grid import (
    OccluderGrid,
    build_grid,
    grid_hit_counts_batch_jnp,
    grid_hit_counts_jnp,
    refit_grid,
    stack_grids,
)
from repro.core.scene import Scene, _next_pad, pad_scene_arrays
from repro.kernels import ops as _ops
from repro.kernels.grid_raycast import (
    pack_cell_coeff_planes,
    prepare_cell_buckets,
    repack_cell_coeff_planes,
    unsort_cell_counts,
)
from repro.obs import span

__all__ = [
    "Backend",
    "QueryRequest",
    "BatchRequest",
    "register_backend",
    "get_backend",
    "available_backends",
    "concrete_backends",
    "timeable_backends",
    "stack_cell_planes",
    "DenseBackend",
    "DenseRefBackend",
    "GridBackend",
    "GridPallasBackend",
    "GridPallasRefBackend",
    "BvhBackend",
    "BruteBackend",
    "PlannerBackend",
]


@dataclasses.dataclass
class QueryRequest:
    """Everything a backend may need for one single-query count.

    Geometric backends read ``xs/ys`` + ``scene`` (+ ``index``); the
    geometry-free brute backend reads ``users/facilities/q_pt/exclude``.
    """

    xs: jnp.ndarray  # [N] f32 user x
    ys: jnp.ndarray  # [N] f32 user y
    k: int
    grid_g: int = 64
    scene: Scene | None = None
    index: Any = None
    users: np.ndarray | None = None  # [N, 2] f64
    facilities: np.ndarray | None = None  # [M, 2] f64
    q_pt: np.ndarray | None = None  # [2]
    exclude: int | None = None
    #: Optional per-snapshot kernel memo (an ``LruCache``): the engine
    #: injects its snapshot's store so per-user-set state (the grid-pallas
    #: cell bucketing) is cached per *version*, not on the backend
    #: singleton.  ``None`` (raw protocol use) falls back to a small
    #: instance cache.
    memo: Any = None


@dataclasses.dataclass
class BatchRequest:
    """One batched multi-query count over a shared user set.

    ``mp`` is the static triangle pad target for stacked dense scenes
    (power-of-two bucketed by the engine so repeat workloads reuse one jit
    trace).  ``dispatch`` optionally overrides the device step: a callable
    taking the prepared batch state and returning ``[Q, N]`` counts — the
    engine injects its persistent mesh-sharded jitted dispatch here (for
    the dense-ref, grid, and bvh batched paths alike).
    """

    xs: jnp.ndarray  # [N] f32
    ys: jnp.ndarray  # [N] f32
    k: int
    rect: Rect | None = None
    grid_g: int = 64
    scenes: list[Scene] | None = None
    indexes: list | None = None
    users: np.ndarray | None = None
    facilities: np.ndarray | None = None
    q_pts: np.ndarray | None = None  # [Q, 2]
    excludes: list[int | None] | None = None
    mp: int | None = None
    dispatch: Callable | None = None
    #: The engine's ``MetricsRegistry``: the verify phase counts the bytes
    #: it copies in ``copy.bytes{dir=h2d|d2h}``.  ``None`` counts nothing.
    metrics: Any = None
    #: Per-snapshot kernel memo — see :attr:`QueryRequest.memo`.
    memo: Any = None


class Backend:
    """Protocol + default implementations for a verification backend."""

    name: ClassVar[str]
    #: False for geometry-free backends (no scene construction at all);
    #: the engine skips the whole filter phase for them.
    uses_scene: ClassVar[bool] = True
    #: True for planning backends that only *route* to concrete backends
    #: (the engine resolves them before filtering; they are excluded from
    #: the concrete-backend lists like ``repro.core.rknn.BACKENDS``).
    is_meta: ClassVar[bool] = False
    #: True for Pallas-kernel backends whose CPU execution is interpret
    #: mode — a bit-faithful correctness tool, orders of magnitude off the
    #: compiled cost.  Timed harnesses (planner calibration, the scenario
    #: sweep) consult :func:`timeable_backends` and skip them while
    #: ``pallas_interpret_default()`` is on; on a real TPU they are
    #: measured like any other backend.  Correctness suites ignore this.
    interpret_mode_on_cpu: ClassVar[bool] = False
    #: True when :meth:`prepare_batch`'s returned object bakes in user
    #: *coordinates* (not just scene geometry).  The dynamic engine's
    #: copy-on-write batch-cache carry consults this: for a user-move-only
    #: delta, prepared state of backends where this is False stays valid
    #: (user arrays enter only at :meth:`count_batch` via the request) and
    #: is carried into the next snapshot; True forces a drop.
    prepared_carries_users: ClassVar[bool] = False

    # ---- filter phase (host) --------------------------------------------
    def build_index(self, scene: Scene, *, grid_g: int = 64, memo: dict | None = None):
        """Host-side per-scene index build (grid/BVH); ``None`` if unused.

        ``memo`` is the engine snapshot's per-scene index store (a plain
        dict scoped to ``scene``): backends that share one built structure
        across registry entries (the grid family) memoize it there under
        their own key, so the snapshot — not the scene object — owns the
        cached index state.  ``None`` builds fresh.
        """
        return None

    def refit_index(
        self,
        index,
        old_scene: Scene,
        new_scene: Scene,
        changed: np.ndarray,
        *,
        grid_g: int = 64,
    ) -> tuple[Any, bool]:
        """Adapt ``index`` (built for ``old_scene``) to ``new_scene``.

        ``changed`` lists the real-triangle ids whose geometry differs; all
        other triangles are bit-identical between the scenes (the dynamic
        subsystem's scene-refit contract).  Returns ``(new_index, refit)``
        where ``refit`` is True when the index was adapted in place rather
        than rebuilt.  The default — and the fallback of every override
        whose cheap path does not apply — is a fresh :meth:`build_index`.
        Either way the returned index must count exactly like a fresh
        build (grid counts are order-independent, BVH boxes stay
        conservative), so refit never changes query results.
        """
        return self.build_index(new_scene, grid_g=grid_g), False

    def prepare_batch(self, req: BatchRequest):
        """Host-side batch stacking; the returned object is what
        :meth:`count_batch` dispatches.  Runs inside ``t_filter_s``."""
        return None

    # ---- persistence (repro.persist) ------------------------------------
    def export_state(self, index) -> tuple[str, dict, dict] | None:
        """Serializable form of a built index: ``(kind, arrays, meta)``.

        ``arrays`` maps names to host numpy arrays; ``meta`` is JSON-safe.
        ``None`` means the backend keeps no persistable index state (the
        dense family stacks scene coefficients directly; brute has no
        geometry) — such backends rebuild for free on restore.  ``kind``
        tags the encoding so :meth:`import_state` can reject a payload it
        does not understand.
        """
        return None

    def import_state(self, kind: str, arrays: dict, meta: dict):
        """Inverse of :meth:`export_state`: rebuild the in-memory index
        object from its serialized form.  Raises ``ValueError`` on an
        unrecognized ``kind`` (a stale or foreign payload must fall back
        to a cold build, not be misread)."""
        raise ValueError(f"backend {self.name!r} cannot import state kind {kind!r}")

    # ---- verify phase (device) ------------------------------------------
    def count(self, req: QueryRequest) -> np.ndarray:
        """``[N]`` int32 hit counts for one query."""
        raise NotImplementedError

    def count_batch(self, req: BatchRequest, prepared) -> np.ndarray:
        """``[Q, N]`` int32 hit counts in one batched device dispatch."""
        raise NotImplementedError


_REGISTRY: dict[str, Backend] = {}


def register_backend(cls: type[Backend]) -> type[Backend]:
    """Class decorator: instantiate and register under ``cls.name``.

    Later registrations override earlier ones (so tests / downstream code
    can shadow a built-in with an instrumented variant).
    """
    _REGISTRY[cls.name] = cls()
    return cls


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"backend must be one of {available_backends()}, got {name!r}"
        ) from None


def available_backends() -> tuple[str, ...]:
    return tuple(_REGISTRY)


def concrete_backends() -> tuple[str, ...]:
    """Registered names that do the counting themselves — meta backends
    (the ``auto`` planner) route to these and are excluded.  Single source
    of truth for every "all real backends" list."""
    return tuple(n for n, b in _REGISTRY.items() if not b.is_meta)


def timeable_backends() -> tuple[str, ...]:
    """Concrete backends whose wall time is meaningful on this runtime.

    Excludes backends flagged ``interpret_mode_on_cpu`` while the Pallas
    kernels would run in interpret mode (see :class:`Backend`) — the
    single source of truth the calibration harness and benchmark sweeps
    share, replacing per-name exclusion lists."""
    interp = _ops.pallas_interpret_default()
    return tuple(
        n
        for n, b in _REGISTRY.items()
        if not b.is_meta and not (interp and b.interpret_mode_on_cpu)
    )


def _count_copy(req: BatchRequest, direction: str, nbytes: int) -> None:
    if req.metrics is not None:
        req.metrics.counter("copy.bytes", dir=direction).inc(nbytes)


def _to_host(counts, req: BatchRequest) -> np.ndarray:
    """The copy back of one batched count, which every ``count_batch``
    goes through: ``verify.wait`` until the device result is ready, then
    ``verify.d2h`` (attribute ``bytes``) for the copy to the host.  The
    wait is the one the copy would make anyway."""
    with span("verify.wait"):
        jax.block_until_ready(counts)
    nbytes = int(counts.nbytes)
    with span("verify.d2h", bytes=nbytes):
        host = np.asarray(counts)
    _count_copy(req, "d2h", nbytes)
    return host


# --------------------------------------------------------------------------
# Dense (stacked edge functions, no index)
# --------------------------------------------------------------------------


@register_backend
class DenseBackend(Backend):
    """Pallas ray-cast kernel over the full padded scene."""

    name = "dense"
    kernel_backend = "pallas"
    interpret_mode_on_cpu = True

    def count(self, req: QueryRequest) -> np.ndarray:
        return np.asarray(
            _ops.raycast_count(
                req.xs, req.ys, req.scene.coeffs, backend=self.kernel_backend
            )
        )

    def prepare_batch(self, req: BatchRequest) -> np.ndarray:
        scenes = req.scenes
        # size the stacked pad from the REAL triangle counts: scenes arrive
        # pre-padded (possibly to a much larger sticky bucket), and sizing
        # from tris.shape[0] over-pads the whole [Q, Mp, 3, 3] stack on the
        # one-shot shim path (req.mp None)
        mp = (
            req.mp
            if req.mp is not None
            else _next_pad(max(s.n_tris for s in scenes))
        )
        return np.stack(
            [
                pad_scene_arrays(
                    s.tris[: s.n_tris], s.coeffs[: s.n_tris], s.owner[: s.n_tris], mp
                )[1]
                for s in scenes
            ]
        ).astype(np.float32)  # [Q, Mp, 3, 3]

    def count_batch(self, req: BatchRequest, prepared) -> np.ndarray:
        if req.dispatch is not None:
            return _to_host(req.dispatch(prepared), req)
        if isinstance(prepared, np.ndarray):
            _count_copy(req, "h2d", prepared.nbytes)
        return _to_host(
            _ops.raycast_count_batch(
                req.xs, req.ys, prepared, backend=self.kernel_backend
            ),
            req,
        )


@register_backend
class DenseRefBackend(DenseBackend):
    """Pure-jnp oracle of the dense path (the fast CPU execution)."""

    name = "dense-ref"
    kernel_backend = "ref"
    interpret_mode_on_cpu = False


# --------------------------------------------------------------------------
# Grid (uniform-grid culling, the TPU BVH analogue)
# --------------------------------------------------------------------------


@register_backend
class GridBackend(Backend):
    name = "grid"

    def build_index(self, scene: Scene, *, grid_g: int = 64, memo: dict | None = None):
        # the grid, grid-pallas, and grid-pallas-ref backends all build the
        # identical index, so within one snapshot's per-scene store they
        # share it under ("grid", G) — a scene queried through more than
        # one of them pays one build (the pallas variants hang their packed
        # planes off the shared object, keyed by lane pad)
        key = ("grid", int(grid_g))
        if memo is not None:
            g = memo.get(key)
            if g is not None:
                return g
        g = build_grid(
            scene.tris[: scene.n_tris],
            scene.coeffs[: scene.n_tris],
            scene.rect,
            G=grid_g,
        )
        if memo is not None:
            memo[key] = g
        return g

    def refit_index(
        self,
        index,
        old_scene: Scene,
        new_scene: Scene,
        changed: np.ndarray,
        *,
        grid_g: int = 64,
    ):
        if index is not None and index.G == grid_g:
            n = old_scene.n_tris
            g = refit_grid(
                index,
                old_scene.tris[:n],
                old_scene.coeffs[:n],
                new_scene.tris[: new_scene.n_tris],
                new_scene.coeffs[: new_scene.n_tris],
                changed,
            )
            if g is not None:
                return g, True
        return self.build_index(new_scene, grid_g=grid_g), False

    def export_state(self, index) -> tuple[str, dict, dict] | None:
        if index is None:
            return None
        arrays = {
            "base": index.base,
            "lists": index.lists,
            "coeffs": index.coeffs,
        }
        r = index.rect
        meta = {
            "G": int(index.G),
            "rect": [float(r.xmin), float(r.ymin), float(r.xmax), float(r.ymax)],
            "plane_pads": [],
        }
        # the pallas variants hang packed per-cell coefficient planes off
        # the shared grid object, keyed by lane pad — persist them so a
        # warm restore skips the re-pack too
        planes = getattr(index, "_cell_planes", None) or {}
        for pad in sorted(planes):
            meta["plane_pads"].append(int(pad))
            arrays[f"planes_{int(pad)}"] = planes[pad]
        return "grid", arrays, meta

    def import_state(self, kind: str, arrays: dict, meta: dict):
        if kind != "grid":
            return super().import_state(kind, arrays, meta)
        g = OccluderGrid(
            base=np.ascontiguousarray(arrays["base"], np.int32),
            lists=np.ascontiguousarray(arrays["lists"], np.int32),
            coeffs=np.ascontiguousarray(arrays["coeffs"], np.float32),
            G=int(meta["G"]),
            rect=Rect(*(float(v) for v in meta["rect"])),
        )
        pads = meta.get("plane_pads") or []
        if pads:
            g._cell_planes = {
                int(p): np.ascontiguousarray(arrays[f"planes_{int(p)}"], np.float32)
                for p in pads
            }
        return g

    def count(self, req: QueryRequest) -> np.ndarray:
        g = req.index
        if g is None:
            g = self.build_index(req.scene, grid_g=req.grid_g)
        return np.asarray(
            grid_hit_counts_jnp(
                req.xs, req.ys, g.base, g.lists, g.coeffs, req.scene.rect, req.grid_g
            )
        )

    def prepare_batch(self, req: BatchRequest):
        indexes = req.indexes
        if indexes is None:
            indexes = [self.build_index(s, grid_g=req.grid_g) for s in req.scenes]
        return stack_grids(indexes)  # (base, lists, coeffs)

    def count_batch(self, req: BatchRequest, prepared) -> np.ndarray:
        if req.dispatch is not None:
            return _to_host(req.dispatch(prepared), req)
        base, lists, coeffs = prepared
        return _to_host(
            grid_hit_counts_batch_jnp(
                req.xs, req.ys, base, lists, coeffs, req.rect, req.grid_g
            ),
            req,
        )


# --------------------------------------------------------------------------
# Grid-Pallas (cell-bucketed scalar-prefetch kernel over the grid index)
# --------------------------------------------------------------------------


def stack_cell_planes(
    planes: list[np.ndarray], *, lane_pad: int = 1, compact: bool = False
) -> np.ndarray:
    """Stack per-scene packed coefficient planes ``[n_cells, 3, 3, L_i]``
    into one ``[Q, n_cells, 3, 3, L]`` batch table.

    Per-scene lane widths ``L_i`` are heterogeneous (each scene pads to
    its own longest cell list); short planes degenerate-pad with the
    third coefficient row at ``-1`` — a plane no point is ever inside —
    so padding lanes can never contribute a hit.

    ``compact=True`` additionally trims dead lanes: ``L`` becomes the
    longest *live* lane across the stack (rounded up to ``lane_pad`` for
    the compiled kernel's tile constraint) instead of the longest padded
    width.  This is the user-axis shard win — a shard whose occupied
    cells carry short candidate lists ships and evaluates proportionally
    fewer ``[BU x L]`` edge tests.
    """
    if compact:
        L = 1
        for p in planes:
            live = np.flatnonzero(np.any(p[:, :, 2, :] != -1.0, axis=(0, 1)))
            if live.size:
                L = max(L, int(live[-1]) + 1)
        pad = max(int(lane_pad), 1)
        L = -(-L // pad) * pad
    else:
        L = max(p.shape[-1] for p in planes)
        if all(p.shape[-1] == L for p in planes):
            return np.stack(planes)
    out = np.zeros((len(planes),) + planes[0].shape[:-1] + (L,), np.float32)
    out[:, :, :, 2, :] = -1.0  # degenerate pad (never inside)
    for i, p in enumerate(planes):
        c = min(L, p.shape[-1])
        out[i, ..., :c] = p[..., :c]
    return out


@register_backend
class GridPallasBackend(GridBackend):
    """Cell-bucketed grid counting via the scalar-prefetch Pallas kernel.

    The jnp grid batch (:func:`repro.core.grid.grid_hit_counts_batch_jnp`)
    pays a gather-bound ``[Q, N, L, 3, 3]`` temporary — per user, per
    query, nine coefficient gathers per list slot.  This backend instead

    * sorts users by grid cell once per ``(users, rect, G)`` (all stacked
      scenes share one domain rect; the bucketing is LRU-cached on the
      backend so successive batches over the same user set reuse it),
    * packs each grid index's per-cell coefficient planes
      ``[G*G, 3, 3, L]`` once (memoized on the index; incrementally
      re-packed for the cells a :meth:`refit_index` touches),
    * compacts the stacked plane/base tables to the user-OCCUPIED cells
      (``cell_map`` becomes a rank into that compact axis — empty fringe
      cells never ship to the device), and
    * dispatches one ``(q, user-block)`` scalar-prefetch kernel where each
      program instance stages one query's planes for one cell into VMEM —
      ``[BU x L]`` edge evaluations plus ``base[q, cell]``.

    Everything host-side (bucketing, packing, stacking) runs in
    :meth:`prepare_batch` (``t_filter_s``); :meth:`count_batch` is the one
    device dispatch plus the unsort scatter that drops padding rows.
    Counts are bit-identical to the ``grid`` backend (property-tested in
    ``tests/test_grid_pallas.py``).
    """

    name = "grid-pallas"
    kernel_backend = "pallas"
    interpret_mode_on_cpu = True
    # prepare_batch's tuple embeds the cell-sorted user coordinates, so a
    # user-move delta invalidates it (the COW batch-cache carry drops it)
    prepared_carries_users = True
    _BUCKET_CACHE_CAP = 4

    @property
    def lane_pad(self) -> int:
        """Lane padding of the packed planes' list axis: the TPU lane
        width for the compiled Mosaic kernel; interpret mode (a
        correctness tool) has no lane constraint and a narrow pad keeps
        its per-step operand slicing cheap."""
        return 128 if not _ops.pallas_interpret_default() else 8

    def __init__(self) -> None:
        # raw-protocol fallback bucketing memo, used only when the request
        # carries no snapshot memo: (users identity, rect, G) -> sorted
        # arrays, with a weakref guard against id() reuse after gc.
        # Engine-routed requests inject their snapshot's kernel memo
        # instead (per-version ownership — see core/snapshot.py).
        self._bucket_cache: "collections.OrderedDict[tuple, tuple]" = (
            collections.OrderedDict()
        )
        self._bucket_lock = threading.Lock()

    # ---- packed per-cell planes (memoized on the grid index) ------------
    def _planes_for(self, grid) -> np.ndarray:
        store = getattr(grid, "_cell_planes", None)
        if store is None:
            store = {}
            grid._cell_planes = store
        planes = store.get(self.lane_pad)
        if planes is None:
            planes = pack_cell_coeff_planes(grid, lane_pad=self.lane_pad)
            store[self.lane_pad] = planes
        return planes

    # ---- user bucketing (shared across batches over one user set) -------
    def _buckets_for(self, xs, ys, rect, G: int, memo=None):
        """``(xs_s, ys_s, order, ranks, occ, block)`` for one user set.

        ``occ`` lists the user-occupied cell ids and ``ranks`` maps each
        user block into that compact axis — the plane/base tables shipped
        to the device carry only occupied cells.

        With a snapshot ``memo`` (engine-routed requests) the bucketing is
        cached per engine version: the memo pins a strong reference to
        ``xs`` so the identity key stays valid for the entry's lifetime,
        and lookups are lock-free.  Without one (raw protocol) a small
        weakref-guarded instance cache is used.
        """
        n = int(xs.shape[0])
        key = ("gp-buckets", id(xs), n, rect, int(G))
        if memo is not None:
            hit = memo.get(key)
            if hit is not None and hit[0] is xs:
                return hit[1]
        else:
            with self._bucket_lock:
                hit = self._bucket_cache.get(key)
                if hit is not None and hit[0]() is xs:
                    self._bucket_cache.move_to_end(key)
                    return hit[1]
        xs_np = np.asarray(xs, np.float32)
        ys_np = np.asarray(ys, np.float32)
        xs_s, ys_s, order, cell_map, nb = prepare_cell_buckets(
            xs_np, ys_np, rect, G, block=None
        )
        block = xs_s.shape[0] // nb if nb else 0
        occ = np.unique(cell_map)
        ranks = np.searchsorted(occ, cell_map).astype(np.int32)
        buckets = (jnp.asarray(xs_s), jnp.asarray(ys_s), order, ranks, occ, block)
        if memo is not None:
            memo.put(key, (xs, buckets))  # strong ref pins id(xs)
            return buckets
        try:
            ref = weakref.ref(xs)
        except TypeError:  # non-weakref-able array type: pin it instead
            ref = (lambda o: (lambda: o))(xs)
        with self._bucket_lock:
            self._bucket_cache[key] = (ref, buckets)
            while len(self._bucket_cache) > self._BUCKET_CACHE_CAP:
                self._bucket_cache.popitem(last=False)
        return buckets

    # ---- filter phase ----------------------------------------------------
    def build_index(self, scene: Scene, *, grid_g: int = 64, memo: dict | None = None):
        grid = super().build_index(scene, grid_g=grid_g, memo=memo)
        self._planes_for(grid)  # pack eagerly: host work belongs to filter
        return grid

    def refit_index(
        self,
        index,
        old_scene: Scene,
        new_scene: Scene,
        changed: np.ndarray,
        *,
        grid_g: int = 64,
    ):
        new_grid, was_refit = super().refit_index(
            index, old_scene, new_scene, changed, grid_g=grid_g
        )
        if was_refit:
            # incremental plane re-pack: refit_grid preserves the padded
            # list width, so only cells whose candidate list changed — or
            # that list a changed triangle (its coefficients moved) — need
            # their [3, 3, L] planes rewritten
            store = getattr(index, "_cell_planes", None) or {}
            old_planes = store.get(self.lane_pad)
            if old_planes is not None:
                touched = np.flatnonzero(
                    np.any(index.lists != new_grid.lists, axis=1)
                    | np.isin(new_grid.lists, np.asarray(changed)).any(axis=1)
                )
                new_grid._cell_planes = {
                    self.lane_pad: repack_cell_coeff_planes(
                        old_planes, new_grid, touched
                    )
                }
        return new_grid, was_refit

    def prepare_batch(self, req: BatchRequest):
        indexes = req.indexes
        if indexes is None:
            indexes = [self.build_index(s, grid_g=req.grid_g) for s in req.scenes]
        G = indexes[0].G
        rect = indexes[0].rect
        if any(g.G != G for g in indexes):
            raise ValueError("all grids in a batch must share G")
        if any(g.rect != rect for g in indexes):
            raise ValueError("all grids in a batch must share the domain rect")
        xs_s, ys_s, order, ranks, occ, block = self._buckets_for(
            req.xs, req.ys, rect, G, memo=req.memo
        )
        planes = [self._planes_for(g)[occ] for g in indexes]  # [n_occ, 3, 3, L]
        planes_q = stack_cell_planes(planes)
        base_q = np.stack([g.base[occ] for g in indexes]).astype(np.int32)
        return (xs_s, ys_s, order, ranks, block, base_q, planes_q)

    # ---- verify phase ----------------------------------------------------
    def count(self, req: QueryRequest) -> np.ndarray:
        grid = req.index
        if grid is None:
            grid = self.build_index(req.scene, grid_g=req.grid_g)
        xs_s, ys_s, order, ranks, occ, block = self._buckets_for(
            req.xs, req.ys, grid.rect, grid.G, memo=req.memo
        )
        counts = _ops.grid_count_cells(
            xs_s, ys_s, ranks, grid.base[occ], self._planes_for(grid)[occ],
            block=block, backend=self.kernel_backend,
        )
        return unsort_cell_counts(np.asarray(counts), order, int(req.xs.shape[0]))

    def count_batch(self, req: BatchRequest, prepared) -> np.ndarray:
        if req.dispatch is not None:
            return _to_host(req.dispatch(prepared), req)
        xs_s, ys_s, order, ranks, block, base_q, planes_q = prepared
        counts = _ops.grid_count_cells_batch(
            xs_s, ys_s, ranks, base_q, planes_q,
            block=block, backend=self.kernel_backend,
        )
        return unsort_cell_counts(_to_host(counts, req), order, int(req.xs.shape[0]))


@register_backend
class GridPallasRefBackend(GridPallasBackend):
    """Pure-jnp execution of the bucketed grid path (fast on CPU; same
    math — mirrors the dense/dense-ref pairing)."""

    name = "grid-pallas-ref"
    kernel_backend = "ref"
    interpret_mode_on_cpu = False
    lane_pad = 1  # no TPU lane constraint: stop at the real max list length


# --------------------------------------------------------------------------
# BVH (paper-faithful traversal with early termination at k)
# --------------------------------------------------------------------------


@register_backend
class BvhBackend(Backend):
    name = "bvh"

    def build_index(self, scene: Scene, *, grid_g: int = 64, memo: dict | None = None):
        return build_bvh(scene.tris[: scene.n_tris])

    def refit_index(
        self,
        index,
        old_scene: Scene,
        new_scene: Scene,
        changed: np.ndarray,
        *,
        grid_g: int = 64,
    ):
        if index is not None:
            bvh = refit_bvh(index, new_scene.tris[: new_scene.n_tris])
            if bvh is not None:
                return bvh, True
        return self.build_index(new_scene, grid_g=grid_g), False

    def export_state(self, index) -> tuple[str, dict, dict] | None:
        if index is None:
            return None
        arrays = {"left": index.left, "right": index.right, "bbox": index.bbox}
        return "bvh", arrays, {"n_tris": int(index.n_tris)}

    def import_state(self, kind: str, arrays: dict, meta: dict):
        if kind != "bvh":
            return super().import_state(kind, arrays, meta)
        return BVH(
            left=np.ascontiguousarray(arrays["left"], np.int32),
            right=np.ascontiguousarray(arrays["right"], np.int32),
            bbox=np.ascontiguousarray(arrays["bbox"], np.float32),
            n_tris=int(meta["n_tris"]),
        )

    def count(self, req: QueryRequest) -> np.ndarray:
        bvh = req.index
        if bvh is None:
            bvh = self.build_index(req.scene, grid_g=req.grid_g)
        return np.asarray(
            bvh_hit_counts(
                req.xs,
                req.ys,
                bvh.left,
                bvh.right,
                bvh.bbox,
                req.scene.coeffs[: req.scene.n_tris],
                k=req.k,
            )
        )

    def prepare_batch(self, req: BatchRequest):
        indexes = req.indexes
        if indexes is None:
            indexes = [self.build_index(s, grid_g=req.grid_g) for s in req.scenes]
        return stack_bvhs(indexes, [s.coeffs[: s.n_tris] for s in req.scenes])

    def count_batch(self, req: BatchRequest, prepared) -> np.ndarray:
        if req.dispatch is not None:
            return _to_host(req.dispatch(prepared), req)
        left, right, bbox, coeffs = prepared
        return _to_host(
            bvh_hit_counts_batch(req.xs, req.ys, left, right, bbox, coeffs, k=req.k),
            req,
        )


# --------------------------------------------------------------------------
# Brute (exact distance-rank counting; no geometry at all)
# --------------------------------------------------------------------------


@register_backend
class BruteBackend(Backend):
    name = "brute"
    uses_scene = False

    def count(self, req: QueryRequest) -> np.ndarray:
        return np.asarray(
            _ops.rank_count(
                req.users, req.facilities, req.q_pt, exclude=req.exclude, backend="ref"
            )
        )

    def count_batch(self, req: BatchRequest, prepared) -> np.ndarray:
        return _to_host(
            _ops.rank_count_batch(
                req.users, req.facilities, req.q_pts, exclude=req.excludes
            ),
            req,
        )


# --------------------------------------------------------------------------
# Auto (the query planner — registered last so concrete backends come first)
# --------------------------------------------------------------------------

from repro.planner.backend import PlannerBackend  # noqa: E402 — deliberate tail
                                                  # import; the planner module
                                                  # has no core imports at
                                                  # module level (acyclic)

register_backend(PlannerBackend)
