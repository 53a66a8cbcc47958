"""Plain reference for bichromatic RkNN, independent of the system under test.

A user ``u`` is a reverse k nearest neighbour of the query point ``q`` when
fewer than ``k`` competing facilities are strictly closer to ``u`` than ``q``
is (ties do not count).  Facility ``f`` is strictly closer exactly when ``u``
lies on ``f``'s side of the bisector of ``q`` and ``f``, so the reference
evaluates the signed distance of ``u`` from each bisector,

    s_f(u) = u . n_f - c_f,   n_f = (q - f) / |q - f|,   c_f = n_f . (q + f) / 2,

which is positive on ``q``'s side.  ``n_f`` and ``c_f`` are formed in float64
on the host; the sum runs in float32 on the device, one block of users at a
time.  With every coordinate in ``[0, 1]`` its error is below ``5e-7``
(three rounded terms of magnitude at most 1.5), far inside ``BAND``.

Each user gets two ranks: ``r_lo`` counts the facilities closer by more than
``BAND`` (``s < -BAND``) and ``r_hi`` those closer or within ``BAND`` of a tie
(``s < BAND``).  The exact rank lies between them, so

* ``r_hi < k`` proves membership and ``r_lo >= k`` proves the opposite;
* a user with ``r_lo < k <= r_hi`` lies within ``BAND`` of a bisector that
  decides its answer.  The system under test evaluates its edge functions in
  float32 from float64 geometry and may round either way there; such users
  are *undecided* and are left out of the comparison.

For a user whose ``r_lo == r_hi`` the exact rank is known, and is returned
so that a caller can compare counts as well as membership.

Nothing here imports the system under test or reads anything it built.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["BAND", "bisector_frames", "reference_ranks", "control_member"]

#: Half-width, in units of distance, of the band around a bisector inside
#: which a user's side is not decided by the comparison (see PERF.md for the
#: error analysis it rests on: the system's float32 edge functions err by at
#: most ~1e-6 there, this module's by at most 5e-7).
BAND = 4e-6

#: Users per block of the device evaluation: bounds the ``[block, F]``
#: temporaries (8 MiB each at ``F = 1000``).
USER_BLOCK = 2048


def bisector_frames(
    facilities: np.ndarray, q_pt: np.ndarray, exclude: int | None
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(nx, ny, c)`` float32 ``[F]`` for one query, from float64 inputs.

    The query's own row (``exclude``) and any facility that coincides with
    ``q`` compete with nothing: ``n = 0, c = -1`` puts every user at
    ``s = 1``, on ``q``'s side.
    """
    f = np.asarray(facilities, np.float64)
    q = np.asarray(q_pt, np.float64).reshape(2)
    d = q[None, :] - f
    norm = np.hypot(d[:, 0], d[:, 1])
    valid = norm > 0.0
    if exclude is not None:
        valid[int(exclude)] = False
    safe = np.where(valid, norm, 1.0)
    n = np.where(valid[:, None], d / safe[:, None], 0.0)
    mid = 0.5 * (q[None, :] + f)
    c = np.where(valid, np.sum(n * mid, axis=1), -1.0)
    return (
        n[:, 0].astype(np.float32),
        n[:, 1].astype(np.float32),
        c.astype(np.float32),
    )


@functools.partial(jax.jit, static_argnames=("block",))
def _ranks(ux, uy, nx, ny, c, band, *, block: int):
    """``(r_lo, r_hi)`` int32 ``[N]`` for one query's bisector frames."""
    n = ux.shape[0]
    pad = (-n) % block
    uxb = jnp.pad(ux, (0, pad)).reshape(-1, block)
    uyb = jnp.pad(uy, (0, pad)).reshape(-1, block)

    def one(xy):
        x, y = xy
        s = x[:, None] * nx[None, :] + y[:, None] * ny[None, :] - c[None, :]
        lo = jnp.sum(s < -band, axis=1, dtype=jnp.int32)
        hi = jnp.sum(s < band, axis=1, dtype=jnp.int32)
        return lo, hi

    lo, hi = jax.lax.map(one, (uxb, uyb))
    return lo.reshape(-1)[:n], hi.reshape(-1)[:n]


def reference_ranks(ux, uy, facilities, q_pt, exclude, *, band: float = BAND):
    """Host ``(r_lo, r_hi)`` int32 ``[N]`` of one query.

    ``ux, uy`` are the users as float32 device arrays (the caller uploads
    its own copy once); ``facilities`` and ``q_pt`` are float64.
    """
    nx, ny, c = bisector_frames(facilities, q_pt, exclude)
    lo, hi = _ranks(
        ux, uy, jnp.asarray(nx), jnp.asarray(ny), jnp.asarray(c),
        jnp.float32(band), block=USER_BLOCK,
    )
    return np.asarray(lo), np.asarray(hi)


@functools.partial(jax.jit, static_argnames=("k", "block", "dtype"))
def _control(ux, uy, fx, fy, qx, qy, valid, *, k: int, block: int, dtype):
    n = ux.shape[0]
    pad = (-n) % block
    uxb = jnp.pad(ux, (0, pad)).reshape(-1, block).astype(dtype)
    uyb = jnp.pad(uy, (0, pad)).reshape(-1, block).astype(dtype)
    fx, fy, qx, qy = (a.astype(dtype) for a in (fx, fy, qx, qy))

    def one(xy):
        x, y = xy
        d2q = (x - qx) ** 2 + (y - qy) ** 2
        d2f = (x[:, None] - fx[None, :]) ** 2 + (y[:, None] - fy[None, :]) ** 2
        closer = (d2f < d2q[:, None]) & valid[None, :]
        return jnp.sum(closer, axis=1, dtype=jnp.int32) < k

    return jax.lax.map(one, (uxb, uyb)).reshape(-1)[:n]


def control_member(ux, uy, facilities, q_pt, exclude, k: int, dtype=jnp.bfloat16):
    """The plain rank test computed in a lower precision (``bfloat16`` by
    default): the control that the comparison has to reject."""
    f = np.asarray(facilities, np.float64)
    q = np.asarray(q_pt, np.float64).reshape(2)
    valid = np.hypot(f[:, 0] - q[0], f[:, 1] - q[1]) > 0.0
    if exclude is not None:
        valid[int(exclude)] = False
    out = _control(
        ux, uy,
        jnp.asarray(f[:, 0], jnp.float32), jnp.asarray(f[:, 1], jnp.float32),
        jnp.float32(q[0]), jnp.float32(q[1]), jnp.asarray(valid),
        k=int(k), block=USER_BLOCK, dtype=dtype,
    )
    return np.asarray(out)
