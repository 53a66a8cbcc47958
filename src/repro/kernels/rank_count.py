"""Pallas TPU kernel: distance-rank counting (brute / "InfZone-GPU" path).

The paper's Fig. 17 baseline offloads InfZone's verification to the GPU
without RT cores; the TPU equivalent is this dense rank count — for every
user, the number of facilities strictly closer than the query facility:

    count[u] = #{ f : (x_u - fx_f)^2 + (y_u - fy_f)^2 < thr_u },
    thr_u    = dist^2(u, q).

It shares the tiling scheme of :mod:`repro.kernels.raycast` (users on the
first grid axis, facilities lane-tiled on the second, int32 accumulator
revisited across facility blocks).  Both operands are 1-D here, so on
the compiled path each block is one ``T(1024)`` tile or a whole array.
It doubles as the *exact* RkNN oracle on device: ``count[u] < k ⇔ u ∈
RkNN(q)``, which makes it both the correctness anchor for the ray-cast
kernels and the measured no-RT baseline in
``benchmarks/bench_no_rt.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rank_count_kernel_call", "DEFAULT_BU", "DEFAULT_BM"]

DEFAULT_BU = 1024
DEFAULT_BM = 1024  # facilities ride 1-D blocks too: one T(1024) tile


def _rank_kernel(x_ref, y_ref, fx_ref, fy_ref, t_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...][:, None]  # [BU, 1]
    y = y_ref[...][:, None]
    t = t_ref[...][:, None]
    dx = x - fx_ref[...][None, :]  # [BU, BM]
    dy = y - fy_ref[...][None, :]
    closer = dx * dx + dy * dy < t
    o_ref[...] += jnp.sum(closer, axis=1, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("bu", "bm", "interpret"))
def rank_count_kernel_call(
    xs, ys, fx, fy, thr, *, bu: int = DEFAULT_BU, bm: int = DEFAULT_BM, interpret: bool
):
    """Pre-padded invoke: ``xs, ys, thr`` are ``[Np]``; ``fx, fy`` are
    ``[Mp]`` with padding facilities pushed to +inf (never closer)."""
    n_p = xs.shape[0]
    m_p = fx.shape[0]
    grid = (n_p // bu, m_p // bm)
    return pl.pallas_call(
        _rank_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bu,), lambda i, j: (i,)),
            pl.BlockSpec((bu,), lambda i, j: (i,)),
            pl.BlockSpec((bm,), lambda i, j: (j,)),
            pl.BlockSpec((bm,), lambda i, j: (j,)),
            pl.BlockSpec((bu,), lambda i, j: (i,)),
        ],
        out_specs=pl.BlockSpec((bu,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_p,), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(xs, ys, fx, fy, thr)
