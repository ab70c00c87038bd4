"""Distributed FastSurvival: the paper's O(n) machinery sharded over the
production mesh (n over `data`, p over `model`).

The scan structure distributes cleanly (DESIGN.md §3):
  * suffix sums: local suffix-scan per shard + one psum of shard totals,
    combined with an exclusive suffix over shard index — a log-depth
    distributed scan implemented in shard_map;
  * the all-coordinate GEMV form is a sharded matvec (XLA inserts a single
    psum over `model` / reduce-scatter over `data`);
  * a CD *sweep* keeps eta resident and sharded; each coordinate touch
    moves only O(1) scalars across the mesh.

Remainder shards: none of the entry points require ``n`` divisible by the
``data`` axis size. Inputs are zero-padded at the *tail* of the time axis
(the youngest suffix positions, so suffix sums over real rows are
untouched) and a 0/1 mask zeroes the padded hazards — ``w = 0`` and
``delta = 0`` on pad rows kill every risk-set and gradient contribution,
and ``s0`` is clamped to 1 there so no 0/0 NaN can leak through a psum.

`fit_cd_sharded` is the paper-representative workload of the §Perf
hillclimb; `sharded_grad_hess_all` powers distributed beam-search scoring.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import cox, surrogate

Array = jax.Array


def _axis_size(mesh, axis: str = "data") -> int:
    return int(mesh.shape[axis])


def _pad0(v: Array, size: int) -> Array:
    """Zero-pad axis 0 up to a multiple of ``size``."""
    pad = (-v.shape[0]) % size
    if pad == 0:
        return v
    widths = [(0, pad)] + [(0, 0)] * (v.ndim - 1)
    return jnp.pad(v, widths)


def _mask_for(n: int, size: int, dtype) -> Array:
    """(n_padded,) 1.0 on real rows, 0.0 on the padded tail."""
    n_pad = n + ((-n) % size)
    return (jnp.arange(n_pad) < n).astype(dtype)


def shard_revcumsum(x: Array, mesh, axis: str = "data") -> Array:
    """Suffix sum of a (n,) array sharded over ``axis``: local suffix scan
    + exclusive suffix of per-shard totals (one all-gather of scalars).
    ``n`` need not divide the axis size (zero tail-padding is exact for
    suffix sums)."""

    n_sh = _axis_size(mesh, axis)

    def local(xs):
        idx = jax.lax.axis_index(axis)
        loc = jax.lax.cumsum(xs, axis=0, reverse=True)
        totals = jax.lax.all_gather(xs.sum(), axis)          # (n_sh,)
        right = jnp.where(jnp.arange(n_sh) > idx, totals, 0.0).sum()
        return loc + right

    n = x.shape[0]
    out = jax.shard_map(local, mesh=mesh, in_specs=P(axis),
                        out_specs=P(axis), check_vma=False)(
        _pad0(x, _axis_size(mesh, axis)))
    return out[:n]


def _risk_stats_local(n_sh: int):
    """Per-shard body: (w, s0_safe, a) on padded shapes (``data`` axis)."""
    ax = "data"

    def local(eta_l, delta_l, mask_l):
        idx = jax.lax.axis_index(ax)
        m = jax.lax.pmax(jnp.max(jnp.where(mask_l > 0, eta_l, -jnp.inf)), ax)
        w = jnp.exp(eta_l - m) * mask_l
        # suffix sum of w
        loc = jax.lax.cumsum(w, axis=0, reverse=True)
        totals = jax.lax.all_gather(w.sum(), ax)
        s0 = loc + jnp.where(jnp.arange(n_sh) > idx, totals, 0.0).sum()
        s0 = jnp.where(mask_l > 0, s0, 1.0)  # pad rows: no 0/0 downstream
        # prefix sum of delta / s0
        d1 = delta_l / s0
        locp = jnp.cumsum(d1)
        totals_p = jax.lax.all_gather(d1.sum(), ax)
        a = locp + jnp.where(jnp.arange(n_sh) < idx, totals_p, 0.0).sum()
        return w, s0, a

    return local


def _risk_stats_padded(eta_p: Array, delta_p: Array, mask: Array, mesh):
    return jax.shard_map(
        _risk_stats_local(_axis_size(mesh)), mesh=mesh,
        in_specs=(P("data"), P("data"), P("data")),
        out_specs=(P("data"), P("data"), P("data")),
        check_vma=False)(eta_p, delta_p, mask)


def sharded_risk_stats(data: cox.CoxData, eta: Array, mesh):
    """(w, s0, a) with every (n,) vector sharded over `data`.

    Tie-free fast path (risk_start == arange), matching the Pallas kernels'
    contract; ties fall back to the replicated path in core.cox. Handles
    n not divisible by the data-axis size via a masked padded tail.
    """
    n = eta.shape[0]
    size = _axis_size(mesh)
    mask = _mask_for(n, size, eta.dtype)
    w, s0, a = _risk_stats_padded(_pad0(eta, size), _pad0(data.delta, size),
                                  mask, mesh)
    return w[:n], s0[:n], a[:n]


def sharded_grad_hess_all(data: cox.CoxData, eta: Array, mesh
                          ) -> Tuple[Array, Array]:
    """All-coordinate (grad, diag hess): X sharded (data, model), result
    sharded over `model`. GEMV form -> XLA emits one psum over `data`."""
    n = eta.shape[0]
    size = _axis_size(mesh)
    xp = _pad0(data.x, size)
    dp = _pad0(data.delta, size)
    mask = _mask_for(n, size, eta.dtype)
    w, s0, a = _risk_stats_padded(_pad0(eta, size), dp, mask, mesh)
    wa = w * a
    grad = xp.T @ (wa - dp)
    term1 = (xp * xp).T @ wa
    # mean term needs the suffix scan of w * x per column (n, p)
    wx = w[:, None] * xp
    s1 = shard_revcumsum_2d(wx, mesh)
    mean = s1 / s0[:, None]
    term2 = (dp[:, None] * mean * mean).sum(axis=0)
    return grad, term1 - term2


def shard_revcumsum_2d(x: Array, mesh) -> Array:
    n_sh = _axis_size(mesh)

    def local(xs):
        ax = "data"
        idx = jax.lax.axis_index(ax)
        loc = jax.lax.cumsum(xs, axis=0, reverse=True)
        totals = jax.lax.all_gather(xs.sum(axis=0), ax)      # (n_sh, p_loc)
        right = (jnp.where((jnp.arange(n_sh) > idx)[:, None], totals, 0.0)
                 .sum(axis=0))
        return loc + right[None, :]

    n = x.shape[0]
    out = jax.shard_map(local, mesh=mesh, in_specs=P("data", "model"),
                        out_specs=P("data", "model"), check_vma=False)(
        _pad0(x, _axis_size(mesh)))
    return out[:n]


@partial(jax.jit, static_argnames=("n_sweeps", "mesh"))
def fit_cd_sharded(data: cox.CoxData, l2c: Array, mesh,
                   lam1: float = 0.0, lam2: float = 0.0,
                   n_sweeps: int = 10):
    """Quadratic-surrogate CD with n sharded over `data` and the feature
    matrix sharded (data, model). Per coordinate: one sharded suffix scan
    (O(n/shards) + scalar collectives) and one sharded axpy on eta."""
    size = _axis_size(mesh)
    xp = _pad0(data.x, size)
    dp = _pad0(data.delta, size)
    mask = _mask_for(data.n, size, data.x.dtype)
    xT = xp.T  # (p, n_padded)
    beta = jnp.zeros(data.p, data.x.dtype)
    eta = jnp.zeros(xp.shape[0], data.x.dtype)

    def coord(l, carry):
        eta, beta = carry
        xl = xT[l]
        w, s0, a = _risk_stats_padded(eta, dp, mask, mesh)
        # grad_l = sum_k w_k a_k x_kl - sum delta x  (tie-free GEMV form)
        g = jnp.sum((w * a - dp) * xl)
        step = surrogate.quad_l1_prox(g + 2.0 * lam2 * beta[l],
                                      l2c[l] + 2.0 * lam2, beta[l], lam1)
        return eta + step * xl, beta.at[l].add(step)

    def sweep(_, carry):
        return jax.lax.fori_loop(0, data.p, coord, carry)

    eta, beta = jax.lax.fori_loop(0, n_sweeps, sweep, (eta, beta))
    return beta, eta[:data.n]
