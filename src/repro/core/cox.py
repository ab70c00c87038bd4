"""Cox proportional hazards: losses, exact partial derivatives, Lipschitz
constants.

Implements Theorem 3.1 / Corollary 3.3 / Theorem 3.4 of FastSurvival
(Liu, Zhang, Rudin; NeurIPS 2024).

Conventions
-----------
All functions operate on *time-sorted* data (ascending observation time).
With samples sorted ascending, the risk set ``R_i = {j : t_j >= t_i}`` is the
suffix starting at ``risk_start[i]`` (ties handled Breslow-style: every
member of a tie group shares the group's first index). All risk-set
statistics therefore become reverse (suffix) cumulative sums — the paper's
O(n) "hidden blessing".

Key quantities (all O(n) to form):
  w_k  = exp(eta_k - max eta)                (stabilized hazards)
  rc0  = revcumsum(w)            -> S0_i = rc0[risk_start[i]]
  d_i  = delta_i / S0_i
  A_k  = cumsum(d)[tie_end[k]]   = sum_{i : t_i <= t_k} delta_i / S0_i
  B_k  = cumsum(delta/S0^2)[tie_end[k]]
  E_k  = sum_{i : risk_start[i] = k} delta_i (events whose risk set starts
         at k; beta-free, so formed once per solve), which turns every
         per-event risk-set sum into a per-position one:
         sum_i delta_i f[risk_start[i]] = sum_k E_k f[k]

Swapped-order ("GEMV") identities used for all-coordinate derivatives:
  grad      = X^T (w * A) - X^T delta
  hess_diag = X^T.^2 (w * A) - sum_i delta_i * M_i.^2,
              M_i = revcumsum(w * X)[risk_start[i]] / S0_i
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# python float (weak-typed in jax): an np.float64 scalar here would promote
# the whole Lipschitz pipeline to f64 whenever jax_enable_x64 is on
INV_6_SQRT3 = float(1.0 / (6.0 * np.sqrt(3.0)))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CoxData:
    """Time-sorted survival design matrix and risk-set indexing."""

    x: Array          # (n, p) features, sorted ascending by time
    delta: Array      # (n,)   event indicator in {0., 1.}, sorted
    risk_start: Array  # (n,)  int32: first index of each sample's tie group
    tie_end: Array     # (n,)  int32: last index of each sample's tie group

    @property
    def n(self) -> int:
        return self.x.shape[0]

    @property
    def p(self) -> int:
        return self.x.shape[1]


def prepare(x: Array, t: Array, delta: Array) -> CoxData:
    """Sort by time ascending and build Breslow tie-group indices."""
    x = jnp.asarray(x)
    t = jnp.asarray(t)
    delta = jnp.asarray(delta, dtype=x.dtype)
    order = jnp.argsort(t, stable=True)
    ts = t[order]
    risk_start = jnp.searchsorted(ts, ts, side="left").astype(jnp.int32)
    tie_end = (jnp.searchsorted(ts, ts, side="right") - 1).astype(jnp.int32)
    return CoxData(
        x=x[order], delta=delta[order], risk_start=risk_start, tie_end=tie_end
    )


def revcumsum(v: Array, axis: int = 0) -> Array:
    """Reverse (suffix) cumulative sum along ``axis``."""
    return jax.lax.cumsum(v, axis=axis, reverse=True)


# ---------------------------------------------------------------------------
# Shared risk-set statistics
# ---------------------------------------------------------------------------

def hazard_weights(eta: Array) -> Tuple[Array, Array]:
    """Stabilized w = exp(eta - m); returns (w, m)."""
    m = jax.lax.stop_gradient(jnp.max(eta))
    return jnp.exp(eta - m), m


def risk_stats(data: CoxData, eta: Array) -> Tuple[Array, Array, Array, Array]:
    """Return (w, s0, a, b) — the O(n) sufficient statistics.

    s0_i = sum_{j in R_i} w_j           (at each sample's risk_start)
    a_k  = sum_{i : t_i <= t_k} delta_i / s0_i
    b_k  = sum_{i : t_i <= t_k} delta_i / s0_i^2
    """
    w, _ = hazard_weights(eta)
    rc0 = revcumsum(w)
    s0 = rc0[data.risk_start]
    d1 = data.delta / s0
    a = jnp.cumsum(d1)[data.tie_end]
    b = jnp.cumsum(d1 / s0)[data.tie_end]
    return w, s0, a, b


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def loss_from_eta(data: CoxData, eta: Array) -> Array:
    """Negative log partial likelihood (Breslow ties), Eq. (4)."""
    m = jnp.max(eta)
    w = jnp.exp(eta - m)
    rc0 = revcumsum(w)
    log_s0 = jnp.log(rc0[data.risk_start]) + m
    return jnp.sum(data.delta * (log_s0 - eta))


def penalty(beta: Array, lam1: Array | float, lam2: Array | float) -> Array:
    return lam1 * jnp.sum(jnp.abs(beta)) + lam2 * jnp.sum(beta * beta)


def objective(
    data: CoxData, beta: Array, lam1: float = 0.0, lam2: float = 0.0
) -> Array:
    eta = data.x @ beta
    return loss_from_eta(data, eta) + penalty(beta, lam1, lam2)


def eta_gradient(data: CoxData, eta: Array) -> Array:
    """d loss / d eta (n,): w*A - delta. Used by deep survival heads."""
    w, _, a, _ = risk_stats(data, eta)
    return w * a - data.delta


# ---------------------------------------------------------------------------
# All-coordinate derivatives (swapped-order GEMV form) — beyond-paper batch
# ---------------------------------------------------------------------------

def grad_all(data: CoxData, eta: Array) -> Array:
    """Exact gradient for all p coordinates in O(np) via two GEMVs."""
    r = eta_gradient(data, eta)  # (n,)
    return data.x.T @ r


def grad_hess_all(data: CoxData, eta: Array) -> Tuple[Array, Array]:
    """Exact (grad, diag Hessian) for all p coordinates, O(np)."""
    w, s0, a, _ = risk_stats(data, eta)
    wa = w * a
    grad = data.x.T @ (wa - data.delta)
    # term1_l = sum_k w_k A_k x_kl^2
    term1 = (data.x * data.x).T @ wa
    # term2_l = sum_i delta_i * (revcumsum(w x_l)[rs_i] / s0_i)^2
    mean = revcumsum(w[:, None] * data.x, axis=0)[data.risk_start] / s0[:, None]
    term2 = (data.delta[:, None] * mean * mean).sum(axis=0)
    return grad, term1 - term2


def exact_hessian(data: CoxData, eta: Array) -> Array:
    """Full (p, p) Hessian in O(n p^2) without materializing the (n, n)
    sample-space Hessian:  X^T diag(w*A) X  -  sum_i delta_i m_i m_i^T."""
    w, s0, a, _ = risk_stats(data, eta)
    h1 = (data.x * (w * a)[:, None]).T @ data.x
    mean = revcumsum(w[:, None] * data.x, axis=0)[data.risk_start] / s0[:, None]
    mw = mean * jnp.sqrt(data.delta)[:, None]
    return h1 - mw.T @ mw


def eta_hessian_diag(data: CoxData, eta: Array) -> Array:
    """Diagonal of the sample-space Hessian nabla^2_eta loss (n,):
    w_k A_k - w_k^2 B_k. Used by the quasi-Newton baseline (Simon et al.)."""
    w, _, a, b = risk_stats(data, eta)
    return w * a - (w * w) * b


def eta_hessian_upper(data: CoxData, eta: Array) -> Array:
    """skglm-style diagonal majorant of nabla^2_eta loss: grad_eta + delta
    = w*A (elementwise, >= diag of the true Hessian)."""
    w, _, a, _ = risk_stats(data, eta)
    return w * a


# ---------------------------------------------------------------------------
# Per-coordinate derivatives (Theorem 3.1) — the paper's CD primitives
# ---------------------------------------------------------------------------

def risk_start_events(data: CoxData) -> Array:
    """E (n,): the number of events whose risk set starts at each position.

    E_k = sum_{i : risk_start[i] = k} delta_i, so for any per-position f
    (a suffix sum, say)

        sum_i delta_i f[risk_start[i]] = sum_k E_k f[k]

    which trades a gather at ``risk_start`` for an elementwise weight.
    E depends on the data alone: form it once per solve, outside any loop
    over coordinates. On tie-free data E == delta.
    """
    return jnp.zeros(data.n, data.delta.dtype).at[data.risk_start].add(
        data.delta)


def coord_derivs(
    data: CoxData, eta: Array, xl: Array, order: int = 2,
    ev: Array | None = None,
) -> Tuple[Array, Array, Array]:
    """(g, h, c3) = 1st/2nd/3rd partial at one coordinate, each O(n).

    ``xl`` is the (n,) feature column (time-sorted). ``order`` controls how
    many cumulants are formed (2 -> g,h; 3 -> also the third partial).
    ``ev`` is ``risk_start_events(data)``; loops over coordinates pass it
    in, otherwise it is formed here. The risk-set moments are taken at
    every position and weighted by ``ev``, so no gather is needed.
    Positions that start no risk set add exactly 0 (their moments may be
    0/0 where the tail's hazards underflow).
    Its device work is named ``cd.stats`` (the risk-set statistics).
    """
    with jax.named_scope("cd.stats"):
        if ev is None:
            ev = risk_start_events(data)
        starts = ev > 0
        w, _ = hazard_weights(eta)
        rc0 = revcumsum(w)
        m1 = revcumsum(w * xl) / rc0
        g = jnp.sum(jnp.where(starts, ev * m1, 0.0) - data.delta * xl)
        m2 = revcumsum(w * xl * xl) / rc0
        h = jnp.sum(jnp.where(starts, ev * (m2 - m1 * m1), 0.0))
        if order < 3:
            return g, h, jnp.zeros_like(g)
        m3 = revcumsum(w * xl * xl * xl) / rc0
        k3 = m3 + 2.0 * m1**3 - 3.0 * m2 * m1
        c3 = jnp.sum(jnp.where(starts, ev * k3, 0.0))
        return g, h, c3


# ---------------------------------------------------------------------------
# Lipschitz constants (Theorem 3.4) — beta-independent, precomputed once
# ---------------------------------------------------------------------------

def lipschitz_constants(data: CoxData) -> Tuple[Array, Array]:
    """(L2, L3), each (p,): L2 bounds the 2nd partial, L3 the |3rd| partial.

    L2_l = 1/4      sum_i delta_i (max_{k in R_i} X_kl - min_{k in R_i})^2
    L3_l = 1/(6√3)  sum_i delta_i |range|^3
    Suffix max/min over the sorted time axis are O(n) reverse cum-extrema.
    """
    smax = jax.lax.cummax(data.x, axis=0, reverse=True)[data.risk_start]
    smin = jax.lax.cummin(data.x, axis=0, reverse=True)[data.risk_start]
    rng = smax - smin
    d = data.delta[:, None]
    l2 = 0.25 * jnp.sum(d * rng * rng, axis=0)
    l3 = INV_6_SQRT3 * jnp.sum(d * rng * rng * rng, axis=0)
    return l2, l3


def central_moment(data: CoxData, eta: Array, xl: Array, r: int) -> Array:
    """C_r of Lemma 3.2 for every event i, returned delta-masked (n,).

    Reference implementation used by tests of the moment recursion
    dC_r/dbeta_l = C_{r+1} - r C_2 C_{r-1}; O(n * r)."""
    w, _ = hazard_weights(eta)
    rc0 = revcumsum(w)
    s0 = rc0[data.risk_start]
    m1 = revcumsum(w * xl)[data.risk_start] / s0
    # E[(X - mu)^r] = sum_j binom(r,j) E[X^j] (-mu)^(r-j)
    out = jnp.zeros_like(s0)
    from math import comb

    for j in range(r + 1):
        ej = revcumsum(w * xl**j)[data.risk_start] / s0
        out = out + comb(r, j) * ej * (-m1) ** (r - j)
    return out
