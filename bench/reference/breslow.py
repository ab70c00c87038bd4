"""Plain reference of the penalized Cox objective (Breslow ties), float64.

Independent of the program: numpy only. For a time-sorted cohort the
risk set of subject i is every subject whose observed time is at least
t_i, so its sums are suffix sums taken at the first index of i's tie
group.

    loss(beta) = sum_i delta_i (log sum_{j in R_i} exp(eta_j) - eta_i)
    F(beta)    = loss(beta) + lam1 |beta|_1 + lam2 |beta|_2^2
"""
from __future__ import annotations

import numpy as np


def _sorted(x, t, delta):
    order = np.argsort(np.asarray(t, np.float64), kind="stable")
    ts = np.asarray(t, np.float64)[order]
    start = np.searchsorted(ts, ts, side="left")
    return (np.asarray(x, np.float64)[order],
            np.asarray(delta, np.float64)[order], start)


def loss_and_grad(x, t, delta, beta):
    """Breslow negative log partial likelihood and its gradient."""
    xs, d, start = _sorted(x, t, delta)
    eta = xs @ np.asarray(beta, np.float64)
    m = eta.max()
    w = np.exp(eta - m)
    s0 = np.cumsum(w[::-1])[::-1][start]
    loss = float(np.sum(d * (np.log(s0) + m - eta)))
    s1 = np.cumsum((w[:, None] * xs)[::-1], axis=0)[::-1][start]
    grad = (d[:, None] * (s1 / s0[:, None] - xs)).sum(axis=0)
    return loss, grad


def objective(x, t, delta, beta, lam1: float, lam2: float) -> float:
    beta = np.asarray(beta, np.float64)
    loss, _ = loss_and_grad(x, t, delta, beta)
    return loss + lam1 * np.abs(beta).sum() + lam2 * (beta * beta).sum()


def kkt_violation(x, t, delta, beta, lam1: float, lam2: float) -> float:
    """Largest violation of the optimality conditions of F at beta, over
    the largest gradient of the loss at beta = 0 (the penalty at which
    the solution path leaves zero): 0 at the optimum, 1 for beta = 0
    under no penalty. For a nonzero coordinate the condition is
    g_j + 2 lam2 beta_j + lam1 sign(beta_j) = 0; for a zero one,
    |g_j| <= lam1."""
    beta = np.asarray(beta, np.float64)
    _, g = loss_and_grad(x, t, delta, beta)
    _, g0 = loss_and_grad(x, t, delta, np.zeros_like(beta))
    g = g + 2.0 * lam2 * beta
    viol = np.where(beta != 0.0, np.abs(g + lam1 * np.sign(beta)),
                    np.maximum(np.abs(g) - lam1, 0.0))
    return float(viol.max() / np.abs(g0).max())
