"""Plain reference of a served Cox model, float64, numpy only.

From the training cohort and the coefficients (both made by the
benchmark), the Breslow baseline cumulative hazard on the time grid;
then, per request, risk = exp(clip(x beta, -30, 30)) and the survival
curve S(t) = exp(-H0(t) risk). The served median is right when it is the
first grid time at which the reference curve reaches 1/2, up to a
rounding band ``tol`` on S. Adapted from the bring-up check
(``chip_smoke.reference_scores`` and ``check_served``).
"""
from __future__ import annotations

import numpy as np

ETA_CLIP = 30.0


def time_grid(t, size: int) -> np.ndarray:
    """``size`` evenly spaced float32 times over the observed range."""
    t = np.asarray(t, np.float32)
    return np.linspace(float(t.min()), float(t.max()), size,
                       dtype=np.float32)


def breslow_cumhaz(x, t, delta, beta, grid) -> np.ndarray:
    """H0(g) = sum_{i: t_i <= g} delta_i / sum_{j: t_j >= t_i} exp(eta_j)."""
    t = np.asarray(t, np.float64)
    order = np.argsort(t, kind="stable")
    ts = t[order]
    eta = np.asarray(x, np.float64)[order] @ np.asarray(beta, np.float64)
    w = np.exp(eta)
    s0 = np.cumsum(w[::-1])[::-1][np.searchsorted(ts, ts, side="left")]
    h = np.cumsum(np.asarray(delta, np.float64)[order] / s0)
    idx = np.searchsorted(ts, np.asarray(grid, np.float64), side="right") - 1
    return np.where(idx >= 0, h[np.clip(idx, 0, len(ts) - 1)], 0.0)


def scores(x, beta, h0):
    """(risk (b,), survival curves (b, g)) in float64."""
    eta = np.clip(np.asarray(x, np.float64) @ np.asarray(beta, np.float64),
                  -ETA_CLIP, ETA_CLIP)
    risk = np.exp(eta)
    return risk, np.exp(-np.asarray(h0, np.float64)[None, :] * risk[:, None])


def medians_ok(median, curves, grid, tol: float) -> np.ndarray:
    """For each row: is ``median`` the first grid time where its curve
    reaches 1/2, up to the band ``tol`` on S? (An infinite median is
    right when the curve stays above 1/2.)"""
    median = np.asarray(median, np.float32)
    curves = np.asarray(curves)
    grid = np.asarray(grid, np.float32)
    g = len(grid)
    finite = np.isfinite(median)
    j = np.clip(np.searchsorted(grid, np.where(finite, median, grid[0])),
                0, g - 1)
    rows = np.arange(len(median))
    at = curves[rows, j]
    before = np.where(j > 0, curves[rows, np.maximum(j - 1, 0)], np.inf)
    on_grid = grid[j] == median
    ok_finite = on_grid & (at <= 0.5 + tol) & (before > 0.5 - tol)
    ok_inf = curves.min(axis=1) > 0.5 - tol
    return np.where(finite, ok_finite, ok_inf & np.isinf(median))


def first_median(curves, grid) -> np.ndarray:
    """The first grid time at which each curve is at most 1/2 (inf where
    none is)."""
    below = np.asarray(curves) <= 0.5
    return np.where(below.any(axis=1), np.asarray(grid)[below.argmax(axis=1)],
                    np.inf)


def _bf16(a: np.ndarray) -> np.ndarray:
    """Round float32 to bfloat16 (nearest even), kept in float32."""
    u = np.asarray(a, np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32)


def dot_bf16x3(x, beta) -> np.ndarray:
    """x @ beta as three bfloat16 passes (hi hi + hi lo + lo hi) with
    float32 accumulation: the TPU's ``high`` matmul precision."""
    x = np.asarray(x, np.float32)
    beta = np.asarray(beta, np.float32)
    xh, bh = _bf16(x), _bf16(beta)
    xl, bl = _bf16(x - xh), _bf16(beta - bh)
    return (xh @ bh + xh @ bl + xl @ bh).astype(np.float32)
