"""Inputs made from a seed: the benchmark's own data generators.

* ``appc``: the synthetic cohort of FastSurvival (Liu, Zhang, Rudin,
  NeurIPS 2024), Appendix C: x ~ N(0, Sigma) with Sigma_jl = rho^|j-l|,
  a k-sparse beta* with ones at every (p/k)-th column,
  t = (-log V / exp(x beta*))^s with V ~ U(0, 1), censoring C ~ U(0, c),
  delta = 1[t <= C], observed min(t, C). Copied from the program's
  ``data/synthetic.make_correlated_survival`` so that the yardstick does
  not move with the program.
* ``survival_tokens``: batches of token sequences whose hidden hazard
  grows with the frequency of a few marker tokens, with censored event
  times. Copied from the program's ``data/pipeline.SurvivalTextStream``;
  each step's rows come from its own stream of the seed.

Seeds are any whole number; numpy's generator takes them unbounded.
"""
from __future__ import annotations

import numpy as np


def _rng(*key: int) -> np.random.Generator:
    return np.random.default_rng([abs(int(k)) for k in key])


def appc(seed: int, n: int, p: int, k: int, rho: float, s: float,
         censor_scale: float = 1.0, stream: int = 0):
    """(x f32 (n, p), t f32 (n,), delta f32 (n,), beta_star f32 (p,))."""
    rng = _rng(seed, stream)
    eps = rng.standard_normal((n, p))
    x = np.empty((n, p), np.float64)
    x[:, 0] = eps[:, 0]
    c = np.sqrt(1.0 - rho * rho)
    for j in range(1, p):
        x[:, j] = rho * x[:, j - 1] + c * eps[:, j]
    stride = max(p // k, 1)
    nz = np.flatnonzero(np.arange(1, p + 1) % stride == 0)[:k]
    beta_star = np.zeros(p)
    beta_star[nz] = 1.0
    risk = np.clip(x @ beta_star, -30.0, 30.0)
    v = rng.uniform(1e-12, 1.0, size=n)
    t_event = (-np.log(v) / np.exp(risk)) ** s
    cens = rng.uniform(0.0, censor_scale, size=n)
    delta = (t_event <= cens).astype(np.float64)
    t_obs = np.minimum(t_event, cens)
    return (x.astype(np.float32), t_obs.astype(np.float32),
            delta.astype(np.float32), beta_star.astype(np.float32))


def survival_tokens(seed: int, step: int, batch: int, seq: int, vocab: int,
                    n_markers: int = 4):
    """One batch: tokens (batch, seq) int32, time and event (batch,) f32."""
    rng = _rng(seed, 1 << 20, step)
    markers = np.arange(1, 1 + n_markers)
    weights = np.linspace(1.0, 2.0, n_markers)
    toks = rng.integers(0, vocab, size=(batch, seq))
    intensity = rng.random((batch, 1)) * 0.2
    plant = rng.random(toks.shape) < intensity
    which = rng.integers(0, n_markers, size=toks.shape)
    toks = np.where(plant, markers[which], toks).astype(np.int32)
    counts = np.stack([(toks == m).mean(axis=1) for m in markers], axis=1)
    risk = counts @ weights * 40.0 - 2.0
    v = rng.uniform(1e-9, 1.0, size=batch)
    t_event = (-np.log(v) / np.exp(np.clip(risk, -20, 20))) ** 0.3
    cens = rng.uniform(0, np.quantile(t_event, 0.85), size=batch)
    event = (t_event <= cens).astype(np.float32)
    t_obs = np.minimum(t_event, cens).astype(np.float32)
    return {"tokens": toks, "time": t_obs, "event": event}
