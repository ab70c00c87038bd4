"""Beam-search cardinality-constrained CPH (Section 3.5, "Constrained
Problem").

Support expansion a la generalized OMP + beam search (FasterRisk/OKRidge
style), but — the paper's point — scored and finetuned with the monotone
surrogate coordinate descent, which is what makes the framework usable for
CPH at all (Newton-type inner solvers blow up).

Host-driven outer loop over support sizes (k <= ~30); all inner work is
jitted:
  * ``score_candidates``: for every feature not in the support, run a few
    1-D surrogate steps on that coordinate alone (vmapped over p) and
    measure the *actual* loss decrease — the paper's selection rule
    ("which coefficient, if optimized, results in the largest decrease").
  * ``finetune``: CD sweeps over the (padded) support columns, through
    the same jnp sweep as ``solvers.fit_cd`` (``solvers.coord_sweep``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from . import cox, solvers, surrogate
from ..obs import trace

Array = jax.Array


@dataclasses.dataclass
class BeamResult:
    """Best model per support size: supports[k] has k+1 indices."""
    supports: List[np.ndarray]
    betas: List[np.ndarray]        # dense (p,) coefficient vectors
    losses: List[float]            # unpenalized CPH loss of the best beam


@partial(jax.jit, static_argnames=("steps",))
def score_candidates(data: cox.CoxData, eta: Array, l2c: Array,
                     lam2: float, in_support: Array, steps: int = 4):
    """Loss decrease achievable by optimizing each coordinate alone.

    Returns (decrease (p,), step_total (p,)); support members get -inf.
    """
    base = cox.loss_from_eta(data, eta)
    ev = cox.risk_start_events(data)

    def one(xl, l2l):
        def body(carry, _):
            eta_l, b = carry
            g, _, _ = cox.coord_derivs(data, eta_l, xl, order=2, ev=ev)
            step = surrogate.quad_min(g + 2.0 * lam2 * b,
                                      l2l + 2.0 * lam2).astype(eta.dtype)
            return (eta_l + step * xl, b + step), None

        (eta_l, b), _ = jax.lax.scan(
            body, (eta, jnp.zeros((), eta.dtype)), None, length=steps)
        dec = base - (cox.loss_from_eta(data, eta_l) + lam2 * b * b)
        return dec, b

    dec, b = jax.vmap(one, in_axes=(1, 0))(data.x, l2c)
    dec = jnp.where(in_support, -jnp.inf, dec)
    return dec, b


@partial(jax.jit, static_argnames=("k_max", "n_sweeps"))
def finetune(data: cox.CoxData, support_idx: Array, support_mask: Array,
             lam2: float, k_max: int, n_sweeps: int = 60):
    """CD (quadratic surrogate) restricted to the padded support columns.

    support_idx: (k_max,) int32 (padding arbitrary), support_mask: (k_max,).
    Returns (beta_s (k_max,), eta (n,), loss).
    """
    cols = data.x[:, support_idx] * support_mask[None, :]  # zero out padding
    l2c, _ = cox.lipschitz_constants(
        cox.CoxData(x=cols, delta=data.delta, risk_start=data.risk_start,
                    tie_end=data.tie_end))
    ev = cox.risk_start_events(data)
    cols_t = cols.T

    def coord_step(j, g, h, bj):
        step = surrogate.quad_min(g + 2.0 * lam2 * bj, l2c[j] + 2.0 * lam2)
        return jnp.where(support_mask[j] > 0, step, 0.0)

    def sweep(carry, _):
        return solvers.coord_sweep(data, *carry, cols_t, ev,
                                   coord_step), None

    eta0 = jnp.zeros(data.n, cols.dtype)
    beta0 = jnp.zeros(k_max, cols.dtype)
    (eta, beta_s), _ = jax.lax.scan(sweep, (eta0, beta0), None,
                                    length=n_sweeps)
    return beta_s, eta, cox.loss_from_eta(data, eta)


def beam_search(data: cox.CoxData, k: int, beam_width: int = 5,
                n_expand: int = 8, lam2: float = 1e-3,
                score_steps: int = 4, finetune_sweeps: int = 60,
                telemetry=None) -> BeamResult:
    """Grow supports 1..k, keeping the ``beam_width`` best at each size.

    The outer loop is host-driven, so telemetry is recorded directly (no
    debug callbacks): nested ``beam.score`` / ``beam.finetune`` spans
    around the jitted inner stages and a ``beam.size`` span per support
    size carrying the candidate count and best loss. Pass an
    ``obs.TelemetryCallback`` to additionally emit a tagged ``beam.size``
    event per size (candidates, best loss, chosen support)."""
    l2c, _ = cox.lipschitz_constants(data)
    p = data.p
    # beams: list of (loss, support tuple, eta, beta_s padded)
    beams = [(float(cox.loss_from_eta(data, jnp.zeros(data.n, data.x.dtype))),
              (), jnp.zeros(data.n, data.x.dtype))]
    out = BeamResult(supports=[], betas=[], losses=[])

    with trace.span("beam.search", k=k, beam_width=beam_width, p=p):
        for size in range(1, k + 1):
            with trace.span("beam.size", size=size) as size_span:
                candidates = {}
                with trace.span("beam.score", n_beams=len(beams)):
                    # every beam's scoring is dispatched before any is
                    # read back, so the device runs them back to back
                    decs = []
                    for loss_b, supp, eta_b in beams:
                        mask = np.zeros(p, dtype=bool)
                        mask[list(supp)] = True
                        dec, _ = score_candidates(data, eta_b, l2c, lam2,
                                                  jnp.asarray(mask),
                                                  steps=score_steps)
                        decs.append((supp, dec))
                    for supp, dec in decs:
                        top = np.argsort(-np.asarray(dec))[:n_expand]
                        for l in top:
                            new_supp = tuple(sorted(supp + (int(l),)))
                            if new_supp in candidates:
                                continue
                            candidates[new_supp] = True
                # finetune every unique candidate support
                scored = []
                with trace.span("beam.finetune",
                                n_candidates=len(candidates)):
                    # likewise every candidate's finetune
                    runs = []
                    for new_supp in candidates:
                        idx = np.zeros(k, dtype=np.int32)
                        msk = np.zeros(k, dtype=np.float32)
                        idx[: len(new_supp)] = np.asarray(new_supp, np.int32)
                        msk[: len(new_supp)] = 1.0
                        runs.append((new_supp, idx, finetune(
                            data, jnp.asarray(idx), jnp.asarray(msk), lam2,
                            k, n_sweeps=finetune_sweeps)))
                    for new_supp, idx, (beta_s, eta, loss) in runs:
                        scored.append((float(loss), new_supp, eta,
                                       np.asarray(beta_s), idx))
                scored.sort(key=lambda s: s[0])
                beams = [(s[0], s[1], s[2]) for s in scored[:beam_width]]
                best = scored[0]
                beta_dense = np.zeros(p, dtype=np.float32)
                beta_dense[best[4][: len(best[1])]] = best[3][: len(best[1])]
                out.supports.append(np.asarray(best[1], np.int64))
                out.betas.append(beta_dense)
                out.losses.append(best[0])
                size_span.set(n_candidates=len(candidates),
                              best_loss=best[0])
                if telemetry is not None:
                    telemetry.record_event(
                        "beam.size", size=size,
                        n_candidates=len(candidates), best_loss=best[0],
                        support=list(map(int, best[1])))
    return out


def omp_greedy(data: cox.CoxData, k: int, lam2: float = 1e-3,
               finetune_sweeps: int = 60) -> BeamResult:
    """Gradient-magnitude OMP baseline (what the paper improves upon):
    pick argmax |grad_l| each round, then finetune. Beam width 1, gradient
    scoring instead of loss-decrease scoring."""
    p = data.p
    supp: tuple = ()
    eta = jnp.zeros(data.n, data.x.dtype)
    out = BeamResult(supports=[], betas=[], losses=[])
    for size in range(1, k + 1):
        g = np.array(cox.grad_all(data, eta))  # copy: jax buffers are read-only
        g[list(supp)] = 0.0
        supp = tuple(sorted(supp + (int(np.argmax(np.abs(g))),)))
        idx = np.zeros(k, dtype=np.int32)
        msk = np.zeros(k, dtype=np.float32)
        idx[: len(supp)] = np.asarray(supp, np.int32)
        msk[: len(supp)] = 1.0
        beta_s, eta, loss = finetune(data, jnp.asarray(idx), jnp.asarray(msk),
                                     lam2, k, n_sweeps=finetune_sweeps)
        beta_dense = np.zeros(p, dtype=np.float32)
        beta_dense[idx[: len(supp)]] = np.asarray(beta_s)[: len(supp)]
        out.supports.append(np.asarray(supp, np.int64))
        out.betas.append(beta_dense)
        out.losses.append(float(loss))
    return out
