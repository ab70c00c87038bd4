"""Deep-survival head: the paper's CPH objective as a first-class training
objective for any backbone in the pool (DeepSurv-style).

The batch is the risk-set universe: risk scores eta_i come from the pooled
final hidden state, the batch is sorted by observed time *inside the step*
(argsort is jit-able), and the loss is the exact Breslow negative log
partial likelihood from repro.core.cox — so the gradient flowing into the
backbone is the same eta-space gradient (w*A - delta) the paper analyzes.

`sparse_refit` then applies the paper's beam-search CD on frozen pooled
features to produce an interpretable sparse linear head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core import beam, cox
from ..models.model import Model

Array = jax.Array


def init_cox_head(rng, d_model: int):
    return {"w": jax.random.normal(rng, (d_model, 1), jnp.float32) * 0.01,
            "b": jnp.zeros((), jnp.float32)}


def cox_partial_likelihood(eta: Array, time: Array, event: Array) -> Array:
    """Exact CPH loss of a batch, sorted on the fly (Breslow ties)."""
    order = jnp.argsort(time, stable=True)
    ts = time[order]
    risk_start = jnp.searchsorted(ts, ts, side="left").astype(jnp.int32)
    tie_end = (jnp.searchsorted(ts, ts, side="right") - 1).astype(jnp.int32)
    data = cox.CoxData(x=jnp.zeros((time.shape[0], 0), eta.dtype),
                       delta=event[order].astype(eta.dtype),
                       risk_start=risk_start, tie_end=tie_end)
    return cox.loss_from_eta(data, eta[order]) \
        / jnp.maximum(jnp.sum(event), 1.0)


def cox_loss(model: Model, params, batch):
    """Survival objective for trainer.make_train_step(objective='cox').

    The pooling and the partial likelihood are named ``cox.head`` on the
    device. Counters the backbone keeps (the layer-pattern stack's routed
    pairs) come back under ``counters``."""
    hidden, aux, parts = model.hidden_states(params, batch)
    with jax.named_scope("cox.head"):
        eta = model.pooled_risk(params, hidden)
        loss = cox_partial_likelihood(eta.astype(jnp.float32),
                                      batch["time"], batch["event"])
        metrics = {"cox_nll": loss, "aux": aux}
        if parts is not None:
            metrics["counters"] = parts
        return loss + 0.01 * aux, metrics


def pooled_features(model: Model, params, batch) -> Array:
    hidden, _, _ = model.hidden_states(params, batch, remat=False)
    return hidden.mean(axis=1).astype(jnp.float32)


def sparse_refit(features: np.ndarray, time: np.ndarray, event: np.ndarray,
                 k: int, beam_width: int = 4):
    """Beam-search L0-constrained CPH on frozen backbone features —
    the paper's variable selection producing an interpretable sparse head."""
    data = cox.prepare(jnp.asarray(features), jnp.asarray(time),
                       jnp.asarray(event))
    return beam.beam_search(data, k=k, beam_width=beam_width)
