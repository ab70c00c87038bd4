"""Training step factory: loss -> grads (with optional microbatch
accumulation) -> clip -> AdamW, as a single jit-able function of
(TrainState, batch). Used identically by the real launcher, the examples
and the dry-run (which only lowers it)."""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..configs.base import TrainConfig
from ..models.model import Model
from . import optimizer as opt_lib


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt: opt_lib.OptState


def init_train_state(model: Model, rng) -> TrainState:
    params = model.init_params(rng)
    return TrainState(params=params, opt=opt_lib.init_opt_state(params))


def make_loss_fn(model: Model, objective: str = "lm",
                 remat: bool = True) -> Callable:
    if objective == "lm":
        def loss_fn(params, batch):
            return model.loss_lm(params, batch, remat=remat)
    elif objective == "cox":
        from ..survival import head as head_lib

        def loss_fn(params, batch):
            return head_lib.cox_loss(model, params, batch)
    else:
        raise ValueError(objective)
    return loss_fn


def make_train_step(model: Model, tcfg: TrainConfig,
                    objective: str = "lm") -> Callable:
    loss_fn = make_loss_fn(model, objective, remat=tcfg.remat)

    def train_step(state: TrainState, batch) -> tuple:
        if tcfg.microbatch > 1:
            # gradient accumulation: split the batch along dim 0 and scan
            def reshape(x):
                return x.reshape(tcfg.microbatch, x.shape[0]
                                 // tcfg.microbatch, *x.shape[1:])

            micro = jax.tree.map(reshape, batch)

            def acc(carry, mb):
                g_sum, l_sum = carry
                (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    state.params, mb)
                return (jax.tree.map(jnp.add, g_sum, g), l_sum + loss), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (grads, loss), _ = jax.lax.scan(
                acc, (zeros, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree.map(lambda g: g / tcfg.microbatch, grads)
            loss = loss / tcfg.microbatch
            metrics: Dict[str, Any] = {}
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, batch)
        new_params, new_opt, opt_metrics = opt_lib.adamw_update(
            grads, state.opt, state.params, tcfg)
        out = {"loss": loss, **opt_metrics}
        if "counters" in metrics:
            out["counters"] = metrics["counters"]
        return TrainState(params=new_params, opt=new_opt), out

    return train_step
