"""AdamW with global-norm clipping and warmup+cosine schedule (pure JAX —
optax is not available offline). Moments are fp32 regardless of param dtype;
with the sharding rules in launch/sharding.py they are ZeRO-sharded."""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import TrainConfig


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class OptState:
    m: Any
    v: Any
    step: jax.Array


def init_opt_state(params) -> OptState:
    zeros = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return OptState(m=zeros,
                    v=jax.tree.map(jnp.copy, zeros),
                    step=jnp.zeros((), jnp.int32))


def lr_schedule(step, cfg: TrainConfig):
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    t = jnp.clip((step - cfg.warmup_steps)
                 / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                 0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * t))
    return cfg.learning_rate * warm * (0.1 + 0.9 * cos)


def global_norm(tree) -> jax.Array:
    sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
             for x in jax.tree.leaves(tree))
    return jnp.sqrt(sq)


def adamw_update(grads, opt: OptState, params, cfg: TrainConfig):
    """Returns (new_params, new_opt, metrics); the device work (the clip
    and the update) is named ``optim.adamw``."""
    with jax.named_scope("optim.adamw"):
        step = opt.step + 1
        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))
        lr = lr_schedule(step, cfg)
        b1, b2 = cfg.beta1, cfg.beta2
        bc1 = 1.0 - b1 ** step.astype(jnp.float32)
        bc2 = 1.0 - b2 ** step.astype(jnp.float32)

        def upd(g, m, v, p):
            g = g.astype(jnp.float32) * scale
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mh = m / bc1
            vh = v / bc2
            delta = mh / (jnp.sqrt(vh) + 1e-8) + cfg.weight_decay \
                * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

        flat_p, tdef = jax.tree.flatten(params)
        flat_g = tdef.flatten_up_to(grads)
        flat_m = tdef.flatten_up_to(opt.m)
        flat_v = tdef.flatten_up_to(opt.v)
        out = [upd(g, m, v, p)
               for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p)]
        new_p = tdef.unflatten([o[0] for o in out])
        new_m = tdef.unflatten([o[1] for o in out])
        new_v = tdef.unflatten([o[2] for o in out])
        return new_p, OptState(m=new_m, v=new_v, step=step), \
            {"grad_norm": gnorm, "lr": lr}
