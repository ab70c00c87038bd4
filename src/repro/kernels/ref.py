"""Pure-jnp oracles for every Pallas kernel (tie-free path, matching the
kernels' contracts exactly). Tests assert_allclose kernels against these."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def revcumsum_ref(x: jax.Array) -> jax.Array:
    return jax.lax.cumsum(x.astype(jnp.float32), axis=0,
                          reverse=True).astype(x.dtype)


def cox_batch_ref(x: jax.Array, w: jax.Array, r: jax.Array, wa: jax.Array,
                  delta: jax.Array, inv_s0: jax.Array):
    """All-coordinate (grad, hess_diag) from precomputed vectors."""
    x = x.astype(jnp.float32)
    g = x.T @ r.astype(jnp.float32)
    term1 = (x * x).T @ wa.astype(jnp.float32)
    s1 = jax.lax.cumsum(w[:, None].astype(jnp.float32) * x, axis=0,
                        reverse=True)
    m = s1 * inv_s0[:, None].astype(jnp.float32)
    term2 = (delta.astype(jnp.float32)[:, None] * m * m).sum(axis=0)
    return g, term1 - term2


def survival_curves_ref(eta: jax.Array, h0: jax.Array) -> jax.Array:
    """(b, g) S(t_g|x_b) = exp(-H0_g * exp(eta_b)), eta clipped to +/-30."""
    risk = jnp.exp(jnp.clip(eta.astype(jnp.float32), -30.0, 30.0))
    return jnp.exp(-risk[:, None] * h0.astype(jnp.float32)[None, :])


def survival_curves_stratified_ref(eta: jax.Array, h0: jax.Array,
                                   strata: jax.Array) -> jax.Array:
    """(b, g) S = exp(-H0[strata_b, g] * exp(eta_b)); h0 is (s, g)."""
    risk = jnp.exp(jnp.clip(eta.astype(jnp.float32), -30.0, 30.0))
    return jnp.exp(-h0.astype(jnp.float32)[strata] * risk[:, None])

