"""Pallas TPU kernel: fused (batch x time-grid) survival-curve evaluation.

S(t_g | x_b) = exp(-H0[g] * exp(eta[b])) — the serving hot path. The naive
jnp version materializes the (b, g) hazard product in HBM before the exp;
here the outer product runs on the MXU ((block_b, 1) @ (1, block_g)) and
the exp fuses on the VPU, so the (b, g) panel is written to HBM exactly
once. eta is clipped to +/-30 inside the kernel (matching the evaluation
path in survival/metrics.py) so extreme risk scores saturate to 0/1
probabilities instead of overflowing.

Grid: (b_blocks, g_blocks); every block is independent (no carry), so any
grid order is legal. VMEM per step is block_b*block_g*4B + O(block_b +
block_g) — the default 256 x 128 panel is ~128 KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _curves_kernel(eta_ref, h0_ref, o_ref):
    eta = jnp.clip(eta_ref[...].astype(jnp.float32), -30.0, 30.0)  # (bb, 1)
    h0 = h0_ref[...].astype(jnp.float32)                           # (1, bg)
    risk = jnp.exp(eta)
    prod = jax.lax.dot_general(
        risk, h0, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    o_ref[...] = jnp.exp(-prod).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_b", "block_g", "interpret"))
def _survival_curves_jit(eta: jax.Array, h0: jax.Array, block_b: int,
                         block_g: int, interpret: bool) -> jax.Array:
    b, g = eta.shape[0], h0.shape[0]
    bb = pl.cdiv(b, block_b)
    gb = pl.cdiv(g, block_g)
    pad_b = bb * block_b - b
    pad_g = gb * block_g - g
    etap = jnp.pad(eta, (0, pad_b)) if pad_b else eta
    h0p = jnp.pad(h0, (0, pad_g)) if pad_g else h0

    out = pl.pallas_call(
        _curves_kernel,
        grid=(bb, gb),
        in_specs=[
            pl.BlockSpec((block_b, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_g), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_g), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bb * block_b, gb * block_g),
                                       jnp.float32),
        interpret=interpret,
    )(etap.reshape(-1, 1), h0p.reshape(1, -1))
    return out[:b, :g]


def survival_curves(eta: jax.Array, h0: jax.Array, block_b: int = 256,
                    block_g: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """(b, g) survival probabilities from risk scores and baseline hazard.

    eta: (b,) linear predictors; h0: (g,) cumulative baseline hazard on the
    model's time grid (must be >= 0 and nondecreasing).
    ``interpret=None`` resolves backend-aware: native on TPU, interpret
    mode elsewhere. Pass an explicit bool to override (tests).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _survival_curves_jit(eta, h0, block_b=block_b, block_g=block_g,
                                interpret=interpret)


# ---------------------------------------------------------------------------
# Stratified variant: per-request baseline row, gathered via scalar prefetch
# ---------------------------------------------------------------------------

def _curves_strat_kernel(strata_ref, eta_ref, h0_ref, o_ref):
    del strata_ref  # consumed by the index maps, not the body
    eta = jnp.clip(eta_ref[...].astype(jnp.float32), -30.0, 30.0)  # (1, 1)
    h0 = h0_ref[...].astype(jnp.float32)                           # (1, bg)
    o_ref[...] = jnp.exp(-h0 * jnp.exp(eta)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_g", "interpret"))
def _survival_curves_strat_jit(eta: jax.Array, h0: jax.Array,
                               strata: jax.Array, block_g: int,
                               interpret: bool) -> jax.Array:
    b, g = eta.shape[0], h0.shape[1]
    gb = pl.cdiv(g, block_g)
    pad_g = gb * block_g - g
    h0p = jnp.pad(h0, ((0, 0), (0, pad_g))) if pad_g else h0

    # rows live on a leading squeezed axis, so every block's last two
    # dims are (1, 1) or (1, block_g) of a (1, .) trailing panel: whole
    # array dims or 128-lane multiples, as the TPU tiling requires
    out = pl.pallas_call(
        _curves_strat_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, gb),
            in_specs=[
                pl.BlockSpec((None, 1, 1), lambda i, j, s: (i, 0, 0)),
                # the prefetched strata vector drives which baseline row
                # is DMA'd for grid step i — the gather never hits VMEM
                # as a full (b, g) materialized panel
                pl.BlockSpec((None, 1, block_g),
                             lambda i, j, s: (s[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((None, 1, block_g),
                                   lambda i, j, s: (i, 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, gb * block_g), jnp.float32),
        interpret=interpret,
    )(strata.astype(jnp.int32), eta.reshape(b, 1, 1), h0p[:, None, :])
    return out[:, 0, :g]


def survival_curves_stratified(eta: jax.Array, h0: jax.Array,
                               strata: jax.Array, block_g: int = 128,
                               interpret: bool | None = None) -> jax.Array:
    """(b, g) curves with a per-request baseline: S = exp(-H0[strata[i]] *
    exp(eta[i])).

    eta: (b,) linear predictors; h0: (s, g) per-stratum cumulative baseline
    hazards; strata: (b,) int row indices into h0. The row gather folds
    into the kernel's index map via scalar prefetch (the ROADMAP
    carry-over): strata rides ahead of the grid in SMEM and selects the
    h0 block DMA per request, so no (b, g) gathered copy of the baselines
    is ever materialized. Grid is (b, g_blocks) — one request per row
    step, eta clipped to +/-30 as in the unstratified kernel.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _survival_curves_strat_jit(eta, h0, strata, block_g=block_g,
                                      interpret=interpret)
