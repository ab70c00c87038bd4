"""Pallas TPU kernel: fused per-coordinate CPH derivatives (Theorem 3.1).

One coordinate-descent touch needs, for a feature column x and current
linear predictor eta (both time-sorted ascending, *strictly increasing
times* — the tie-free fast path; ops.py falls back to the jnp reference
when ties exist):

    w    = exp(eta - eta_max)
    s_r  = suffix_sum(w * x^r),  r = 0..order+1
    g    = sum delta * (s1/s0 - x)
    h    = sum delta * (s2/s0 - (s1/s0)^2)
    c3   = sum delta * (s3/s0 + 2(s1/s0)^3 - 3(s2/s0)(s1/s0))

On CPU this is 6+ passes over n; here it is one HBM pass. The n-vectors
are laid out as rows of 128 lanes, ``(n / 128, 128)`` in row-major time
order, and the grid walks ``(block / 128, 128)`` row groups right-to-left,
so every block is a whole number of (8, 128) f32 tiles. Within a block the
suffix sum of each moment is two MXU matmuls: a lower-triangular ones
matrix sums the later lanes of each row, a strictly-upper one sums the
rows below. A (k, 128) VMEM scratch carries the totals of every later
block, one moment per row, replicated across the lanes. Outputs are
(1, 1) scalars accumulated across grid steps (legal: TPU grids execute
sequentially and output blocks map to the same tile every step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
TILE = 8 * LANES   # elements in one (8, 128) f32 tile: the block granule


def _lower_tri(bs: int, dtype=jnp.float32):
    """(P @ L)[., i] = sum_{j >= i} P[., j]  (suffix over the lane axis)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
    return (row >= col).astype(dtype)


def _rows_below(br: int, dtype=jnp.float32):
    """(U @ P)[r, .] = sum_{r' > r} P[r', .]  (rows strictly below)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (br, br), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (br, br), 1)
    return (col > row).astype(dtype)


def _mm(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _sum2(v):
    """Sum of a 2-D tile as a (1, 1) array."""
    return jnp.sum(jnp.sum(v, axis=1, keepdims=True), axis=0, keepdims=True)


def _make_kernel(order: int):
    k = order + 2  # moments 0..order+1

    def kernel(eta_max_ref, eta_ref, x_ref, d_ref, g_ref, h_ref, c3_ref,
               carry_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            carry_ref[...] = jnp.zeros_like(carry_ref)
            g_ref[...] = jnp.zeros_like(g_ref)
            h_ref[...] = jnp.zeros_like(h_ref)
            c3_ref[...] = jnp.zeros_like(c3_ref)

        e = eta_ref[...].astype(jnp.float32)   # (br, 128)
        x = x_ref[...].astype(jnp.float32)
        d = d_ref[...].astype(jnp.float32)
        w = jnp.exp(e - eta_max_ref[0, 0])
        lane_suffix = _lower_tri(LANES)
        below = _rows_below(e.shape[0])

        moments = [w]
        for _ in range(k - 1):
            moments.append(moments[-1] * x)
        suff = []
        for r, p in enumerate(moments):
            rows_after = jnp.sum(_mm(below, p), axis=1, keepdims=True)
            suff.append(_mm(p, lane_suffix) + (rows_after
                                               + carry_ref[r:r + 1, :]))
        # padded tail rows have w == 0 -> s0 == 0; clamp so the delta-masked
        # (d == 0) contributions stay finite instead of 0 * nan
        s0 = jnp.maximum(suff[0], 1e-30)
        m1 = suff[1] / s0
        m2 = suff[2] / s0
        g_ref[...] += _sum2(d * (m1 - x))
        h_ref[...] += _sum2(d * (m2 - m1 * m1))
        if order >= 3:
            m3 = suff[3] / s0
            c3_ref[...] += _sum2(d * (m3 + 2.0 * m1**3 - 3.0 * m2 * m1))
        for r, p in enumerate(moments):
            carry_ref[r:r + 1, :] += jnp.broadcast_to(_sum2(p), (1, LANES))

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("order", "block", "interpret"))
def _cox_coord_jit(eta: jax.Array, x: jax.Array, delta: jax.Array,
                   order: int, block: int, interpret: bool):
    n = eta.shape[0]
    block = -(-block // TILE) * TILE   # whole (8, 128) tiles per grid step
    nb = pl.cdiv(n, block)
    pad = nb * block - n
    br = block // LANES

    def prep(v, fill=0.0):
        v = jnp.pad(v, (0, pad), constant_values=fill) if pad else v
        return v.reshape(nb * br, LANES)

    # pad eta with -inf-ish so padded w == 0 (exp(-1e30 - max) underflows)
    eta_max = jnp.max(eta).reshape(1, 1).astype(jnp.float32)
    eta_p = prep(eta, fill=-1e30)
    x_p = prep(x)
    d_p = prep(delta)
    k = order + 2

    rows = pl.BlockSpec((br, LANES), lambda i: (nb - 1 - i, 0))
    scalar = jax.ShapeDtypeStruct((1, 1), jnp.float32)
    g, h, c3 = pl.pallas_call(
        _make_kernel(order),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            rows, rows, rows,
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[scalar, scalar, scalar],
        scratch_shapes=[pltpu.VMEM((k, LANES), jnp.float32)],
        interpret=interpret,
    )(eta_max, eta_p, x_p, d_p)
    return g[0, 0], h[0, 0], c3[0, 0]


def cox_coord(eta: jax.Array, x: jax.Array, delta: jax.Array,
              order: int = 2, block: int = 1024,
              interpret: bool | None = None):
    """Fused (g, h[, c3]) for one coordinate; n-length 1-D inputs, no ties.

    ``block`` is the number of samples per grid step, rounded up to a
    whole number of (8, 128) tiles (a multiple of 1024).
    ``interpret=None`` resolves backend-aware: native on TPU, interpret
    mode elsewhere. Pass an explicit bool to override (tests).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _cox_coord_jit(eta, x, delta, order=order, block=block,
                          interpret=interpret)
