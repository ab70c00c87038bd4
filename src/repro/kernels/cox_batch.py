"""Pallas TPU kernel: all-coordinate CPH gradient + diagonal Hessian.

The beyond-paper GEMV reframing (DESIGN.md §3): with
    A_k = sum_{i : t_i <= t_k} delta_i / S0_i,     r = w*A - delta,
the full gradient is  X^T r  and the diagonal Hessian is
    (X.^2)^T (w*A)  -  sum_i delta_i * (suffix(w x_l)_i / S0_i)^2.

The kernel tiles (n x p) into (block_n x block_p) VMEM panels on a
(p_blocks, n_blocks) grid with n innermost walked right-to-left, so the
suffix of w*X is carried in a (1, block_p) scratch row per feature panel.
Both reductions run on the MXU. Vectors (w, r, wa, delta, 1/s0) stream in
as (block_n, 1) columns. Tie-free fast path (ops.py precomputes s0/A with
Breslow gathers in jnp and falls back entirely when ties exist).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .revcumsum import _suffix_tri


def _kernel(x_ref, r_ref, wa_ref, w_ref, d_ref, inv_s0_ref,
            g_ref, h_ref, carry_ref):
    i = pl.program_id(1)  # n-block counter (innermost, reversed)

    @pl.when(i == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)
        g_ref[...] = jnp.zeros_like(g_ref)
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[...].astype(jnp.float32)        # (bn, bp)
    r = r_ref[...].astype(jnp.float32)        # (bn, 1)
    wa = wa_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    inv_s0 = inv_s0_ref[...].astype(jnp.float32)

    def colsum(vec, mat):  # (bn,1)^T @ (bn,bp) -> (1,bp) on the MXU
        return jax.lax.dot_general(
            vec, mat, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    g_ref[...] += colsum(r, x)
    h_ref[...] += colsum(wa, x * x)

    bn = x.shape[0]
    s1 = jax.lax.dot_general(
        _suffix_tri(bn), w * x, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32) + carry_ref[...]
    m = s1 * inv_s0                            # (bn, bp)
    h_ref[...] += -colsum(d, m * m)
    carry_ref[...] = carry_ref[...] + jnp.sum(w * x, axis=0, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_p", "interpret"))
def _cox_batch_jit(x: jax.Array, w: jax.Array, r: jax.Array, wa: jax.Array,
                   delta: jax.Array, inv_s0: jax.Array,
                   block_n: int, block_p: int, interpret: bool):
    n, p = x.shape
    nb = pl.cdiv(n, block_n)
    pb = pl.cdiv(p, block_p)
    pad_n = nb * block_n - n
    pad_p = pb * block_p - p
    xp = jnp.pad(x, ((0, pad_n), (0, pad_p))) if (pad_n or pad_p) else x

    def col(v):
        v = jnp.pad(v, (0, pad_n)) if pad_n else v
        return v.reshape(-1, 1)

    vec_spec = pl.BlockSpec((block_n, 1), lambda j, i: (nb - 1 - i, 0))
    out_spec = pl.BlockSpec((1, block_p), lambda j, i: (0, j))
    g, h = pl.pallas_call(
        _kernel,
        grid=(pb, nb),
        in_specs=[
            pl.BlockSpec((block_n, block_p), lambda j, i: (nb - 1 - i, j)),
            vec_spec, vec_spec, vec_spec, vec_spec, vec_spec,
        ],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((1, pb * block_p), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((1, block_p), jnp.float32)],
        interpret=interpret,
    )(xp, col(r), col(wa), col(w), col(delta), col(inv_s0))
    return g[0, :p], h[0, :p]


def cox_batch(x: jax.Array, w: jax.Array, r: jax.Array, wa: jax.Array,
              delta: jax.Array, inv_s0: jax.Array,
              block_n: int = 512, block_p: int = 256,
              interpret: bool | None = None):
    """(grad, hess_diag) for all p coordinates. Inputs time-sorted, no ties.

    x: (n, p); w, r, wa, delta, inv_s0: (n,) precomputed in ops.py.
    ``interpret=None`` resolves backend-aware: native on TPU, interpret
    mode elsewhere. Pass an explicit bool to override (tests).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _cox_batch_jit(x, w, r, wa, delta, inv_s0,
                          block_n=block_n, block_p=block_p,
                          interpret=interpret)
