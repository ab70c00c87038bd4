"""Pallas TPU kernel: blocked reverse (suffix) cumulative sum along axis 0.

The paper's O(n) blessing is a suffix scan; on TPU we implement it as a
decoupled two-phase scan: the grid walks n-blocks right-to-left (sequential
grid ordering on TPU makes the carry legal), each block does its in-block
suffix sum on the MXU via an upper-triangular ones matmul, and a VMEM
scratch row carries the running total of everything to the right.

Input  (n, m)  ->  Output (n, m), out[i, :] = sum_{j >= i} x[j, :].

Block shape (block_n, m): the whole feature panel stays resident; VMEM use
is 2 * block_n * m * 4B + block_n^2 * 4B (the triangular matrix), so e.g.
block_n=512, m=256 is ~1.6 MB — comfortably inside the ~16 MB VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _suffix_tri(block_n: int, dtype=jnp.float32):
    """Upper-triangular (incl. diagonal) ones matrix: (U @ x)[i] = sum_{j>=i} x[j]."""
    row = jax.lax.broadcasted_iota(jnp.int32, (block_n, block_n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (block_n, block_n), 1)
    return (col >= row).astype(dtype)


def _revcumsum_kernel(x_ref, o_ref, carry_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    x = x_ref[...].astype(jnp.float32)  # (block_n, m)
    u = _suffix_tri(x.shape[0])
    suff = jax.lax.dot_general(
        u, x, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    o_ref[...] = (suff + carry_ref[...]).astype(o_ref.dtype)
    carry_ref[...] = carry_ref[...] + jnp.sum(x, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _revcumsum_jit(x: jax.Array, block_n: int, interpret: bool) -> jax.Array:
    n, m = x.shape
    nb = pl.cdiv(n, block_n)
    pad = nb * block_n - n
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x

    out = pl.pallas_call(
        _revcumsum_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block_n, m), lambda i: (nb - 1 - i, 0))],
        out_specs=pl.BlockSpec((block_n, m), lambda i: (nb - 1 - i, 0)),
        out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((1, m), jnp.float32)],
        interpret=interpret,
    )(xp)
    return out[:n]


def revcumsum(x: jax.Array, block_n: int = 512,
              interpret: bool | None = None) -> jax.Array:
    """Suffix cumulative sum along axis 0 of a 2-D array via Pallas.

    ``interpret=None`` (the default) resolves backend-aware: native on TPU,
    interpret mode elsewhere. Pass an explicit bool to override (tests).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _revcumsum_jit(x, block_n=block_n, interpret=interpret)
