"""Pallas TPU kernel: one whole coordinate-descent sweep of the Cox fit.

A sweep visits the p coordinates in order; each visit is the
per-coordinate work of ``core/cox.coord_derivs`` (``order=2``) and the
surrogate update of ``core/solvers``:

    w    = exp(eta - max eta)
    S_r  = suffix_sum(w * x^r),  r = 0, 1, 2
    m1   = S_1 / S_0,  m2 = S_2 / S_0
    g    = sum_k E_k m1_k - sum_i delta_i x_i        (only where E_k > 0)
    h    = sum_k E_k (m2_k - m1_k^2)                 (only where E_k > 0)
    step = quad_l1_prox(...) or cubic_l1_prox(...)
    beta_l += step,  eta += step * x

``E`` is ``cox.risk_start_events`` (the events whose risk set starts at
each position), so tied data runs here too, and ``sum_i delta_i x_i``
comes in per column, formed once per solve. Each visit depends on the
last through ``eta``, so the sweep is a chain of small steps: as separate
XLA operations each step pays a launch and a loop iteration. Here the
whole chain runs in one kernel and nothing leaves VMEM between
coordinates.

Layout: every n-vector is ``(n_pad / 128, 128)`` f32 rows in time order,
n padded to whole (8, 128) tiles; padded positions have ``eta = -1e30``
(so ``w = 0``), ``x = 0`` and ``E = 0``. The grid walks blocks of ``bc``
columns of the transposed design, ``(bc, n_pad / 128, 128)`` each, so the
pipeline fetches the next block while this block's coordinates run; an
in-kernel ``fori_loop`` visits the block's columns. ``eta`` lives in a
VMEM scratch from the first grid step to the last. The per-column
scalars (``beta``, the Lipschitz constants, ``sum delta x``) sit in SMEM;
the kernel returns each coordinate's step and the caller adds it to
``beta``, which is the same float32 addition the jnp sweep makes.

Suffix sums, all in float32: two matmuls with triangular ones matrices
at ``Precision.HIGHEST`` (``_suffix_sums``), the moments in one operand
(two for the quadratic surrogate, which takes no h). On a v5e this ran a
solve of the paper's n = p = 1200 fit 1.8x faster than log-step
roll-and-add suffix sums on the vector unit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import surrogate

LANES = 128
L2, L3, DX = 0, 1, 2   # rows of the per-column constants
SUBLANES = 8
TILE = SUBLANES * LANES
ETA_PAD = -1e30
BLOCK_COLS = 8
# VMEM the kernel may plan for: the column block twice (pipelining) and
# some 32 n-vectors of state and temporaries must fit
VMEM_BUDGET = 12 * 1024 * 1024
_LIVE_VECTORS = 32


def n_padded(n: int) -> int:
    """n rounded up to whole (8, 128) f32 tiles."""
    return -(-n // TILE) * TILE


def fits(n: int) -> bool:
    """Whether a sweep over n samples fits the kernel's VMEM budget."""
    return (2 * BLOCK_COLS + _LIVE_VECTORS) * n_padded(n) * 4 <= VMEM_BUDGET


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


def _suffix_sums(*vs):
    """Inclusive suffix sums of ``(R, 128)`` f32 arrays in row-major time
    order: out[r, i] = the sum of v at and after (r, i), for each v.

    Two MXU matmuls at ``HIGHEST`` serve all the arrays: the later lanes
    of each row (a triangular ones matrix on the right of the arrays
    stacked by rows) and the rows strictly below (a strictly-upper ones
    matrix on the left of the arrays side by side, then summed over
    lanes)."""
    rows = vs[0].shape[0]
    lanes = _mm(jnp.concatenate(vs, axis=0), _lower_tri(LANES))
    below = _mm(_rows_below(rows), jnp.concatenate(vs, axis=1))
    return [lanes[k * rows:(k + 1) * rows]
            + jnp.sum(below[:, k * LANES:(k + 1) * LANES], axis=1,
                      keepdims=True)
            for k in range(len(vs))]


def _sum11(v):
    """Sum of a 2-D array as a (1, 1) array."""
    return jnp.sum(jnp.sum(v, axis=1, keepdims=True), axis=0, keepdims=True)


def _max11(v):
    return jnp.max(jnp.max(v, axis=1, keepdims=True), axis=0, keepdims=True)


def _make_kernel(block_cols: int, cubic: bool):
    def kernel(lam_ref, beta_ref, col_ref, eta_ref, ev_ref, x_ref,
               eta_out_ref, step_ref, eta_sc):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _load():
            eta_sc[...] = eta_ref[...]

        lam1 = lam_ref[0]
        lam2 = lam_ref[1]
        ev = ev_ref[...]
        starts = ev > 0.0
        col = jax.lax.broadcasted_iota(jnp.int32, (1, block_cols), 1)

        def visit(c, carry):
            eta, steps = carry
            x = x_ref[c]
            w = jnp.exp(eta - _max11(eta))
            wx = w * x
            # the quadratic surrogate takes no h: its third moment is
            # left out, as XLA leaves it out of the jnp sweep
            sums = _suffix_sums(w, wx, wx * x) if cubic else \
                _suffix_sums(w, wx)
            m1 = sums[1] / sums[0]
            g = _sum11(jnp.where(starts, ev * m1, 0.0)) - col_ref[0, DX, c]
            bl = beta_ref[0, 0, c]
            a = g + 2.0 * lam2 * bl
            if cubic:
                m2 = sums[2] / sums[0]
                h = _sum11(jnp.where(starts, ev * (m2 - m1 * m1), 0.0))
                step = surrogate.cubic_l1_prox(
                    a, h + 2.0 * lam2, jnp.full((1, 1), col_ref[0, L3, c]),
                    jnp.full((1, 1), bl), lam1)
            else:
                b = jnp.full((1, 1), col_ref[0, L2, c] + 2.0 * lam2)
                step = surrogate.quad_l1_prox(a, b, bl, lam1)
            steps = jnp.where(col == c, step, steps)
            return eta + step * x, steps

        eta, steps = jax.lax.fori_loop(
            0, block_cols, visit,
            (eta_sc[...], jnp.zeros((1, block_cols), jnp.float32)))
        eta_sc[...] = eta
        step_ref[0] = steps

        @pl.when(i == pl.num_programs(0) - 1)
        def _store():
            eta_out_ref[...] = eta

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("cubic", "interpret"))
def _sweep_jit(eta, beta, xt, ev, cols, lam, cubic, interpret):
    nblk, k, block_cols = cols.shape
    rows = xt.shape[1]
    vec = pl.BlockSpec((rows, LANES), lambda i: (0, 0))
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    eta_out, steps = pl.pallas_call(
        _make_kernel(block_cols, cubic),
        grid=(nblk,),
        in_specs=[smem(), smem((1, 1, block_cols), lambda i: (i, 0, 0)),
                  smem((1, k, block_cols), lambda i: (i, 0, 0)), vec, vec,
                  pl.BlockSpec((block_cols, rows, LANES),
                               lambda i: (i, 0, 0))],
        out_specs=[vec, pl.BlockSpec((1, 1, block_cols),
                                     lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((nblk, 1, block_cols),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lam, beta.reshape(nblk, 1, block_cols), cols, eta, ev, xt)
    return eta_out, steps.reshape(-1)


def prepare(x, ev, delta, l2c, l3c):
    """The sweep's beta-free inputs in the kernel's layout, formed once
    per solve: the transposed design ``(p_pad, n_pad / 128, 128)``, E as
    rows, and per column block the Lipschitz constants and
    ``sum_i delta_i x_i`` (``(p_pad / bc, 3, bc)``), zero past p."""
    n, p = x.shape
    x = x.astype(jnp.float32)
    npad = n_padded(n)
    ppad = -(-p // BLOCK_COLS) * BLOCK_COLS
    xt = jnp.pad(x.T, ((0, ppad - p), (0, npad - n)))
    dx = jnp.sum(delta.astype(jnp.float32)[:, None] * x, axis=0)
    cols = jnp.stack([l2c.astype(jnp.float32), l3c.astype(jnp.float32), dx])
    cols = jnp.pad(cols, ((0, 0), (0, ppad - p)))
    return dict(xt=xt.reshape(ppad, npad // LANES, LANES),
                ev=_rows(ev, 0.0),
                cols=cols.reshape(3, -1, BLOCK_COLS).transpose(1, 0, 2))


def _rows(v, fill: float):
    """An (n,) vector as ``(n_pad / 128, 128)`` f32 rows, padded with
    ``fill``."""
    n = v.shape[0]
    v = jnp.pad(v.astype(jnp.float32), (0, n_padded(n) - n),
                constant_values=fill)
    return v.reshape(-1, LANES)


def cd_sweep(eta, beta, prep, lam1, lam2, cubic: bool = False,
             interpret: bool | None = None):
    """One sweep over all p coordinates; returns the new (eta, beta).

    ``eta`` (n,) and ``beta`` (p,) are float32; ``prep`` is
    ``prepare(...)``'s output for the same data. ``interpret=None``
    resolves backend-aware: native on TPU, interpret mode elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, p = eta.shape[0], beta.shape[0]
    ppad = prep["xt"].shape[0]
    beta_p = jnp.pad(beta.astype(jnp.float32), (0, ppad - p))
    lam = jnp.stack([jnp.asarray(lam1, jnp.float32),
                     jnp.asarray(lam2, jnp.float32)])
    eta_r, steps = _sweep_jit(_rows(eta, ETA_PAD), beta_p, prep["xt"],
                              prep["ev"], prep["cols"], lam, cubic=cubic,
                              interpret=interpret)
    return eta_r.reshape(-1)[:n], beta + steps[:p]
