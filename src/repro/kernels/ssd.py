"""Pallas TPU kernels: the chunked SSD of the Mamba-2 block, forward and
backward, under one ``jax.custom_vjp``.

For head h of group g, in a chunk of Q positions, with la_s = dt_s A_h
and cum_t = sum_{s <= t} la_s within the chunk:

    y_t   = sum_{s <= t} (C_t . B_s) exp(cum_t - cum_s) dt_s x_s   (intra)
          + exp(cum_t) C_t S_in                                  (inter)
    S_out = exp(cum_Q) S_in + sum_s exp(cum_Q - cum_s) dt_s x_s B_s^T

each exponent clipped to [-60, 0] (the chunk's decay exp(cum_Q) only
below): ``models/ssm._ssd_chunked``'s semantics, which stays the jnp body
off TPU and the reference of the tests.

Grid (batch, group, chunk), the chunk innermost and sequential. A grid
step holds one chunk of one group's J heads: x as (Q, J*hd) straight
from the (B, S, H*hd) activations, dt as (Q, J), and the group's B and C
as (Q, N). The state S of the group's heads is carried from chunk to
chunk in VMEM scratch, transposed to (N, J*hd) so that heads lie along
lanes as they do in x and y. In VMEM only, and in float32, the step forms
the chunk's cumulative log decay (an MXU product with a ones matrix at
``HIGHEST``), C.B once for the group, and per head the masked (Q, Q)
decay tile. The products run on the MXU with bfloat16 operands and
float32 accumulation, as the jnp body's einsums run at the chip's
default precision; sums, exponentials, clips and the state carry stay
float32.

Heads are packed 128 // hd to a 128-lane slab, and a ``fori_loop`` walks
a group's slabs, so the kernel's code does not grow with J. A head's
products use the whole slab with the other heads' lanes zeroed, so every
operand stays lane-aligned; what is elementwise per head (its decays of
S, exp(cum_t), the cotangents of those) runs on the whole slab at once,
each lane holding its head's value, and goes back to heads through a
one-hot product at ``HIGHEST``. The (Q, Q) tiles are formed by row blocks
of 128 up to the diagonal, so a chunk of 256 skips the quarter above it.

The backward kernel walks the chunks in reverse, carrying the state's
cotangent in VMEM. It recomputes the decay and C.B tiles from the
inputs, reads each chunk's incoming state (the forward kernel's one
residual besides the inputs, (B, nc, N, H*hd) float32), and returns the
cotangents of x, dt, A, B and C. A clip passes its gradient strictly
inside its bounds; the one exact tie, a position's difference with
itself, cancels either way. Nothing (Q, Q)-shaped leaves VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
CLIP = -60.0
HIGHEST = jax.lax.Precision.HIGHEST
# VMEM a grid step may plan for (a v5e core has 128 MiB)
VMEM_BUDGET = 96 * 1024 * 1024


def tiles(h: int, hd: int, g: int, n: int, q: int) -> bool:
    """Whether the kernel takes these shapes: heads packed whole into
    128-lane slabs, each group's heads filling whole slabs, the state
    size a multiple of the lane width, Q a multiple of 8, and a grid
    step's blocks within the VMEM budget."""
    if h % g or LANES % hd or (h // g * hd) % LANES or n % LANES or q % 8:
        return False
    return _vmem_bytes(h // g * hd, n, q, h // g) <= VMEM_BUDGET


def _vmem_bytes(width: int, n: int, q: int, j: int) -> int:
    """A backward grid step's VMEM, the larger of the two kernels: its
    float32 blocks twice (pipelining), its scratch, and some 16 (Q, Q)
    or (Q, 128) float32 temporaries of a slab."""
    blocks = 3 * q * width + 2 * n * width + 4 * q * n + 2 * q * j
    scratch = n * width + 6 * j * q + q * q + 2 * q * n
    return 4 * (2 * blocks + scratch + 16 * q * max(q, LANES))


def _dot(a, b, ta=False, tb=False, precision=None):
    """a @ b in float32, either operand transposed."""
    dims = (((0 if ta else 1,), (1 if tb else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _rounder(interpret: bool):
    """How matmul operands are rounded: to bfloat16, held as float32
    values in interpret mode (the CPU's dots take no bfloat16 x bfloat16
    -> float32 inside a loop)."""
    if interpret:
        return lambda v: v.astype(jnp.bfloat16).astype(jnp.float32)
    return lambda v: v.astype(jnp.bfloat16)


def _lower(q: int):
    """[t >= s] as float32 (Q, Q): (L @ v)[t] = sum_{s <= t} v[s]."""
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return (row >= col).astype(jnp.float32)


def _inside(v):
    """Where a value clipped to [CLIP, 0] passes its gradient."""
    return (v > CLIP) & (v < 0.0)


class _Chunk:
    """What a grid step derives from one chunk's dt, A, B and C. The
    per-head rows of cum and dt go to ``rows_sc`` (2, J, Q), so that a
    loop over slabs can read them by head."""

    def __init__(self, a_ref, dt_ref, b_ref, c_ref, rows_sc, hd, rnd):
        self.hd = hd
        self.dt = dt_ref[0, 0]                                   # (Q, J)
        self.q, self.j = self.dt.shape
        low = _lower(self.q)
        self.low = low
        self.causal = low > 0.0
        self.cum = _dot(low, self.dt * a_ref[0], precision=HIGHEST)
        rows_sc[0] = self.cum.T
        rows_sc[1] = self.dt.T
        self.rows_sc = rows_sc
        bm = b_ref[0].astype(jnp.float32)                        # (Q, N)
        cm = c_ref[0].astype(jnp.float32)
        self.b, self.c = rnd(bm), rnd(cm)
        self.bt, self.ct = bm.T, cm.T                            # (N, Q)
        self.cbm = jnp.where(self.causal, _dot(self.c, self.b, tb=True),
                             0.0)                                # (Q, Q)

    def slab(self, k):
        """Slab k's lanes: the lane slice, each lane's head cum and dt
        (Q, 128), and the one-hot (J, 128) of each head's first lane."""
        per = LANES // self.hd
        head = jax.lax.broadcasted_iota(jnp.int32, (self.j, LANES), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (self.j, LANES), 1)
        own = head == k * per + lane // self.hd
        first = (own & (lane % self.hd == 0)).astype(jnp.float32)
        own = own.astype(jnp.float32)
        return (pl.ds(pl.multiple_of(k * LANES, LANES), LANES),
                _dot(self.cum, own, precision=HIGHEST),
                _dot(self.dt, own, precision=HIGHEST), first)

    def heads(self, k):
        """Slab k's heads: (head in group, lane mask (1, 128), first lane,
        cum row (1, Q), dt row (1, Q))."""
        per = LANES // self.hd
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) // self.hd
        for i in range(per):
            h = k * per + i
            yield (h, lane == i, i * self.hd,
                   self.rows_sc[0, pl.ds(h, 1), :],
                   self.rows_sc[1, pl.ds(h, 1), :])

    @staticmethod
    def decay(cc, cr):
        """A head's (Q, Q) exponent and decay exp(clip(cum_t - cum_s))."""
        seg = cc - cr
        return seg, jnp.exp(jnp.clip(seg, CLIP, 0.0))


def _row_blocks(q: int):
    """The lower triangle of a (Q, Q) tile by row blocks of 128: (rows,
    columns up to and including the block's diagonal)."""
    rb = LANES if q % LANES == 0 else q
    return [(slice(r, r + rb), r + rb) for r in range(0, q, rb)]


def _pad_lanes(v, q: int):
    """A (1, c) row as (1, Q), zero past c."""
    c = v.shape[1]
    if c == q:
        return v
    return jnp.concatenate([v, jnp.zeros((1, q - c), v.dtype)], axis=1)


def _per_head(m_sums, shape):
    """Lanes of a slab, each holding its head's value: ``m_sums`` is
    [(lane mask, value)] over the slab's heads."""
    out = jnp.zeros(shape, jnp.float32)
    for m, v in m_sums:
        out = jnp.where(m, v, out)
    return out


def _fwd_kernel(hd: int, emit: bool, rnd):
    def kernel(a_ref, dt_ref, x_ref, b_ref, c_ref, y_ref, sf_ref, *rest):
        sc_ref = rest[0] if emit else None
        st_sc, rows_sc = rest[-2:]
        ci = pl.program_id(2)

        @pl.when(ci == 0)
        def _start():
            st_sc[...] = jnp.zeros_like(st_sc)

        if emit:
            sc_ref[0, 0] = st_sc[...]
        ch = _Chunk(a_ref, dt_ref, b_ref, c_ref, rows_sc, hd, rnd)
        q = ch.q

        def slab(k, carry):
            sl, cum, _, _ = ch.slab(k)
            xs = x_ref[0, :, sl]
            st = st_sc[:, sl]
            cq = cum[q - 1:q, :]
            y = _dot(ch.c, rnd(st)) * jnp.exp(jnp.clip(cum, CLIP, 0.0))
            st_out = st * jnp.exp(jnp.maximum(cq, CLIP))
            for _, m, f, cr, dr in ch.heads(k):
                xm = rnd(jnp.where(m, xs, 0.0))
                parts = []
                for rs, cs in _row_blocks(q):
                    _, dec = ch.decay(cum[rs, f:f + 1], cr[:, :cs])
                    parts.append(_dot(rnd(ch.cbm[rs, :cs] * dec * dr[:, :cs]),
                                      xm[:cs]))
                y = y + jnp.concatenate(parts, axis=0)
                u = jnp.exp(jnp.clip(cq[:, f:f + 1] - cr, CLIP, 0.0)) * dr
                st_out = st_out + _dot(rnd(ch.bt * u), xm)
            y_ref[0, :, sl] = y
            st_sc[:, sl] = st_out
            return carry

        jax.lax.fori_loop(0, st_sc.shape[1] // LANES, slab, 0)

        @pl.when(ci == pl.num_programs(2) - 1)
        def _final():
            sf_ref[0] = st_sc[...]

    return kernel


def _bwd_kernel(hd: int, rnd):
    def kernel(a_ref, dt_ref, x_ref, b_ref, c_ref, sc_ref, dy_ref, dsf_ref,
               dx_ref, db_ref, dc_ref, ddt_ref, da_ref,
               dst_sc, rows_sc, drow_sc, dcol_sc, dcb_sc, dbc_sc, dx_sc,
               col_sc):
        ci = pl.program_id(2)

        @pl.when(ci == 0)
        def _start():
            dst_sc[...] = dsf_ref[0]
            da_ref[...] = jnp.zeros_like(da_ref)

        ch = _Chunk(a_ref, dt_ref, b_ref, c_ref, rows_sc, hd, rnd)
        q = ch.q
        for ref in (drow_sc, dcol_sc, dcb_sc, dbc_sc):
            ref[...] = jnp.zeros_like(ref)
        at_last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1

        def slab(k, carry):
            sl, cum, dtl, first = ch.slab(k)
            xs = x_ref[0, :, sl]
            dys = dy_ref[0, :, sl]
            st = sc_ref[0, 0, :, sl]              # the chunk's incoming S
            dst = dst_sc[:, sl]                   # d S_out
            cq = cum[q - 1:q, :]
            ecum = jnp.exp(jnp.clip(cum, CLIP, 0.0))
            cdec = jnp.exp(jnp.maximum(cq, CLIP))
            decq = jnp.exp(jnp.clip(cq - cum, CLIP, 0.0))
            u = decq * dtl                        # S_out = cdec S + B^T(u x)
            z = _dot(ch.c, rnd(st))               # C S_in, before exp(cum)
            v = _dot(ch.b, rnd(dst))              # d (u x)
            heads = list(ch.heads(k))
            # the inter-chunk term, the decays of S_in and of u, by lanes
            de = _per_head([(m, jnp.sum(jnp.where(m, dys * z, 0.0), axis=1,
                                        keepdims=True))
                            for _, m, _, _, _ in heads], xs.shape)
            du = _per_head([(m, jnp.sum(jnp.where(m, v * xs, 0.0), axis=1,
                                        keepdims=True))
                            for _, m, _, _, _ in heads], xs.shape)
            dcd = _per_head([(m, jnp.sum(jnp.where(m, dst * st, 0.0),
                                         keepdims=True))
                             for _, m, _, _, _ in heads], cq.shape)
            dsq = du * dtl * decq * _inside(cq - cum)
            dcq = dcd * cdec * (cq > CLIP) \
                + jnp.sum(dsq, axis=0, keepdims=True)
            col = de * ecum * _inside(cum) - dsq + jnp.where(at_last, dcq,
                                                             0.0)
            dx_sc[...] = v * u
            col_sc[...] = col
            # the intra-chunk term, head by head, by row blocks; dy masked
            # to the head's lanes keeps both products to the head
            xs_r = rnd(xs)
            for h, m, f, cr, dr in heads:
                dym = rnd(jnp.where(m, dys, 0.0))
                dseg_c = jnp.zeros((1, q), jnp.float32)
                gw_c = jnp.zeros((1, q), jnp.float32)
                for rs, cs in _row_blocks(q):
                    seg, dec = ch.decay(cum[rs, f:f + 1], cr[:, :cs])
                    cb, drs = ch.cbm[rs, :cs], dr[:, :cs]
                    dx_sc[:cs] += _dot(rnd(cb * dec * drs), dym[rs], ta=True)
                    t = _dot(dym[rs], xs_r[:cs], tb=True) * dec  # dW dec
                    gw = t * cb
                    dcb_sc[rs, :cs] += t * drs
                    dseg = jnp.where(_inside(seg), gw * drs, 0.0)
                    col_sc[rs] += jnp.where(m, jnp.sum(dseg, axis=1,
                                                       keepdims=True), 0.0)
                    dseg_c = dseg_c + _pad_lanes(
                        jnp.sum(dseg, axis=0, keepdims=True), q)
                    gw_c = gw_c + _pad_lanes(
                        jnp.sum(gw, axis=0, keepdims=True), q)
                drow_sc[0, pl.ds(h, 1), :] -= dseg_c
                drow_sc[1, pl.ds(h, 1), :] += gw_c
            dx, col = dx_sc[...], col_sc[...]
            dye = rnd(dys * ecum)
            dbc_sc[0] += _dot(rnd(u * xs), rnd(dst), tb=True)
            dbc_sc[1] += _dot(dye, rnd(st), tb=True)
            dst_sc[:, sl] = dst * cdec + _dot(rnd(ch.ct), dye)
            dx_ref[0, :, sl] = dx.astype(dx_ref.dtype)
            # back from lanes to heads: each head's first lane
            dcol_sc[0] += _dot(col, first, tb=True, precision=HIGHEST)
            dcol_sc[1] += _dot(du * decq, first, tb=True, precision=HIGHEST)
            return carry

        jax.lax.fori_loop(0, dst_sc.shape[1] // LANES, slab, 0)
        dcbm = rnd(jnp.where(ch.causal, dcb_sc[...], 0.0))
        dc_ref[0] = (dbc_sc[1] + _dot(dcbm, ch.b)).astype(dc_ref.dtype)
        db_ref[0] = (dbc_sc[0] + _dot(dcbm, ch.c, ta=True)).astype(
            db_ref.dtype)
        dcum = dcol_sc[0] + drow_sc[0].T
        # d la_s = sum_{t >= s} d cum_t
        dla = _dot(ch.low, dcum, ta=True, precision=HIGHEST)
        ddt_ref[0, 0] = dcol_sc[1] + drow_sc[1].T + dla * a_ref[0]
        da_ref[0, 0] += jnp.sum(dla * ch.dt, axis=0, keepdims=True)

    return kernel


def _layout(x, dt, a, b, c, q):
    """The kernels' layout: S padded to whole chunks, x as (B, S', H*hd),
    dt as (B, G, S', J), A as (G, 1, J), B and C as (B, S', G*N)."""
    bsz, s, h, hd = x.shape
    g, n = b.shape[2], b.shape[3]
    pad = -s % q
    if pad:
        x, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for v in (x, b, c))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    return (x.reshape(bsz, sp, h * hd),
            dt.astype(jnp.float32).reshape(bsz, sp, g, h // g)
            .transpose(0, 2, 1, 3),
            a.astype(jnp.float32).reshape(g, 1, h // g),
            b.reshape(bsz, sp, g * n), c.reshape(bsz, sp, g * n))


def _specs(x, dt, b, q, width, n, rev=False):
    """Block specs of the per-chunk arrays, the chunk axis walked forward
    or in reverse."""
    nc = x.shape[1] // q
    at = (lambda ci: nc - 1 - ci) if rev else (lambda ci: ci)
    j = dt.shape[-1]
    return dict(
        a=pl.BlockSpec((1, 1, j), lambda bi, gi, ci: (gi, 0, 0)),
        dt=pl.BlockSpec((1, 1, q, j), lambda bi, gi, ci: (bi, gi, at(ci), 0)),
        x=pl.BlockSpec((1, q, width), lambda bi, gi, ci: (bi, at(ci), gi)),
        bc=pl.BlockSpec((1, q, n), lambda bi, gi, ci: (bi, at(ci), gi)),
        st=pl.BlockSpec((1, n, width), lambda bi, gi, ci: (bi, 0, gi)),
        sc=pl.BlockSpec((1, 1, n, width),
                        lambda bi, gi, ci: (bi, at(ci), 0, gi)),
        da=pl.BlockSpec((1, 1, 1, j), lambda bi, gi, ci: (bi, gi, 0, 0)),
    )


def _params(width, n, q, j):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=min(max(2 * _vmem_bytes(width, n, q, j),
                                 32 * 1024 * 1024), VMEM_BUDGET + (8 << 20)))


def _forward(x, dt, a, b, c, q, hd, emit, interpret):
    bsz, sp, hw = x.shape
    g, _, j = a.shape
    n = b.shape[-1] // g
    width = j * hd
    sp_ = _specs(x, dt, b, q, width, n)
    f32 = jnp.float32
    out_shape = [jax.ShapeDtypeStruct((bsz, sp, hw), f32),
                 jax.ShapeDtypeStruct((bsz, n, hw), f32)]
    out_specs = [sp_["x"], sp_["st"]]
    if emit:
        out_shape.append(jax.ShapeDtypeStruct((bsz, sp // q, n, hw), f32))
        out_specs.append(sp_["sc"])
    return pl.pallas_call(
        _fwd_kernel(hd, emit, _rounder(interpret)),
        grid=(bsz, g, sp // q),
        in_specs=[sp_["a"], sp_["dt"], sp_["x"], sp_["bc"], sp_["bc"]],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, width), f32),
                        pltpu.VMEM((2, j, q), f32)],
        compiler_params=_params(width, n, q, j),
        interpret=interpret,
        name="ssd_fwd",
    )(a, dt, x, b, c)


def _backward(x, dt, a, b, c, sc, dy, dsf, q, hd, interpret):
    bsz, sp, hw = x.shape
    g, _, j = a.shape
    n = b.shape[-1] // g
    width = j * hd
    sp_ = _specs(x, dt, b, q, width, n, rev=True)
    f32 = jnp.float32
    return pl.pallas_call(
        _bwd_kernel(hd, _rounder(interpret)),
        grid=(bsz, g, sp // q),
        in_specs=[sp_["a"], sp_["dt"], sp_["x"], sp_["bc"], sp_["bc"],
                  sp_["sc"], sp_["x"], sp_["st"]],
        out_specs=[sp_["x"], sp_["bc"], sp_["bc"], sp_["dt"], sp_["da"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype),
                   jax.ShapeDtypeStruct(dt.shape, f32),
                   jax.ShapeDtypeStruct((bsz, g, 1, j), f32)],
        scratch_shapes=[pltpu.VMEM((n, width), f32),
                        pltpu.VMEM((2, j, q), f32),
                        pltpu.VMEM((2, j, q), f32),
                        pltpu.VMEM((2, q, j), f32),
                        pltpu.VMEM((q, q), f32),
                        pltpu.VMEM((2, q, n), f32),
                        pltpu.VMEM((q, LANES), f32),
                        pltpu.VMEM((q, LANES), f32)],
        compiler_params=_params(width, n, q, j),
        interpret=interpret,
        name="ssd_bwd",
    )(a, dt, x, b, c, sc, dy, dsf)


def _unstate(sf, h, hd):
    """(B, N, H*hd) -> (B, H, hd, N)."""
    bsz, n, _ = sf.shape
    return sf.reshape(bsz, n, h, hd).transpose(0, 2, 3, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dt, a, b, c, q, interpret):
    bsz, s, h, hd = x.shape
    y, sf = _forward(*_layout(x, dt, a, b, c, q), q=q, hd=hd, emit=False,
                     interpret=interpret)
    return y[:, :s].reshape(x.shape), _unstate(sf, h, hd)


def _ssd_fwd(x, dt, a, b, c, q, interpret):
    bsz, s, h, hd = x.shape
    args = _layout(x, dt, a, b, c, q)
    y, sf, sc = _forward(*args, q=q, hd=hd, emit=True, interpret=interpret)
    return (y[:, :s].reshape(x.shape), _unstate(sf, h, hd)), (args, sc)


def _ssd_bwd(q, interpret, res, cot):
    (xk, dtk, ak, bk, ck), sc = res
    dy, dsf = cot
    bsz, s, h, hd = dy.shape
    g = ak.shape[0]
    sp = xk.shape[1]
    dy = jnp.pad(dy.astype(jnp.float32),
                 ((0, 0), (0, sp - s), (0, 0), (0, 0))).reshape(xk.shape)
    dsf = dsf.astype(jnp.float32).transpose(0, 3, 1, 2).reshape(
        bsz, -1, h * hd)
    dx, db, dc, ddt, da = _backward(xk, dtk, ak, bk, ck, sc, dy, dsf, q=q,
                                    hd=hd, interpret=interpret)
    n = bk.shape[-1] // g
    ddt = ddt.transpose(0, 2, 1, 3).reshape(bsz, sp, h)[:, :s]
    return (dx[:, :s].reshape(bsz, s, h, hd), ddt,
            da.sum(axis=0).reshape(h),
            db[:, :s].reshape(bsz, s, g, n), dc[:, :s].reshape(bsz, s, g, n))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x, dt, a, b, c, chunk: int, interpret: bool | None = None):
    """The chunked SSD: x (B, S, H, hd), dt (B, S, H) float32, a (H,),
    b and c (B, S, G, N), head h reading group h // (H / G). Returns
    (y (B, S, H, hd) float32, final state (B, H, hd, N) float32), as
    ``models/ssm._ssd_chunked`` does, and is differentiable in x, dt, a,
    b and c. The shapes must pass ``tiles``. ``interpret=None`` resolves
    backend-aware: native on TPU, interpret mode elsewhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _ssd(x, dt, a, b, c, chunk, interpret)
