"""Mamba2 SSD (state-space duality) block — chunked dual form for
training/prefill, O(1)-state recurrent step for decode.

Recurrence per head (Mamba2, arXiv:2405.21060):
    h_t = exp(dt_t A) h_{t-1} + dt_t * x_t B_t^T        h: (hd, N)
    y_t = C_t h_t + D x_t
Chunked (SSD) evaluation over chunks of length Q:
    intra-chunk: masked (Q x Q) quadratic form on the MXU
    inter-chunk: per-chunk states passed through a lax.scan
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..kernels import ops as kops

Array = jax.Array


def init_mamba2(rng, d_model: int, d_state: int, head_dim: int = 64,
                expand: int = 2, conv_width: int = 4, dtype=jnp.bfloat16,
                n_groups: int = 1, n_heads: int = 0):
    """``n_heads`` > 0 sets d_inner = n_heads * head_dim (Nemotron-H);
    otherwise d_inner = expand * d_model."""
    d_inner = n_heads * head_dim if n_heads else expand * d_model
    n_heads = d_inner // head_dim
    k = jax.random.split(rng, 5)
    s = d_model ** -0.5
    d_conv = d_inner + 2 * n_groups * d_state
    return {
        # projects to [z (d_inner), x (d_inner), B (g*N), C (g*N), dt (H)]
        "w_in": jax.random.normal(
            k[0], (d_model, 2 * d_inner + 2 * n_groups * d_state + n_heads),
            dtype) * s,
        "conv_w": jax.random.normal(k[1], (conv_width, d_conv), dtype) * 0.2,
        "conv_b": jnp.zeros((d_conv,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads)).astype(jnp.float32),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "d_skip": jnp.ones((n_heads,), jnp.float32),
        "norm_scale": jnp.ones((d_inner,), dtype),
        "w_out": jax.random.normal(k[2], (d_inner, d_model), dtype)
        * (d_inner ** -0.5),
    }


class SSMState(NamedTuple):
    conv: Array   # (B, conv_width-1, d_conv) rolling conv inputs
    ssm: Array    # (B, H, hd, N) recurrent state


def _split(params, head_dim: int):
    """(d_inner, n_heads), read from the weights' shapes."""
    d_inner = params["w_out"].shape[0]
    return d_inner, d_inner // head_dim


def _gated_norm(y: Array, z: Array, scale: Array, n_groups: int,
                eps: float) -> Array:
    """Mamba-2's gated RMSNorm: RMSNorm(y * silu(z)) over each of
    ``n_groups`` equal groups of channels."""
    g = y * jax.nn.silu(z)
    g32 = g.astype(jnp.float32)
    if n_groups > 1:
        g32 = g32.reshape(*g32.shape[:-1], n_groups, -1)
    var = jnp.mean(g32 * g32, axis=-1, keepdims=True)
    g32 = g32 * jax.lax.rsqrt(var + eps)
    if n_groups > 1:
        g32 = g32.reshape(*y.shape)
    return g32.astype(y.dtype) * scale


def _causal_conv(xbc: Array, w: Array, b: Array) -> Array:
    """Depthwise causal conv over (B, S, C) with kernel (W, C)."""
    wdt = xbc.dtype
    width = w.shape[0]
    xp = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + xbc.shape[1], :] * w[i] for i in range(width))
    return jax.nn.silu(out + b).astype(wdt)


def mamba2_forward(params, x: Array, *, d_state: int, head_dim: int = 64,
                   chunk: int = 256, n_groups: int = 1,
                   norm_eps: float = 1e-6, return_state: bool = False):
    """x: (B, S, D) -> (y: (B, S, D)[, final SSMState]).

    Heads are split evenly over ``n_groups`` groups of B and C, and the
    gated RMSNorm (epsilon ``norm_eps``) normalizes each group of
    channels on its own. The device work is named by part:
    ``ssm.in_proj``, ``ssm.conv``, ``ssm.ssd`` (discretization, the
    chunked scan and the skip), ``ssm.gated_norm`` and ``ssm.out_proj``."""
    b, s, d_model = x.shape
    d_inner, n_heads = _split(params, head_dim)
    with jax.named_scope("ssm.in_proj"):
        proj = x @ params["w_in"]
        z, xbc, dt = jnp.split(
            proj, [d_inner, 2 * d_inner + 2 * n_groups * d_state], axis=-1)
    with jax.named_scope("ssm.conv"):
        xbc = _causal_conv(xbc, params["conv_w"], params["conv_b"])
        xs, bb, cc = jnp.split(xbc, [d_inner, d_inner + n_groups * d_state],
                               axis=-1)
    with jax.named_scope("ssm.ssd"):
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + params["dt_bias"])                     # (B,S,H)
        a = -jnp.exp(params["a_log"])                                 # (H,)

        xh = xs.reshape(b, s, n_heads, head_dim)
        bb = bb.reshape(b, s, n_groups, d_state)
        cc = cc.reshape(b, s, n_groups, d_state)

        y, st = _ssd(xh, dt, a, bb, cc, chunk)
        y = y + params["d_skip"][None, None, :, None] \
            * xh.astype(jnp.float32)
        y = y.reshape(b, s, d_inner).astype(x.dtype)
    with jax.named_scope("ssm.gated_norm"):
        g = _gated_norm(y, z, params["norm_scale"], n_groups, norm_eps)
    with jax.named_scope("ssm.out_proj"):
        out = g @ params["w_out"]
    if return_state:
        with jax.named_scope("ssm.conv"):
            conv_tail = jnp.pad(
                (x @ params["w_in"])[:, :, d_inner:2 * d_inner
                                     + 2 * n_groups * d_state],
                ((0, 0), (max(0, 3 - s), 0), (0, 0)))[:, -3:, :]
        return out, SSMState(conv=conv_tail, ssm=st)
    return out


def _ssd(xh, dt, a, bb, cc, chunk):
    """The chunked SSD, lowered per platform where the kernel takes the
    shapes (``kernels.ops.ssd_path``): on TPU one Pallas kernel forward
    and one backward (``kernels/ssd.py``), elsewhere ``_ssd_chunked``.
    Each lowering is counted under ``ssd_path_total``."""
    _, _, h, hd = xh.shape
    g, n = bb.shape[2], bb.shape[3]
    if kops.ssd_path(h, hd, g, n, chunk) != "kernel":
        return _ssd_chunked(xh, dt, a, bb, cc, chunk)

    def kernel(xh, dt, a, bb, cc):
        dt = kops.ssd_lowered(dt, "kernel", "fits")
        return kops.ssd(xh, dt, a, bb, cc, chunk, interpret=False)

    def jnp_body(xh, dt, a, bb, cc):
        dt = kops.ssd_lowered(dt, "jnp", "backend")
        return _ssd_chunked(xh, dt, a, bb, cc, chunk)

    return jax.lax.platform_dependent(xh, dt, a, bb, cc, tpu=kernel,
                                      default=jnp_body)


def _ssd_chunked(xh, dt, a, bb, cc, chunk):
    """Chunked SSD. xh: (B,S,H,hd); dt: (B,S,H); a: (H,);
    bb/cc: (B,S,G,N), head h reading group h // (H/G).
    Returns (y (B,S,H,hd) f32, state (B,H,hd,N))."""
    if bb.shape[2] > 1:
        return _ssd_chunked_grouped(xh, dt, a, bb, cc, chunk)
    b, s, h, hd = xh.shape
    n = bb.shape[-1]
    q = chunk
    nc = -(-s // q)
    pad = nc * q - s
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bb = jnp.pad(bb, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cc = jnp.pad(cc, ((0, 0), (0, pad), (0, 0), (0, 0)))
    xc = xh.reshape(b, nc, q, h, hd).astype(jnp.float32)
    dtc = dt.reshape(b, nc, q, h)
    bc = bb.reshape(b, nc, q, n).astype(jnp.float32)   # G=1 squeezed
    ccx = cc.reshape(b, nc, q, n).astype(jnp.float32)

    la = dtc * a  # (B,nc,q,H) log decay per step
    cum = jnp.cumsum(la, axis=2)  # L_t
    total = cum[:, :, -1:, :]     # L_Q

    # intra-chunk: y[t] = sum_{s<=t} C_t.B_s exp(L_t - L_s) dt_s x_s
    idx = jnp.arange(q)
    causal = idx[:, None] >= idx[None, :]
    # decay(t,s) = exp(L_t - L_s) for s <= t
    dec = jnp.exp(jnp.clip(cum[:, :, :, None, :] - cum[:, :, None, :, :],
                           -60.0, 0.0))              # (B,nc,q,q,H)
    cb = jnp.einsum("bcqn,bcsn->bcqs", ccx, bc)      # (B,nc,q,q)
    w_ = cb[..., None] * dec * dtc[:, :, None, :, :] \
        * causal[None, None, :, :, None]
    y_intra = jnp.einsum("bcqsh,bcshd->bcqhd", w_, xc)

    # chunk-level input state: sum_s exp(L_Q - L_s) dt_s x_s B_s^T
    decq = jnp.exp(jnp.clip(total - cum, -60.0, 0.0))  # (B,nc,q,H)
    sin = jnp.einsum("bcqh,bcqhd,bcqn->bchdn", decq * dtc, xc, bc)

    # scan chunk states: st_c = exp(L_Q_c) st_{c-1} + sin_c
    chunk_decay = jnp.exp(jnp.clip(total[:, :, 0, :], -60.0, None))  # (B,nc,H)

    def scan_fn(carry, inp):
        sin_c, dec_c = inp
        new = carry * dec_c[..., None, None] + sin_c
        return new, carry  # emit the INCOMING state for chunk c

    st0 = jnp.zeros((b, h, hd, n), jnp.float32)
    stf, st_in = jax.lax.scan(
        scan_fn, st0,
        (jnp.moveaxis(sin, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    st_in = jnp.moveaxis(st_in, 0, 1)  # (B,nc,H,hd,N)

    # inter-chunk: y[t] += C_t (exp(L_t) st_in)
    y_inter = jnp.einsum("bcqn,bcqh,bchdn->bcqhd",
                         ccx, jnp.exp(jnp.clip(cum, -60.0, 0.0)), st_in)
    y = (y_intra + y_inter).reshape(b, nc * q, h, hd)[:, :s]
    return y, stf


def _ssd_chunked_grouped(xh, dt, a, bb, cc, chunk):
    """``_ssd_chunked`` for G > 1 groups of B and C: heads are split as
    (G, H/G), so each group's C.B product is formed once for its heads."""
    b, s, h, hd = xh.shape
    g, n = bb.shape[2], bb.shape[3]
    j = h // g
    q = chunk
    nc = -(-s // q)
    pad = nc * q - s
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bb = jnp.pad(bb, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cc = jnp.pad(cc, ((0, 0), (0, pad), (0, 0), (0, 0)))
    xc = xh.reshape(b, nc, q, g, j, hd).astype(jnp.float32)
    dtc = dt.reshape(b, nc, q, g, j)
    bc = bb.reshape(b, nc, q, g, n).astype(jnp.float32)
    ccx = cc.reshape(b, nc, q, g, n).astype(jnp.float32)

    cum = jnp.cumsum(dtc * a.reshape(g, j), axis=2)      # (B,nc,q,G,J)
    total = cum[:, :, -1:]

    idx = jnp.arange(q)
    causal = idx[:, None] >= idx[None, :]
    dec = jnp.exp(jnp.clip(cum[:, :, :, None] - cum[:, :, None],
                           -60.0, 0.0))              # (B,nc,q,q,G,J)
    cb = jnp.einsum("bcqgn,bcsgn->bcqsg", ccx, bc)   # (B,nc,q,q,G)
    w_ = cb[..., None] * dec * dtc[:, :, None] \
        * causal[None, None, :, :, None, None]
    y_intra = jnp.einsum("bcqsgj,bcsgjd->bcqgjd", w_, xc)

    decq = jnp.exp(jnp.clip(total - cum, -60.0, 0.0))  # (B,nc,q,G,J)
    sin = jnp.einsum("bcqgj,bcqgjd,bcqgn->bcgjdn", decq * dtc, xc, bc)
    chunk_decay = jnp.exp(jnp.clip(total[:, :, 0], -60.0, None))

    def scan_fn(carry, inp):
        sin_c, dec_c = inp
        return carry * dec_c[..., None, None] + sin_c, carry

    st0 = jnp.zeros((b, g, j, hd, n), jnp.float32)
    stf, st_in = jax.lax.scan(
        scan_fn, st0,
        (jnp.moveaxis(sin, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    st_in = jnp.moveaxis(st_in, 0, 1)                # (B,nc,G,J,hd,N)
    y_inter = jnp.einsum("bcqgn,bcqgj,bcgjdn->bcqgjd",
                         ccx, jnp.exp(jnp.clip(cum, -60.0, 0.0)), st_in)
    y = (y_intra + y_inter).reshape(b, nc * q, h, hd)[:, :s]
    return y, stf.reshape(b, h, hd, n)


def mamba2_decode_step(params, x: Array, state: SSMState, *, d_state: int,
                       head_dim: int = 64):
    """Single-token recurrent step (one group of B and C). x: (B, 1, D)."""
    b, _, d_model = x.shape
    d_inner, n_heads = _split(params, head_dim)
    n_groups = 1
    proj = x @ params["w_in"]
    z, xbc_new, dt = jnp.split(
        proj, [d_inner, 2 * d_inner + 2 * n_groups * d_state], axis=-1)
    # rolling conv window: state.conv holds previous (width-1) inputs
    win = jnp.concatenate([state.conv, xbc_new], axis=1)  # (B, W, C)
    w = params["conv_w"]
    out = (win * w[None, :, :]).sum(axis=1, keepdims=True)
    xbc = jax.nn.silu(out + params["conv_b"]).astype(x.dtype)
    new_conv = win[:, 1:, :]

    xs, bb, cc = jnp.split(xbc, [d_inner, d_inner + n_groups * d_state],
                           axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])[:, 0]
    a = -jnp.exp(params["a_log"])
    xhh = xs.reshape(b, n_heads, head_dim).astype(jnp.float32)
    bvec = bb.reshape(b, d_state).astype(jnp.float32)
    cvec = cc.reshape(b, d_state).astype(jnp.float32)

    dec = jnp.exp(dt * a)  # (B,H)
    upd = jnp.einsum("bh,bhd,bn->bhdn", dt, xhh, bvec)
    new_ssm = state.ssm * dec[..., None, None] + upd
    y = jnp.einsum("bhdn,bn->bhd", new_ssm, cvec) \
        + params["d_skip"][None, :, None] * xhh
    y = y.reshape(b, 1, d_inner).astype(x.dtype)
    g = _gated_norm(y, z, params["norm_scale"], n_groups, 1e-6)
    return g @ params["w_out"], SSMState(conv=new_conv, ssm=new_ssm)
