"""Plain reference of a Mamba-2 stack under the exact Cox partial
likelihood, with its gradients and the AdamW update, in ``jax.numpy``.

Follows the published equations (Dao and Gu, "Transformers are SSMs",
arXiv:2405.21060, and the ``Mamba2`` block of ``state-spaces/mamba``):

    u      = RMSNorm(h)                                  (pre-norm)
    z, xBC, dt_raw = u W_in                              (in_proj)
    xBC    = SiLU(causal depthwise conv_W(xBC) + b)
    x, B, C = xBC                                        (ngroups = 1)
    dt     = softplus(dt_raw + dt_bias),  A = -exp(A_log)     (per head)
    s_t    = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T        (sequential)
    y_t    = s_t C_t + D x_t
    h     += RMSNorm(y * SiLU(z)) W_out                  (gated norm)

then the final RMSNorm, the mean over positions, a linear risk
eta = pooled w + b, and the Breslow negative log partial likelihood of
the batch over its number of events. Departures, each noted:

* the state recurrence runs position by position, never in chunks; its
  backward pass recomputes segments of ``segment`` positions and each
  layer is recomputed in the backward pass. That changes memory, not
  the arithmetic;
* the task head (pooled risk, Cox loss) is the deep-survival head, not
  part of the published model;
* the AdamW update (global-norm clipping, linear warm-up then cosine to a
  tenth, decoupled weight decay on every leaf) follows the training
  settings of the configuration file.

Parameters are the leaves the configuration names, in the layout
``drivers/train.py`` makes them. ``dtype`` float32 with
``precision="highest"`` is the reference; a lower ``dtype`` computes the
same equations with parameters, activations and matmul operands in that
type (the control).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _dot(a, b, precision):
    return jnp.matmul(a, b, precision=precision)


def rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return y.astype(x.dtype) * scale


def causal_conv(x, w, b):
    """Depthwise causal convolution: out_t = sum_i w_i x_{t-W+1+i} + b."""
    width, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(xp[:, i:i + s] * w[i] for i in range(width)) + b


def ssm(x, dt, a, bm, cm, d_skip, segment, precision):
    """Sequential selective-state recurrence.

    x (B, S, H, P), dt (B, S, H), a (H,), bm and cm (B, S, N).
    Returns y (B, S, H, P)."""
    b, s, h, p = x.shape
    n = bm.shape[-1]
    dtype = x.dtype

    def step(st, inp):
        x_t, dt_t, b_t, c_t = inp
        st = st * jnp.exp(dt_t * a)[:, :, None, None] \
            + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :]
        y = jnp.einsum("bhpn,bn->bhp", st, c_t, precision=precision)
        return st, y

    @jax.checkpoint
    def run_segment(st, seg):
        return jax.lax.scan(step, st, seg)

    seg = math.gcd(segment, s)
    tm = lambda v: jnp.moveaxis(v, 1, 0).reshape(s // seg, seg,  # noqa
                                                 *v.shape[:1], *v.shape[2:])
    st0 = jnp.zeros((b, h, p, n), dtype)
    _, ys = jax.lax.scan(run_segment, st0, (tm(x), tm(dt), tm(bm), tm(cm)))
    y = jnp.moveaxis(ys.reshape(s, b, h, p), 0, 1)
    return y + d_skip[None, None, :, None] * x


def layer(p, hid, cfg, precision):
    d = cfg["d_model"]
    e = cfg["expand"] * d
    n = cfg["d_state"] * cfg["ngroups"]
    hp = cfg["headdim"]
    nh = e // hp
    b, s, _ = hid.shape
    m = p["mamba"]
    u = rmsnorm(hid, p["ln"]["scale"], cfg["rms_norm_eps"])
    zxbcdt = _dot(u, m["w_in"], precision)
    z, xbc, dt = jnp.split(zxbcdt, [e, 2 * e + 2 * n], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, m["conv_w"], m["conv_b"]))
    xs, bm, cm = jnp.split(xbc, [e, e + n], axis=-1)
    dt = jax.nn.softplus(dt + m["dt_bias"])
    a = -jnp.exp(m["a_log"])
    y = ssm(xs.reshape(b, s, nh, hp), dt, a, bm, cm, m["d_skip"],
            cfg["segment"], precision)
    y = y.reshape(b, s, e) * jax.nn.silu(z)
    y = rmsnorm(y, m["norm_scale"], cfg["gated_norm_eps"])
    return hid + _dot(y, m["w_out"], precision)


def risk(params, tokens, cfg, precision):
    """eta (B,) of a batch of token sequences."""
    hid = params["embed"][tokens]

    def body(h, p_l):
        return jax.checkpoint(
            functools.partial(layer, cfg=cfg, precision=precision))(
                p_l, h), None

    hid, _ = jax.lax.scan(body, hid, params["layers"])
    hid = rmsnorm(hid, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    pooled = hid.mean(axis=1)
    w = params["cox_head"]["w"][:, 0]
    return _dot(pooled, w, precision) + params["cox_head"]["b"]


def cox_nll(eta, time, event):
    """Breslow negative log partial likelihood over the number of events;
    the risk set of i is every j with time_j >= time_i."""
    eta = eta.astype(jnp.float32)
    order = jnp.argsort(time, stable=True)
    ts, e, d = time[order], eta[order], event[order].astype(jnp.float32)
    start = jnp.searchsorted(ts, ts, side="left")
    mx = jax.lax.stop_gradient(jnp.max(e))
    s0 = jnp.cumsum(jnp.exp(e - mx)[::-1])[::-1][start]
    return jnp.sum(d * (jnp.log(s0) + mx - e)) / jnp.maximum(jnp.sum(d), 1.0)


def loss(params, batch, cfg, precision):
    eta = risk(params, batch["tokens"], cfg, precision)
    return cox_nll(eta, batch["time"], batch["event"])


def lr_at(step, hp):
    warm = min(step / max(hp["warmup_steps"], 1), 1.0)
    t = min(max((step - hp["warmup_steps"])
                / max(hp["total_steps"] - hp["warmup_steps"], 1), 0.0), 1.0)
    return hp["learning_rate"] * warm * (0.1 + 0.9 * 0.5
                                         * (1.0 + math.cos(math.pi * t)))


def adamw(params, grads, m, v, step, hp):
    """One AdamW step; returns (params, m, v, clipped grads)."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in leaves))
    scale = jnp.minimum(1.0, hp["grad_clip"] / (gnorm + 1e-9))
    lr = lr_at(step, hp)
    b1, b2 = hp["beta1"], hp["beta2"]
    bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step

    def upd(p, g, m_, v_):
        g = g.astype(jnp.float32) * scale
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        delta = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + hp["eps"]) \
            + hp["weight_decay"] * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m_, v_, g

    out = jax.tree.map(upd, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,  # noqa: E731
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2), pick(3)


def leaf_norms(tree):
    """{leaf path: L2 norm} in float32."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in flat}


def train_steps(params, batches, cfg, hp, dtype=jnp.float32,
                precision="highest"):
    """Run len(batches) AdamW steps from ``params``.

    Returns (losses, first clipped gradient's leaf norms, leaf norms of
    the parameters' change after the last step), as host floats."""
    import numpy as np

    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype)  # noqa: E731
                                  if jnp.issubdtype(a.dtype, jnp.floating)
                                  else a, t)
    p0 = cast(params)
    params = p0
    m = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    v = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    prec = getattr(jax.lax.Precision, precision.upper())
    grad_fn = jax.jit(jax.value_and_grad(
        functools.partial(loss, cfg=cfg, precision=prec)))
    step_fn = jax.jit(functools.partial(adamw, hp=hp),
                      static_argnames=("step",))
    losses, first = [], None
    with jax.default_matmul_precision(precision):
        for i, batch in enumerate(batches):
            batch = {"tokens": batch["tokens"], "time": batch["time"],
                     "event": batch["event"]}
            val, grads = grad_fn(params, batch)
            params, m, v, g = step_fn(params, grads, m, v, step=i + 1)
            losses.append(float(val))
            if first is None:
                first = {k: float(x) for k, x in leaf_norms(g).items()}
            del grads, g
        change = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                              - b.astype(jnp.float32), params, p0)
        moved = {k: float(x) for k, x in leaf_norms(change).items()}
    return np.asarray(losses), first, moved
