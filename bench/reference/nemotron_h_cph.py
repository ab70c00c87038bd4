"""Plain reference of a Nemotron-H stack under the exact Cox partial
likelihood, with its gradients and the AdamW update, in ``jax.numpy``.

Follows the published block (``NemotronHForCausalLM`` of the model's
``config.json``, Nemotron-H: Mamba-2, expert and attention blocks in the
order of ``hybrid_override_pattern``), every block

    h += mixer(RMSNorm(h))                                (pre-norm)

with the mixers

* ``M``, Mamba-2 with G groups of B and C (head h reads group
  h // (H / G)), the sequential state recurrence of ``mamba2_cph`` and
  a gated RMSNorm taken over each of the G groups of channels;
* ``E``, the expert layer: s = sigmoid(u W_r), the top k of s + b_corr
  chosen, their s normalized to sum 1 and scaled; each held expert
  relu(u W_up)^2 W_down computed densely over every token and weighted
  by the token's weight for it (zero where it was not chosen); plus the
  shared expert relu(u S_up)^2 S_down;
* ``*``, attention: grouped-query causal softmax over the full sequence,
  no positional encoding,

then the final RMSNorm, the mean over positions, a linear risk and the
Breslow negative log partial likelihood over the number of events.
Departures, each noted:

* only the experts ``drivers/train_hybrid.py`` holds are computed; the
  part the other experts of the router would add is left out, as the
  program leaves it out (one chip's share of an expert-parallel layer);
* the task head (pooled risk, Cox loss) is the deep-survival head, not
  part of the published model; the untied LM head is held, unused;
* the batch is run in blocks of ``block_rows`` sequences: the risks of
  every block first, then the loss and its gradient in eta, then each
  block's pull-back of that gradient, summed. That changes memory, not
  the arithmetic;
* the state recurrence recomputes segments of ``segment`` positions and
  every block is recomputed in the backward pass;
* the AdamW update of ``mamba2_cph`` (its settings from the
  configuration file).

Parameters are the leaves ``drivers/train_hybrid.py`` makes, in the
program's layout. ``dtype`` float32 with ``precision="highest"`` is the
reference; a lower ``dtype`` computes the same equations with
parameters, activations and matmul operands in that type (the control).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import mamba2_cph as m2


def _dot(a, b, precision):
    return jnp.matmul(a, b, precision=precision)


def ssm(x, dt, a, bm, cm, d_skip, segment, precision):
    """Sequential selective-state recurrence with grouped B and C.

    x (B, S, H, P), dt (B, S, H), a (H,), bm and cm (B, S, G, N).
    Returns y (B, S, H, P)."""
    b, s, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    group = jnp.arange(h) // (h // g)

    def step(st, inp):
        x_t, dt_t, b_t, c_t = inp
        bh, ch = b_t[:, group], c_t[:, group]            # (B, H, N)
        st = st * jnp.exp(dt_t * a)[:, :, None, None] \
            + (dt_t[:, :, None] * x_t)[..., None] * bh[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", st, ch, precision=precision)
        return st, y

    @jax.checkpoint
    def run_segment(st, seg):
        return jax.lax.scan(step, st, seg)

    seg = math.gcd(segment, s)
    tm = lambda v: jnp.moveaxis(v, 1, 0).reshape(s // seg, seg,  # noqa
                                                 *v.shape[:1], *v.shape[2:])
    st0 = jnp.zeros((b, h, p, n), x.dtype)
    _, ys = jax.lax.scan(run_segment, st0, (tm(x), tm(dt), tm(bm), tm(cm)))
    y = jnp.moveaxis(ys.reshape(s, b, h, p), 0, 1)
    return y + d_skip[None, None, :, None] * x


def mamba(m, u, cfg, precision):
    e = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    hp, nh = cfg["mamba_head_dim"], cfg["mamba_num_heads"]
    b, s, _ = u.shape
    zxbcdt = _dot(u, m["w_in"], precision)
    z, xbc, dt = jnp.split(zxbcdt, [e, 2 * e + 2 * g * n], axis=-1)
    xbc = jax.nn.silu(m2.causal_conv(xbc, m["conv_w"], m["conv_b"]))
    xs, bm, cm = jnp.split(xbc, [e, e + g * n], axis=-1)
    dt = jax.nn.softplus(dt + m["dt_bias"])
    a = -jnp.exp(m["a_log"])
    y = ssm(xs.reshape(b, s, nh, hp), dt, a, bm.reshape(b, s, g, n),
            cm.reshape(b, s, g, n), m["d_skip"], cfg["segment"], precision)
    y = y.reshape(b, s, e) * jax.nn.silu(z)
    y = m2.rmsnorm(y.reshape(b, s, g, e // g), 1.0,
                   cfg["layer_norm_epsilon"]).reshape(b, s, e) \
        * m["norm_scale"]
    return _dot(y, m["w_out"], precision)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def routing(p, u, cfg, precision):
    """(weights (T, k), chosen experts (T, k)) over the router's width."""
    t = u.shape[0] * u.shape[1]
    scores = jax.nn.sigmoid(_dot(u.reshape(t, -1), p["router"], precision))
    _, idx = jax.lax.top_k(scores + p["b_corr"], cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    return w, idx


def shared(p, u, precision):
    return _dot(relu2(_dot(u, p["shared"]["w_up"], precision)),
                p["shared"]["w_down"], precision)


def routed(p, u, cfg, precision, first=0):
    """The held experts' part: expert first + e, for each e held."""
    b, s, d = u.shape
    w, idx = routing(p, u, cfg, precision)
    ut = u.reshape(b * s, d)
    out = jnp.zeros_like(ut)
    for e in range(p["w_up"].shape[0]):
        gate = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        y = _dot(relu2(_dot(ut, p["w_up"][e], precision)), p["w_down"][e],
                 precision)
        out = out + gate[:, None].astype(y.dtype) * y
    return out.reshape(b, s, d)


def experts(p, u, cfg, precision, first=0):
    return routed(p, u, cfg, precision, first) + shared(p, u, precision)


def attention(p, u, cfg, precision):
    b, s, _ = u.shape
    nh, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = _dot(u, p["wq"], precision).reshape(b, s, nh, hd)
    k = _dot(u, p["wk"], precision).reshape(b, s, kh, hd)
    v = _dot(u, p["wv"], precision).reshape(b, s, kh, hd)
    group = jnp.arange(nh) // (nh // kh)
    k, v = k[:, :, group], v[:, :, group]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=precision) \
        / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal, sc.astype(jnp.float32), -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", pr, v, precision=precision)
    return _dot(o.reshape(b, s, nh * hd), p["wo"], precision)


def block(kind, p, hid, cfg, precision):
    u = m2.rmsnorm(hid, p["ln"]["scale"], cfg["layer_norm_epsilon"])
    if kind == "M":
        y = mamba(p["mamba"], u, cfg, precision)
    elif kind == "E":
        y = experts(p["moe"], u, cfg, precision, cfg["experts_first"])
    else:
        y = attention(p["attn"], u, cfg, precision)
    return hid + y


def risk(params, tokens, cfg, precision):
    """eta (B,) of a batch of token sequences."""
    hid = params["embed"][tokens]
    for kind, p in zip(cfg["hybrid_override_pattern"], params["blocks"]):
        hid = jax.checkpoint(functools.partial(
            block, kind, cfg=cfg, precision=precision))(p, hid)
    hid = m2.rmsnorm(hid, params["final_norm"]["scale"],
                     cfg["layer_norm_epsilon"])
    pooled = hid.mean(axis=1)
    return _dot(pooled, params["cox_head"]["w"][:, 0], precision) \
        + params["cox_head"]["b"]


def gradient(params, batch, cfg, eta_block, grad_block):
    """(loss, gradient) of the batch's Cox loss, the risks and their
    pull-backs taken ``block_rows`` sequences at a time."""
    rows = cfg["block_rows"]
    tokens = batch["tokens"]
    blocks = [tokens[i:i + rows] for i in range(0, tokens.shape[0], rows)]
    eta = jnp.concatenate([eta_block(params, t) for t in blocks])
    val, g_eta = jax.value_and_grad(m2.cox_nll)(
        eta.astype(jnp.float32), jnp.asarray(batch["time"]),
        jnp.asarray(batch["event"]))
    acc = None
    for i, t in enumerate(blocks):
        g = grad_block(params, t, g_eta[i * rows:(i + 1) * rows])
        acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
    return val, acc


def train_steps(params, batches, cfg, hp, dtype=jnp.float32,
                precision="highest"):
    """Run len(batches) AdamW steps from ``params``.

    Returns (losses, first clipped gradient's leaf norms, leaf norms of
    the parameters' change after the last step), as host floats. The
    starting parameters are kept on the host."""
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype)  # noqa: E731
                                  if jnp.issubdtype(a.dtype, jnp.floating)
                                  else a, t)
    params = cast(params)
    p0 = jax.device_get(params)
    m = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    v = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    prec = getattr(jax.lax.Precision, precision.upper())

    eta_block = jax.jit(functools.partial(risk, cfg=cfg, precision=prec))

    @jax.jit
    def grad_block(p, tokens, g_eta):
        _, pull = jax.vjp(lambda q: risk(q, tokens, cfg, prec), p)
        return pull(g_eta.astype(dtype))[0]

    @functools.partial(jax.jit, static_argnames=("step",),
                       donate_argnums=(0, 2, 3))
    def update(p, g, m_, v_, step):
        p, m_, v_, g = m2.adamw(p, g, m_, v_, step, hp)
        return p, m_, v_, m2.leaf_norms(g)

    losses, first = [], None
    with jax.default_matmul_precision(precision):
        for i, batch in enumerate(batches):
            val, grads = gradient(params, batch, cfg, eta_block, grad_block)
            params, m, v, gn = update(params, grads, m, v, step=i + 1)
            losses.append(float(val))
            if first is None:
                first = {k: float(x) for k, x in gn.items()}
            del grads
    flat0, _ = jax.tree_util.tree_flatten_with_path(p0)
    flat1 = jax.tree.leaves(jax.device_get(params))
    moved = {jax.tree_util.keystr(k): float(np.linalg.norm(
        (np.asarray(b, np.float32) - np.asarray(a, np.float32)).ravel()))
        for (k, a), b in zip(flat0, flat1)}
    return np.asarray(losses), first, moved
