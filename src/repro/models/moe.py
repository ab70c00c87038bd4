"""Mixture-of-experts layers.

``moe_ffn``: top-k (Mixtral: top-2) softmax routing with capacity-based
scatter/gather dispatch.

``expert_share``: the expert layer of Nemotron-H as one chip of an
expert-parallel deployment holds it. It is told which experts it holds,
routes every token over all of them (sigmoid scores, top-k of the scores
plus a fixed correction bias, the chosen scores normalized to sum 1 and
scaled), and computes the part of the result its own experts give, with
no token dropped, plus the shared expert every token goes through.

Why not the classic GShard one-hot einsum dispatch: it materializes a
(T, E, C) tensor, i.e. O(T^2) at fixed capacity factor — at train_4k's
1M-token global batch that is exabytes. The scatter formulation below is
O(T*k*d): tokens are placed into an (E*C, d) buffer by computed slot ids
(position-within-expert via one cumsum over (T*k, E)), expert FFNs run as
an E-batched GEMM, and outputs gather back by the same slot ids. Overflow
beyond capacity goes to a trash slot (standard token dropping).

Weight layouts (DESIGN.md §5): "tp" shards each expert's FFN hidden dim
over `model` (default); "ep" (experts over a mesh axis) is exercised in the
§Perf hillclimb with a reshaped mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def init_moe(rng, d_model: int, d_ff: int, n_experts: int,
             dtype=jnp.bfloat16):
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    s = d_model ** -0.5
    return {
        "router": jax.random.normal(k1, (d_model, n_experts), jnp.float32) * s,
        "w_gate": jax.random.normal(k2, (n_experts, d_model, d_ff), dtype) * s,
        "w_up": jax.random.normal(k3, (n_experts, d_model, d_ff), dtype) * s,
        "w_down": jax.random.normal(k4, (n_experts, d_ff, d_model), dtype)
        * (d_ff ** -0.5),
    }


def moe_ffn(params, x: Array, n_experts_per_tok: int = 2,
            capacity_factor: float = 1.25):
    """x: (B, S, D) -> (out (B, S, D), aux load-balancing loss)."""
    b, s, d = x.shape
    e = params["w_gate"].shape[0]
    k = n_experts_per_tok
    t = b * s
    xt = x.reshape(t, d)
    logits = xt.astype(jnp.float32) @ params["router"]     # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)                   # (T, k)
    topv = (topv / topv.sum(axis=-1, keepdims=True)).astype(x.dtype)

    cap = max(int(capacity_factor * t * k / e), 8)

    # position of each (token, slot) within its expert, FCFS by token index
    flat_e = topi.reshape(t * k)                           # (T*k,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)    # (T*k, E)
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(axis=-1) - 1
    keep = pos < cap
    # overflow -> out-of-bounds slot: scatter drops OOB under jit, gather
    # back-fills zeros; keeps the buffer exactly (E*C, D) so the expert dim
    # can shard over an `expert` mesh axis (EP layout, §Perf)
    slot = jnp.where(keep, flat_e * cap + pos, e * cap)

    xrep = jnp.repeat(xt, k, axis=0)                       # (T*k, D)
    buf = jnp.zeros((e * cap, d), x.dtype).at[slot].set(xrep, mode="drop")
    xin = buf.reshape(e, cap, d)

    hmid = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, params["w_gate"],
                                   preferred_element_type=jnp.float32))
            * jnp.einsum("ecd,edf->ecf", xin, params["w_up"],
                         preferred_element_type=jnp.float32)).astype(x.dtype)
    xout = jnp.einsum("ecf,efd->ecd", hmid, params["w_down"],
                      preferred_element_type=jnp.float32).astype(x.dtype)

    # gather back (OOB -> zeros) and combine with renormalized weights
    back = jnp.take(xout.reshape(e * cap, d), slot, axis=0,
                    mode="fill", fill_value=0).reshape(t, k, d)
    out = (back * topv[..., None]).sum(axis=1)

    # Switch-style load-balance aux: E * sum_e f_e * p_e
    frac = onehot.reshape(t, k, e).sum(axis=1).astype(jnp.float32).mean(axis=0)
    pmean = probs.mean(axis=0)
    aux = e * jnp.sum(frac * pmean)
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Expert share (Nemotron-H): sigmoid router, relu^2 experts, shared expert
# ---------------------------------------------------------------------------

def init_expert_share(rng, d_model: int, d_ff: int, n_experts: int,
                      n_held: int, shared_ff: int, dtype=jnp.bfloat16):
    """Router over all ``n_experts``, the ``n_held`` experts held here
    (up (d_model, d_ff) and down, no gate) and the shared expert."""
    k = jax.random.split(rng, 5)
    s = d_model ** -0.5
    return {
        "router": jax.random.normal(k[0], (d_model, n_experts),
                                    jnp.float32) * s,
        # the score-correction bias: a fixed buffer, read, never trained
        "b_corr": jnp.zeros((n_experts,), jnp.float32),
        "w_up": jax.random.normal(k[1], (n_held, d_model, d_ff), dtype) * s,
        "w_down": jax.random.normal(k[2], (n_held, d_ff, d_model), dtype)
        * d_ff ** -0.5,
        "shared": {
            "w_up": jax.random.normal(k[3], (d_model, shared_ff), dtype) * s,
            "w_down": jax.random.normal(k[4], (shared_ff, d_model), dtype)
            * shared_ff ** -0.5,
        },
    }


def relu2(x: Array) -> Array:
    return jnp.square(jax.nn.relu(x))


def route(params, xt: Array, top_k: int, scaling: float):
    """(weights (T, k) f32, experts (T, k) int32) of tokens xt (T, D):
    s = sigmoid(x W_r) in float32 at the highest precision, the top k of
    s + b_corr chosen, their s normalized to sum 1 and scaled."""
    logits = jnp.matmul(xt.astype(jnp.float32), params["router"],
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(
        scores + jax.lax.stop_gradient(params["b_corr"]), top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return w / (w.sum(axis=-1, keepdims=True) + 1e-20) * scaling, idx


def shared_expert(params, xt: Array) -> Array:
    sh = params["shared"]
    return relu2(xt @ sh["w_up"]) @ sh["w_down"]


# (token, expert) rows a block of tokens computes at most: tokens are
# taken in blocks so that a block's activations, and the backward pass's
# copies of them, stay small next to the weights
BLOCK_ROWS = 1 << 14


def expert_share(params, x: Array, *, top_k: int, scaling: float,
                 first: int = 0):
    """x: (B, S, D) -> (out (B, S, D), pairs (held,) int32).

    ``params`` holds experts first .. first + held - 1 of the router's.
    Tokens are routed over every expert, then taken in blocks; in each,
    every held expert runs (up, relu^2, down) over all of the block's
    tokens, and its output is added to each token scaled by the token's
    weight for it, zero where the token did not choose it. So no routed
    pair is dropped, and a step's work is set by the shapes, not by how
    the router loads the experts (which moves with training, seed to
    seed). ``pairs`` counts the pairs each held expert received. The
    device work is named ``moe.route``, ``moe.dispatch`` (the weights by
    expert held), ``moe.experts``, ``moe.combine`` and ``moe.shared``."""
    b, s, d = x.shape
    held = params["w_up"].shape[0]
    t = b * s
    xt = x.reshape(t, d)
    n_blocks = 1
    while t % (2 * n_blocks) == 0 and t * held // n_blocks > BLOCK_ROWS:
        n_blocks *= 2
    with jax.named_scope("moe.route"):
        w, idx = route(params, xt, top_k, scaling)

    @jax.checkpoint
    def part(blk):
        return _held_part(params, *blk, first=first)

    routed, pairs = jax.lax.map(part, (
        xt.reshape(n_blocks, t // n_blocks, d),
        w.reshape(n_blocks, t // n_blocks, top_k),
        idx.reshape(n_blocks, t // n_blocks, top_k)))
    with jax.named_scope("moe.shared"):
        out = routed.reshape(t, d).astype(x.dtype) \
            + shared_expert(params, xt)
    return out.reshape(b, s, d), pairs.sum(axis=0)


def _held_part(params, xt: Array, w: Array, idx: Array, first: int):
    """The held experts' part of the result for tokens xt (T, D) routed
    to experts idx (T, k) with weights w: (routed (T, D), pairs (held,))."""
    held = params["w_up"].shape[0]
    with jax.named_scope("moe.dispatch"):
        chosen = idx[:, :, None] - first == jnp.arange(held)  # (T, k, held)
        gate = jnp.sum(jnp.where(chosen, w[:, :, None], 0.0), axis=1)
        pairs = jnp.sum(chosen, axis=(0, 1), dtype=jnp.int32)
    with jax.named_scope("moe.experts"):
        ys = jnp.einsum("etf,efd->etd", relu2(jnp.einsum(
            "td,edf->etf", xt, params["w_up"])), params["w_down"])
    with jax.named_scope("moe.combine"):
        routed = jnp.sum(ys * gate.T[:, :, None].astype(ys.dtype), axis=0)
    return routed, pairs
