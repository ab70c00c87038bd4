"""The Nemotron-H pieces of the program against the plain reference
(``bench/reference/nemotron_h_cph.py``) at tiny widths on the CPU, with
seeded random weights: the grouped Mamba-2 block, the sigmoid router,
the position-free attention mixer, the expert-share layer (its shares
add up to the uncut layer; holding every expert drops nothing) and the
whole ``MEMEM*E`` stack under the Cox loss with its gradients."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, TrainConfig, reduced_config
from repro.models import build_model, moe, ssm, transformer
from repro.survival.head import cox_loss, init_cox_head
from repro.train.optimizer import init_opt_state
from repro.train.trainer import TrainState, make_train_step

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench"))
from reference import mamba2_cph, nemotron_h_cph as ref  # noqa: E402

HIGHEST = jax.lax.Precision.HIGHEST


@pytest.fixture(autouse=True)
def f32():
    """The program's 32-bit default (other modules turn x64 on)."""
    before = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", before)


def tiny(**kw):
    cfg = reduced_config(REGISTRY["nemotron-3-nano-30b-a3b"])
    return cfg.scaled(**kw) if kw else cfg


def ref_cfg(cfg):
    """The reference's keys (the configuration file's names)."""
    return {
        "hybrid_override_pattern": cfg.layer_pattern,
        "mamba_num_heads": cfg.ssm_heads, "mamba_head_dim": cfg.ssm_head_dim,
        "n_groups": cfg.ssm_groups, "ssm_state_size": cfg.ssm_state,
        "layer_norm_epsilon": cfg.rms_eps,
        "num_experts_per_tok": cfg.n_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling,
        "experts_first": cfg.experts_first,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "segment": 8, "block_rows": 2,
    }


def normal(seed, shape, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape,
                                     jnp.float32)


def close(a, b, tol=2e-5):
    a, b = np.asarray(a), np.asarray(b)
    scale = max(float(np.abs(b).max()), 1e-6)
    assert float(np.abs(a - b).max()) <= tol * scale, \
        (float(np.abs(a - b).max()), scale)


def mamba_params(cfg, seed):
    p = ssm.init_mamba2(jax.random.PRNGKey(seed), cfg.d_model, cfg.ssm_state,
                        cfg.ssm_head_dim, dtype=jnp.float32,
                        n_groups=cfg.ssm_groups, n_heads=cfg.ssm_heads)
    k = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    # dt, conv bias and the norm's scale away from their neutral values
    return dict(p, dt_bias=jax.random.uniform(k[0], p["dt_bias"].shape,
                                              jnp.float32, -3.0, -1.0),
                conv_b=0.1 * jax.random.normal(k[1], p["conv_b"].shape),
                norm_scale=1.0 + 0.1 * jax.random.normal(
                    k[2], p["norm_scale"].shape))


@pytest.mark.parametrize("groups", [2, 4])
def test_grouped_mamba2_block_matches_reference(groups):
    cfg = tiny(ssm_groups=groups, ssm_norm_eps=1e-5, rms_eps=1e-5)
    p = mamba_params(cfg, groups)
    u = normal(7, (2, 40, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = ssm.mamba2_forward(p, u, d_state=cfg.ssm_state,
                                 head_dim=cfg.ssm_head_dim,
                                 chunk=cfg.ssm_chunk, n_groups=groups,
                                 norm_eps=1e-5)
        want = ref.mamba(p, u, ref_cfg(cfg), HIGHEST)
    close(got, want)


def expert_params(cfg, seed, held=None):
    p = moe.init_expert_share(jax.random.PRNGKey(seed), cfg.d_model,
                              cfg.d_ff, cfg.n_experts,
                              held or cfg.n_experts, cfg.shared_expert_ff,
                              jnp.float32)
    return dict(p, b_corr=normal(seed + 1, (cfg.n_experts,), 0.05))


def test_router_matches_reference():
    cfg = tiny()
    p = expert_params(cfg, 3)
    u = normal(4, (2, 16, cfg.d_model))
    w, idx = moe.route(p, u.reshape(32, -1), cfg.n_experts_per_tok,
                       cfg.routed_scaling)
    with jax.default_matmul_precision("highest"):
        w_ref, idx_ref = ref.routing(p, u, ref_cfg(cfg), HIGHEST)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))
    close(w, w_ref)
    np.testing.assert_allclose(np.asarray(w).sum(-1), cfg.routed_scaling,
                               rtol=1e-6)
    # the bias steers the choice, not the weights
    scores = jax.nn.sigmoid(u.reshape(32, -1) @ p["router"])
    close(w, cfg.routed_scaling * jnp.take_along_axis(scores, idx, -1)
          / jnp.take_along_axis(scores, idx, -1).sum(-1, keepdims=True))


def test_attention_mixer_matches_reference_without_rope():
    cfg = tiny(q_chunk=8, kv_chunk=8)
    from repro.models import layers
    p = layers.init_attention(jax.random.PRNGKey(5), cfg.d_model,
                              cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                              False, jnp.float32)
    u = normal(6, (2, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = transformer.attention_mixer(p, cfg, u)
        want = ref.attention(p, u, ref_cfg(cfg), HIGHEST)
    close(got, want)
    # position-free: the first position attends to itself alone
    v = (u[:, :1] @ p["wv"]).reshape(2, 1, cfg.n_kv_heads, cfg.head_dim)
    v = jnp.repeat(v, cfg.n_heads // cfg.n_kv_heads, axis=2)
    close(got[:, 0], v.reshape(2, -1) @ p["wo"])


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four shares of two experts each: their routed parts summed, with
    the shared expert counted once, give the reference layer that holds
    all eight."""
    cfg = tiny()
    full = expert_params(cfg, 11)
    u = normal(12, (2, 16, cfg.d_model))
    per, total = 2, 0.0
    for first in range(0, cfg.n_experts, per):
        share = dict(full, w_up=full["w_up"][first:first + per],
                     w_down=full["w_down"][first:first + per])
        with jax.default_matmul_precision("highest"):
            out, pairs = moe.expert_share(share, u,
                                          top_k=cfg.n_experts_per_tok,
                                          scaling=cfg.routed_scaling,
                                          first=first)
        total = total + out
        assert pairs.shape == (per,)
    with jax.default_matmul_precision("highest"):
        shared = ref.shared(full, u, HIGHEST)
        want = ref.experts(full, u, dict(ref_cfg(cfg), experts_first=0),
                           HIGHEST)
    close(total - (cfg.n_experts // per - 1) * shared, want)


def test_holding_every_expert_drops_nothing():
    cfg = tiny()
    p = expert_params(cfg, 21)
    u = normal(22, (3, 16, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        out, pairs = moe.expert_share(p, u, top_k=cfg.n_experts_per_tok,
                                      scaling=cfg.routed_scaling)
        want = ref.experts(p, u, dict(ref_cfg(cfg), experts_first=0),
                           HIGHEST)
    close(out, want)
    assert int(pairs.sum()) == 3 * 16 * cfg.n_experts_per_tok


def test_expert_share_counts_only_its_own_pairs():
    cfg = tiny()
    p = expert_params(cfg, 31, held=3)
    u = normal(32, (2, 16, cfg.d_model))
    _, idx = moe.route(p, u.reshape(32, -1), cfg.n_experts_per_tok,
                       cfg.routed_scaling)
    _, pairs = moe.expert_share(p, u, top_k=cfg.n_experts_per_tok,
                                scaling=cfg.routed_scaling, first=5)
    want = [int((np.asarray(idx) == e).sum()) for e in (5, 6, 7)]
    assert [int(n) for n in np.asarray(pairs)] == want


def stack_params(cfg, seed):
    model = build_model(cfg)
    p = model.init_params(jax.random.PRNGKey(seed))
    p["cox_head"] = init_cox_head(jax.random.PRNGKey(seed + 1), cfg.d_model)
    p["cox_head"]["w"] = p["cox_head"]["w"] * 50.0
    for i, kind in enumerate(cfg.layer_pattern):
        if kind == "M":
            p["blocks"][i]["mamba"] = mamba_params(cfg, seed + 10 + i)
        if kind == "E":
            p["blocks"][i]["moe"]["b_corr"] = normal(seed + 30 + i,
                                                     (cfg.n_experts,), 0.05)
    return model, p


def batch(cfg, seed, rows=6, seq=24):
    rng = np.random.default_rng(seed)
    return {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                               (rows, seq)), jnp.int32),
            "time": jnp.asarray(rng.exponential(size=rows), jnp.float32),
            "event": jnp.asarray([1, 0, 1, 1, 0, 1][:rows], jnp.float32)}


def test_stack_cox_loss_and_gradients_match_reference():
    cfg = tiny(ssm_norm_eps=1e-5, rms_eps=1e-5)
    assert cfg.layer_pattern == "MEMEM*E"
    model, p = stack_params(cfg, 40)
    b = batch(cfg, 41)
    rc = ref_cfg(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, metrics), grads = jax.jit(jax.value_and_grad(
            lambda q: cox_loss(model, q, b), has_aux=True))(p)
        want, want_g = jax.jit(jax.value_and_grad(
            lambda q: mamba2_cph.cox_nll(ref.risk(q, b["tokens"], rc, HIGHEST),
                                         b["time"], b["event"])))(p)
    close(loss, want)
    assert metrics["counters"]["expert_pairs"].shape == (3, 4)
    close_leaves(grads, want_g, 1e-4)


def close_leaves(got, want, tol):
    """Each leaf's error norm within ``tol`` of the larger of its own
    norm and the median leaf's."""
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    flat_ref = jax.tree.leaves(want)
    assert len(flat) == len(flat_ref)
    med = np.median([float(jnp.linalg.norm(g)) for g in flat_ref])
    for (path, g), g_ref in zip(flat, flat_ref):
        err = float(jnp.linalg.norm(g - g_ref))
        assert err <= tol * max(float(jnp.linalg.norm(g_ref)), med), \
            jax.tree_util.keystr(path)


def test_blocked_reference_gradient_equals_whole_batch():
    """The reference's blocks of rows change memory, not the gradient."""
    cfg = tiny()
    _, p = stack_params(cfg, 50)
    b = batch(cfg, 51)
    rc = ref_cfg(cfg)
    with jax.default_matmul_precision("highest"):
        whole, g_whole = jax.jit(jax.value_and_grad(
            lambda q: mamba2_cph.cox_nll(ref.risk(q, b["tokens"], rc, HIGHEST),
                                         b["time"], b["event"])))(p)
        eta = jax.jit(lambda q, t: ref.risk(q, t, rc, HIGHEST))

        @jax.jit
        def grad_block(q, t, g):
            return jax.vjp(lambda r: ref.risk(r, t, rc, HIGHEST), q)[1](g)[0]

        val, g_blocked = ref.gradient(p, b, rc, eta, grad_block)
    close(val, whole, 1e-6)
    close_leaves(g_blocked, g_whole, 1e-5)


def test_train_step_returns_routed_pairs():
    cfg = tiny()
    model, p = stack_params(cfg, 60)
    state = TrainState(params=p, opt=init_opt_state(p))
    step = jax.jit(make_train_step(model, TrainConfig(learning_rate=1e-3),
                                   objective="cox"))
    state, out = step(state, batch(cfg, 61))
    pairs = np.asarray(out["counters"]["expert_pairs"])
    assert pairs.shape == (3, 4) and pairs.dtype == np.int32
    assert np.isfinite(float(out["loss"]))
    # b_corr is a fixed buffer: no gradient, so AdamW leaves it alone
    m = state.opt.m["blocks"][1]["moe"]["b_corr"]
    assert float(jnp.abs(m).max()) == 0.0
