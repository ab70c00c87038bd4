"""Per-architecture smoke tests: reduced same-family config, one forward /
train-loss evaluation and one prefill->decode step on CPU; asserts output
shapes and absence of NaNs. (Full configs are exercised only via the
dry-run with ShapeDtypeStructs.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, reduced_config
from repro.models import build_model

ARCHS = sorted(REGISTRY)
# the layer-pattern stack (Nemotron-H) trains and scores whole sequences;
# it has no token-decode cache
DECODE_ARCHS = [a for a in ARCHS if REGISTRY[a].family != "pattern"]


def make_batch(cfg, rng, bsz=2, seq=24, train=True):
    batch = {}
    if cfg.family == "encdec":
        batch["src_embeds"] = jax.random.normal(
            rng, (bsz, seq, cfg.d_model), jnp.float32)
        batch["tokens"] = jax.random.randint(rng, (bsz, seq), 0,
                                             cfg.vocab_size)
    elif cfg.frontend in ("audio", "vision"):
        batch["embeds"] = jax.random.normal(rng, (bsz, seq, cfg.d_model),
                                            jnp.float32)
        if cfg.mrope_sections:
            pos = jnp.broadcast_to(jnp.arange(seq)[None, :], (bsz, seq))
            batch["positions"] = jnp.stack([pos, pos, pos])
    else:
        batch["tokens"] = jax.random.randint(rng, (bsz, seq), 0,
                                             cfg.vocab_size)
    if train:
        batch["labels"] = jax.random.randint(rng, (bsz, seq), 0,
                                             cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_train_loss_finite(arch):
    cfg = reduced_config(REGISTRY[arch])
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init_params(rng)
    batch = make_batch(cfg, rng)
    loss, metrics = jax.jit(model.loss_lm)(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss))
    assert float(metrics["ce"]) > 0.0


@pytest.mark.parametrize("arch", ARCHS)
def test_train_grads_finite(arch):
    cfg = reduced_config(REGISTRY[arch])
    model = build_model(cfg)
    rng = jax.random.PRNGKey(1)
    params = model.init_params(rng)
    batch = make_batch(cfg, rng)
    grads = jax.jit(jax.grad(lambda p: model.loss_lm(p, batch)[0]))(params)
    flat, _ = jax.tree.flatten(grads)
    assert all(np.all(np.isfinite(np.asarray(g, np.float32))) for g in flat)
    # at least one grad is nonzero
    assert any(float(jnp.abs(g).max()) > 0 for g in flat)


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_prefill_and_decode_step(arch):
    cfg = reduced_config(REGISTRY[arch])
    model = build_model(cfg)
    rng = jax.random.PRNGKey(2)
    params = model.init_params(rng)
    bsz, seq = 2, 24
    batch = make_batch(cfg, rng, bsz=bsz, seq=seq, train=False)
    logits, cache = jax.jit(model.prefill)(params, batch)
    assert logits.shape == (bsz, cfg.vocab_padded)
    assert np.all(np.isfinite(np.asarray(logits[:, : cfg.vocab_size])))
    tok = jnp.argmax(logits[:, : cfg.vocab_size], axis=-1)[:, None] \
        .astype(jnp.int32)
    logits2, cache2 = jax.jit(model.decode_step)(params, cache, tok)
    assert logits2.shape == (bsz, cfg.vocab_padded)
    assert np.all(np.isfinite(np.asarray(logits2[:, : cfg.vocab_size])))
    assert int(cache2.length[0]) == seq + 1


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "mamba2-130m",
                                  "deepseek-67b", "qwen1.5-4b"])
def test_decode_matches_full_forward(arch):
    """Cache correctness: decoding token S after prefilling S tokens must
    match the full forward over S+1 tokens (full-attention / SSM archs)."""
    cfg = reduced_config(REGISTRY[arch])
    model = build_model(cfg)
    rng = jax.random.PRNGKey(3)
    params = model.init_params(rng)
    bsz, seq = 2, 17
    tokens = jax.random.randint(rng, (bsz, seq + 1), 0, cfg.vocab_size)
    _, cache = jax.jit(model.prefill)(params, {"tokens": tokens[:, :seq]})
    dec_logits, _ = jax.jit(model.decode_step)(
        params, cache, tokens[:, seq:seq + 1].astype(jnp.int32))
    hidden, _, _ = model.hidden_states(params, {"tokens": tokens},
                                       remat=False)
    full_logits = model._logits(params, hidden[:, seq])
    np.testing.assert_allclose(
        np.asarray(dec_logits[:, : cfg.vocab_size]),
        np.asarray(full_logits[:, : cfg.vocab_size]), rtol=2e-3, atol=2e-3)


def test_swa_rolling_cache_matches_windowed_forward():
    """After prefill of S > window, one decode step against the rolling
    cache must equal the full forward (windowed attention) on S+1 tokens.

    Uses a dense+SWA config: MoE archs drop tokens when an expert exceeds
    capacity, so prefill(S) vs forward(S+1) are not bit-comparable there
    (that nondeterminism is inherent to capacity routing, not the cache).
    """
    cfg = reduced_config(REGISTRY["mixtral-8x7b"]).scaled(
        n_experts=0, n_experts_per_tok=0, family="dense")
    model = build_model(cfg)
    rng = jax.random.PRNGKey(4)
    params = model.init_params(rng)
    bsz, seq = 2, 37  # > window 16, not a multiple of it
    tokens = jax.random.randint(rng, (bsz, seq + 1), 0, cfg.vocab_size)
    _, cache = jax.jit(model.prefill)(params, {"tokens": tokens[:, :seq]})
    assert cache.k.shape[2] == cfg.sliding_window
    dec_logits, _ = jax.jit(model.decode_step)(
        params, cache, tokens[:, seq:seq + 1].astype(jnp.int32))
    hidden, _, _ = model.hidden_states(params, {"tokens": tokens},
                                       remat=False)
    full_logits = model._logits(params, hidden[:, seq])
    np.testing.assert_allclose(
        np.asarray(dec_logits[:, : cfg.vocab_size]),
        np.asarray(full_logits[:, : cfg.vocab_size]), rtol=2e-3, atol=2e-3)
