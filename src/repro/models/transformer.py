"""Backbone model zoo: decoder-only transformers (dense / MoE / pattern
attention), Mamba2 SSM stacks, Zamba2-style hybrids, and encoder-decoder.

All models are pure functions over stacked param pytrees; layer loops use
``lax.scan`` over stacked (L, ...) params so the HLO is O(1) in depth (vital
for the 80-cell dry-run on one CPU core). Per-layer heterogeneity (gemma3's
5:1 local:global pattern, theta switches) rides along the scan as traced
per-layer arrays rather than unrolled python branches.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import layers, moe, pspec, ssm
from .layers import apply_rope, decode_attention, flash_attention, mlp, \
    qkv_project, rmsnorm
from ..configs.base import ModelConfig

Array = jax.Array


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: Array        # (L, B, S_cache, KH, hd)
    v: Array
    length: Array   # (B,) tokens generated so far (absolute position)


def init_kv_cache(cfg: ModelConfig, n_layers: int, batch: int,
                  max_len: int, dtype=jnp.bfloat16) -> KVCache:
    s_cache = max_len if cfg.sliding_window <= 0 \
        else min(max_len, cfg.sliding_window)
    shape = (n_layers, batch, s_cache, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   length=jnp.zeros((batch,), jnp.int32))


def _cache_write(k_cache: Array, v_cache: Array, k_new: Array, v_new: Array,
                 pos: Array) -> Tuple[Array, Array]:
    """Write one position (B,1,KH,hd) at slot ``pos`` (B,) — rolling caches
    pass pos = cur_len % window."""
    b = k_new.shape[0]
    oh = jax.nn.one_hot(pos, k_cache.shape[1], dtype=k_cache.dtype)
    k_cache = k_cache * (1 - oh)[:, :, None, None] \
        + oh[:, :, None, None] * k_new
    v_cache = v_cache * (1 - oh)[:, :, None, None] \
        + oh[:, :, None, None] * v_new
    return k_cache, v_cache


# ---------------------------------------------------------------------------
# Transformer blocks
# ---------------------------------------------------------------------------

def init_block(rng, cfg: ModelConfig, dtype=jnp.bfloat16,
               cross_attn: bool = False) -> Dict[str, Any]:
    ks = jax.random.split(rng, 6)
    p = {
        "ln1": layers.init_rmsnorm(cfg.d_model, dtype),
        "attn": layers.init_attention(ks[0], cfg.d_model, cfg.n_heads,
                                      cfg.n_kv_heads, cfg.head_dim,
                                      cfg.qkv_bias, dtype),
        "ln2": layers.init_rmsnorm(cfg.d_model, dtype),
    }
    if cfg.n_experts > 0:
        p["moe"] = moe.init_moe(ks[1], cfg.d_model, cfg.d_ff, cfg.n_experts,
                                dtype)
    else:
        p["mlp"] = layers.init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype)
    if cross_attn:
        p["ln_x"] = layers.init_rmsnorm(cfg.d_model, dtype)
        p["xattn"] = layers.init_attention(ks[2], cfg.d_model, cfg.n_heads,
                                           cfg.n_kv_heads, cfg.head_dim,
                                           False, dtype)
    return p


def block_forward(p, cfg: ModelConfig, x: Array, positions: Array,
                  window: Array, theta: Array, *, causal: bool = True,
                  enc_out: Optional[Array] = None, want_kv: bool = False):
    """Full-sequence block (train / prefill). Returns (x, aux, (k, v))."""
    x = pspec.constrain(x, "dp", None, None)
    h = rmsnorm(p["ln1"], x, cfg.rms_eps)
    q, k, v = qkv_project(p["attn"], h, cfg.n_heads, cfg.n_kv_heads,
                          cfg.head_dim)
    q = apply_rope(q, positions, theta, cfg.mrope_sections)
    k = apply_rope(k, positions, theta, cfg.mrope_sections)
    o = flash_attention(q, k, v, causal=causal, window=window,
                        q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
    x = x + o.reshape(x.shape[0], x.shape[1], -1) @ p["attn"]["wo"]
    x = pspec.constrain(x, "dp", None, None)

    if enc_out is not None:
        h = rmsnorm(p["ln_x"], x, cfg.rms_eps)
        qx = (h @ p["xattn"]["wq"]).reshape(
            x.shape[0], x.shape[1], cfg.n_heads, cfg.head_dim)
        kx = (enc_out @ p["xattn"]["wk"]).reshape(
            x.shape[0], enc_out.shape[1], cfg.n_kv_heads, cfg.head_dim)
        vx = (enc_out @ p["xattn"]["wv"]).reshape(
            x.shape[0], enc_out.shape[1], cfg.n_kv_heads, cfg.head_dim)
        ox = flash_attention(qx, kx, vx, causal=False, window=-1,
                             q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
        x = x + ox.reshape(x.shape[0], x.shape[1], -1) @ p["xattn"]["wo"]

    h = rmsnorm(p["ln2"], x, cfg.rms_eps)
    aux = jnp.zeros((), jnp.float32)
    if cfg.n_experts > 0:
        m, aux = moe.moe_ffn(p["moe"], h, cfg.n_experts_per_tok)
    else:
        m = mlp(p["mlp"], h)
    x = pspec.constrain(x + m, "dp", None, None)
    return x, aux, ((k, v) if want_kv else None)


def attention_mixer(p, cfg: ModelConfig, x: Array) -> Array:
    """Nemotron-H's attention mixer: grouped-query causal softmax
    attention with no positional encoding and no FFN after it. x (B, S,
    D) is the block's normed input; the projections are named
    ``attn.proj`` on the device, the attention itself ``attn.core``."""
    b, s, _ = x.shape
    with jax.named_scope("attn.proj"):
        q, k, v = qkv_project(p, x, cfg.n_heads, cfg.n_kv_heads,
                              cfg.head_dim)
    with jax.named_scope("attn.core"):
        o = flash_attention(q, k, v, causal=True,
                            q_chunk=min(cfg.q_chunk, s),
                            kv_chunk=min(cfg.kv_chunk, s))
    with jax.named_scope("attn.proj"):
        return o.reshape(b, s, -1) @ p["wo"]


def prefill_cache_kv(cfg: ModelConfig, k: Array, v: Array):
    """Turn full-sequence (B,S,KH,hd) K/V into the cache layout: the last
    ``window`` entries rolled so slot == pos % window (SWA), or unchanged."""
    w = cfg.sliding_window
    if w <= 0 or k.shape[1] <= w:
        return k, v
    s = k.shape[1]
    return (jnp.roll(k[:, -w:], s % w, axis=1),
            jnp.roll(v[:, -w:], s % w, axis=1))


def block_decode(p, cfg: ModelConfig, x: Array, cur_len: Array,
                 window: Array, theta: Array, k_cache: Array, v_cache: Array,
                 enc_kv: Optional[Tuple[Array, Array]] = None):
    """One-token block step against the cache. x: (B, 1, D).

    enc_kv: precomputed cross-attention (kx, vx) — (B, S_src, KH, hd);
    projecting the encoder output per decode step would cost a full
    S_src x d^2 GEMM per layer per token, so prefill does it once.
    """
    b = x.shape[0]
    h = rmsnorm(p["ln1"], x, cfg.rms_eps)
    q, k, v = qkv_project(p["attn"], h, cfg.n_heads, cfg.n_kv_heads,
                          cfg.head_dim)
    pos = cur_len[:, None]  # (B,1) absolute positions
    q = apply_rope(q, pos, theta)
    k = apply_rope(k, pos, theta)
    s_cache = k_cache.shape[1]
    slot = cur_len % s_cache if cfg.sliding_window > 0 else cur_len
    k_cache, v_cache = _cache_write(k_cache, v_cache, k, v, slot)
    eff_len = jnp.minimum(cur_len + 1, s_cache)
    o = decode_attention(q, k_cache, v_cache, eff_len)
    x = x + o.reshape(b, 1, -1) @ p["attn"]["wo"]

    if enc_kv is not None:
        kx, vx = enc_kv
        h = rmsnorm(p["ln_x"], x, cfg.rms_eps)
        qx = (h @ p["xattn"]["wq"]).reshape(b, 1, cfg.n_heads, cfg.head_dim)
        ox = decode_attention(qx, kx, vx,
                              jnp.full((b,), kx.shape[1], jnp.int32))
        x = x + ox.reshape(b, 1, -1) @ p["xattn"]["wo"]

    h = rmsnorm(p["ln2"], x, cfg.rms_eps)
    if cfg.n_experts > 0:
        m, _ = moe.moe_ffn(p["moe"], h, cfg.n_experts_per_tok)
    else:
        m = mlp(p["mlp"], h)
    return x + m, k_cache, v_cache


# ---------------------------------------------------------------------------
# Attention pattern arrays (per-layer window / theta, scanned with params)
# ---------------------------------------------------------------------------

def attention_pattern(cfg: ModelConfig, n_layers: int):
    """Returns (window (L,) i32, theta (L,) f32) as scan inputs."""
    windows = np.full(n_layers, -1, np.int32)
    thetas = np.full(n_layers, cfg.rope_theta, np.float32)
    if cfg.sliding_window > 0:
        windows[:] = cfg.sliding_window
    if cfg.local_global_ratio > 0:
        r = cfg.local_global_ratio
        for i in range(n_layers):
            if (i + 1) % (r + 1) == 0:
                windows[i] = -1                      # global layer
                thetas[i] = cfg.rope_theta_global
            else:
                windows[i] = cfg.local_window
                thetas[i] = cfg.rope_theta
    return jnp.asarray(windows), jnp.asarray(thetas)
