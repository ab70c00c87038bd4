"""Model dispatch: one functional `Model` facade over every family in the
pool (dense / moe / vlm decoder-only, ssm, hybrid, encdec).

All public entry points are jit-friendly pure functions of (params, batch)
or (params, cache, tokens); `make_input_specs` produces the
ShapeDtypeStruct stand-ins the dry-run lowers against.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig, ShapeSpec
from . import layers, moe, pspec, ssm, transformer as tf

Array = jax.Array


class SSMCache(NamedTuple):
    conv: Array    # (L, B, W-1, C)
    state: Array   # (L, B, H, hd, N)
    length: Array  # (B,)


class HybridCache(NamedTuple):
    conv: Array    # (L, B, W-1, C)
    state: Array   # (L, B, H, hd, N)
    k: Array       # (G, B, S, KH, hd) shared-attn caches per application
    v: Array
    length: Array


class EncDecCache(NamedTuple):
    k: Array       # (L, B, S_dec, KH, hd) decoder self-attention
    v: Array
    xk: Array      # (L, B, S_src, KH, hd) precomputed cross K/V
    xv: Array
    length: Array


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _maybe_remat(body, remat):
    """remat: False | True/'nothing' (recompute all) | 'dots' (save matmul
    outputs — the capacity/traffic middle ground of §Perf B6)."""
    if remat is False or remat is None:
        return body
    policy = jax.checkpoint_policies.dots_saveable if remat == "dots" \
        else jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(body, policy=policy)


def _norm(params, x: Array, eps: float) -> Array:
    """A layer's or the final RMSNorm; its device work is named
    ``model.norm``."""
    with jax.named_scope("model.norm"):
        return layers.rmsnorm(params, x, eps)


def _init_mamba(rng, cfg: ModelConfig, dt):
    return ssm.init_mamba2(rng, cfg.d_model, cfg.ssm_state, cfg.ssm_head_dim,
                           cfg.ssm_expand, dtype=dt, n_groups=cfg.ssm_groups,
                           n_heads=cfg.ssm_heads)


def _mamba(params, x: Array, cfg: ModelConfig, return_state: bool = False):
    return ssm.mamba2_forward(params, x, d_state=cfg.ssm_state,
                              head_dim=cfg.ssm_head_dim, chunk=cfg.ssm_chunk,
                              n_groups=cfg.ssm_groups,
                              norm_eps=cfg.ssm_norm_eps,
                              return_state=return_state)


def _init_pattern_block(kind: str, rng, cfg: ModelConfig, dt):
    """One block of a layer pattern: its pre-norm and its mixer."""
    p = {"ln": layers.init_rmsnorm(cfg.d_model, dt)}
    if kind == "M":
        p["mamba"] = _init_mamba(rng, cfg, dt)
    elif kind == "E":
        p["moe"] = moe.init_expert_share(
            rng, cfg.d_model, cfg.d_ff, cfg.n_experts,
            cfg.experts_held or cfg.n_experts, cfg.shared_expert_ff, dt)
    elif kind == "*":
        p["attn"] = layers.init_attention(rng, cfg.d_model, cfg.n_heads,
                                          cfg.n_kv_heads, cfg.head_dim,
                                          False, dt)
    else:
        raise ValueError(f"unknown block kind {kind!r} in "
                         f"{cfg.layer_pattern!r}")
    return p


def _pattern_block(kind: str, cfg: ModelConfig, p, x: Array):
    """x + mixer(RMSNorm(x)) for one block; returns (x, the expert
    layer's routed pairs per held expert, or None)."""
    h = _norm(p["ln"], x, cfg.rms_eps)
    pairs = None
    if kind == "M":
        y = _mamba(p["mamba"], h, cfg)
    elif kind == "E":
        y, pairs = moe.expert_share(p["moe"], h, top_k=cfg.n_experts_per_tok,
                                    scaling=cfg.routed_scaling,
                                    first=cfg.experts_first)
    else:
        y = tf.attention_mixer(p["attn"], cfg, h)
    return pspec.constrain(x + y, "dp", None, None), pairs


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dt = _dtype(cfg)

    # ------------------------------------------------------------------
    # Init
    # ------------------------------------------------------------------
    def init_params(self, rng) -> Dict[str, Any]:
        cfg, dt = self.cfg, self.dt
        keys = jax.random.split(rng, 8)
        p: Dict[str, Any] = {
            "embed": jax.random.normal(
                keys[0], (cfg.vocab_padded, cfg.d_model), dt) * 0.02,
            "final_norm": layers.init_rmsnorm(cfg.d_model, dt),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = jax.random.normal(
                keys[1], (cfg.d_model, cfg.vocab_padded), dt) \
                * cfg.d_model ** -0.5

        if cfg.family in ("dense", "moe", "vlm"):
            p["layers"] = jax.vmap(
                lambda r: tf.init_block(r, cfg, dt))(
                    jax.random.split(keys[2], cfg.n_layers))
        elif cfg.family == "ssm":
            p["layers"] = jax.vmap(lambda r: {
                "ln": layers.init_rmsnorm(cfg.d_model, dt),
                "mamba": _init_mamba(r, cfg, dt),
            })(jax.random.split(keys[2], cfg.n_layers))
        elif cfg.family == "hybrid":
            p["layers"] = jax.vmap(lambda r: {
                "ln": layers.init_rmsnorm(cfg.d_model, dt),
                "mamba": _init_mamba(r, cfg, dt),
            })(jax.random.split(keys[2], cfg.n_layers))
            p["shared"] = tf.init_block(keys[3], cfg, dt)
        elif cfg.family == "pattern":
            p["blocks"] = [_init_pattern_block(kind, r, cfg, dt) for kind, r
                           in zip(cfg.layer_pattern, jax.random.split(
                               keys[2], len(cfg.layer_pattern)))]
        elif cfg.family == "encdec":
            p["enc_layers"] = jax.vmap(
                lambda r: tf.init_block(r, cfg, dt))(
                    jax.random.split(keys[2], cfg.encoder_layers))
            p["enc_norm"] = layers.init_rmsnorm(cfg.d_model, dt)
            p["layers"] = jax.vmap(
                lambda r: tf.init_block(r, cfg, dt, cross_attn=True))(
                    jax.random.split(keys[3], cfg.n_layers))
        else:
            raise ValueError(cfg.family)
        return p

    # ------------------------------------------------------------------
    # Embedding / logits
    # ------------------------------------------------------------------
    def _embed_in(self, params, batch) -> Array:
        with jax.named_scope("model.embed"):
            if "embeds" in batch:
                x = batch["embeds"].astype(self.dt)
            else:
                x = params["embed"][batch["tokens"]]
            if self.cfg.name.startswith("gemma"):
                x = x * jnp.asarray(self.cfg.d_model ** 0.5, self.dt)
            return pspec.constrain(x, "dp", None, None)

    def _logits(self, params, hidden: Array) -> Array:
        head = params["embed"].T if self.cfg.tie_embeddings \
            else params["lm_head"]
        logits = hidden @ head
        spec = ["dp"] + [None] * (logits.ndim - 2) + ["model"]
        logits = pspec.constrain(logits, *spec)
        v = self.cfg.vocab_size
        if self.cfg.vocab_padded != v:
            pad_mask = jnp.arange(self.cfg.vocab_padded) >= v
            logits = jnp.where(pad_mask, -1e30, logits.astype(jnp.float32))
        return logits

    def _positions(self, batch, seq: int, bsz: int) -> Array:
        if "positions" in batch:
            return batch["positions"]
        return jnp.broadcast_to(jnp.arange(seq)[None, :], (bsz, seq))

    # ------------------------------------------------------------------
    # Hidden-state stacks (train / prefill)
    # ------------------------------------------------------------------
    def _decoder_stack(self, params, x, positions, want_kv: bool,
                       remat: bool = True):
        cfg = self.cfg
        windows, thetas = tf.attention_pattern(cfg, cfg.n_layers)

        def body(carry, xs):
            h, aux = carry
            p_l, w_l, th_l = xs
            h, a, kv = tf.block_forward(p_l, cfg, h, positions, w_l, th_l,
                                        want_kv=want_kv)
            return (h, aux + a), kv

        body = _maybe_remat(body, remat)
        (x, aux), kvs = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (params["layers"], windows, thetas), unroll=cfg.scan_unroll)
        return x, aux, kvs

    def _ssm_stack(self, params, x, want_state: bool, remat: bool = True):
        cfg = self.cfg

        def body(carry, p_l):
            h = carry
            y = _mamba(p_l["mamba"], _norm(p_l["ln"], h, cfg.rms_eps), cfg,
                       return_state=want_state)
            if want_state:
                y, st = y
                return pspec.constrain(h + y, "dp", None, None), st
            return pspec.constrain(h + y, "dp", None, None), None

        body = _maybe_remat(body, remat)
        x, states = jax.lax.scan(body, x, params["layers"],
                                 unroll=cfg.scan_unroll)
        return x, states

    def _hybrid_stack(self, params, x, positions, want_kv: bool,
                      remat: bool = True):
        """Zamba2: groups of `shared_attn_every` mamba layers, with the
        SHARED transformer block (one param set) applied after each group."""
        cfg = self.cfg
        per = cfg.shared_attn_every
        g = cfg.n_layers // per
        grouped = jax.tree.map(
            lambda a: a.reshape(g, per, *a.shape[1:]), params["layers"])
        shared = params["shared"]
        win = jnp.asarray(-1, jnp.int32)
        theta = jnp.asarray(cfg.rope_theta, jnp.float32)

        def group_body(carry, p_g):
            h = carry

            def inner(hh, p_l):
                y = _mamba(p_l["mamba"], _norm(p_l["ln"], hh, cfg.rms_eps),
                           cfg, return_state=want_kv)
                if want_kv:
                    y, st = y
                    return pspec.constrain(hh + y, "dp", None, None), \
                        (st.conv, st.ssm)
                return pspec.constrain(hh + y, "dp", None, None), None

            h, states = jax.lax.scan(inner, h, p_g)
            h, _, kv = tf.block_forward(shared, cfg, h, positions, win,
                                        theta, want_kv=want_kv)
            return h, (kv, states)

        group_body = _maybe_remat(group_body, remat)
        x, (kvs, states) = jax.lax.scan(group_body, x, grouped,
                                        unroll=cfg.scan_unroll)
        return x, (kvs, states)

    def _pattern_stack(self, params, x, remat: bool = True):
        """Nemotron-H: the blocks of ``layer_pattern`` in order, each
        rematerialized on its own. Returns (x, {"expert_pairs": (E
        blocks, experts held) int32}) where the pattern has E blocks."""
        cfg = self.cfg
        pairs = []
        for kind, p_b in zip(cfg.layer_pattern, params["blocks"]):
            block = _maybe_remat(functools.partial(_pattern_block, kind, cfg),
                                 remat)
            x, n = block(p_b, x)
            if n is not None:
                pairs.append(n)
        return x, ({"expert_pairs": jnp.stack(pairs)} if pairs else None)

    def _encoder(self, params, src: Array, remat: bool = True):
        cfg = self.cfg
        pos = jnp.broadcast_to(jnp.arange(src.shape[1])[None, :],
                               src.shape[:2])
        win = jnp.asarray(-1, jnp.int32)
        theta = jnp.asarray(cfg.rope_theta, jnp.float32)

        def body(h, p_l):
            h, _, _ = tf.block_forward(p_l, cfg, h, pos, win, theta,
                                       causal=False)
            return h, None

        body = _maybe_remat(body, remat)
        h, _ = jax.lax.scan(body, src.astype(self.dt), params["enc_layers"],
                            unroll=cfg.scan_unroll)
        return _norm(params["enc_norm"], h, cfg.rms_eps)

    def _decoder_cross_stack(self, params, x, enc_out, want_kv: bool,
                             remat: bool = True):
        cfg = self.cfg
        pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None, :], x.shape[:2])
        win = jnp.asarray(-1, jnp.int32)
        theta = jnp.asarray(cfg.rope_theta, jnp.float32)

        def body(h, p_l):
            h, _, kv = tf.block_forward(p_l, cfg, h, pos, win, theta,
                                        enc_out=enc_out, want_kv=want_kv)
            return h, kv

        body = _maybe_remat(body, remat)
        return jax.lax.scan(body, x, params["layers"],
                            unroll=cfg.scan_unroll)

    def hidden_states(self, params, batch, want_cache: bool = False,
                      remat: bool = True):
        """(hidden (B,S,D), aux, parts) for train/prefill: ``parts`` is
        the decode cache's pieces with ``want_cache``; without, None, or
        the layer-pattern stack's counters (``_pattern_stack``)."""
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        cache_parts = None
        if cfg.family in ("dense", "moe", "vlm"):
            x = self._embed_in(params, batch)
            pos = self._positions(batch, x.shape[1], x.shape[0])
            x, aux, cache_parts = self._decoder_stack(params, x, pos,
                                                      want_cache, remat)
        elif cfg.family == "ssm":
            x = self._embed_in(params, batch)
            x, cache_parts = self._ssm_stack(params, x, want_cache, remat)
        elif cfg.family == "hybrid":
            x = self._embed_in(params, batch)
            pos = self._positions(batch, x.shape[1], x.shape[0])
            x, cache_parts = self._hybrid_stack(params, x, pos, want_cache,
                                                remat)
        elif cfg.family == "pattern":
            if want_cache:
                raise NotImplementedError(
                    "the layer-pattern stack has no decode cache")
            x = self._embed_in(params, batch)
            x, cache_parts = self._pattern_stack(params, x, remat)
        elif cfg.family == "encdec":
            enc = self._encoder(params, batch["src_embeds"], remat)
            with jax.named_scope("model.embed"):
                x = params["embed"][batch["tokens"]]
            x, cache_parts = self._decoder_cross_stack(params, x, enc,
                                                       want_cache, remat)
            cache_parts = (cache_parts, enc)
        return _norm(params["final_norm"], x, cfg.rms_eps), aux, \
            cache_parts

    # ------------------------------------------------------------------
    # Objectives
    # ------------------------------------------------------------------
    def loss_lm(self, params, batch, remat: bool = True):
        hidden, aux, _ = self.hidden_states(params, batch, remat=remat)
        logits = self._logits(params, hidden).astype(jnp.float32)
        labels = batch["labels"]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None],
                                   axis=-1)[..., 0]
        ce = (lse - gold).mean()
        return ce + 0.01 * aux, {"ce": ce, "aux": aux}

    def risk_scores(self, params, batch, remat: bool = True):
        """Deep-survival head: mean-pool final hidden -> risk (B,)."""
        hidden, aux, _ = self.hidden_states(params, batch, remat=remat)
        return self.pooled_risk(params, hidden), aux

    @staticmethod
    def pooled_risk(params, hidden: Array) -> Array:
        """Risk (B,) from the final hidden states: mean-pool, then the
        linear Cox head."""
        pooled = hidden.mean(axis=1).astype(jnp.float32)
        return pooled @ params["cox_head"]["w"][:, 0] \
            + params["cox_head"]["b"]

    # ------------------------------------------------------------------
    # Serving
    # ------------------------------------------------------------------
    def prefill(self, params, batch, max_len: int = 0):
        """Full-sequence forward that also builds the decode cache.

        ``max_len``: cache capacity (room for decode); defaults to S + 128.
        SWA rolling caches are always window-sized.
        """
        cfg = self.cfg

        def grow(kv, seq):  # pad seq axis (axis=2 of (L,B,S,KH,hd))
            cap = max_len if max_len > 0 else seq + 128
            if cfg.sliding_window > 0:
                return kv  # rolling buffer: fixed window capacity
            pad = max(cap - kv.shape[2], 0)
            return jnp.pad(kv, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))) \
                if pad else kv

        hidden, _, parts = self.hidden_states(params, batch, want_cache=True,
                                              remat=False)
        logits = self._logits(params, hidden[:, -1])
        bsz, seq = hidden.shape[0], hidden.shape[1]
        length = jnp.full((bsz,), seq, jnp.int32)
        if cfg.family in ("dense", "moe", "vlm"):
            ks, vs = parts  # (L, B, S, KH, hd)
            ks, vs = jax.vmap(lambda k, v: tf.prefill_cache_kv(cfg, k, v))(
                ks, vs)
            cache = tf.KVCache(k=grow(ks, seq), v=grow(vs, seq),
                               length=length)
        elif cfg.family == "ssm":
            cache = SSMCache(conv=parts.conv, state=parts.ssm, length=length)
        elif cfg.family == "hybrid":
            (ks, vs), (conv_g, st_g) = parts  # (G,B,S,KH,hd), (G,per,B,...)
            l = cfg.n_layers
            cache = HybridCache(
                conv=conv_g.reshape(l, *conv_g.shape[2:]),
                state=st_g.reshape(l, *st_g.shape[2:]),
                k=grow(ks, seq), v=grow(vs, seq), length=length)
        elif cfg.family == "encdec":
            (ks_vs, enc) = parts
            ks, vs = ks_vs
            xk = jnp.einsum(
                "bsd,ldh->lbsh", enc,
                params["layers"]["xattn"]["wk"]).reshape(
                    cfg.n_layers, enc.shape[0], enc.shape[1],
                    cfg.n_kv_heads, cfg.head_dim)
            xv = jnp.einsum(
                "bsd,ldh->lbsh", enc,
                params["layers"]["xattn"]["wv"]).reshape(
                    cfg.n_layers, enc.shape[0], enc.shape[1],
                    cfg.n_kv_heads, cfg.head_dim)
            cache = EncDecCache(k=grow(ks, seq), v=grow(vs, seq), xk=xk,
                                xv=xv, length=length)
        return logits, cache

    def decode_step(self, params, cache, tokens: Array):
        """One token for every sequence. tokens: (B, 1) int32."""
        cfg = self.cfg
        x = params["embed"][tokens]
        if cfg.name.startswith("gemma"):
            x = x * jnp.asarray(cfg.d_model ** 0.5, self.dt)
        cur = cache.length

        if cfg.family in ("dense", "moe", "vlm"):
            windows, thetas = tf.attention_pattern(cfg, cfg.n_layers)

            def body(h, xs):
                p_l, w_l, th_l, kc, vc = xs
                h, kc, vc = tf.block_decode(p_l, cfg, h, cur, w_l, th_l,
                                            kc, vc)
                return h, (kc, vc)

            x, (ks, vs) = jax.lax.scan(
                body, x, (params["layers"], windows, thetas, cache.k,
                          cache.v), unroll=cfg.scan_unroll)
            new_cache = tf.KVCache(k=ks, v=vs, length=cur + 1)
        elif cfg.family == "ssm":
            def body(h, xs):
                p_l, conv_l, st_l = xs
                y, st = ssm.mamba2_decode_step(
                    p_l["mamba"], _norm(p_l["ln"], h, cfg.rms_eps),
                    ssm.SSMState(conv=conv_l, ssm=st_l),
                    d_state=cfg.ssm_state, head_dim=cfg.ssm_head_dim)
                return h + y, (st.conv, st.ssm)

            x, (conv, st) = jax.lax.scan(
                body, x, (params["layers"], cache.conv, cache.state),
                unroll=cfg.scan_unroll)
            new_cache = SSMCache(conv=conv, state=st, length=cur + 1)
        elif cfg.family == "hybrid":
            per = cfg.shared_attn_every
            g = cfg.n_layers // per
            grouped = jax.tree.map(
                lambda a: a.reshape(g, per, *a.shape[1:]), params["layers"])
            conv_g = cache.conv.reshape(g, per, *cache.conv.shape[1:])
            st_g = cache.state.reshape(g, per, *cache.state.shape[1:])
            win = jnp.asarray(-1, jnp.int32)
            theta = jnp.asarray(cfg.rope_theta, jnp.float32)
            shared = params["shared"]

            def group(h, xs):
                p_g, conv_l, st_l, kc, vc = xs

                def inner(hh, ys):
                    p_l, c_l, s_l = ys
                    y, st = ssm.mamba2_decode_step(
                        p_l["mamba"],
                        _norm(p_l["ln"], hh, cfg.rms_eps),
                        ssm.SSMState(conv=c_l, ssm=s_l),
                        d_state=cfg.ssm_state, head_dim=cfg.ssm_head_dim)
                    return hh + y, (st.conv, st.ssm)

                h, (nc, ns) = jax.lax.scan(inner, h, (p_g, conv_l, st_l))
                h, kc, vc = tf.block_decode(shared, cfg, h, cur, win, theta,
                                            kc, vc)
                return h, (nc, ns, kc, vc)

            x, (conv, st, ks, vs) = jax.lax.scan(
                group, x, (grouped, conv_g, st_g, cache.k, cache.v),
                unroll=cfg.scan_unroll)
            new_cache = HybridCache(
                conv=conv.reshape(cfg.n_layers, *conv.shape[2:]),
                state=st.reshape(cfg.n_layers, *st.shape[2:]),
                k=ks, v=vs, length=cur + 1)
        elif cfg.family == "encdec":
            win = jnp.asarray(-1, jnp.int32)
            theta = jnp.asarray(cfg.rope_theta, jnp.float32)

            def body(h, xs):
                p_l, kc, vc, xk, xv = xs
                h, kc, vc = tf.block_decode(p_l, cfg, h, cur, win, theta,
                                            kc, vc, enc_kv=(xk, xv))
                return h, (kc, vc)

            x, (ks, vs) = jax.lax.scan(
                body, x, (params["layers"], cache.k, cache.v, cache.xk,
                          cache.xv), unroll=cfg.scan_unroll)
            new_cache = EncDecCache(k=ks, v=vs, xk=cache.xk, xv=cache.xv,
                                    length=cur + 1)
        hidden = _norm(params["final_norm"], x, cfg.rms_eps)
        return self._logits(params, hidden[:, 0]), new_cache

    # ------------------------------------------------------------------
    # Cache + input specs (for the dry-run and serving)
    # ------------------------------------------------------------------
    def init_cache_specs(self, batch: int, max_len: int):
        """ShapeDtypeStruct pytree of the decode cache."""
        cfg, dt = self.cfg, self.dt
        sds = jax.ShapeDtypeStruct
        ln = sds((batch,), jnp.int32)
        if cfg.family in ("dense", "moe", "vlm"):
            s_cache = max_len if cfg.sliding_window <= 0 \
                else min(max_len, cfg.sliding_window)
            kv = sds((cfg.n_layers, batch, s_cache, cfg.n_kv_heads,
                      cfg.head_dim), dt)
            return tf.KVCache(k=kv, v=kv, length=ln)
        if cfg.family == "ssm":
            return SSMCache(conv=self._conv_spec(batch),
                            state=self._state_spec(batch), length=ln)
        if cfg.family == "hybrid":
            g = cfg.n_layers // cfg.shared_attn_every
            kv = sds((g, batch, max_len, cfg.n_kv_heads, cfg.head_dim), dt)
            return HybridCache(conv=self._conv_spec(batch),
                               state=self._state_spec(batch),
                               k=kv, v=kv, length=ln)
        if cfg.family == "encdec":
            kv = sds((cfg.n_layers, batch, max_len, cfg.n_kv_heads,
                      cfg.head_dim), dt)
            xkv = sds((cfg.n_layers, batch, self._src_len(max_len),
                       cfg.n_kv_heads, cfg.head_dim), dt)
            return EncDecCache(k=kv, v=kv, xk=xkv, xv=xkv, length=ln)
        raise ValueError(cfg.family)

    def _conv_spec(self, batch):
        cfg = self.cfg
        d_inner = cfg.ssm_expand * cfg.d_model
        c = d_inner + 2 * cfg.ssm_state
        return jax.ShapeDtypeStruct((cfg.n_layers, batch, 3, c), self.dt)

    def _state_spec(self, batch):
        cfg = self.cfg
        d_inner = cfg.ssm_expand * cfg.d_model
        h = d_inner // cfg.ssm_head_dim
        return jax.ShapeDtypeStruct(
            (cfg.n_layers, batch, h, cfg.ssm_head_dim, cfg.ssm_state),
            jnp.float32)

    @staticmethod
    def _src_len(tgt_len: int) -> int:
        return tgt_len  # encdec shapes: source frames match target length

    def make_input_specs(self, shape: ShapeSpec) -> Dict[str, Any]:
        """Batch ShapeDtypeStructs for a shape cell (no allocation)."""
        cfg, dt = self.cfg, self.dt
        sds = jax.ShapeDtypeStruct
        b, s = shape.global_batch, shape.seq_len
        if shape.kind == "decode":
            return {"tokens": sds((b, 1), jnp.int32)}
        batch: Dict[str, Any] = {}
        if cfg.family == "encdec":
            batch["src_embeds"] = sds((b, s, cfg.d_model), dt)
            batch["tokens"] = sds((b, s), jnp.int32)
        elif cfg.frontend in ("audio", "vision"):
            batch["embeds"] = sds((b, s, cfg.d_model), dt)
            if cfg.mrope_sections:
                batch["positions"] = sds((3, b, s), jnp.int32)
        else:
            batch["tokens"] = sds((b, s), jnp.int32)
        if shape.kind == "train":
            batch["labels"] = sds((b, s), jnp.int32)
        return batch


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
