"""Back-to-back Cox train steps of a layer-pattern hybrid (Nemotron-H).

The entry is the jitted step of ``train.trainer.make_train_step(model,
tcfg, objective="cox")`` on the model the configuration file describes,
as ``drivers/train.py`` drives the Mamba-2 stack: weights made on the
device in one jitted call from ``--seed``, in the program's layout;
batches made on the host from the seed, one stream per step, token ids
drawn from the vocabulary held; the first ``check_steps`` steps in
set-up, through the same call and feed the window uses; then the
window. Besides the loss, each step returns how many (token, expert)
pairs each held expert of each expert layer received; the window sums
them (``expert_pairs``).

The configuration file is one chip's share of an expert-parallel
deployment: ``n_routed_experts`` experts held of the router's
``published.n_routed_experts``, from ``experts_first`` on. The check
runs the plain float32 reference (``reference/nemotron_h_cph``) on the
same share, weights and batches once the program's state is freed, in
blocks of ``block_rows`` sequences, and compares as ``drivers/train.py``
does.
"""
from __future__ import annotations

import functools
import time

import numpy as np

import tracing
from drivers import train
from reference import mamba2_cph, nemotron_h_cph


def make_params(key, cfg, vocab_rows):
    """Seeded weights in the program's layout, float32, on the device.

    Every linear weight uniform in +-1/sqrt(fan_in); the output
    projection of each block (Mamba-2 out_proj, the experts' and the
    shared expert's down projections, attention's o_proj) further over
    sqrt(num_hidden_layers). Mamba-2 as ``drivers/train.py`` makes it;
    the router's score-correction bias zero; embedding and LM head
    N(0, 0.02) and uniform; the risk head N(0, 0.01), zero bias.
    """
    import jax
    import jax.numpy as jnp

    d = cfg["hidden_size"]
    pattern = cfg["hybrid_override_pattern"]
    out_scale = len(pattern) ** -0.5
    keys = iter(jax.random.split(key, 8 * len(pattern) + 4))
    u = lambda shape, bound: jax.random.uniform(  # noqa: E731
        next(keys), shape, jnp.float32, -bound, bound)

    def mamba():
        e = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
        n = cfg["ssm_state_size"] * cfg["n_groups"]
        h, w = cfg["mamba_num_heads"], cfg["conv_kernel"]
        dt = jnp.exp(jax.random.uniform(
            next(keys), (h,), jnp.float32, jnp.log(cfg["time_step_min"]),
            jnp.log(cfg["time_step_max"])))
        dt = jnp.maximum(dt, cfg["time_step_floor"])
        return {"w_in": u((d, 2 * e + 2 * n + h), d ** -0.5),
                "conv_w": u((w, e + 2 * n), w ** -0.5),
                "conv_b": u((e + 2 * n,), w ** -0.5),
                "a_log": jnp.log(jax.random.uniform(
                    next(keys), (h,), jnp.float32, 1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "d_skip": jnp.ones((h,), jnp.float32),
                "norm_scale": jnp.ones((e,), jnp.float32),
                "w_out": u((e, d), e ** -0.5 * out_scale)}

    def experts():
        held, ff = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
        sff = cfg["moe_shared_expert_intermediate_size"]
        width = cfg["published"]["n_routed_experts"]
        return {"router": u((d, width), d ** -0.5),
                "b_corr": jnp.zeros((width,), jnp.float32),
                "w_up": u((held, d, ff), d ** -0.5),
                "w_down": u((held, ff, d), ff ** -0.5 * out_scale),
                "shared": {"w_up": u((d, sff), d ** -0.5),
                           "w_down": u((sff, d), sff ** -0.5 * out_scale)}}

    def attention():
        q = cfg["num_attention_heads"] * cfg["head_dim"]
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
        return {"wq": u((d, q), d ** -0.5), "wk": u((d, kv), d ** -0.5),
                "wv": u((d, kv), d ** -0.5),
                "wo": u((q, d), q ** -0.5 * out_scale)}

    mixer = {"M": ("mamba", mamba), "E": ("moe", experts),
             "*": ("attn", attention)}
    blocks = []
    for kind in pattern:
        name, make = mixer[kind]
        blocks.append({"ln": {"scale": jnp.ones((d,), jnp.float32)},
                       name: make()})
    p = {"embed": 0.02 * jax.random.normal(next(keys), (vocab_rows, d),
                                           jnp.float32),
         "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
         "blocks": blocks,
         "cox_head": {"w": 0.01 * jax.random.normal(next(keys), (d, 1),
                                                    jnp.float32),
                      "b": jnp.zeros((), jnp.float32)}}
    if not cfg["tie_word_embeddings"]:
        p["lm_head"] = u((d, vocab_rows), d ** -0.5)
    return p


def model_config(cfg):
    """The program's ModelConfig for the configuration file: every width
    from the file, the experts held and the router's width as it
    states them."""
    from repro.configs import get_config

    return get_config(cfg["program_arch"]).scaled(
        n_layers=cfg["num_hidden_layers"],
        layer_pattern=cfg["hybrid_override_pattern"],
        d_model=cfg["hidden_size"], vocab_size=cfg["vocab_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ssm_state=cfg["ssm_state_size"], ssm_head_dim=cfg["mamba_head_dim"],
        ssm_heads=cfg["mamba_num_heads"], ssm_groups=cfg["n_groups"],
        ssm_chunk=cfg["chunk_size"], ssm_norm_eps=cfg["layer_norm_epsilon"],
        rms_eps=cfg["layer_norm_epsilon"],
        d_ff=cfg["moe_intermediate_size"],
        n_experts=cfg["published"]["n_routed_experts"],
        n_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling=cfg["routed_scaling_factor"],
        shared_expert_ff=cfg["moe_shared_expert_intermediate_size"],
        experts_first=cfg["experts_first"],
        experts_held=cfg["n_routed_experts"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["param_dtype"])


class Driver(train.Driver):
    def setup(self):
        import jax
        import jax.numpy as jnp

        from repro.configs.base import TrainConfig
        from repro.models import build_model
        from repro.survival.head import init_cox_head
        from repro.train.optimizer import init_opt_state
        from repro.train.trainer import TrainState, make_train_step

        c, hp = self.cfg, self.cfg["training"]
        mcfg = model_config(c)
        model = build_model(mcfg)
        seed = abs(int(self.seed))
        self.key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(0), seed >> 31), seed & 0x7fffffff)
        self.maker = jax.jit(functools.partial(
            make_params, cfg=c, vocab_rows=mcfg.vocab_padded))
        want = jax.eval_shape(lambda: {
            **model.init_params(jax.random.PRNGKey(0)),
            "cox_head": init_cox_head(jax.random.PRNGKey(1), mcfg.d_model)})
        have = jax.eval_shape(self.maker, self.key)
        if jax.tree.structure(want) != jax.tree.structure(have) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(have))):
            raise ValueError("the benchmark's weights do not match the "
                             "program's parameter layout")
        params = self.maker(self.key)
        state = TrainState(params=params, opt=init_opt_state(params))
        tcfg = TrainConfig(
            learning_rate=hp["learning_rate"],
            warmup_steps=hp["warmup_steps"], total_steps=hp["total_steps"],
            weight_decay=hp["weight_decay"], beta1=hp["beta1"],
            beta2=hp["beta2"], grad_clip=hp["grad_clip"],
            remat=hp["remat"])
        self.step = jax.jit(make_train_step(model, tcfg, objective="cox"),
                            donate_argnums=(0,))
        moment = jax.jit(lambda m: mamba2_cph.leaf_norms(
            jax.tree.map(lambda a: a / (1.0 - hp["beta1"]), m)))
        change = jax.jit(lambda a, b: mamba2_cph.leaf_norms(
            jax.tree.map(jnp.subtract, a, b)))
        self.losses, self.first_grad = [], None
        for i in range(self.traffic["check_steps"]):
            state, out = self.step(state, self.batch(i))
            self.losses.append(float(out["loss"]))
            if i == 0:
                self.first_grad = {k: float(v) for k, v in
                                   moment(state.opt.m).items()}
                print(f"train: routed pairs of the first step by layer and "
                      f"expert held {_pairs(out).tolist()}", file=self.log)
        p0 = self.maker(self.key)
        self.moved = {k: float(v) for k, v in
                      change(state.params, p0).items()}
        del p0
        self.state = state
        self.next_step = self.traffic["check_steps"]

    def window(self, seconds, capture):
        import jax

        state, step = self.state, self.step
        tokens = self.traffic["batch"] * self.traffic["seq_len"]
        if capture is not None:
            capture.start()
        losses, pairs, prev = [], 0, None
        with tracing.window(capture):
            t0 = time.perf_counter()
            while True:
                with tracing.annotate("bench.feed"):
                    b = self.batch(self.next_step)
                with tracing.annotate("bench.step"):
                    state, out = step(state, b)
                self.next_step += 1
                if prev is not None:
                    with tracing.annotate("bench.sync"):
                        losses.append(float(prev["loss"]))
                        pairs = pairs + _pairs(prev)
                prev = out
                if time.perf_counter() - t0 >= seconds:
                    break
            losses.append(float(prev["loss"]))
            pairs = pairs + _pairs(prev)
            jax.block_until_ready(state)
            elapsed = time.perf_counter() - t0
        if capture is not None:
            capture.stop()
        self.state = state
        n = len(losses)
        self.window_losses = np.asarray(losses)
        self.counters.update(steps=n, window_s=elapsed, tokens=n * tokens,
                             tokens_per_s=n * tokens / elapsed,
                             expert_pairs=np.asarray(pairs))
        print(f"train: {n} steps in {elapsed:.3f} s; routed pairs by layer "
              f"and expert held {np.asarray(pairs).tolist()}", file=self.log)
        return {"train_tokens_per_s": n * tokens / elapsed}

    def _reference(self, dtype=None, precision="highest"):
        import jax
        import jax.numpy as jnp

        c, lim = self.cfg, self.cfg["limits"]["train"]
        batches = [self.batch(i) for i in range(self.traffic["check_steps"])]
        ref_cfg = dict(c, segment=lim["segment"],
                       block_rows=lim["block_rows"])
        out = nemotron_h_cph.train_steps(
            self.maker(self.key), batches, ref_cfg, c["training"],
            dtype or jnp.float32, precision)
        jax.clear_caches()
        return out


def _pairs(out):
    """The routed pairs of one step, (expert layers, experts held)."""
    return np.asarray(out["counters"]["expert_pairs"], np.int64)
