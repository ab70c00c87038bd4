"""Back-to-back train steps of a sequence backbone under the Cox loss.

The entry is the jitted step of ``train.trainer.make_train_step(model,
tcfg, objective="cox")``, as ``survival.deep.train_backbone`` builds it,
on the model the configuration file describes. The benchmark makes the
weights on the device in one jitted call from ``--seed``, in the
program's layout, and the batches on the host from the seed
(``datagen.survival_tokens``), one stream per step.

Set-up builds the one compiled step and its state and drives it through
its first ``check_steps`` steps, through the same call and feed the
window uses, on rows that all differ; it keeps the loss of each, the
leaf norms of the first gradient as the optimizer holds it (its first
moment over 1 - beta1) and the leaf norms of the parameters' change.
The window continues from that state. ``train_tokens_per_s`` is the
tokens of every step completed in the window over the window.

The check runs the plain float32 reference (``reference/mamba2_cph``)
over the same weights and batches once the program's state is freed,
and compares the losses, the first gradient's leaf norms and the
change's leaf norms: each as the gap between the program's number and
the reference's, over the larger of the reference's number for that
leaf and its median leaf. Leaves whose reference gradient is under
``exclude_below`` of the median leaf's move by round-off alone under
Adam and are left out of the change.
"""
from __future__ import annotations

import functools
import time

import numpy as np

import datagen
import tracing
from reference import mamba2_cph


def make_params(key, cfg, vocab_rows):
    """Seeded weights in the program's layout, float32, on the device.

    Initialization follows the published block: in_proj and out_proj
    uniform in +-1/sqrt(fan_in) (out_proj also over sqrt(2 n_layer)),
    depthwise conv weight and bias uniform in +-1/sqrt(d_conv), dt_bias
    the inverse softplus of dt log-uniform in [1e-3, 1e-1], A_log the
    log of U(1, 16), D one, norm scales one, embedding N(0, 0.02), the
    risk head N(0, 0.01) with zero bias.
    """
    import jax
    import jax.numpy as jnp

    L, d = cfg["n_layer"], cfg["d_model"]
    e = cfg["expand"] * d
    n = cfg["d_state"] * cfg["ngroups"]
    h = e // cfg["headdim"]
    w = cfg["d_conv"]
    k = jax.random.split(key, 8)
    u = lambda kk, shape, bound: jax.random.uniform(  # noqa: E731
        kk, shape, jnp.float32, -bound, bound)
    dt = jnp.exp(jax.random.uniform(k[5], (L, h), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "embed": 0.02 * jax.random.normal(k[0], (vocab_rows, d),
                                          jnp.float32),
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "layers": {
            "ln": {"scale": jnp.ones((L, d), jnp.float32)},
            "mamba": {
                "w_in": u(k[1], (L, d, 2 * e + 2 * n + h), d ** -0.5),
                "conv_w": u(k[2], (L, w, e + 2 * n), w ** -0.5),
                "conv_b": u(k[3], (L, e + 2 * n), w ** -0.5),
                "a_log": jnp.log(jax.random.uniform(
                    k[4], (L, h), jnp.float32, 1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "d_skip": jnp.ones((L, h), jnp.float32),
                "norm_scale": jnp.ones((L, e), jnp.float32),
                "w_out": u(k[6], (L, e, d), e ** -0.5 / (2 * L) ** 0.5),
            },
        },
        "cox_head": {"w": 0.01 * jax.random.normal(k[7], (d, 1),
                                                   jnp.float32),
                     "b": jnp.zeros((), jnp.float32)},
    }


def model_config(cfg):
    """The program's ModelConfig for the configuration file."""
    from repro.configs import get_config

    return get_config(cfg["program_arch"]).scaled(
        n_layers=cfg["n_layer"], d_model=cfg["d_model"],
        vocab_size=cfg["vocab_size"], ssm_state=cfg["d_state"],
        ssm_head_dim=cfg["headdim"], ssm_expand=cfg["expand"],
        ssm_chunk=cfg["chunk_size"], rms_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_embeddings"], dtype=cfg["param_dtype"])


def gap(prog: dict, ref: dict, keys=None) -> float:
    """Worst leaf: |prog - ref| over max(ref leaf, median ref leaf)."""
    keys = sorted(ref) if keys is None else keys
    med = float(np.median([ref[k] for k in sorted(ref)]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


class Driver:
    def __init__(self, config, traffic, seed, devices, log):
        self.cfg = config
        self.traffic = traffic
        self.seed = seed
        self.devices = devices
        self.log = log
        self.counters = {}

    def batch(self, step):
        t = self.traffic
        return datagen.survival_tokens(self.seed, step, t["batch"],
                                       t["seq_len"], self.cfg["vocab_size"])

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
        rows = mcfg.vocab_padded
        seed = abs(int(self.seed))
        self.key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(0), seed >> 31), seed & 0x7fffffff)
        self.maker = jax.jit(functools.partial(make_params, cfg=c,
                                               vocab_rows=rows))
        want = jax.eval_shape(lambda: {
            **model.init_params(jax.random.PRNGKey(0)),
            "cox_head": init_cox_head(jax.random.PRNGKey(1), c["d_model"])})
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
        losses, prev = [], None
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
                prev = out
                if time.perf_counter() - t0 >= seconds:
                    break
            losses.append(float(prev["loss"]))
            jax.block_until_ready(state)
            elapsed = time.perf_counter() - t0
        if capture is not None:
            capture.stop()
        self.state = state
        n = len(losses)
        self.window_losses = np.asarray(losses)
        self.counters.update(steps=n, window_s=elapsed,
                             tokens_per_s=n * tokens / elapsed)
        print(f"train: {n} steps in {elapsed:.3f} s", file=self.log)
        return {"train_tokens_per_s": n * tokens / elapsed}

    def release(self):
        self.state = None
        self.step = None

    def _reference(self, dtype=None, precision="highest"):
        import jax
        import jax.numpy as jnp

        c = self.cfg
        batches = [self.batch(i) for i in range(self.traffic["check_steps"])]
        ref_cfg = dict(c, segment=c["limits"]["train"]["segment"])
        out = mamba2_cph.train_steps(self.maker(self.key), batches, ref_cfg,
                                     c["training"], dtype or jnp.float32,
                                     precision)
        jax.clear_caches()
        return out

    def compare(self, losses, first_grad, moved):
        """The numbers compared, against the float32 reference."""
        lim = self.cfg["limits"]["train"]
        if getattr(self, "ref", None) is None:
            self.ref = self._reference()
        ref_losses, ref_grad, ref_moved = self.ref
        med = float(np.median(list(ref_grad.values())))
        moving = [k for k in sorted(ref_grad)
                  if ref_grad[k] >= lim["exclude_below"] * med]
        self.left_out = sorted(set(ref_grad) - set(moving))
        losses = np.asarray(losses)
        return {"loss_gap": float(np.max(np.abs(losses - ref_losses)
                                         / np.abs(ref_losses))),
                "grad_gap": gap(first_grad, ref_grad),
                "update_gap": gap(moved, ref_moved, moving)}

    def check(self):
        lim = self.cfg["limits"]["train"]
        nums = self.compare(self.losses, self.first_grad, self.moved)
        failed = int((~np.isfinite(self.window_losses)).sum())
        print(f"train check: program losses {self.losses}, reference "
              f"{self.ref[0].tolist()}; change left out for "
              f"{self.left_out}", file=self.log)
        return {"correct": failed == 0,
                "attempted": len(self.window_losses), "failed": failed,
                "checks": {k: {"value": v, "limit": lim[k]}
                           for k, v in nums.items()}}

    def control(self):
        """The reference in the program's place in bfloat16: parameters,
        activations and matmul operands, one pass."""
        import jax.numpy as jnp

        return self.compare(*self._reference(jnp.bfloat16, "default"))
