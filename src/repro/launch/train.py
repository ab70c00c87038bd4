"""Production training launcher.

``python -m repro.launch.train --arch <id> [--objective lm|cox] ...``

Wires together: config registry -> model -> sharded TrainState -> jit'd
train step -> deterministic pipeline -> heartbeat/straggler monitor ->
async checkpointing with resume. On this CPU container it runs reduced
configs end-to-end (see examples/); on a TPU fleet the same file is the
per-host entry point (jax.distributed.initialize is a no-op locally).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..configs import REGISTRY, TrainConfig, get_config, reduced_config
from ..data.pipeline import SurvivalTextStream, TokenTaskStream, put_batch
from ..models import build_model
from ..survival.head import init_cox_head
from ..train import checkpoint as ckpt_lib
from ..train import fault_tolerance as ft
from ..train.optimizer import init_opt_state
from ..train.trainer import TrainState, make_train_step
from . import sharding as sh
from .mesh import make_host_mesh, make_production_mesh


def build_state(model, objective: str, rng):
    params = model.init_params(rng)
    if objective == "cox":
        params["cox_head"] = init_cox_head(jax.random.PRNGKey(7),
                                           model.cfg.d_model)
    return TrainState(params=params, opt=init_opt_state(params))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(REGISTRY))
    ap.add_argument("--objective", default="lm", choices=["lm", "cox"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--scale", default="",
                    help="comma k=v ModelConfig overrides, e.g. "
                         "n_layers=8,d_model=512")
    ap.add_argument("--production-mesh", action="store_true",
                    help="16x16 mesh (requires 256 devices)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    if args.scale:
        kw = {}
        for kv in args.scale.split(","):
            k, v = kv.split("=")
            kw[k] = type(getattr(cfg, k))(v)
        cfg = cfg.scaled(**kw)
    cfg = cfg.scaled(vocab_size=min(cfg.vocab_size, 4096))
    model = build_model(cfg)
    tcfg = TrainConfig(learning_rate=args.lr, warmup_steps=20,
                       total_steps=args.steps, microbatch=args.microbatch)
    mesh = make_production_mesh() if args.production_mesh \
        else make_host_mesh()

    stream_cls = TokenTaskStream if args.objective == "lm" \
        else SurvivalTextStream
    stream = stream_cls(cfg.vocab_size, args.seq, args.batch, seed=args.seed)

    step_fn = jax.jit(make_train_step(model, tcfg, args.objective))
    hb = ft.Heartbeat((args.ckpt_dir or "/tmp/repro") + "/heartbeat.json")
    mon = ft.StragglerMonitor()
    checkpointer = ckpt_lib.AsyncCheckpointer(args.ckpt_dir) \
        if args.ckpt_dir else None

    with jax.set_mesh(mesh):
        init = lambda: build_state(model, args.objective,
                                   jax.random.PRNGKey(args.seed))
        if args.ckpt_dir:
            state, start = ft.resume_or_init(args.ckpt_dir, init)
            if start:
                print(f"[train] resumed from step {start}")
        else:
            state, start = init(), 0

        losses = []
        for step in range(start, args.steps):
            t0 = time.time()
            batch = put_batch(stream.batch_for_step(step), mesh)
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.time() - t0
            straggler = mon.record(dt)
            hb.beat(step, {"loss": loss})
            if step % args.log_every == 0 or straggler:
                tag = " STRAGGLER" if straggler else ""
                print(f"[train] step {step} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"{dt*1e3:.0f}ms{tag}", flush=True)
            if checkpointer and (step + 1) % args.ckpt_every == 0:
                checkpointer.save(step + 1, state)
        if checkpointer:
            checkpointer.save(args.steps, state)
            checkpointer.wait()
    print(f"[train] done: first-10 mean {np.mean(losses[:10]):.4f} "
          f"last-10 mean {np.mean(losses[-10:]):.4f}")
    return state, losses


if __name__ == "__main__":
    main()
