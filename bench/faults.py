"""Faults planted underneath a cell's timed path, to show that its check
catches them: a step that returns its state unchanged, half of the batch
left out, and an answer altered where it is produced.

    with faults.planted("train", "half_batch"):
        ...  # drive the cell; its check must come out not correct

The tests drive every cell with each fault on the CPU; ``calibrate.py
--fault`` reads the numbers a fault gives on the chip at the cell's own
size. The benchmark's own runs never plant one.
"""
from __future__ import annotations

import contextlib

import numpy as np

KINDS = ("state_unchanged", "half_batch", "answer_altered")


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _fit(kind):
    import jax.numpy as jnp

    from repro.core import cox, solvers

    if kind == "state_unchanged":
        return _patched(solvers, "_cd_sweep",
                        lambda data, eta, beta, *a, **k: (eta, beta))
    original = solvers.fit_cd_tol

    def broken(data, **kw):
        if kind == "half_batch":
            m = data.n // 2
            idx = jnp.arange(m, dtype=jnp.int32)
            data = cox.CoxData(x=data.x[:m], delta=data.delta[:m],
                               risk_start=idx, tie_end=idx)
        out = original(data, **kw)
        if kind == "answer_altered":
            out = solvers.FitResult(beta=out.beta,
                                    objective=out.objective * 1.001,
                                    n_iters=out.n_iters)
        return out

    return _patched(solvers, "fit_cd_tol", broken)


def _serve(kind):
    from repro.serving import engine

    original = engine.ScoringEngine.score

    def broken(self, x, strata=None, with_curves=False):
        risk, median = original(self, x, strata, with_curves)[:2]
        risk, median = np.array(risk), np.array(median)
        if kind == "answer_altered":
            risk = risk * 1.001
        elif kind == "half_batch":
            # the first half scored, its answers handed to the rest
            h = max(len(risk) // 2, 1)
            risk = np.resize(risk[:h], len(risk))
            median = np.resize(median[:h], len(median))
        elif kind == "state_unchanged":
            risk = np.zeros_like(risk)
        return risk, median

    return _patched(engine.ScoringEngine, "score", broken)


def _train(kind):
    from repro.train import trainer

    original = trainer.make_train_step

    def make(model, tcfg, objective="lm"):
        step = original(model, tcfg, objective)

        def broken(state, batch):
            if kind == "half_batch":
                batch = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            new, out = step(state, batch)
            if kind == "state_unchanged":
                new = state
            elif kind == "answer_altered":
                out = dict(out, loss=out["loss"] * 1.02)
            return new, out

        return broken

    return _patched(trainer, "make_train_step", make)


PLANT = {"fit": _fit, "serve": _serve, "train": _train}


@contextlib.contextmanager
def planted(driver: str, kind: str):
    """Plant ``kind`` under the cell driven by ``driver``; compiled
    programs are dropped on the way in and out, so the fault is traced
    into what runs and nothing of it outlives the block."""
    import jax

    if kind not in KINDS:
        raise ValueError(f"unknown fault {kind!r}; have {KINDS}")
    jax.clear_caches()
    try:
        with PLANT[driver](kind):
            yield
    finally:
        jax.clear_caches()
