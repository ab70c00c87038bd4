"""Bring-up check: drive the main path once on a TPU chip and check it.

    python chip_smoke.py              # one chip: fit, stream, serve, deep
    python chip_smoke.py --chips 4    # four chips: sharded fit + scoring

With one chip, five phases run in this one process, on seeded data:

* ``fit``    -- ``solvers.fit_cd`` and ``beam.beam_search(k=15)`` on the
  paper's Appendix-C generator at the ``SyntheticSpec`` defaults, then
  ``fit_cd`` on the chip (each sweep one ``cd_sweep`` kernel) against the
  same call on the CPU device (the jnp sweep);
* ``stream`` -- ``solvers.fit_stream`` (global mode) over 1 GiB of f32
  features held on the device in chunks, through the ``revcumsum`` kernel;
* ``serve``  -- the fitted model, and an 8-stratum variant, rolled out
  through ``ModelRegistry`` and answered by ``RiskService``, compared with
  a numpy reference;
* ``deep``   -- mamba2-130m at its published widths trained for a few
  steps under the CPH objective, refit to a sparse head and served;
* ``ssd``    -- the chunked SSD kernel (``kernels/ssd.py``) against the jnp
  body (``models/ssm._ssd_chunked``) on the same device, forward and
  backward, at the shapes of the ``mamba2.train`` and ``nemotron3.train``
  cells: the largest relative gaps of y, the final state and each
  cotangent.

With ``--chips 4`` only the paths that span chips run:
``distributed.fit_cd_sharded`` against ``solvers.fit_cd`` on one device,
and ``ScoringEngine(shard=4)`` against ``shard=None``.

Each phase prints one JSON line with its wall time, its compile time and
its checks. The last line of standard output is
``{"ok": true, "device": {...}}``. Without a TPU the script exits with
code 2 before any phase runs; if a phase fails it exits with code 1 and
prints no such line. No number printed here is a benchmark metric.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))


# -- sizes -------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Sizes:
    fit_n: int = 1200           # SyntheticSpec defaults (Appendix C)
    fit_p: int = 1200
    fit_k: int = 15
    fit_iters: int = 20
    kernel_iters: int = 3
    stream_n: int = 1 << 20     # 1 GiB of f32 features at p = 256
    stream_p: int = 256
    stream_chunk: int = 1 << 16
    stream_epochs: int = 2
    requests: int = 256
    strata: int = 8
    grid: int = 512
    deep_steps: int = 5
    deep_batch: int = 32
    deep_seq: int = 512
    deep_k: int = 8
    mc_n: int = 1 << 22         # 2 GiB of f32 features at p = 128
    mc_p: int = 128
    mc_sweeps: int = 2
    mc_batch: int = 1 << 16
    ssd_batch: int = 32
    ssd_seq: int = 512
    # (heads, head width, groups, state size, chunk) of the SSD in the
    # mamba2.train and the nemotron3.train cells
    ssd_shapes: tuple = ((24, 64, 1, 128, 256), (64, 64, 8, 128, 128))


# request batch sizes submitted back to back; the service cuts them into
# micro-batches of at most max_batch
MIXED_BATCHES = (1, 5, 17, 64, 33, 100, 36)


# -- instrumentation ---------------------------------------------------------

class CompileClock:
    """Seconds JAX spent compiling (or reading the persistent cache)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HITS = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == self.HITS:
            self.cache_hits += 1


def kernel_dispatches() -> dict:
    """``kernel_dispatch_total`` summed over block provenance, per kernel,
    and as ``cd_sweep`` the coordinate sweeps traced onto the one-kernel
    path (``cd_sweep_path_total{path="kernel"}``)."""
    from repro.kernels import autotune, ops

    counter = ops._M_DISPATCH
    out = {k: sum(counter.value(kernel=k, blocks=b)
                  for b in ("tuned", "default", "explicit"))
           for k in autotune.DEFAULT_CONFIGS}
    out["cd_sweep"] = ops._M_SWEEP.value(path="kernel", reason="fits")
    return out


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# -- reference checks --------------------------------------------------------

def reference_scores(model, x, strata):
    """numpy risk and S(t) panel: exp(clip(x b)), exp(-H0[s] risk)."""
    import numpy as np

    eta = np.clip(x.astype(np.float64) @ model.beta.astype(np.float64),
                  -30.0, 30.0)
    risk = np.exp(eta)
    surv = np.exp(-model.base_cumhaz.astype(np.float64)[strata]
                  * risk[:, None])
    return risk, surv


def check_served(model, x, strata, responses, tol=1e-4) -> dict:
    """Served risks equal the reference; each served median is the first
    grid time where the reference curve reaches 1/2 (up to rounding)."""
    import numpy as np

    risk_ref, surv = reference_scores(model, x, strata)
    risk = np.asarray([r.risk for r in responses])
    median = np.asarray([r.median for r in responses])
    np.testing.assert_allclose(risk, risk_ref, rtol=tol)
    grid = model.time_grid
    for i, m in enumerate(median):
        s = surv[i]
        if np.isinf(m):
            check(s.min() > 0.5 - tol, f"request {i}: median inf, "
                  f"reference curve reaches {s.min()}")
            continue
        j = int(np.searchsorted(grid, m))
        check(grid[j] == m, f"request {i}: median {m} not on the grid")
        check(s[j] <= 0.5 + tol and (j == 0 or s[j - 1] > 0.5 - tol),
              f"request {i}: median {m} but reference S={s[max(j - 1, 0)]},"
              f"{s[j]} around it")
    return {"risk_max_rel_err": float(np.max(np.abs(risk - risk_ref)
                                             / risk_ref)),
            "finite_medians": int(np.isfinite(median).sum())}


def serve_through_registry(model, x, strata, model_id: str,
                           max_batch: int = 64) -> dict:
    """Roll ``model`` out and answer one request per row of ``x``."""
    import numpy as np

    from repro.serving import ModelRegistry, RiskService, registry

    svc = RiskService(engine=None, max_batch=max_batch)
    reg = ModelRegistry(svc)
    reg.rollout(model_id, model)
    check(reg.get(model_id).state == registry.LIVE,
          f"{model_id}: registry state {reg.get(model_id).state}")
    rids, lo = [], 0
    while lo < len(x):
        for size in MIXED_BATCHES:
            hi = min(lo + size, len(x))
            rids += [svc.submit(x[i], int(strata[i])) for i in range(lo, hi)]
            svc.drain()
            lo = hi
            if lo == len(x):
                break
    responses = [svc.result(r) for r in rids]
    check(all(r is not None for r in responses), "missing responses")
    errors = [r.error for r in responses if r.error is not None]
    check(not errors, f"{len(errors)} error responses, e.g. {errors[:1]}")
    stats = svc.stats()
    check(stats["engine_failures"] == 0,
          f"{stats['engine_failures']} engine failures")
    out = check_served(model, x, np.asarray(strata), responses)
    out.update(requests=len(responses), batches=stats["n_batches"],
               engine_failures=stats["engine_failures"],
               errors=stats["error_count"], health=stats["health"])
    return out


# -- phases ------------------------------------------------------------------

def phase_fit(sz: Sizes, state: dict) -> dict:
    import jax
    import numpy as np

    from repro.core import beam, cox, solvers
    from repro.data.synthetic import SyntheticSpec, make_correlated_survival
    from repro.obs.solver import TelemetryCallback

    spec = SyntheticSpec(n=sz.fit_n, p=sz.fit_p, k=sz.fit_k)
    x, t, delta, beta_star = make_correlated_survival(spec)
    data = cox.prepare(x, t, delta)
    lam2 = 1.0
    tel = TelemetryCallback("chip_smoke.fit_cd")
    res = solvers.fit_cd(data, lam2=lam2, n_iters=sz.fit_iters,
                         telemetry=tel)
    obj = np.asarray(res.objective)
    jax.effects_barrier()
    check(np.isfinite(obj).all(), "fit_cd objective not finite")
    check(tel.violations == 0, f"{tel.violations} monotonicity violations")
    check(tel.iterations == sz.fit_iters,
          f"{tel.iterations} of {sz.fit_iters} iterations recorded")

    tel_beam = TelemetryCallback("chip_smoke.beam")
    br = beam.beam_search(data, k=sz.fit_k, telemetry=tel_beam)
    check(np.isfinite(br.losses).all(), "beam losses not finite")
    check(len(br.supports[-1]) == sz.fit_k, "beam support size")
    truth = set(np.flatnonzero(beta_star).tolist())
    found = set(br.supports[-1].tolist())

    # the sweep kernel against the jnp sweep: the same fit, lowered for
    # the default device and for the CPU
    cpu = jax.devices("cpu")[0]
    fits = {"kernel": solvers.fit_cd(data, lam2=lam2,
                                     n_iters=sz.kernel_iters)}
    with jax.default_device(cpu):
        fits["jnp"] = solvers.fit_cd(jax.device_put(data, cpu), lam2=lam2,
                                     n_iters=sz.kernel_iters)
    b_k, b_j = (np.asarray(fits[k].beta) for k in ("kernel", "jnp"))
    o_k, o_j = (np.asarray(fits[k].objective) for k in ("kernel", "jnp"))
    beta_diff = float(np.max(np.abs(b_k - b_j)))
    np.testing.assert_allclose(o_k, o_j, rtol=1e-5)
    np.testing.assert_allclose(b_k, b_j, rtol=1e-3, atol=1e-4)

    state["fit"] = dict(x=x, t=t, delta=delta, beta=np.asarray(res.beta))
    return {"n": sz.fit_n, "p": sz.fit_p, "objective_first": float(obj[0]),
            "objective_last": float(obj[-1]),
            "monotonicity_violations": tel.violations,
            "beam_k": len(found), "beam_loss": float(br.losses[-1]),
            "beam_true_support_hits": len(found & truth),
            "kernel_vs_jnp_beta_max_abs_diff": beta_diff,
            "kernel_vs_jnp_objective": [float(o_k[-1]), float(o_j[-1])]}


def tie_free_data(key, n: int, p: int):
    """Seeded tie-free survival rows, made on the device: row order is
    time order, and events are likelier at a higher true risk."""
    import jax
    import jax.numpy as jnp

    from repro.core import cox

    kx, kd = jax.random.split(key)
    beta_star = jnp.zeros(p).at[:: max(p // 16, 1)].set(1.0)
    x = 0.5 * jax.random.normal(kx, (n, p), jnp.float32)
    eta = jnp.dot(x, beta_star, precision=jax.lax.Precision.HIGHEST)
    d = jax.random.uniform(kd, (n,)) < 0.3 + 0.4 * jax.nn.sigmoid(eta)
    idx = jnp.arange(n, dtype=jnp.int32)
    return cox.CoxData(x=x, delta=d.astype(jnp.float32), risk_start=idx,
                       tie_end=idx)


def phase_stream(sz: Sizes, state: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import cox, solvers, streaming
    from repro.obs.solver import TelemetryCallback

    make = jax.jit(tie_free_data, static_argnums=(1, 2))
    key, rows = jax.random.PRNGKey(0), sz.stream_chunk
    src = []
    for i in range(-(-sz.stream_n // rows)):
        c = make(jax.random.fold_in(key, i),
                 min(rows, sz.stream_n - i * rows), sz.stream_p)
        src.append(streaming.Chunk(x=c.x, delta=c.delta))
    jax.block_until_ready([c.x for c in src])
    tel = TelemetryCallback("chip_smoke.fit_stream")
    res = solvers.fit_stream(src, lam2=0.01, n_epochs=sz.stream_epochs,
                             mode="global", telemetry=tel)
    obj = np.asarray(res.objective)
    check(np.isfinite(obj).all(), "fit_stream objective not finite")
    check(tel.violations == 0, f"{tel.violations} monotonicity violations")
    check(tel.iterations >= 1, "no streaming epoch recorded")

    # the chunked statistics with and without the kernel, and the loss
    # against the one-piece reference on the concatenated stream
    g_k, h_k, f_k = streaming.streaming_grad_hess(src, res.beta, True)
    g_j, h_j, f_j = streaming.streaming_grad_hess(src, res.beta, False)
    np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_j),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_j), rtol=1e-3)
    eta = jnp.concatenate([c.x @ res.beta for c in src])
    idx = jnp.arange(eta.shape[0], dtype=jnp.int32)
    whole = cox.CoxData(x=jnp.zeros((eta.shape[0], 0)),
                        delta=jnp.concatenate([c.delta for c in src]),
                        risk_start=idx, tie_end=idx)
    f_ref = float(cox.loss_from_eta(whole, eta))
    np.testing.assert_allclose(float(f_k), f_ref, rtol=1e-4)
    return {"n": sz.stream_n, "p": sz.stream_p, "chunks": len(src),
            "feature_bytes": sz.stream_n * sz.stream_p * 4,
            "epochs": tel.iterations, "objective": obj.tolist(),
            "monotonicity_violations": tel.violations,
            "loss_kernel": float(f_k), "loss_reference": f_ref}


def phase_serve(sz: Sizes, state: dict) -> dict:
    import numpy as np

    from repro.serving import fit_survival_model

    fit = state["fit"]
    rng = np.random.default_rng(7)
    # requests are rows of the training cohort, in a seeded order
    x = fit["x"][rng.permutation(len(fit["x"]))[:sz.requests]]
    model = fit_survival_model(fit["x"], fit["t"], fit["delta"],
                               fit["beta"], grid_size=sz.grid)
    single = serve_through_registry(model, x, np.zeros(len(x), int), "fit")
    labels = rng.integers(0, sz.strata, size=len(fit["t"]))
    strat_model = fit_survival_model(fit["x"], fit["t"], fit["delta"],
                                     fit["beta"], strata=labels,
                                     grid_size=sz.grid)
    check(strat_model.n_strata == sz.strata, "stratified artifact strata")
    req_strata = rng.integers(0, sz.strata, size=len(x))
    strat = serve_through_registry(strat_model, x, req_strata, "fit_strat")
    return {"grid": sz.grid, "single_stratum": single,
            f"strata_{sz.strata}": strat}


def phase_deep(sz: Sizes, state: dict, cfg=None) -> dict:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import build_model
    from repro.survival import deep

    cfg = cfg or get_config("mamba2-130m")
    model = build_model(cfg)
    dcfg = deep.DeepSurvivalConfig(steps=sz.deep_steps, batch=sz.deep_batch,
                                   seq=sz.deep_seq, warmup_steps=1,
                                   log_every=0, k=sz.deep_k)
    trained, losses, stream = deep.train_backbone(model, dcfg)
    check(len(losses) == sz.deep_steps, "deep steps")
    check(bool(np.isfinite(losses).all()), f"deep losses {losses}")
    n_params = sum(int(np.prod(v.shape))
                   for v in jax.tree.leaves(trained.params))
    held = deep.collect_features(model, trained, stream, sz.deep_steps,
                                 dcfg.refit_batches)
    _, beta, artifact = deep.refit_and_export(
        held["features"], held["time"], held["event"], k=sz.deep_k,
        beam_width=dcfg.beam_width, grid_size=dcfg.grid_size)
    check(int((beta != 0).sum()) <= sz.deep_k, "sparse head support")
    feats = held["features"]
    served = serve_through_registry(artifact, feats,
                                    np.zeros(len(feats), int), "deep")
    return {"arch": cfg.name, "n_layers": cfg.n_layers,
            "d_model": cfg.d_model, "vocab": cfg.vocab_size,
            "ssm_state": cfg.ssm_state, "params": n_params,
            "batch": sz.deep_batch, "seq": sz.deep_seq,
            "losses": [float(v) for v in losses],
            "refit_rows": len(feats), "served": served}


def ssd_inputs(key, b: int, s: int, h: int, hd: int, g: int, n: int):
    """Seeded SSD arguments (x, dt, A, B, C) as the Mamba-2 block makes
    them, and the weights (of y and of the final state) of a scalar."""
    import jax
    import jax.numpy as jnp

    k = jax.random.split(key, 6)
    normal = lambda i, shape: jax.random.normal(k[i], shape,  # noqa: E731
                                                jnp.float32)
    return ((normal(0, (b, s, h, hd)),
             jax.nn.softplus(normal(1, (b, s, h)) - 1.0),
             -jnp.linspace(1.0, 16.0, h, dtype=jnp.float32),
             normal(2, (b, s, g, n)), normal(3, (b, s, g, n))),
            normal(4, (b, s, h, hd)), normal(5, (b, h, hd, n)))


def phase_ssd(sz: Sizes, state: dict) -> dict:
    """The SSD kernel against the jnp body, both on the default device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.models import ssm

    out = {}
    for i, (h, hd, g, n, q) in enumerate(sz.ssd_shapes):
        args, wy, ws = ssd_inputs(jax.random.PRNGKey(i), sz.ssd_batch,
                                  sz.ssd_seq, h, hd, g, n)
        bodies = {"kernel": lambda *a: ops.ssd(*a, q),
                  "jnp": lambda *a: ssm._ssd_chunked(*a, q)}
        res, times = {}, {}
        for name, body in bodies.items():
            def scalar(*a, body=body):
                y, st = body(*a)
                return jnp.sum(y * wy) + jnp.sum(st * ws)

            run = jax.jit(lambda *a, body=body, scalar=scalar: (
                body(*a), jax.grad(scalar, argnums=range(5))(*a)))
            (y, st), grads = jax.block_until_ready(run(*args))
            t0 = time.perf_counter()
            for _ in range(3):
                jax.block_until_ready(run(*args))
            times[name] = (time.perf_counter() - t0) / 3 * 1e3
            res[name] = [np.asarray(v) for v in (y, st, *grads)]
        gaps = {}
        for key, got, want in zip(("y", "state", "dx", "ddt", "dA", "dB",
                                   "dC"), res["kernel"], res["jnp"]):
            check(bool(np.isfinite(got).all()), f"kernel {key} not finite")
            gaps[key] = float(np.max(np.abs(got - want))
                              / max(float(np.max(np.abs(want))), 1e-30))
        out[f"H{h}_hd{hd}_G{g}_N{n}_Q{q}"] = {
            "max_rel_gap": gaps, "fwd_bwd_ms": times}
    return {"batch": sz.ssd_batch, "seq": sz.ssd_seq, **out}


def phase_multichip(sz: Sizes, state: dict) -> dict:
    """Sharded exact fit and data-parallel scoring across every device."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from repro.core import cox, distributed, solvers
    from repro.launch.mesh import make_data_mesh
    from repro.serving import ScoringEngine, fit_survival_model

    devices = jax.devices()
    k = len(devices)
    mesh = make_data_mesh(k)
    rows = NamedSharding(mesh, P("data"))
    one = SingleDeviceSharding(devices[0])
    n, p = sz.mc_n, sz.mc_p
    data_sh = jax.jit(tie_free_data, static_argnums=(1, 2),
                      out_shardings=rows)(jax.random.PRNGKey(1), n, p)
    data_1 = jax.device_put(data_sh, one)
    lam2 = 1.0
    l2c = jax.device_put(jax.jit(cox.lipschitz_constants)(data_1)[0],
                         NamedSharding(mesh, P()))
    beta_sh, _ = distributed.fit_cd_sharded(data_sh, l2c, mesh, lam2=lam2,
                                            n_sweeps=sz.mc_sweeps)
    beta_1 = solvers.fit_cd(data_1, lam2=lam2, n_iters=sz.mc_sweeps).beta
    b_sh, b_1 = np.asarray(beta_sh), np.asarray(beta_1)
    np.testing.assert_allclose(b_sh, b_1, rtol=1e-3, atol=1e-4)

    # scoring: a model exported from the head of the stream, one batch
    m = sz.mc_batch
    x_host = np.asarray(data_sh.x[:m])
    model = fit_survival_model(x_host, np.arange(m, dtype=np.float32),
                               np.asarray(data_sh.delta[:m]), b_1,
                               grid_size=sz.grid)
    batch = np.asarray(data_sh.x[m:2 * m])
    r_1, m_1 = ScoringEngine(model).score(batch)
    r_k, m_k = ScoringEngine(model, shard=k).score(batch)
    np.testing.assert_allclose(r_k, r_1, rtol=1e-6, atol=0)
    check(np.array_equal(m_k, m_1), "sharded medians differ")

    # the CPU backend keeps no memory statistics (None)
    peaks = {str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices}
    check(all(v is None or v > 0 for v in peaks.values()),
          f"a device held nothing: {peaks}")
    return {"devices": k, "n": n, "p": p, "feature_bytes": n * p * 4,
            "sharded_input_devices": sorted(
                str(d.id) for d in data_sh.x.sharding.device_set),
            "sweeps": sz.mc_sweeps,
            "beta_max_abs_diff": float(np.max(np.abs(b_sh - b_1))),
            "scoring_batch": m,
            "scoring_bitwise_equal": bool(np.array_equal(r_k, r_1)),
            "peak_bytes_in_use": peaks}


# -- runner ------------------------------------------------------------------

EXPECTED_KERNELS = {
    "fit": ("cd_sweep",),
    "stream": ("revcumsum",),
    "serve": ("survival_curves", "survival_curves_strat"),
    "deep": ("survival_curves",),
}


def run_phases(phases, sz: Sizes, clock: CompileClock,
               expected=EXPECTED_KERNELS, **kwargs) -> bool:
    """Run each phase, print its line; True when every phase passed."""
    state: dict = {}
    ok = True
    for name, fn in phases:
        before_k = kernel_dispatches()
        before_c, hits = clock.seconds, clock.cache_hits
        t0 = time.perf_counter()
        line = {"phase": name}
        try:
            line.update(fn(sz, state, **kwargs.get(name, {})))
            line["ok"] = True
        except Exception as e:
            traceback.print_exc()
            line.update(ok=False, error=f"{type(e).__name__}: {e}"[:2000])
        line["wall_s"] = time.perf_counter() - t0
        line["compile_s"] = clock.seconds - before_c
        line["compile_cache_hits"] = clock.cache_hits - hits
        after = kernel_dispatches()
        line["kernel_dispatch_total"] = {k: after[k] - before_k[k]
                                         for k in after
                                         if after[k] > before_k[k]}
        missing = [k for k in expected.get(name, ())
                   if k not in line["kernel_dispatch_total"]]
        if missing and line["ok"]:
            line.update(ok=False, error=f"no dispatch of kernels {missing}")
        ok = ok and line["ok"]
        print(json.dumps(line, default=str), flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded fit and scoring phase")
    args = ap.parse_args(argv)

    from repro.launch import runtime

    runtime.apply()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX found {platform}); nothing run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    clock = CompileClock()
    print(json.dumps({"jax": jax.__version__,
                      "compile_cache": os.environ.get(
                          runtime.COMPILE_CACHE_ENV)}), flush=True)
    if args.chips == 4:
        phases = [("multichip", phase_multichip)]
    else:
        phases = [("fit", phase_fit), ("stream", phase_stream),
                  ("serve", phase_serve), ("deep", phase_deep),
                  ("ssd", phase_ssd)]
    if not run_phases(phases, Sizes(), clock):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
