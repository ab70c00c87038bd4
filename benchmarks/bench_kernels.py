"""Kernel microbenchmarks (Cor. 3.3's O(n) machinery).

On this container Pallas executes in interpret mode, so the `pallas_*`
rows measure the correctness path, not TPU performance."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


def _time(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run():
    rows = []
    rng = np.random.default_rng(0)
    n, p = 100_000, 64
    x = jnp.asarray(rng.standard_normal((n, p)), jnp.float32)
    eta = jnp.asarray(rng.standard_normal(n) * 0.3, jnp.float32)
    d = jnp.asarray((rng.uniform(size=n) < 0.7).astype(np.float32))
    batch = jax.jit(lambda e, xx, dd: ops.cox_batch_grad_hess(e, xx, dd))
    rows.append((f"kernels/pallas_cox_batch_interp/n={n},p={p}",
                 _time(batch, eta, x, d, reps=2), "interpret-mode"))
    n = 65536
    v = jnp.asarray(rng.standard_normal((n, 128)), jnp.float32)
    rows.append((f"kernels/pallas_revcumsum_interp/n={n},m=128",
                 _time(ops.revcumsum, v, reps=2), "interpret-mode"))
    b, g = 1024, 128
    etac = jnp.asarray(rng.standard_normal(b) * 0.5, jnp.float32)
    h0 = jnp.asarray(np.linspace(0.0, 2.0, g), jnp.float32)
    rows.append((f"kernels/pallas_survival_curves_interp/b={b},g={g}",
                 _time(ops.survival_curves, etac, h0), "interpret-mode"))
    return rows
