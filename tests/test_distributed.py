"""Distributed CPH (shard_map) correctness on 8 host devices.

Runs in a subprocess so the main pytest process keeps 1 device (the
harness contract: only the dry-run and explicit distributed tests may
fork the device count)."""
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import cox, distributed, solvers
from repro.launch.mesh import _make_mesh
from repro.train.compression import compressed_psum

mesh = _make_mesh((4, 2), ("data", "model"))
rng = np.random.default_rng(0)
# odd n (not divisible by the 4-way data axis): exercises the padded-tail
# remainder-shard path in every entry point below
n, p = 509, 32
x = rng.standard_normal((n, p)).astype(np.float32)
t = rng.uniform(1.0, 2.0, size=n).astype(np.float32)  # continuous: no ties
delta = (rng.uniform(size=n) < 0.7).astype(np.float32)
data = cox.prepare(x, t, delta)
beta = rng.standard_normal(p).astype(np.float32) * 0.3
eta = np.asarray(data.x @ beta)

# --- sharded suffix sum (1d + 2d), remainder tail
v = jnp.asarray(rng.standard_normal(n), jnp.float32)
out = distributed.shard_revcumsum(v, mesh)
np.testing.assert_allclose(np.asarray(out),
                           np.asarray(jax.lax.cumsum(v, reverse=True)),
                           rtol=2e-5, atol=2e-5)
v2 = jnp.asarray(rng.standard_normal((n, 4)), jnp.float32)
out2 = distributed.shard_revcumsum_2d(v2, mesh)
np.testing.assert_allclose(np.asarray(out2),
                           np.asarray(jax.lax.cumsum(v2, axis=0,
                                                     reverse=True)),
                           rtol=2e-5, atol=2e-5)
print("revcumsum ok")

# --- sharded risk stats match the replicated reference
w_sh, s0_sh, a_sh = distributed.sharded_risk_stats(data, jnp.asarray(eta),
                                                   mesh)
w_r, s0_r, a_r, _ = cox.risk_stats(data, jnp.asarray(eta))
np.testing.assert_allclose(np.asarray(s0_sh), np.asarray(s0_r),
                           rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(np.asarray(a_sh), np.asarray(a_r),
                           rtol=2e-4, atol=2e-4)
print("risk stats ok")

# --- sharded all-coordinate derivatives
g_sh, h_sh = distributed.sharded_grad_hess_all(data, jnp.asarray(eta), mesh)
g_ref, h_ref = cox.grad_hess_all(data, jnp.asarray(eta))
np.testing.assert_allclose(np.asarray(g_sh), np.asarray(g_ref),
                           rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(np.asarray(h_sh), np.asarray(h_ref),
                           rtol=2e-4, atol=2e-4)
print("grad_hess ok")

# --- sharded CD reaches the same objective as replicated CD
l2c, _ = cox.lipschitz_constants(data)
beta_sh, eta_out = distributed.fit_cd_sharded(
    data, jnp.asarray(l2c), mesh, lam2=0.5, n_sweeps=12)
res = solvers.fit_cd(data, lam2=0.5, n_iters=12)
f_sh = float(cox.loss_from_eta(data, jnp.asarray(eta_out))
             + 0.5 * jnp.sum(beta_sh * beta_sh))
f_ref = float(res.objective[-1])
assert abs(f_sh - f_ref) < 1e-2 * max(1.0, abs(f_ref)), (f_sh, f_ref)
print("cd ok", f_sh, f_ref)

# --- compressed psum ~= psum
y = jnp.asarray(rng.standard_normal((8, 256)), jnp.float32)
exact = jax.shard_map(lambda a: jax.lax.psum(a, "data"), mesh=mesh,
                      in_specs=P("data"), out_specs=P("data"),
                      check_vma=False)(y)
approx = jax.shard_map(lambda a: compressed_psum(a, "data"), mesh=mesh,
                       in_specs=P("data"), out_specs=P("data"),
                       check_vma=False)(y)
rel = float(jnp.sqrt(jnp.mean((approx - exact) ** 2))
            / jnp.sqrt(jnp.mean(exact ** 2)))
assert rel < 0.02, rel  # int8 wire format: ~1% normalized RMSE
print("compressed psum ok", rel)
print("ALL_OK")
"""


def test_distributed_cph_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "ALL_OK" in out.stdout, out.stdout + "\n---\n" + out.stderr
