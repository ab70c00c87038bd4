"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cox
from repro.kernels import ops, ref
from repro.kernels.cox_batch import cox_batch
from repro.kernels.cox_coord import cox_coord
from repro.kernels.revcumsum import revcumsum


def _rand(shape, dtype, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape) * scale, dtype=dtype)


@pytest.mark.parametrize("n", [1, 7, 128, 513, 1000, 4096])
@pytest.mark.parametrize("m", [1, 3, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_revcumsum_matches_ref(n, m, dtype):
    x = _rand((n, m), dtype, seed=n + m)
    out = revcumsum(x, block_n=256, interpret=True)
    expect = ref.revcumsum_ref(x)
    # blocked-matmul vs sequential-scan accumulation order differs -> allow
    # summation noise proportional to sqrt(n)
    tol = 1e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("n", [5, 64, 257, 1024, 3000])
@pytest.mark.parametrize("block", [128, 1024])
@pytest.mark.parametrize("order", [2, 3])
def test_cox_coord_matches_ref(n, block, order):
    rng = np.random.default_rng(n + order)
    eta = jnp.asarray(rng.standard_normal(n) * 0.8, jnp.float32)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    d = jnp.asarray((rng.uniform(size=n) < 0.7).astype(np.float32))
    g, h, c3 = cox_coord(eta, x, d, order=order, block=block, interpret=True)
    g_r, h_r, c3_r = ref.cox_coord_ref(eta, x, d, order=order)
    np.testing.assert_allclose(g, g_r, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h, h_r, rtol=2e-5, atol=2e-5)
    if order >= 3:
        np.testing.assert_allclose(c3, c3_r, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n,p", [(64, 8), (500, 33), (1024, 256), (2050, 70)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cox_batch_matches_ref(n, p, dtype):
    rng = np.random.default_rng(n + p)
    x = jnp.asarray(rng.standard_normal((n, p)), dtype)
    eta = jnp.asarray(rng.standard_normal(n) * 0.5, jnp.float32)
    d = jnp.asarray((rng.uniform(size=n) < 0.7).astype(np.float32))
    w = jnp.exp(eta - jnp.max(eta))
    s0 = jax.lax.cumsum(w, axis=0, reverse=True)
    inv_s0 = 1.0 / s0
    a = jnp.cumsum(d * inv_s0)
    wa = w * a
    r = wa - d
    g, h = cox_batch(x, w, r, wa, d, inv_s0, block_n=256, block_p=128,
                     interpret=True)
    g_r, h_r = ref.cox_batch_ref(x, w, r, wa, d, inv_s0)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(g, g_r, rtol=tol, atol=tol * 10)
    np.testing.assert_allclose(h, h_r, rtol=tol, atol=tol * 10)


def test_ops_against_core_no_ties():
    """End-to-end: kernel wrappers agree with core.cox on tie-free data."""
    rng = np.random.default_rng(0)
    n, p = 400, 12
    x = rng.standard_normal((n, p)).astype(np.float32)
    t = rng.uniform(1.0, 2.0, size=n).astype(np.float32)  # continuous: no ties
    assert len(np.unique(t)) == n
    delta = (rng.uniform(size=n) < 0.6).astype(np.float32)
    data = cox.prepare(x, t, delta)
    beta = jnp.asarray(rng.standard_normal(p).astype(np.float32) * 0.3)
    eta = data.x @ beta

    g_all, h_all = ops.cox_batch_grad_hess(eta, data.x, data.delta)
    g_core, h_core = cox.grad_hess_all(data, eta)
    np.testing.assert_allclose(g_all, g_core, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h_all, h_core, rtol=2e-4, atol=2e-4)

    for l in [0, 5, 11]:
        g, h = ops.cox_coord_grad_hess(eta, data.x[:, l], data.delta)
        g_c, h_c, _ = cox.coord_derivs(data, eta, data.x[:, l])
        np.testing.assert_allclose(g, g_c, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(h, h_c, rtol=2e-4, atol=2e-4)


def test_revcumsum_ops_1d():
    x = _rand((777,), jnp.float32, seed=9)
    np.testing.assert_allclose(ops.revcumsum(x), ref.revcumsum_ref(x),
                               rtol=1e-5, atol=1e-5)


def test_fit_cd_with_pallas_kernel_path():
    """End-to-end: the fused-kernel CD (interpret mode) walks the same
    trajectory as the jnp CD on tie-free data — the paper's solver with the
    TPU fast path engaged."""
    from repro.core import solvers

    rng = np.random.default_rng(7)
    n, p = 300, 10
    x = rng.standard_normal((n, p)).astype(np.float32)
    t = rng.uniform(1.0, 2.0, size=n).astype(np.float32)
    assert len(np.unique(t)) == n
    delta = (rng.uniform(size=n) < 0.6).astype(np.float32)
    data = cox.prepare(x, t, delta)
    for method in ("cd_quad", "cd_cubic"):
        res_k = solvers.fit_cd(data, lam1=0.5, lam2=0.5, n_iters=8,
                               method=method, use_kernel=True)
        res_j = solvers.fit_cd(data, lam1=0.5, lam2=0.5, n_iters=8,
                               method=method, use_kernel=False)
        np.testing.assert_allclose(np.asarray(res_k.objective),
                                   np.asarray(res_j.objective),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(res_k.beta),
                                   np.asarray(res_j.beta),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("n,m", [(50, 4), (513, 8), (2000, 16)])
def test_lipschitz_kernel_matches_core(n, m):
    """Pallas Lipschitz constants == core.cox.lipschitz_constants on
    tie-free sorted data (sweep shapes incl. non-multiple-of-block n)."""
    rng = np.random.default_rng(n + m)
    x = rng.standard_normal((n, m)).astype(np.float32)
    # distinct-by-construction times (f32 uniform draws collide at n=2000)
    t = rng.permutation(1.0 + np.arange(n) / n).astype(np.float32)
    assert len(np.unique(t)) == n
    delta = (rng.uniform(size=n) < 0.6).astype(np.float32)
    data = cox.prepare(x, t, delta)
    l2_ref, l3_ref = cox.lipschitz_constants(data)
    l2_k, l3_k = ops.lipschitz_constants(data.x, data.delta, block_n=256)
    np.testing.assert_allclose(l2_k, l2_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(l3_k, l3_ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,s,g", [(1, 1, 16), (37, 5, 200), (64, 3, 128),
                                   (130, 8, 257)])
def test_survival_curves_stratified_matches_ref(b, s, g):
    """Scalar-prefetch baseline-row gather == jnp oracle (interpret mode)."""
    from repro.kernels.survival_curves import survival_curves_stratified

    rng = np.random.default_rng(b + s + g)
    eta = jnp.asarray(rng.standard_normal(b).astype(np.float32))
    h0 = jnp.asarray(np.cumsum(rng.uniform(0, 0.05, (s, g)),
                               axis=1).astype(np.float32))
    strata = jnp.asarray(rng.integers(0, s, b).astype(np.int32))
    out = survival_curves_stratified(eta, h0, strata, block_g=128,
                                     interpret=True)
    want = ref.survival_curves_stratified_ref(eta, h0, strata)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_survival_curves_stratified_clips_extreme_eta():
    from repro.kernels.survival_curves import survival_curves_stratified

    eta = jnp.asarray([100.0, -100.0], jnp.float32)
    h0 = jnp.asarray(np.linspace(0.0, 2.0, 32, dtype=np.float32))[None, :]
    strata = jnp.zeros(2, jnp.int32)
    out = np.asarray(survival_curves_stratified(eta, h0, strata,
                                                interpret=True))
    want = np.asarray(ref.survival_curves_stratified_ref(eta, h0, strata))
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)
    assert np.all(np.isfinite(out))


def test_ops_stratified_dispatch_matches_ref():
    """ops-level dispatch (autotune lookup path) agrees with the oracle."""
    rng = np.random.default_rng(99)
    b, s, g = 25, 4, 64
    eta = jnp.asarray(rng.standard_normal(b).astype(np.float32))
    h0 = jnp.asarray(np.cumsum(rng.uniform(0, 0.05, (s, g)),
                               axis=1).astype(np.float32))
    strata = jnp.asarray(rng.integers(0, s, b).astype(np.int32))
    out = ops.survival_curves_stratified(eta, h0, strata)
    want = ref.survival_curves_stratified_ref(eta, h0, strata)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def _sweep_cohort(n, p, ties, seed):
    """A time-sorted cohort; ``ties`` puts event times on a coarse grid."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, p)).astype(np.float32)
    t = (rng.integers(0, max(n // 5, 2), n) if ties
         else rng.permutation(n)).astype(np.float32)
    delta = (rng.uniform(size=n) < 0.6).astype(np.float32)
    return cox.prepare(x, t, delta)


@pytest.mark.parametrize("n,p,ties,method", [
    (300, 13, False, "cd_quad"), (300, 13, True, "cd_cubic"),
    (1100, 21, True, "cd_quad"), (1100, 8, False, "cd_cubic")])
def test_cd_sweep_kernel_matches_jnp_sweep(n, p, ties, method):
    """One kernel sweep (interpret mode) against the jnp sweep it
    replaces on TPU: tied and tie-free cohorts, both surrogates, n and p
    that fill no whole tile or column block."""
    from repro.core import solvers

    data = _sweep_cohort(n, p, ties, seed=n + p)
    if ties:
        assert len(np.unique(np.asarray(data.risk_start))) < n
    l2c, l3c = cox.lipschitz_constants(data)
    ev = cox.risk_start_events(data)
    rng = np.random.default_rng(p)
    beta = jnp.asarray(rng.standard_normal(p).astype(np.float32) * 0.2)
    eta = data.x @ beta
    cubic = method == "cd_cubic"
    want = solvers._cd_sweep_jnp(data, eta, beta, l2c, l3c, ev, 0.5, 0.5,
                                 cubic)
    prep = ops.cd_sweep_prepare(data.x, ev, data.delta, l2c, l3c)
    got = ops.cd_sweep(eta, beta, prep, 0.5, 0.5, cubic=cubic,
                       interpret=True)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert np.all(np.isfinite(g))
        np.testing.assert_allclose(g, w, rtol=1e-5,
                                   atol=1e-5 * np.max(np.abs(w)))


def test_fit_cd_tol_through_sweep_kernel(monkeypatch):
    """A whole early-stopping fit with its sweeps on the kernel (the TPU
    branch, run in TPU interpret mode) stops after as many sweeps as the
    jnp path and reaches its objective to 1e-6."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.core import solvers

    data = _sweep_cohort(200, 12, True, seed=5)
    kw = dict(lam1=0.5, lam2=0.5, max_iters=200, tol=1e-3,
              method="cd_quad")
    want = solvers.fit_cd_tol(data, **kw)
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *a, tpu, default: tpu(*a))
    fit = jax.jit(lambda d: solvers.fit_cd_tol.__wrapped__(d, **kw))
    with pltpu.force_tpu_interpret_mode():
        got = fit(data)
    assert int(got.n_iters) == int(want.n_iters)
    np.testing.assert_allclose(np.asarray(got.objective),
                               np.asarray(want.objective), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got.beta), np.asarray(want.beta),
                               rtol=1e-4, atol=1e-5)


def test_cd_sweep_path_counter():
    """``cd_sweep_path_total`` counts the kernel where it engages, and
    the jnp sweep with its reason for bfloat16 data and for an n past the
    kernel's VMEM budget."""
    from repro.core import solvers
    from repro.kernels import cd_sweep
    from repro.obs import metrics

    counter = metrics.REGISTRY.counter("cd_sweep_path_total",
                                       label_names=("path", "reason"))

    def traced(n, p, dtype):
        s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt)  # noqa: E731
        data = cox.CoxData(x=s((n, p), dtype), delta=s((n,), dtype),
                           risk_start=s((n,), jnp.int32),
                           tie_end=s((n,), jnp.int32))
        jax.eval_shape(lambda d: solvers.fit_cd_tol.__wrapped__(
            d, lam1=1.0, lam2=1.0, max_iters=5), data)

    big = cd_sweep.TILE * 64 + 1
    assert not cd_sweep.fits(big) and cd_sweep.fits(1200)
    for (n, dtype), label in [
            ((1200, jnp.float32), dict(path="kernel", reason="fits")),
            ((1200, jnp.bfloat16), dict(path="jnp", reason="dtype")),
            ((big, jnp.float32), dict(path="jnp", reason="vmem"))]:
        before = counter.value(**label)
        traced(n, 3, dtype)
        assert counter.value(**label) == before + 1, label
