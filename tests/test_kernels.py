"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cox
from repro.kernels import ops, ref
from repro.kernels.cox_batch import cox_batch
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


def test_revcumsum_ops_1d():
    x = _rand((777,), jnp.float32, seed=9)
    np.testing.assert_allclose(ops.revcumsum(x), ref.revcumsum_ref(x),
                               rtol=1e-5, atol=1e-5)


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
    want = solvers._cd_sweep(data, eta, beta, l2c, l3c, ev, 0.5, 0.5, cubic)
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


@pytest.mark.parametrize("fit", ["cd_quad", "cd_cubic"])
def test_fit_cd_through_sweep_kernel(monkeypatch, fit):
    """The scan fit with its sweeps on the kernel (the TPU branch, run in
    TPU interpret mode) walks the jnp path's objective trace to 1e-6."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.core import solvers

    data = _sweep_cohort(200, 12, True, seed=6)
    kw = dict(lam1=0.5, lam2=0.5, n_iters=10, method=fit)
    want = solvers.fit_cd(data, **kw)
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *a, tpu, default: tpu(*a))
    run = jax.jit(lambda d: solvers.fit_cd.__wrapped__(d, **kw))
    with pltpu.force_tpu_interpret_mode():
        got = run(data)
    assert int(got.n_iters) == int(want.n_iters) == 10
    np.testing.assert_allclose(np.asarray(got.objective),
                               np.asarray(want.objective), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got.beta), np.asarray(want.beta),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("n", [5, 64, 257, 1024, 3000])
@pytest.mark.parametrize("method", ["cd_quad", "cd_cubic"])
@pytest.mark.parametrize("ties", [False, True])
def test_sweep_coordinate_matches_coord_derivs(n, method, ties):
    """A one-column sweep through the kernel (interpret mode) is one
    coordinate visit: ``cox.coord_derivs`` at that column, the
    surrogate's prox, and the step added to beta and to eta."""
    from repro.core import surrogate

    data = _sweep_cohort(n, 1, ties, seed=n + 11)
    rng = np.random.default_rng(n)
    eta = jnp.asarray(rng.standard_normal(n).astype(np.float32) * 0.8)
    beta = jnp.asarray([0.3], jnp.float32)
    lam1, lam2 = 0.05, 0.5
    l2c, l3c = cox.lipschitz_constants(data)
    ev = cox.risk_start_events(data)
    xl = data.x[:, 0]
    g, h, _ = cox.coord_derivs(data, eta, xl, order=2, ev=ev)
    a = g + 2.0 * lam2 * beta[0]
    if method == "cd_cubic":
        step = surrogate.cubic_l1_prox(a, h + 2.0 * lam2, l3c[0], beta[0],
                                       lam1)
    else:
        step = surrogate.quad_l1_prox(a, l2c[0] + 2.0 * lam2, beta[0], lam1)
    assert float(step) != 0.0
    prep = ops.cd_sweep_prepare(data.x, ev, data.delta, l2c, l3c)
    got_eta, got_beta = ops.cd_sweep(eta, beta, prep, lam1, lam2,
                                     cubic=method == "cd_cubic",
                                     interpret=True)
    for got, want in ((got_beta, beta + step), (got_eta, eta + step * xl)):
        got, want = np.asarray(got), np.asarray(want)
        np.testing.assert_allclose(got, want, rtol=2e-5,
                                   atol=2e-5 * np.max(np.abs(want)))


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
