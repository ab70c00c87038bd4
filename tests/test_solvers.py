"""Solver behaviour: monotone decrease (the paper's headline guarantee),
agreement of every convergent method on the same convex optimum, and the
early-stopping variant."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import beam, cox, solvers, surrogate
from repro.data.synthetic import SyntheticSpec, make_correlated_survival, \
    make_tied_survival

jax.config.update("jax_enable_x64", True)


@pytest.fixture(scope="module")
def problem():
    x, t, delta, _ = make_correlated_survival(
        SyntheticSpec(n=300, p=20, k=4, rho=0.7, seed=2))
    return cox.prepare(x.astype(np.float64), t, delta)


def test_cd_monotone_decrease(problem):
    for method in ("cd_quad", "cd_cubic"):
        res = solvers.fit_cd(problem, lam1=0.0, lam2=0.1, n_iters=30,
                             method=method)
        obj = np.asarray(res.objective)
        assert np.all(np.diff(obj) <= 1e-9), method
        assert np.all(np.isfinite(obj)), method


def test_cd_monotone_decrease_l1(problem):
    for method in ("cd_quad", "cd_cubic"):
        res = solvers.fit_cd(problem, lam1=1.0, lam2=1.0, n_iters=30,
                             method=method)
        obj = np.asarray(res.objective)
        assert np.all(np.diff(obj) <= 1e-9), method
        assert np.all(np.isfinite(obj)), method


def test_all_solvers_reach_same_smooth_optimum(problem):
    """lam2 > 0 -> strongly convex, unique optimum; every convergent method
    must agree. newton_ls is the high-precision reference."""
    ref = solvers.fit_newton(problem, lam2=1.0, n_iters=40, line_search=True)
    f_ref = float(ref.objective[-1])
    for name in ("cd_quad", "cd_cubic", "quasi_newton", "prox_newton"):
        res = solvers.SOLVERS[name](problem, 0.0, 1.0, 400)
        assert float(res.objective[-1]) <= f_ref + 1e-6, (
            name, float(res.objective[-1]), f_ref)


def test_cd_l1_matches_prox_newton_optimum(problem):
    """Same convex l1+l2 objective -> same optimal value across methods."""
    r1 = solvers.fit_cd(problem, lam1=1.0, lam2=1.0, n_iters=500,
                        method="cd_quad")
    r2 = solvers.fit_cd(problem, lam1=1.0, lam2=1.0, n_iters=500,
                        method="cd_cubic")
    r3 = solvers.fit_working_newton(problem, lam1=1.0, lam2=1.0, n_iters=200,
                                    variant="prox")
    f1, f2, f3 = (float(r.objective[-1]) for r in (r1, r2, r3))
    assert abs(f1 - f2) < 1e-6
    assert f1 <= f3 + 1e-5


def test_cubic_converges_faster_per_iteration(problem):
    """2nd-order surrogate uses curvature -> at least as good per sweep."""
    rq = solvers.fit_cd(problem, lam2=0.1, n_iters=25, method="cd_quad")
    rc = solvers.fit_cd(problem, lam2=0.1, n_iters=25, method="cd_cubic")
    assert float(rc.objective[-1]) <= float(rq.objective[-1]) + 1e-8


def test_fit_cd_tol_early_stops(problem):
    res = solvers.fit_cd_tol(problem, lam2=1.0, max_iters=500, tol=1e-9)
    assert int(res.n_iters) < 500
    ref = solvers.fit_newton(problem, lam2=1.0, n_iters=40, line_search=True)
    assert float(res.objective[-1]) <= float(ref.objective[-1]) + 1e-5


def test_exact_newton_blows_up_without_line_search():
    """Reproduces the paper's critical-flaw demonstration (Fig. 1a): from
    beta=0 with weak regularization, the pure Newton step overshoots and the
    loss explodes / fails to decrease monotonically, while CD stays
    monotone on the same problem."""
    rng = np.random.default_rng(1)
    n, p = 120, 4
    # rare, heavy-tailed features: risk-set variance (the 2nd partial) is
    # tiny at beta=0 while the gradient is O(1) -> the raw Newton step
    # overshoots into the loss's linear tail and explodes.
    x = ((rng.uniform(size=(n, p)) < 0.04)
         * rng.lognormal(1.5, 1.0, size=(n, p))).astype(np.float64)
    risk = np.clip(x @ np.array([3.0, -3.0, 2.0, -2.0]), -30, 30)
    t = (-np.log(rng.uniform(1e-12, 1, n)) / np.exp(risk)) ** 0.3
    delta = (rng.uniform(size=n) < 0.8).astype(np.float64)
    data = cox.prepare(x, t, delta)
    res = solvers.fit_newton(data, lam2=0.0, n_iters=12, line_search=False)
    obj = np.asarray(res.objective)
    bad = (~np.all(np.isfinite(obj))) or np.any(np.diff(obj) > 1e-6) or \
        float(obj[-1]) > float(obj[0])
    assert bad, "expected divergence-style behaviour from raw Newton"
    res_cd = solvers.fit_cd(data, lam2=0.0, n_iters=12, method="cd_quad")
    obj_cd = np.asarray(res_cd.objective)
    assert np.all(np.isfinite(obj_cd))
    assert np.all(np.diff(obj_cd) <= 1e-9)


def test_gd_decreases(problem):
    res = solvers.fit_gd(problem, lam1=0.5, lam2=0.5, n_iters=100)
    obj = np.asarray(res.objective)
    assert np.all(np.isfinite(obj))
    assert float(obj[-1]) < float(obj[0])


@pytest.fixture(scope="module")
def tied():
    x, t, delta = make_tied_survival(n=300, p=10, n_times=15, seed=4)
    return cox.prepare(x.astype(np.float64), t, delta)


@partial(jax.jit, static_argnames=("n_iters",))
def _gather_fit_cd(data, lam1, lam2, n_iters):
    """Quadratic-surrogate CD with each coordinate's gradient summed over
    events, the risk-set moments gathered at every event's risk_start.
    Returns the objective after ``n_iters`` sweeps."""
    l2c, _ = cox.lipschitz_constants(data)
    xT = data.x.T
    rs = data.risk_start

    def coord(i, c):
        eta, beta = c
        l = i % data.p
        w = jnp.exp(eta - jnp.max(eta))
        m1 = cox.revcumsum(w * xT[l])[rs] / cox.revcumsum(w)[rs]
        g = jnp.sum(data.delta * (m1 - xT[l]))
        step = surrogate.quad_l1_prox(g + 2.0 * lam2 * beta[l],
                                      l2c[l] + 2.0 * lam2, beta[l], lam1)
        return eta + step * xT[l], beta.at[l].add(step)

    zero = jnp.zeros(data.p, data.x.dtype)
    _, beta = jax.lax.fori_loop(0, n_iters * data.p, coord,
                                (data.x @ zero, zero))
    return cox.objective(data, beta, lam1, lam2)


@pytest.mark.parametrize("case", ["fit_cd", "fit_cd_tol", "fit_cd_tol_l2"])
def test_cd_on_tied_cohort_matches_gather_form(tied, case):
    """Weighting the suffix sums by the events that start there is the
    per-event gather regrouped: the same objective on tied data."""
    if case == "fit_cd":
        f = solvers.fit_cd(tied, lam1=1.0, lam2=1.0, n_iters=40).objective[-1]
        ref = _gather_fit_cd(tied, 1.0, 1.0, n_iters=40)
    elif case == "fit_cd_tol":
        f = solvers.fit_cd_tol(tied, lam1=1.0, lam2=1.0, max_iters=500,
                               tol=1e-10).objective[0]
        ref = _gather_fit_cd(tied, 1.0, 1.0, n_iters=500)
    else:
        f = solvers.fit_cd_tol(tied, lam2=1.0, max_iters=500,
                               tol=1e-10).objective[0]
        ref = solvers.fit_newton(tied, lam2=1.0, n_iters=40,
                                 line_search=True).objective[-1]
    np.testing.assert_allclose(float(f), float(ref), rtol=1e-5)


def test_finetune_with_and_without_hoisted_events(tied, monkeypatch):
    """beam.finetune forms the event weights once; forming them inside
    every coordinate's derivatives gives the same support loss."""
    idx = jnp.asarray([1, 4, 7, 0], jnp.int32)
    mask = jnp.asarray([1.0, 1.0, 1.0, 0.0])
    _, _, hoisted = beam.finetune(tied, idx, mask, 1e-3, 4, n_sweeps=30)
    derivs = cox.coord_derivs
    monkeypatch.setattr(
        cox, "coord_derivs",
        lambda data, eta, xl, order=2, ev=None: derivs(data, eta, xl, order))
    inner = jax.jit(beam.finetune.__wrapped__,
                    static_argnames=("k_max", "n_sweeps"))
    _, _, per_coord = inner(tied, idx, mask, 1e-3, 4, n_sweeps=30)
    np.testing.assert_allclose(float(hoisted), float(per_coord), rtol=1e-12)


def _f32_cohort(ties):
    """n = 200, p = 12 in float32; ``ties`` puts times on a coarse grid."""
    rng = np.random.default_rng(12)
    x = rng.standard_normal((200, 12)).astype(np.float32)
    t = (rng.integers(0, 40, 200) if ties
         else rng.permutation(200)).astype(np.float32)
    delta = (rng.uniform(size=200) < 0.6).astype(np.float32)
    return cox.prepare(x, t, delta)


@pytest.mark.parametrize("penalty", ["scad", "mcp"])
@pytest.mark.parametrize("ties", [False, True])
def test_penalized_at_zero_lam_equals_fit_cd(penalty, ties):
    """With lam1 = 0 the SCAD / MCP prox is the quadratic surrogate's
    Newton step, so the penalized fit walks ``fit_cd(cd_quad)``'s
    path: the two run the one coordinate sweep."""
    data = _f32_cohort(ties)
    pen = solvers.fit_cd_penalized(data, penalty=penalty, lam1=0.0,
                                   lam2=0.1, n_iters=15)
    quad = solvers.fit_cd(data, lam1=0.0, lam2=0.1, n_iters=15,
                          method="cd_quad")
    np.testing.assert_allclose(np.asarray(pen.beta), np.asarray(quad.beta),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(pen.objective),
                               np.asarray(quad.objective), rtol=1e-6)


@pytest.mark.parametrize("ties", [False, True])
def test_finetune_full_support_equals_fit_cd(ties):
    """beam.finetune over every column is the ridge-only ``fit_cd``."""
    data = _f32_cohort(ties)
    idx = jnp.arange(data.p, dtype=jnp.int32)
    mask = jnp.ones(data.p, jnp.float32)
    beta_s, _, _ = beam.finetune(data, idx, mask, 0.1, data.p, n_sweeps=20)
    want = solvers.fit_cd(data, lam1=0.0, lam2=0.1, n_iters=20,
                          method="cd_quad")
    np.testing.assert_allclose(np.asarray(beta_s), np.asarray(want.beta),
                               rtol=0, atol=1e-6)
