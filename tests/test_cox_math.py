"""Theorem 3.1 / Lemma 3.2 / Corollary 3.3 validation against autodiff.

The loss is written independently (naive O(n^2) risk-set form) and the
paper's O(n) formulas are checked against jax.grad / nested grads of it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cox
from repro.data.synthetic import make_tied_survival

jax.config.update("jax_enable_x64", True)


def naive_loss(x, t, delta, beta):
    """O(n^2) direct implementation of Eq. (4) with Breslow risk sets."""
    eta = x @ beta
    n = x.shape[0]
    total = 0.0
    for i in range(n):
        mask = t >= t[i]
        total = total + delta[i] * (
            jnp.log(jnp.sum(mask * jnp.exp(eta))) - eta[i]
        )
    return total


@pytest.fixture(scope="module")
def small():
    x, t, delta = make_tied_survival(n=60, p=5, n_times=12, seed=1)
    x = x.astype(np.float64)
    data = cox.prepare(x, t, delta)
    rng = np.random.default_rng(3)
    beta = rng.standard_normal(5) * 0.3
    return x, t, delta, data, jnp.asarray(beta)


def test_loss_matches_naive(small):
    x, t, delta, data, beta = small
    ours = cox.objective(data, beta)
    ref = naive_loss(jnp.asarray(x), jnp.asarray(t), jnp.asarray(delta), beta)
    np.testing.assert_allclose(ours, ref, rtol=1e-10)


def test_grad_all_matches_autodiff(small):
    x, t, delta, data, beta = small
    g_ref = jax.grad(
        lambda b: naive_loss(jnp.asarray(x), jnp.asarray(t),
                             jnp.asarray(delta), b))(beta)
    g = cox.grad_all(data, data.x @ beta)
    np.testing.assert_allclose(g, g_ref, rtol=1e-8, atol=1e-10)


def test_coord_derivs_match_autodiff(small):
    x, t, delta, data, beta = small
    xj, tj, dj = jnp.asarray(x), jnp.asarray(t), jnp.asarray(delta)
    f = lambda b: naive_loss(xj, tj, dj, b)
    g_ref = jax.grad(f)(beta)
    h_ref = jnp.diagonal(jax.hessian(f)(beta))
    for l in range(data.p):
        # third derivative along coordinate l via nested scalar grads
        fl = lambda s: f(beta.at[l].set(s))
        d3 = jax.grad(jax.grad(jax.grad(fl)))(beta[l])
        g, h, c3 = cox.coord_derivs(data, data.x @ beta, data.x[:, l], order=3)
        np.testing.assert_allclose(g, g_ref[l], rtol=1e-8, atol=1e-10)
        np.testing.assert_allclose(h, h_ref[l], rtol=1e-8, atol=1e-10)
        np.testing.assert_allclose(c3, d3, rtol=1e-6, atol=1e-8)


def test_grad_hess_all_matches_coord(small):
    _, _, _, data, beta = small
    eta = data.x @ beta
    g_all, h_all = cox.grad_hess_all(data, eta)
    for l in range(data.p):
        g, h, _ = cox.coord_derivs(data, eta, data.x[:, l])
        np.testing.assert_allclose(g_all[l], g, rtol=1e-9)
        np.testing.assert_allclose(h_all[l], h, rtol=1e-9)


def test_exact_hessian_matches_autodiff(small):
    x, t, delta, data, beta = small
    xj, tj, dj = jnp.asarray(x), jnp.asarray(t), jnp.asarray(delta)
    h_ref = jax.hessian(lambda b: naive_loss(xj, tj, dj, b))(beta)
    h = cox.exact_hessian(data, data.x @ beta)
    np.testing.assert_allclose(h, h_ref, rtol=1e-7, atol=1e-9)


def test_eta_gradient_matches_autodiff(small):
    _, _, _, data, beta = small
    eta = data.x @ beta
    g_ref = jax.grad(lambda e: cox.loss_from_eta(data, e))(eta)
    np.testing.assert_allclose(cox.eta_gradient(data, eta), g_ref,
                               rtol=1e-8, atol=1e-10)


def test_eta_hessian_diag_matches_autodiff(small):
    _, _, _, data, beta = small
    eta = data.x @ beta
    h_full = jax.hessian(lambda e: cox.loss_from_eta(data, e))(eta)
    np.testing.assert_allclose(
        cox.eta_hessian_diag(data, eta), jnp.diagonal(h_full),
        rtol=1e-7, atol=1e-10)
    # majorant dominates the diagonal
    assert np.all(np.asarray(cox.eta_hessian_upper(data, eta))
                  >= np.asarray(jnp.diagonal(h_full)) - 1e-12)


def test_moment_recursion_lemma_3_2(small):
    """dC_r/dbeta_l == C_{r+1} - r C_2 C_{r-1}, checked per event row."""
    _, _, _, data, beta = small
    l = 2
    xl = data.x[:, l]

    def cr_of_beta(b, r):
        return cox.central_moment(data, data.x @ b, xl, r)

    for r in (2, 3, 4):
        jac = jax.jacobian(lambda b: cr_of_beta(b, r))(beta)[:, l]
        rhs = (cr_of_beta(beta, r + 1)
               - r * cr_of_beta(beta, 2) * cr_of_beta(beta, r - 1))
        np.testing.assert_allclose(jac, rhs, rtol=1e-6, atol=1e-9)


def test_third_derivative_not_fourth_moment(small):
    """Sanity for the paper's negative result: for r>=3 the pattern breaks;
    C_2' == C_3 but C_3' != C_4 in general."""
    _, _, _, data, beta = small
    l = 1
    xl = data.x[:, l]
    jac3 = jax.jacobian(
        lambda b: cox.central_moment(data, data.x @ b, xl, 3))(beta)[:, l]
    c4 = cox.central_moment(data, data.x @ beta, xl, 4)
    assert not np.allclose(np.asarray(jac3), np.asarray(c4), rtol=1e-3)


def gather_derivs(data, eta, xl):
    """(g, h, c3) as sums over events of the moments read at each event's
    ``risk_start``: the per-event form of Theorem 3.1."""
    w = jnp.exp(eta - jnp.max(eta))
    rs = data.risk_start
    s0 = cox.revcumsum(w)[rs]
    m1, m2, m3 = (cox.revcumsum(w * xl**j)[rs] / s0 for j in (1, 2, 3))
    d = data.delta
    return (jnp.sum(d * (m1 - xl)), jnp.sum(d * (m2 - m1 * m1)),
            jnp.sum(d * (m3 + 2.0 * m1**3 - 3.0 * m2 * m1)))


@pytest.fixture(scope="module", params=["tied", "tie_free"])
def cohort(request, small):
    if request.param == "tied":
        _, _, _, data, beta = small
        return request.param, data, beta
    rng = np.random.default_rng(7)
    x = rng.standard_normal((80, 5))
    t = rng.permutation(80).astype(np.float64) + 1.0
    delta = (rng.uniform(size=80) < 0.6).astype(np.float64)
    return (request.param, cox.prepare(x, t, delta),
            jnp.asarray(rng.standard_normal(5) * 0.3))


def test_risk_start_events(cohort):
    kind, data, _ = cohort
    ev = np.asarray(cox.risk_start_events(data))
    delta = np.asarray(data.delta)
    rs = np.asarray(data.risk_start)
    assert ev.dtype == delta.dtype
    np.testing.assert_allclose(ev.sum(), delta.sum(), rtol=1e-12)
    assert np.all(ev[rs != np.arange(data.n)] == 0.0)
    if kind == "tie_free":
        np.testing.assert_array_equal(ev, delta)
    else:
        assert np.any(rs != np.arange(data.n))


@pytest.mark.parametrize("order", [2, 3])
def test_coord_derivs_with_events_match_gather(cohort, order):
    _, data, beta = cohort
    eta = data.x @ beta
    ev = cox.risk_start_events(data)
    for l in range(data.p):
        xl = data.x[:, l]
        ours = cox.coord_derivs(data, eta, xl, order=order, ev=ev)
        ref = gather_derivs(data, eta, xl)
        for k in range(order):
            np.testing.assert_allclose(ours[k], ref[k], rtol=1e-5)


def test_coord_derivs_finite_where_tail_hazards_underflow():
    """A float32 cohort whose last tie group's hazards underflow past its
    first member: rc0 is 0 at positions that start no risk set, and the
    moments there are 0/0. They must add nothing."""
    x, t, delta = make_tied_survival(n=60, p=3, n_times=12, seed=1)
    tail = np.argsort(t)[-5:]           # one last tie group of five,
    t[tail] = t.max()                   # two of them events
    delta[tail[:2]] = 1.0
    data = cox.prepare(jnp.asarray(x), jnp.asarray(t), jnp.asarray(delta))
    start = int(data.risk_start[-1])
    assert start == data.n - 5
    eta = np.zeros(data.n, np.float32)
    eta[start + 1:] = -200.0
    eta = jnp.asarray(eta)
    rc0 = cox.revcumsum(jnp.exp(eta - jnp.max(eta)))
    assert float(rc0[-1]) == 0.0 and float(rc0[start]) > 0.0
    ev = cox.risk_start_events(data)
    for l in range(data.p):
        xl = data.x[:, l]
        g, h, _ = cox.coord_derivs(data, eta, xl, ev=ev)
        g_ref, h_ref, _ = gather_derivs(data, eta, xl)
        assert np.isfinite(float(g)) and np.isfinite(float(h))
        np.testing.assert_allclose(g, g_ref, rtol=1e-5)
        np.testing.assert_allclose(h, h_ref, rtol=1e-5)
