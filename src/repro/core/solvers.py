"""Training algorithms for (regularized) CPH.

Ours (the paper's contribution):
  * ``cd_quad``  — coordinate descent on the quadratic surrogate (Eq. 15/17/20)
  * ``cd_cubic`` — coordinate descent on the cubic surrogate (Eq. 16/18/22)

Baselines (Section 2):
  * ``newton``        — exact Newton, full Hessian in beta space (O(n p^2)
                        via the swapped-order identity; no line search, which
                        is exactly the flaw the paper demonstrates)
  * ``newton_ls``     — exact Newton + backtracking (reference optimum)
  * ``quasi_newton``  — glmnet/Simon et al.: diagonal sample-space Hessian,
                        inner CD on the fixed quadratic model
  * ``prox_newton``   — skglm: diagonal majorant w*A, inner CD likewise
  * ``gd``            — proximal gradient with the global 1/L step from the
                        paper's Lipschitz constants (ISTA)

Every solver minimizes  loss(beta) + lam1 ||beta||_1 + lam2 ||beta||_2^2
and returns the objective trace so benchmarks can reproduce Fig. 1 / App. D.

Telemetry: every fit function takes a static ``telemetry`` argument (an
``obs.TelemetryCallback`` or None). When set, each outer iteration emits
(objective, smooth-part gradient norm, ||step||, nnz(beta)) to the host
via ``jax.debug.callback``, and consecutive objective increases beyond
the callback's tol are counted as monotonicity violations — the paper's
descent guarantee as a production invariant. ``telemetry=None`` (the
default) traces the exact pre-telemetry graph: no callback op, no extra
gradient evaluations. Reuse one instance per solver to avoid retraces.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import cox, surrogate
from ..kernels import ops as kops
from ..obs import solver as obs_solver

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FitResult:
    beta: Array        # (p,)
    objective: Array   # (n_iters,) objective after each outer iteration
    n_iters: Array     # scalar int (== len unless early-stopped variant)


def _objective(data: cox.CoxData, eta: Array, beta: Array, lam1, lam2) -> Array:
    return cox.loss_from_eta(data, eta) + cox.penalty(beta, lam1, lam2)


def _emit(telemetry, data, it, eta, beta, beta_prev, obj, lam2) -> None:
    """Stage one telemetry callback (traced code; no-op when disabled).

    Gradient norm is of the smooth part (loss + l2) — the standard
    convergence diagnostic that exists for every solver here, l1 or not.
    The extra ``grad_all`` is only paid when telemetry is on.
    """
    if telemetry is None:
        return
    g = cox.grad_all(data, eta) + 2.0 * lam2 * beta
    obs_solver.emit_iter(telemetry, it, obj, jnp.linalg.norm(g),
                         jnp.linalg.norm(beta - beta_prev),
                         jnp.sum(beta != 0))


# ---------------------------------------------------------------------------
# Coordinate descent (ours)
# ---------------------------------------------------------------------------

def coord_sweep(data: cox.CoxData, eta: Array, beta: Array, cols_t: Array,
                ev: Array, coord_step) -> Tuple[Array, Array]:
    """One coordinate-descent sweep as jnp ops: the columns ``cols_t``
    (p', n) visited in order (a sequential ``lax.fori_loop``). Every jnp
    CD loop over the Cox surrogate runs this body.

    A visit takes (g, h) of the column from ``cox.coord_derivs`` (named
    ``cd.stats``); then, named ``cd.update``, ``step = coord_step(l, g,
    h, beta[l])``, ``beta[l] += step`` and ``eta += step * x_l``.
    ``coord_step`` is the caller's prox; ``ev`` is
    ``cox.risk_start_events(data)``, formed once per solve. Returns the
    new (eta, beta)."""
    def body(l, carry):
        eta, beta = carry
        xl = cols_t[l]
        g, h, _ = cox.coord_derivs(data, eta, xl, order=2, ev=ev)
        with jax.named_scope("cd.update"):
            step = coord_step(l, g, h, beta[l])
            beta = beta.at[l].add(step)
            eta = eta + step * xl
        return eta, beta

    return jax.lax.fori_loop(0, cols_t.shape[0], body, (eta, beta))


def _sweep_prep(data: cox.CoxData, l2c: Array, l3c: Array, ev: Array):
    """The sweep kernel's inputs for one solve (the transposed, padded
    design and the per-column constants), formed once, outside the loop
    over sweeps; None where the kernel does not take this data
    (``kernels.ops.cd_sweep_path`` counts which)."""
    if kops.cd_sweep_path(data.x) != "kernel":
        return None
    return kops.cd_sweep_prepare(data.x, ev, data.delta, l2c, l3c)


def _cd_sweep(data: cox.CoxData, eta: Array, beta: Array, l2c: Array,
              l3c: Array, ev: Array, lam1, lam2, cubic: bool,
              prep=None) -> Tuple[Array, Array]:
    """One full sweep of ``fit_cd``/``fit_cd_tol`` over all p coordinates,
    at the quadratic (``l2c``) or cubic (``l3c``) surrogate's l1 prox.

    With ``prep`` (``_sweep_prep``'s) the sweep is lowered per platform:
    on TPU one Pallas kernel runs the whole sweep (``kernels/cd_sweep.py``,
    its device work named ``cd.stats``), elsewhere ``coord_sweep``.
    Without it, ``coord_sweep`` everywhere."""
    def coord_step(l, g, h, bl):
        a = g + 2.0 * lam2 * bl
        if cubic:
            return surrogate.cubic_l1_prox(a, h + 2.0 * lam2, l3c[l], bl,
                                           lam1)
        return surrogate.quad_l1_prox(a, l2c[l] + 2.0 * lam2, bl, lam1)

    def jnp_sweep(eta, beta):
        return coord_sweep(data, eta, beta, data.x.T, ev, coord_step)

    if prep is None:
        return jnp_sweep(eta, beta)

    def kernel_sweep(eta, beta):
        with jax.named_scope("cd.stats"):
            return kops.cd_sweep(eta, beta, prep, lam1, lam2, cubic=cubic,
                                 interpret=False)

    return jax.lax.platform_dependent(eta, beta, tpu=kernel_sweep,
                                      default=jnp_sweep)


def _cd_solve(data: cox.CoxData, lam1, lam2, beta0, method: str,
              telemetry):
    """What ``fit_cd`` and ``fit_cd_tol`` share: the start (eta, beta),
    with the per-solve constants formed once, and ``step(eta, beta, it)
    -> (eta, beta, objective)``, which runs one sweep, the objective
    (named ``cd.objective``) and the telemetry callback."""
    cubic = method == "cd_cubic"
    beta = jnp.zeros(data.p, data.x.dtype) if beta0 is None else beta0
    eta = data.x @ beta
    l2c, l3c = cox.lipschitz_constants(data)
    ev = cox.risk_start_events(data)
    prep = _sweep_prep(data, l2c, l3c, ev)

    def step(eta, beta, it):
        beta_prev = beta
        eta, beta = _cd_sweep(data, eta, beta, l2c, l3c, ev, lam1, lam2,
                              cubic, prep=prep)
        with jax.named_scope("cd.objective"):
            obj = _objective(data, eta, beta, lam1, lam2)
        _emit(telemetry, data, it, eta, beta, beta_prev, obj, lam2)
        return eta, beta, obj

    return eta, beta, step


@partial(jax.jit, static_argnames=("n_iters", "method", "telemetry"))
def fit_cd(data: cox.CoxData, lam1: float = 0.0, lam2: float = 0.0,
           n_iters: int = 100, beta0: Optional[Array] = None,
           method: str = "cd_quad", telemetry=None) -> FitResult:
    """FastSurvival coordinate descent (quadratic or cubic surrogate),
    ``n_iters`` sweeps over all p coordinates.

    Each sweep runs as one Pallas kernel where the program is lowered for
    TPU and the data takes it (float32, n within the kernel's VMEM budget;
    ``kernels/cd_sweep.py``), else as the jnp ``coord_sweep``. Both take
    tied event times."""
    eta, beta, step = _cd_solve(data, lam1, lam2, beta0, method, telemetry)

    def scan_step(carry, it):
        eta, beta, obj = step(*carry, it)
        return (eta, beta), obj

    (eta, beta), obj = jax.lax.scan(scan_step, (eta, beta),
                                    jnp.arange(n_iters))
    return FitResult(beta=beta, objective=obj, n_iters=jnp.int32(n_iters))


@partial(jax.jit, static_argnames=("max_iters", "method", "telemetry"))
def fit_cd_tol(data: cox.CoxData, lam1: float = 0.0, lam2: float = 0.0,
               max_iters: int = 200, tol: float = 1e-7,
               beta0: Optional[Array] = None,
               method: str = "cd_quad", telemetry=None) -> FitResult:
    """Early-stopping variant (while_loop): stops when the objective
    decrease over one sweep falls below ``tol`` (monotonicity is guaranteed
    by the surrogate majorization, so this is a sound criterion)."""
    eta, beta, step = _cd_solve(data, lam1, lam2, beta0, method, telemetry)
    f0 = _objective(data, eta, beta, lam1, lam2)

    def cond(state):
        _, _, prev, cur, it = state
        return (it < max_iters) & (prev - cur > tol)

    def body(state):
        eta, beta, _, cur, it = state
        eta, beta, obj = step(eta, beta, it)
        return eta, beta, cur, obj, it + 1

    state = (eta, beta, f0 + 2.0 * tol + 1.0, f0, jnp.int32(0))
    eta, beta, _, cur, it = jax.lax.while_loop(cond, body, state)
    return FitResult(beta=beta, objective=cur[None], n_iters=it)


# ---------------------------------------------------------------------------
# Streaming mini-batch CD (BigSurvSGD-style) — large-n path
# ---------------------------------------------------------------------------

def fit_stream(source, lam1: float = 0.0, lam2: float = 0.0,
               n_epochs: int = 200, tol: float = 0.0,
               mode: str = "global", beta0: Optional[Array] = None,
               telemetry=None, max_backtracks: int = 30) -> FitResult:
    """Streaming proximal diagonal-Newton fit over a chunk source.

    ``source`` is any indexable of ``streaming.Chunk``s (``len`` +
    ``[i]``); the full design matrix is never materialized — per epoch
    the chunks are streamed through ``core/streaming.py``'s carried
    suffix-sum statistics, so the working set is one chunk plus O(n)
    scalar caches.

    ``mode="global"`` optimizes the exact full-stream partial likelihood
    (chunks must be globally time-sorted and tie-free) and therefore
    converges to the same optimum as ``fit_cd``; ``mode="chunk"`` is the
    BigSurvSGD estimand — each chunk its own stratum, no cross-chunk
    risk sets, no global-order requirement.

    The update is an all-coordinates quadratic prox step at the exact
    diagonal Hessian, with objective backtracking (the diagonal is not a
    majorizer, so the paper's automatic-descent property is restored by
    halving the step scale until the streamed objective decreases —
    guaranteeing monotonicity, which telemetry verifies live). The fixed
    point is unchanged by the damping: step 0 at a coordinate iff the
    KKT condition holds there.

    Host-orchestrated (one Python loop per epoch), eager jnp per chunk;
    telemetry fires eagerly through the same ``TelemetryCallback``.
    """
    from . import streaming

    if mode == "global":
        grad_hess = streaming.streaming_grad_hess
        loss_fn = streaming.streaming_loss
    elif mode == "chunk":
        grad_hess = streaming.stratified_grad_hess
        loss_fn = streaming.stratified_loss
    else:
        raise ValueError(f"unknown mode: {mode!r}")

    p = source[0].x.shape[1]
    dtype = source[0].x.dtype
    beta = jnp.zeros(p, dtype) if beta0 is None else beta0
    obj = loss_fn(source, beta) + cox.penalty(beta, lam1, lam2)
    objs = []
    step_scale = 1.0
    it = 0
    for it in range(n_epochs):
        g_s, h_s, _ = grad_hess(source, beta)
        g = g_s + 2.0 * lam2 * beta
        h = jnp.maximum(h_s + 2.0 * lam2, 1e-12)
        cand, new_obj = beta, obj
        for _ in range(max_backtracks):
            step = surrogate.quad_l1_prox(g, h / step_scale, beta, lam1)
            cand = beta + step
            new_obj = loss_fn(source, cand) + cox.penalty(cand, lam1, lam2)
            if float(new_obj) <= float(obj):
                break
            step_scale *= 0.5
        else:
            objs.append(obj)   # no descent step left: converged
            break
        prev, beta, obj = obj, cand, new_obj
        objs.append(obj)
        if telemetry is not None:
            obs_solver.emit_iter(telemetry, jnp.int32(it), obj,
                                 jnp.linalg.norm(g), jnp.linalg.norm(step),
                                 jnp.sum(beta != 0))
        step_scale = min(step_scale * 2.0, 1.0)
        if tol > 0.0 and float(prev) - float(obj) < tol:
            break
    return FitResult(beta=beta, objective=jnp.stack(objs),
                     n_iters=jnp.int32(it + 1))


# ---------------------------------------------------------------------------
# Newton-type baselines
# ---------------------------------------------------------------------------

def _newton_direction(data, eta, beta, lam2) -> Tuple[Array, Array]:
    g = cox.grad_all(data, eta) + 2.0 * lam2 * beta
    h = cox.exact_hessian(data, eta) + 2.0 * lam2 * jnp.eye(data.p, dtype=eta.dtype)
    h = h + 1e-9 * jnp.eye(data.p, dtype=eta.dtype)
    return jnp.linalg.solve(h, -g), g


@partial(jax.jit, static_argnames=("n_iters", "line_search", "telemetry"))
def fit_newton(data: cox.CoxData, lam2: float = 0.0, n_iters: int = 50,
               beta0: Optional[Array] = None,
               line_search: bool = False, telemetry=None) -> FitResult:
    """Exact Newton (lam1 unsupported, as in the paper). ``line_search=True``
    adds Armijo backtracking and serves as the high-precision reference."""
    beta = jnp.zeros(data.p, data.x.dtype) if beta0 is None else beta0

    def step(carry, it):
        beta = carry
        beta_prev = beta
        eta = data.x @ beta
        d, g = _newton_direction(data, eta, beta, lam2)
        if line_search:
            f0 = _objective(data, eta, beta, 0.0, lam2)
            gd = g @ d

            def ls_body(state):
                t, _ = state
                return t * 0.5, _objective(
                    data, data.x @ (beta + t * 0.5 * d), beta + t * 0.5 * d,
                    0.0, lam2)

            def ls_cond(state):
                t, f = state
                return (f > f0 + 1e-4 * t * gd) & (t > 1e-8)

            f1 = _objective(data, data.x @ (beta + d), beta + d, 0.0, lam2)
            t, _ = jax.lax.while_loop(ls_cond, ls_body, (1.0, f1))
            beta = beta + t * d
        else:
            beta = beta + d
        eta = data.x @ beta
        obj = _objective(data, eta, beta, 0.0, lam2)
        _emit(telemetry, data, it, eta, beta, beta_prev, obj, lam2)
        return beta, obj

    beta, obj = jax.lax.scan(step, beta, jnp.arange(n_iters))
    return FitResult(beta=beta, objective=obj, n_iters=jnp.int32(n_iters))


def _inner_cd_quadratic(data: cox.CoxData, dvec: Array, g: Array, beta: Array,
                        lam1, lam2, sweeps: int) -> Array:
    """Solve min_D g^T D + 1/2 D^T X^T diag(dvec) X D + pen(beta + D) by CD.

    Maintains r = diag(dvec) X D so each coordinate touch is O(n); this is
    the glmnet inner loop (all-coefficients-at-once quadratic model)."""
    xT = data.x.T
    q = jnp.maximum((data.x * data.x * dvec[:, None]).sum(0), 1e-12)  # (p,)

    def coord(l, carry):
        delta, r = carry
        xl = xT[l]
        a = g[l] + xl @ r + 2.0 * lam2 * (beta[l] + delta[l])
        b = q[l] + 2.0 * lam2
        step = surrogate.quad_l1_prox(a, b, beta[l] + delta[l], lam1)
        return delta.at[l].add(step), r + (step * dvec) * xl

    def sweep(_, carry):
        return jax.lax.fori_loop(0, data.p, coord, carry)

    delta0 = jnp.zeros_like(beta)
    r0 = jnp.zeros_like(dvec)
    delta, _ = jax.lax.fori_loop(0, sweeps, sweep, (delta0, r0))
    return delta


@partial(jax.jit, static_argnames=("n_iters", "variant", "inner_sweeps",
                                   "telemetry"))
def fit_working_newton(data: cox.CoxData, lam1: float = 0.0, lam2: float = 0.0,
                       n_iters: int = 50, beta0: Optional[Array] = None,
                       variant: str = "quasi",
                       inner_sweeps: int = 3, telemetry=None) -> FitResult:
    """quasi_newton (Simon et al. 2011) / prox_newton (skglm) baselines."""
    beta = jnp.zeros(data.p, data.x.dtype) if beta0 is None else beta0

    def step(carry, it):
        beta = carry
        beta_prev = beta
        eta = data.x @ beta
        g = cox.grad_all(data, eta)
        if variant == "quasi":
            dvec = cox.eta_hessian_diag(data, eta)
        else:
            dvec = cox.eta_hessian_upper(data, eta)
        dvec = jnp.maximum(dvec, 1e-12)
        delta = _inner_cd_quadratic(data, dvec, g, beta, lam1, lam2,
                                    inner_sweeps)
        beta = beta + delta
        eta = data.x @ beta
        obj = _objective(data, eta, beta, lam1, lam2)
        _emit(telemetry, data, it, eta, beta, beta_prev, obj, lam2)
        return beta, obj

    beta, obj = jax.lax.scan(step, beta, jnp.arange(n_iters))
    return FitResult(beta=beta, objective=obj, n_iters=jnp.int32(n_iters))


@partial(jax.jit, static_argnames=("n_iters", "telemetry"))
def fit_gd(data: cox.CoxData, lam1: float = 0.0, lam2: float = 0.0,
           n_iters: int = 200, beta0: Optional[Array] = None,
           telemetry=None) -> FitResult:
    """Proximal gradient (ISTA) with the paper-derived global step 1/L,
    L = sum_l L2_l + 2 lam2 (trace bound on the Hessian spectrum)."""
    beta = jnp.zeros(data.p, data.x.dtype) if beta0 is None else beta0
    l2c, _ = cox.lipschitz_constants(data)
    lr = 1.0 / (jnp.sum(l2c) + 2.0 * lam2 + 1e-12)

    def step(carry, it):
        beta = carry
        beta_prev = beta
        eta = data.x @ beta
        g = cox.grad_all(data, eta) + 2.0 * lam2 * beta
        z = beta - lr * g
        beta = jnp.sign(z) * jnp.maximum(jnp.abs(z) - lr * lam1, 0.0)
        eta = data.x @ beta
        obj = _objective(data, eta, beta, lam1, lam2)
        _emit(telemetry, data, it, eta, beta, beta_prev, obj, lam2)
        return beta, obj

    beta, obj = jax.lax.scan(step, beta, jnp.arange(n_iters))
    return FitResult(beta=beta, objective=obj, n_iters=jnp.int32(n_iters))


SOLVERS = {
    "cd_quad": lambda data, lam1, lam2, n, b0=None: fit_cd(
        data, lam1, lam2, n, b0, method="cd_quad"),
    "cd_cubic": lambda data, lam1, lam2, n, b0=None: fit_cd(
        data, lam1, lam2, n, b0, method="cd_cubic"),
    "newton": lambda data, lam1, lam2, n, b0=None: fit_newton(
        data, lam2, n, b0, line_search=False),
    "newton_ls": lambda data, lam1, lam2, n, b0=None: fit_newton(
        data, lam2, n, b0, line_search=True),
    "quasi_newton": lambda data, lam1, lam2, n, b0=None: fit_working_newton(
        data, lam1, lam2, n, b0, variant="quasi"),
    "prox_newton": lambda data, lam1, lam2, n, b0=None: fit_working_newton(
        data, lam1, lam2, n, b0, variant="prox"),
    "gd": lambda data, lam1, lam2, n, b0=None: fit_gd(data, lam1, lam2, n, b0),
}


@partial(jax.jit, static_argnames=("n_iters", "penalty"))
def fit_cd_penalized(data: cox.CoxData, penalty: str = "scad",
                     lam1: float = 0.1, gamma: float = 3.7,
                     lam2: float = 0.0, n_iters: int = 100,
                     beta0: Optional[Array] = None) -> FitResult:
    """Quadratic-surrogate CD with nonconvex separable penalties (SCAD /
    MCP — the §3.5 extensions). Same O(n) coordinate machinery; the
    coordinate update is the penalty prox at the surrogate's Newton point.
    Objective trace uses the true penalized objective; descent still holds
    per coordinate because the prox minimizes the majorizer exactly."""
    from . import penalties

    prox = penalties.PROX[penalty]
    pval = penalties.VALUE[penalty]
    beta = jnp.zeros(data.p, data.x.dtype) if beta0 is None else beta0
    eta = data.x @ beta
    l2c, _ = cox.lipschitz_constants(data)
    ev = cox.risk_start_events(data)
    xT = data.x.T

    def coord_step(l, g, h, bl):
        a = g + 2.0 * lam2 * bl
        return prox(a, l2c[l] + 2.0 * lam2, bl, lam1, gamma)

    def sweep(carry, _):
        eta, beta = coord_sweep(data, *carry, xT, ev, coord_step)
        obj = cox.loss_from_eta(data, eta) + lam2 * jnp.sum(beta * beta) \
            + pval(beta, lam1, gamma)
        return (eta, beta), obj

    (eta, beta), obj = jax.lax.scan(sweep, (eta, beta), None,
                                    length=n_iters)
    return FitResult(beta=beta, objective=obj, n_iters=jnp.int32(n_iters))
