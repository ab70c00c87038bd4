"""Quadratic and cubic surrogate minimizers and their l1-regularized
analytic solutions (Section 3.4/3.5 and Appendix A.4/A.5 of FastSurvival).

Every function here is a scalar map (jnp-vectorizable, jit/vmap safe,
branchless) so CD sweeps can run inside ``lax.fori_loop``/``scan``.

Notation follows the paper:
  quadratic surrogate at x:  g(D) = f(x) + a D + 1/2 b D^2,   a=f'(x), b=L2
  cubic surrogate at x:      h(D) = f(x) + a D + 1/2 b D^2 + 1/6 c |D|^3,
                             a=f'(x), b=f''(x), c=L3
Ridge (lam2 ||.||^2) is absorbed by a += 2 lam2 x, b += 2 lam2 (footnote 2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array
_EPS = 1e-12


def quad_min(a: Array, b: Array) -> Array:
    """argmin a*D + 1/2 b D^2  =  -a/b (Eq. 17)."""
    return -a / jnp.maximum(b, _EPS)


def cubic_min(a: Array, b: Array, c: Array) -> Array:
    """argmin a*D + 1/2 b D^2 + 1/6 c |D|^3 (Eq. 18).

    = sgn(a) * (b - sqrt(b^2 + 2 c |a|)) / c, with a Newton fallback as
    c -> 0. Numerically rewritten to avoid catastrophic cancellation:
    (b - sqrt(b^2 + 2c|a|))/c = -2|a| / (b + sqrt(b^2 + 2c|a|)).
    """
    c = jnp.maximum(c, 0.0)
    disc = jnp.sqrt(b * b + 2.0 * c * jnp.abs(a))
    step = -2.0 * jnp.abs(a) / jnp.maximum(b + disc, _EPS)
    return jnp.sign(a) * step


def quad_l1_prox(a: Array, b: Array, c: Array, lam1: Array) -> Array:
    """argmin a*D + 1/2 b D^2 + lam1 |c + D|  (Eq. 20); c = current coord.

    Equivalent to soft-thresholding the Newton point of the surrogate.
    """
    b = jnp.maximum(b, _EPS)
    u = b * c - a
    z = jnp.sign(u) * jnp.maximum(jnp.abs(u) - lam1, 0.0) / b  # new coord value
    return z - c


def _cubic_piece_value(delta: Array, a: Array, b: Array, c: Array,
                       lam1: Array, d: Array) -> Array:
    """Objective a D + 1/2 b D^2 + 1/6 c |D|^3 + lam1 |d + D|."""
    return (a * delta + 0.5 * b * delta * delta
            + (c / 6.0) * jnp.abs(delta) ** 3 + lam1 * jnp.abs(d + delta))


def cubic_l1_prox(a: Array, b: Array, c: Array, d: Array, lam1: Array) -> Array:
    """argmin_D a D + 1/2 b D^2 + 1/6 c |D|^3 + lam1 |d + D| (Eq. 21/22).

    Robust candidate-enumeration form: the objective is piecewise smooth with
    kinks at D = 0 (from |D|^3's derivative pieces) and D = -d; on each
    smooth piece the stationary point solves a quadratic. We enumerate every
    stationary candidate clamped to its validity interval plus both kinks and
    take the argmin — branchless, exactly equivalent to the paper's Eq. (22)
    case analysis but immune to sgn(0) edge cases.
    """
    a, b, c, d, lam1 = map(jnp.asarray, (a, b, c, d, lam1))
    c = jnp.maximum(c, 0.0)
    cands = []
    # Pieces indexed by (sign of D -> s3 in {+1,-1}, sign of d+D -> s1):
    # derivative: a + b D + s3 * c/2 D^2 + s1 * lam1 = 0
    for s3 in (1.0, -1.0):
        for s1 in (1.0, -1.0):
            aa = 0.5 * s3 * c
            bb = b
            cc = a + s1 * lam1
            disc = bb * bb - 4.0 * aa * cc
            sq = jnp.sqrt(jnp.maximum(disc, 0.0))
            valid = disc >= 0.0
            for sgn in (1.0, -1.0):
                # quadratic root (guard aa ~ 0 -> linear root)
                root_q = (-bb + sgn * sq) / jnp.where(
                    jnp.abs(2.0 * aa) < _EPS, jnp.inf, 2.0 * aa
                )
                root_l = -cc / jnp.where(jnp.abs(bb) < _EPS, jnp.inf, bb)
                root = jnp.where(jnp.abs(aa) < _EPS, root_l, root_q)
                # validity: sign(root) == s3 and sign(d + root) == s1
                ok = (
                    valid
                    & (root * s3 >= 0.0)
                    & ((d + root) * s1 >= 0.0)
                    & jnp.isfinite(root)
                )
                cands.append(jnp.where(ok, root, 0.0))
    cands.append(jnp.zeros_like(a))      # kink at D = 0
    cands.append(-d)                     # kink at D = -d
    # the first candidate of least value (argmin's choice), selected
    # elementwise so that array arguments work as scalars do
    best = cands[0]
    best_val = _cubic_piece_value(best, a, b, c, lam1, d)
    for cand in cands[1:]:
        val = _cubic_piece_value(cand, a, b, c, lam1, d)
        better = val < best_val
        best = jnp.where(better, cand, best)
        best_val = jnp.where(better, val, best_val)
    return best


def cubic_l1_prox_paper(a: Array, b: Array, c: Array, d: Array,
                        lam1: Array) -> Array:
    """Eq. (22) unified formula, with the appendix-correct signs.

    NOTE (reproduction finding): the unified formula printed as Eq. (22) in
    the main text has ``(b + sqrt(b^2 + 2c(...)))/c`` in its second and third
    branches, but the case-by-case derivation in Appendix A.5 (cases 3 and 5
    for d>=0, cases 1 and 3 for d<0) yields ``(b - sqrt(...))/c`` — with the
    published "+" the step lands on the wrong side of 0 (e.g. a=1, b=0, c=1,
    d=1, lam1=0 gives +sqrt(2) instead of the true minimizer -sqrt(2)). We
    follow the appendix; tests cross-check against grid search and against
    the branch-free candidate solver above.

    Valid when d != 0 (paper's case analysis); sgn(0) handled by falling
    back to the d=0 analysis (threshold at |a| <= lam1).
    """
    c = jnp.maximum(c, _EPS)
    s = jnp.sign(d)
    cond1 = s * a + lam1 <= 0.0
    cond2 = s * (a - b * d) - 0.5 * c * d * d > lam1
    cond3 = s * (a - b * d) - 0.5 * c * d * d < -lam1
    r1 = s * (-b + jnp.sqrt(jnp.maximum(b * b - 2.0 * c * (s * a + lam1), 0.0))) / c
    r2 = s * (b - jnp.sqrt(jnp.maximum(b * b + 2.0 * c * (s * a - lam1), 0.0))) / c
    r3 = s * (b - jnp.sqrt(jnp.maximum(b * b + 2.0 * c * (s * a + lam1), 0.0))) / c
    out = jnp.where(cond1, r1, jnp.where(cond2, r2, jnp.where(cond3, r3, -d)))
    # d == 0: soft-threshold then one-sided cubic root
    a0 = jnp.abs(a) - lam1
    zero_step = jnp.where(
        a0 <= 0.0,
        0.0,
        -jnp.sign(a) * 2.0 * a0 / (b + jnp.sqrt(b * b + 2.0 * c * a0)),
    )
    return jnp.where(d == 0.0, zero_step, out)


def quad_decrease(a: Array, b: Array) -> Array:
    """Guaranteed decrease of the quadratic surrogate: a^2 / (2b).
    Used by beam search to score candidate coordinates."""
    return 0.5 * a * a / jnp.maximum(b, _EPS)
