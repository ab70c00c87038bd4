"""The chunked SSD kernel (``kernels/ssd.py``) in interpret mode on the CPU
against the jnp bodies it replaces on TPU (``models/ssm._ssd_chunked`` and
``_ssd_chunked_grouped``): y, the final state, and the cotangents of x,
dt, A, B and C through ``jax.grad`` of a scalar of both outputs.

With its matmul operands kept in float32 the kernel is the jnp body's
algebra, so the two agree to float32 rounding; as it runs (bfloat16
operands, float32 accumulation, as the chip's default precision rounds
the jnp body's einsums) they agree to bfloat16 rounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ssd as ssd_kernel
from repro.models import ssm


@pytest.fixture(autouse=True)
def f32():
    """The program's 32-bit default (other modules turn x64 on)."""
    before = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", before)


# (batch, seq, heads, head width, groups, state size, chunk)
SHAPES = {
    "one_group": (2, 48, 4, 64, 1, 128, 16),
    "groups": (1, 48, 4, 64, 2, 128, 16),
    "one_group_part_chunk": (1, 40, 2, 128, 1, 128, 16),
    "groups_part_chunk": (1, 37, 8, 32, 2, 128, 8),
    # a chunk of two 128-row blocks of the (Q, Q) tiles
    "one_group_long_chunk": (1, 300, 2, 64, 1, 128, 256),
}
# largest |kernel - jnp| over the largest |jnp|, by operand precision; in
# float32 the cumulative log decay reaches some hundreds over a chunk of
# 256, so exp(cum_t - cum_s) carries |cum| x 6e-8 of rounding in each body
TOL = {"float32": 1e-4, "bfloat16": 2.5e-2}


def inputs(b, s, h, hd, g, n, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    args = (jax.random.normal(k[0], (b, s, h, hd)),
            jax.nn.softplus(jax.random.normal(k[1], (b, s, h)) - 1.0),
            -jnp.exp(jnp.linspace(0.0, 2.5, h)),
            0.5 * jax.random.normal(k[2], (b, s, g, n)),
            0.5 * jax.random.normal(k[3], (b, s, g, n)))
    return args, jax.random.normal(k[4], (b, s, h, hd)), \
        jax.random.normal(k[5], (b, h, hd, n))


def outputs_and_grads(body, args, wy, ws):
    def scalar(*a):
        y, st = body(*a)
        return jnp.sum(y * wy) + jnp.sum(st * ws)

    run = jax.jit(lambda *a: (body(*a), jax.grad(scalar,
                                                 argnums=range(5))(*a)))
    (y, st), grads = run(*args)
    return [np.asarray(v) for v in (y, st, *grads)]


@pytest.mark.parametrize("operands", sorted(TOL))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_ssd_kernel_matches_jnp_body(shape, operands, monkeypatch):
    b, s, h, hd, g, n, q = SHAPES[shape]
    assert ssd_kernel.tiles(h, hd, g, n, q)
    if operands == "float32":
        monkeypatch.setattr(ssd_kernel, "_rounder",
                            lambda interpret: lambda v: v)
    args, wy, ws = inputs(b, s, h, hd, g, n)
    got = outputs_and_grads(
        lambda *a: ssd_kernel.ssd(*a, q, interpret=True), args, wy, ws)
    with jax.default_matmul_precision("highest"):
        want = outputs_and_grads(lambda *a: ssm._ssd_chunked(*a, q), args,
                                 wy, ws)
    for name, x, w in zip(("y", "state", "dx", "ddt", "dA", "dB", "dC"),
                          got, want):
        assert x.shape == w.shape and x.dtype == w.dtype, name
        gap = float(np.max(np.abs(x - w))) / float(np.max(np.abs(w)))
        assert gap < TOL[operands], (name, gap)
