"""Compile every Pallas kernel of the main path for a described TPU v5e.

Interpret mode accepts block layouts that the chip's compiler refuses (a
block whose last two dims are neither (8, 128)-aligned nor the whole
array). These tests lower each kernel at a real shape against a v5e chip
that is described, not attached, and require the Mosaic kernel in the
compiled program. The paper's coordinate-descent fit is compiled the same
way, to guard what its hot loop holds. No device runs anything here.

The topology is described inside a module-scoped fixture, so only the
worker that runs this file loads the TPU compiler library.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import cox, solvers
from repro.kernels import autotune, cd_sweep, ssd
from repro.kernels.cox_batch import cox_batch
from repro.kernels.revcumsum import revcumsum
from repro.kernels.survival_curves import (survival_curves,
                                           survival_curves_stratified)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_config():
    """The program's own JAX config: 32-bit (other test modules turn x64
    on at import, and Mosaic refuses 64-bit index maps), and no persistent
    cache (a described-chip compile cannot be read back without the
    chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    before = {k: getattr(jax.config, k) for k in
              ("jax_enable_compilation_cache", "jax_enable_x64")}
    for k in before:
        jax.config.update(k, False)
    compilation_cache.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


F32, I32 = jnp.float32, jnp.int32
N_SWEEP = P_SWEEP = 1200
# the largest n the sweep kernel takes (cd_sweep.fits)
N_SWEEP_MAX = max(k * cd_sweep.TILE for k in range(1, 1024)
                  if cd_sweep.fits(k * cd_sweep.TILE))


def _cd_sweep(eta, beta, xt, ev, cols, lam1, lam2, cubic, interpret):
    prep = dict(xt=xt, ev=ev, cols=cols)
    return cd_sweep.cd_sweep(eta, beta, prep, lam1, lam2, cubic=cubic,
                             interpret=interpret)


def _sweep_shapes(n, p):
    """``_cd_sweep``'s argument shapes for n samples and p columns."""
    rows = cd_sweep.n_padded(n) // cd_sweep.LANES
    blocks = -(-p // cd_sweep.BLOCK_COLS)
    return [((n,), F32), ((p,), F32),
            ((blocks * cd_sweep.BLOCK_COLS, rows, cd_sweep.LANES), F32),
            ((rows, cd_sweep.LANES), F32),
            ((blocks, 3, cd_sweep.BLOCK_COLS), F32),
            ((), F32), ((), F32)]


_SWEEP_SHAPES = _sweep_shapes(N_SWEEP, P_SWEEP)


def _ssd_fwd(x, dt, a, b, c, chunk, interpret):
    return ssd.ssd(x, dt, a, b, c, chunk, interpret=interpret)


def _ssd_bwd(x, dt, a, b, c, chunk, interpret):
    """The SSD's cotangents (its forward kernel, residuals kept, then its
    backward kernel) of a scalar of y and of the final state."""
    def scalar(*args):
        y, st = ssd.ssd(*args, chunk, interpret=interpret)
        return jnp.sum(y) + jnp.sum(st)

    return jax.grad(scalar, argnums=range(5))(x, dt, a, b, c)


def _ssd_shapes(b, s, h, hd, g, n):
    return [((b, s, h, hd), F32), ((b, s, h), F32), ((h,), F32),
            ((b, s, g, n), F32), ((b, s, g, n), F32)]


# the SSD of mamba2.train (chunk 256, one group of 24 heads) and of
# nemotron3.train (chunk 128, 8 groups of 8 heads), 32 x 512 tokens
_SSD_MAMBA2 = _ssd_shapes(32, 512, 24, 64, 1, 128)
_SSD_NEMOTRON3 = _ssd_shapes(32, 512, 64, 64, 8, 128)

# (kernel, default-config key, fixed kwargs, argument shapes)
CASES = {
    "cox_batch": (
        cox_batch, "cox_batch", {},
        [((65536, 256), F32)] + [((65536,), F32)] * 5),
    "revcumsum_m1": (
        revcumsum, "revcumsum", {}, [((65536, 1), F32)]),
    "revcumsum_m256": (
        revcumsum, "revcumsum", {}, [((65536, 256), F32)]),
    "survival_curves": (
        survival_curves, "survival_curves", {},
        [((1024,), F32), ((2048,), F32)]),
    "survival_curves_stratified": (
        survival_curves_stratified, "survival_curves_strat", {},
        [((1024,), F32), ((8, 2048), F32), ((1024,), I32)]),
    "cd_sweep_quad": (_cd_sweep, None, {"cubic": False}, _SWEEP_SHAPES),
    "cd_sweep_cubic": (_cd_sweep, None, {"cubic": True}, _SWEEP_SHAPES),
    "cd_sweep_max_n": (_cd_sweep, None, {"cubic": True},
                       _sweep_shapes(N_SWEEP_MAX, 2 * cd_sweep.BLOCK_COLS)),
    "ssd_fwd_mamba2": (_ssd_fwd, None, {"chunk": 256}, _SSD_MAMBA2),
    "ssd_bwd_mamba2": (_ssd_bwd, None, {"chunk": 256}, _SSD_MAMBA2),
    "ssd_fwd_nemotron3": (_ssd_fwd, None, {"chunk": 128}, _SSD_NEMOTRON3),
    "ssd_bwd_nemotron3": (_ssd_bwd, None, {"chunk": 128}, _SSD_NEMOTRON3),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, chip_config):
    kernel, cfg_key, kwargs, shapes = CASES[case]
    blocks = autotune.DEFAULT_CONFIGS[cfg_key] if cfg_key else {}
    fn = functools.partial(kernel, **kwargs, **blocks, interpret=False)
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


def test_coordinate_sweep_has_no_risk_set_gather(one_chip, chip_config):
    """The paper's n = p = 1200 fit as the benchmark's fit driver calls it:
    the per-coordinate risk-set statistics read no gather at
    ``risk_start`` (the event weights stand in for it)."""
    n = p = 1200
    one = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    data = cox.CoxData(x=one((n, p), F32), delta=one((n,), F32),
                       risk_start=one((n,), I32), tie_end=one((n,), I32))
    fit = functools.partial(solvers.fit_cd_tol, lam1=1.0, lam2=1.0,
                            max_iters=2000, tol=0.1, method="cd_quad")
    text = jax.jit(fit).lower(data).compile().as_text()
    assert 'op_name="' in text and "cd.stats/" in text
    assert not re.findall(r'op_name="[^"]*cd\.stats/gather', text)


def _fit_hlo(one_chip, dtype):
    """The compiled HLO text of the n = p = 1200 ``fit_cd_tol`` as the
    benchmark's fit driver calls it, its data in ``dtype``."""
    n = p = 1200
    one = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    data = cox.CoxData(x=one((n, p), dtype), delta=one((n,), dtype),
                       risk_start=one((n,), I32), tie_end=one((n,), I32))
    fit = functools.partial(solvers.fit_cd_tol, lam1=1.0, lam2=1.0,
                            max_iters=2000, tol=0.1, method="cd_quad")
    return jax.jit(fit).lower(data).compile().as_text()


def _op_names(text, pattern):
    """The op_names of the instructions whose HLO line matches."""
    return [m.group(1) if m else ""
            for line in text.splitlines() if re.search(pattern, line)
            for m in [re.search(r'op_name="([^"]*)"', line)]]


def test_coordinate_sweep_is_one_kernel(one_chip, chip_config):
    """On the chip each sweep of the n = p = 1200 fit is one Mosaic
    kernel under ``cd.stats``: no XLA loop runs per coordinate (the one
    while loop is the loop over sweeps) and no suffix sum runs as a
    ``reduce-window`` there. bfloat16 data keeps the jnp sweep."""
    text = _fit_hlo(one_chip, F32)
    calls = _op_names(text, r"custom_call_target=\"tpu_custom_call\"")
    assert len(calls) == 1 and "/cd.stats/" in calls[0], calls
    loops = _op_names(text, r"= .*\bwhile\(")
    assert len(loops) == 1 and loops[0].endswith("fit_cd_tol)/while"), loops
    assert not [o for o in _op_names(text, r"\breduce-window\(")
                if "cd.stats/" in o]

    text = _fit_hlo(one_chip, jnp.bfloat16)
    assert "tpu_custom_call" not in text
    assert len(_op_names(text, r"= .*\bwhile\(")) == 2


def test_nemotron_train_step_fits_one_v5e(one_chip, chip_config):
    """The Cox train step of the nemotron3.train cell at its shapes (the
    published blocks 0-6, 8 of 128 experts held, a vocabulary of 16,384
    rows, 32 x 512 tokens, float32 weights and Adam moments): the chip's
    compiler takes it, its device work is named, and the compiled
    program's memory fits the chip's 16 GB."""
    from repro.configs import TrainConfig, get_config
    from repro.models import build_model
    from repro.survival.head import init_cox_head
    from repro.train.optimizer import init_opt_state
    from repro.train.trainer import TrainState, make_train_step

    cfg = get_config("nemotron-3-nano-30b-a3b")
    cfg = cfg.scaled(n_layers=7, layer_pattern=cfg.layer_pattern[:7],
                     experts_held=8, vocab_size=16384, dtype="float32")
    model = build_model(cfg)

    def init():
        p = model.init_params(jax.random.PRNGKey(0))
        p["cox_head"] = init_cox_head(jax.random.PRNGKey(1), cfg.d_model)
        return TrainState(params=p, opt=init_opt_state(p))

    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,  # noqa: E731
                                           sharding=one_chip)
    state = jax.tree.map(place, jax.eval_shape(init))
    batch = {"tokens": place(jax.ShapeDtypeStruct((32, 512), I32)),
             "time": place(jax.ShapeDtypeStruct((32,), F32)),
             "event": place(jax.ShapeDtypeStruct((32,), F32))}
    step = jax.jit(make_train_step(model, TrainConfig(), objective="cox"),
                   donate_argnums=(0,))
    compiled = step.lower(state, batch).compile()
    text = compiled.as_text()
    for scope in ("moe.experts/", "moe.dispatch/", "attn.core/", "ssm.ssd/"):
        assert scope in text, scope
    # the SSD runs as its kernels, forward, recomputed forward and
    # backward, under ssm.ssd, and no (B, nc, Q, Q, ...) tile reaches HBM
    calls = _op_names(text, r"custom_call_target=\"tpu_custom_call\"")
    for kernel in ("/ssm.ssd/", "/ssd_fwd/", "/ssd_bwd/"):
        assert any(kernel in c for c in calls), (kernel, calls)
    assert not re.search(r"\[32,4,128,128\b", text)
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert need < 16e9, need


def test_ssd_path_counter(one_chip, chip_config):
    """``ssd_path_total`` counts the kernel where the Mamba-2 block's SSD
    is lowered for TPU at a shape that tiles, and the jnp body with its
    reason where it is lowered for the CPU (``backend``) or its head
    width does not pack into lanes (``shape``)."""
    from repro.models import ssm
    from repro.obs import metrics

    counter = metrics.REGISTRY.counter("ssd_path_total",
                                       label_names=("path", "reason"))
    labels = [("kernel", "fits"), ("jnp", "backend"), ("jnp", "shape")]

    def lowered(hd, sharding):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
                for s, dt in _ssd_shapes(2, 256, 4, hd, 1, 128)]
        before = [counter.value(path=p, reason=r) for p, r in labels]
        jax.jit(lambda *a: ssm._ssd(*a, 128)).lower(*args)
        return [counter.value(path=p, reason=r) - v
                for (p, r), v in zip(labels, before)]

    assert lowered(64, one_chip) == [1, 0, 0]
    assert lowered(64, None) == [0, 1, 0]
    assert lowered(48, one_chip) == [0, 0, 1]
