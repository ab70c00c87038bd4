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
from repro.kernels import autotune
from repro.kernels.cox_batch import cox_batch
from repro.kernels.cox_coord import cox_coord
from repro.kernels.lipschitz import lipschitz
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
N_COORD = 1 << 20

# (kernel, default-config key, fixed kwargs, argument shapes)
CASES = {
    "cox_coord_order2": (
        cox_coord, "cox_coord", {"order": 2},
        [((N_COORD,), F32)] * 3),
    "cox_coord_order3": (
        cox_coord, "cox_coord", {"order": 3},
        [((N_COORD,), F32)] * 3),
    "cox_batch": (
        cox_batch, "cox_batch", {},
        [((65536, 256), F32)] + [((65536,), F32)] * 5),
    "revcumsum_m1": (
        revcumsum, "revcumsum", {}, [((65536, 1), F32)]),
    "revcumsum_m256": (
        revcumsum, "revcumsum", {}, [((65536, 256), F32)]),
    "lipschitz": (
        lipschitz, "lipschitz", {},
        [((65536, 256), F32), ((65536,), F32)]),
    "survival_curves": (
        survival_curves, "survival_curves", {},
        [((1024,), F32), ((2048,), F32)]),
    "survival_curves_stratified": (
        survival_curves_stratified, "survival_curves_strat", {},
        [((1024,), F32), ((8, 2048), F32), ((1024,), I32)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, chip_config):
    kernel, cfg_key, kwargs, shapes = CASES[case]
    fn = functools.partial(kernel, **kwargs,
                           **autotune.DEFAULT_CONFIGS[cfg_key],
                           interpret=False)
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
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert need < 16e9, need
