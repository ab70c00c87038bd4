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
