"""pspec.constrain contract under the ambient mesh of ``jax.set_mesh``.

The mesh probe must (a) no-op without a mesh, (b) resolve logical axis
names against the mesh ``jax.set_mesh`` installs, and (c) constrain both
traced values and concrete arrays outside a trace.
"""
import numpy as np

import jax
import jax.numpy as jnp
import pytest

from repro.launch.mesh import make_host_mesh
from repro.models import pspec


# -- resolve_spec: pure resolution logic (no mesh required) -----------------

NAMES = ("data", "model")
SIZES = (("data", 4), ("model", 2))
POD_NAMES = ("pod", "data", "model")
POD_SIZES = (("pod", 2), ("data", 4), ("model", 2))


def test_resolve_dp_without_pod_axis():
    spec = pspec.resolve_spec(("dp", None, "model"), (8, 16, 64),
                              NAMES, SIZES)
    assert spec == (("data",), None, "model")


def test_resolve_dp_with_pod_axis():
    spec = pspec.resolve_spec(("dp", None, "model"), (8, 16, 64),
                              POD_NAMES, POD_SIZES)
    assert spec == (("pod", "data"), None, "model")


def test_resolve_dp_include_model_knob():
    spec = pspec.resolve_spec(("dp",), (16,), NAMES, SIZES,
                              dp_include_model=True)
    assert spec == ((("data", "model")),)


def test_resolve_divisibility_fallback_to_none():
    # batch 6 is not divisible by pod*data=8, d_model 65 not by model=2
    spec = pspec.resolve_spec(("dp", None, "model"), (6, 16, 65),
                              POD_NAMES, POD_SIZES)
    assert spec == (None, None, None)


def test_resolve_unknown_axis_is_replicated():
    spec = pspec.resolve_spec(("expert",), (8,), NAMES, SIZES)
    assert spec == (None,)


# -- constrain: ambient-mesh behavior ---------------------------------------

def test_constrain_no_mesh_is_identity():
    x = jnp.ones((4, 8))
    assert pspec.constrain(x, "dp", None) is x


def test_constrain_no_mesh_inside_jit():
    @jax.jit
    def f(x):
        return pspec.constrain(x, "dp", None, "model") * 2.0

    out = f(jnp.ones((2, 3, 4)))
    np.testing.assert_array_equal(np.asarray(out), 2.0)


@pytest.mark.parametrize("mode", ["eager", "jit"])
def test_constrain_under_ambient_mesh(mode):
    """With a real 1-device mesh set, constrain must go through
    with_sharding_constraint (and stay numerically a no-op), whether it
    sees a concrete array or a tracer."""
    x = jnp.arange(8.0).reshape(4, 2)
    fn = lambda v: pspec.constrain(v, "dp", "model")
    if mode == "jit":
        fn = jax.jit(fn)
    with jax.set_mesh(make_host_mesh()):
        y = fn(x)
    assert set(y.sharding.mesh.axis_names) == {"data", "model"}
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_mesh_probe_none_outside_any_mesh():
    assert pspec._mesh() is None


def test_mesh_probe_sees_set_mesh():
    with jax.set_mesh(make_host_mesh()):
        am = pspec._mesh()
        assert am is not None
        assert dict(zip(am.axis_names, am.axis_sizes)) == {"data": 1,
                                                           "model": 1}
    assert pspec._mesh() is None


def test_constrain_resolves_pod_dp_spec():
    """End-to-end: a fake ambient mesh with a pod axis resolves "dp" to
    ("pod","data") and divisibility gates each dim independently."""

    class FakeMesh:
        axis_names = ("pod", "data")
        axis_sizes = (2, 2)

    captured = {}

    def fake_constrain(x, spec):
        captured["spec"] = spec
        return x

    orig_mesh, orig_wsc = pspec._mesh, jax.lax.with_sharding_constraint
    pspec._mesh = lambda: FakeMesh()
    jax.lax.with_sharding_constraint = fake_constrain
    try:
        jax.jit(lambda v: pspec.constrain(v, "dp", "data"))(jnp.ones((8, 5)))
    finally:
        pspec._mesh = orig_mesh
        jax.lax.with_sharding_constraint = orig_wsc
    # dim0: 8 % (2*2) == 0 -> ("pod","data"); dim1: 5 % 2 != 0 -> None
    assert tuple(captured["spec"]) == (("pod", "data"), None)
