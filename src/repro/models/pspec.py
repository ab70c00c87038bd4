"""Activation sharding constraints with logical axis names.

``constrain(x, "dp", None, "model")`` resolves "dp" to ("pod","data") when
the ambient mesh has a pod axis, checks divisibility per dim, and no-ops
entirely when no mesh is set (CPU unit tests). These anchors stop GSPMD
from replicating the token dimension when weight shardings win the
propagation contest (observed: without the post-embedding anchor, every
per-layer GEMM ran on the full global batch per device).

The ambient mesh is the one ``jax.set_mesh`` installs.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# experiment knob (§Perf A6/B2): resolve "dp" to include the model axis
# (pure-DP layouts that use every chip for batch parallelism)
DP_INCLUDE_MODEL = False


def _mesh():
    """The ambient mesh's axis names/sizes, or None when no mesh is set."""
    am = jax.sharding.get_abstract_mesh()
    return am if am.axis_names else None


def resolve_spec(spec, shape, names, sizes, *,
                 dp_include_model: bool = None):
    """Resolve logical axis names against mesh (names, sizes) per dim.

    Pure function of the spec, the array shape, and the mesh geometry —
    ``constrain`` feeds it the ambient mesh; tests feed it synthetic
    geometries. Any dim whose size is not divisible by the product of its
    mesh axes falls back to ``None`` (replicated) instead of an XLA error.
    """
    if dp_include_model is None:
        dp_include_model = DP_INCLUDE_MODEL
    sizes = dict(sizes)
    resolved = []
    for dim, s in enumerate(spec):
        if s == "dp":
            cand = ("pod", "data", "model") if dp_include_model \
                else ("pod", "data")
            axes = tuple(a for a in cand if a in names)
            n = 1
            for a in axes:
                n *= sizes[a]
            resolved.append(axes if axes and shape[dim] % n == 0 else None)
        elif s is None:
            resolved.append(None)
        else:
            ok = s in names and shape[dim] % sizes[s] == 0
            resolved.append(s if ok else None)
    return tuple(resolved)


def constrain(x, *spec):
    am = _mesh()
    if am is None:
        return x
    target = P(*resolve_spec(spec, x.shape, tuple(am.axis_names),
                             zip(am.axis_names, am.axis_sizes)))
    if not isinstance(x, jax.core.Tracer):
        # outside a trace the target must name the devices of the mesh
        # ``jax.set_mesh`` holds; a bare spec would bind the abstract mesh
        target = NamedSharding(jax.sharding.get_mesh(), target)
    return jax.lax.with_sharding_constraint(x, target)
