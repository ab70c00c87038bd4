"""Production meshes.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state. The multi-pod mesh's leading ``pod`` axis is pure
data parallelism: the only cross-pod traffic in a train step is the gradient
all-reduce, which is what the (slower) DCN between pods can sustain.

Every mesh has ``Auto`` axis types. SPMD callers (core/distributed.py,
serving/engine.py) wrap their per-shard bodies in
``jax.shard_map(..., check_vma=False)``: the varying-manual-axes checker
has no rule for ``pallas_call``, and the serving curve kernel runs
per shard.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke paths (tests / examples)."""
    return _make_mesh((1, 1), ("data", "model"))


def make_data_mesh(n_shards: int):
    """1-D ``data`` mesh over ``n_shards`` local devices — the scoring
    engine's batch-parallel mesh (requests shard over rows, model state is
    replicated). ``n_shards`` must not exceed ``jax.local_device_count()``."""
    return _make_mesh((int(n_shards),), ("data",))


def dp_axes(mesh) -> tuple:
    """Axes the batch is sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def fsdp_axis(mesh) -> str:
    """Axis weights/optimizer state are FSDP-sharded over (in-pod only —
    cross-pod weight gathering over DCN would dominate the step)."""
    return "data"
