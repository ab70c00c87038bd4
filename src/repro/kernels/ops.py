"""jit'd public wrappers around the Pallas kernels.

The kernels, and what each serves:
  * ``cd_sweep`` (kernels/cd_sweep.py) -- one whole coordinate sweep of
    ``solvers.fit_cd``/``fit_cd_tol`` where the program is lowered for
    TPU. It takes tied data: it weights each suffix sum by E, the events
    whose risk set starts there (``core.cox.risk_start_events``), so it
    needs no gather at risk_start. It runs for float32 data whose n fits
    its VMEM budget (``cd_sweep_path`` says which path a sweep takes, and
    why);
  * ``ssd`` (kernels/ssd.py) -- the chunked SSD of the Mamba-2 block
    (``models/ssm.mamba2_forward``), forward and backward, where the
    program is lowered for TPU and the shapes tile (``ssd_path``);
  * ``revcumsum`` -- the chunked suffix sums of the streaming fit
    (``core/streaming.py``, ``solvers.fit_stream``);
  * ``cox_batch_grad_hess`` -- all-coordinate (grad, hess_diag) of one
    tie-free chunk, in the streaming fit's chunk mode;
  * ``survival_curves`` / ``survival_curves_stratified`` -- the serving
    engine's survival curves (``serving/engine.py``).

Selection logic:
  * on TPU the compiled kernels run natively; elsewhere (the CPU) they
    run in interpret mode for correctness (the kernels resolve
    ``interpret=None`` backend-aware themselves);
  * block sizes default to the autotuner's winners (kernels/autotune.py):
    every dispatch looks up backend + kernel + power-of-two shape bucket
    in the JSON tune cache and falls back to the historical static
    defaults when the bucket is untuned. Pass an explicit block to pin.

Telemetry: every dispatch increments ``kernel_dispatch_total`` labelled
with the kernel name and block provenance (``tuned`` cache hit /
``default`` static fallback / ``explicit`` caller-pinned). Counts are
dispatch-side: a kernel traced once inside an outer ``jit`` counts once
per compilation, eager callers count per call — either way, a fleet
silently running default blocks is visible in the metrics.
``cd_sweep_path_total`` counts, the same way, which path each traced
coordinate sweep took (``kernel`` or ``jnp``) and why. ``ssd_path_total``
counts which path each chunked SSD took: a shape that does not tile when
it is traced, the platform's choice when the program is lowered (so a
rematerialised forward counts again).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.extend.core import Primitive
from jax.interpreters import ad, batching, mlir

from ..obs import metrics as obs_metrics
from . import autotune, cd_sweep as _cd_sweep, ssd as _ssd
from .cd_sweep import cd_sweep  # noqa: F401  (one whole sweep, one kernel)
from .cd_sweep import prepare as cd_sweep_prepare  # noqa: F401
from .cox_batch import cox_batch as _cox_batch_kernel
from .ssd import ssd  # noqa: F401  (the chunked SSD, forward and backward)
from .revcumsum import revcumsum as _revcumsum_kernel
from .survival_curves import survival_curves as _survival_curves_kernel
from .survival_curves import (
    survival_curves_stratified as _survival_curves_strat_kernel)

_M_DISPATCH = obs_metrics.REGISTRY.counter(
    "kernel_dispatch_total", "Pallas kernel dispatches by block provenance",
    ("kernel", "blocks"))

_M_SWEEP = obs_metrics.REGISTRY.counter(
    "cd_sweep_path_total",
    "coordinate sweeps traced, by path: kernel (the one-kernel sweep, run "
    "where the program is lowered for TPU) or jnp, and the reason",
    ("path", "reason"))

_M_SSD = obs_metrics.REGISTRY.counter(
    "ssd_path_total",
    "chunked SSDs by path: kernel (kernels/ssd.py, where the program is "
    "lowered for TPU and the shapes tile) or jnp, and the reason: fits, "
    "backend or shape",
    ("path", "reason"))


def _blocks(kernel: str, explicit: bool, **shape):
    """Resolve blocks + count the dispatch under its provenance tag."""
    if explicit:
        _M_DISPATCH.inc(kernel=kernel, blocks="explicit")
        return None
    cfg, tag = autotune.lookup_tagged(kernel, **shape)
    _M_DISPATCH.inc(kernel=kernel, blocks=tag)
    return cfg


def revcumsum(x: jax.Array, block_n: Optional[int] = None) -> jax.Array:
    """Suffix sum along axis 0; accepts (n,) or (n, m)."""
    squeeze = x.ndim == 1
    x2 = x[:, None] if squeeze else x
    cfg = _blocks("revcumsum", block_n is not None,
                  n=x2.shape[0], m=x2.shape[1])
    if block_n is None:
        block_n = cfg["block_n"]
    out = _revcumsum_kernel(x2, block_n=block_n)
    return out[:, 0] if squeeze else out


def cox_batch_grad_hess(eta: jax.Array, x: jax.Array, delta: jax.Array,
                        block_n: Optional[int] = None,
                        block_p: Optional[int] = None):
    """All-coordinate (grad, hess_diag) — tie-free fast path.

    Precomputes the O(n) vectors in jnp (one pass), then the O(np) panel
    work runs in the kernel.
    """
    cfg = _blocks("cox_batch", block_n is not None and block_p is not None,
                  n=x.shape[0], p=x.shape[1])
    if block_n is None or block_p is None:
        block_n = cfg["block_n"] if block_n is None else block_n
        block_p = cfg["block_p"] if block_p is None else block_p
    eta32 = eta.astype(jnp.float32)
    d32 = delta.astype(jnp.float32)
    w = jnp.exp(eta32 - jnp.max(eta32))
    s0 = jax.lax.cumsum(w, axis=0, reverse=True)
    inv_s0 = 1.0 / s0
    a = jnp.cumsum(d32 * inv_s0)
    wa = w * a
    r = wa - d32
    return _cox_batch_kernel(x, w, r, wa, d32, inv_s0,
                             block_n=block_n, block_p=block_p)


def survival_curves(eta: jax.Array, h0: jax.Array,
                    block_b: Optional[int] = None,
                    block_g: Optional[int] = None) -> jax.Array:
    """Fused (batch x grid) survival curves — the serving hot path."""
    cfg = _blocks("survival_curves",
                  block_b is not None and block_g is not None,
                  b=eta.shape[0], g=h0.shape[0])
    if block_b is None or block_g is None:
        block_b = cfg["block_b"] if block_b is None else block_b
        block_g = cfg["block_g"] if block_g is None else block_g
    return _survival_curves_kernel(eta, h0, block_b=block_b,
                                   block_g=block_g)


def survival_curves_stratified(eta: jax.Array, h0: jax.Array,
                               strata: jax.Array,
                               block_g: Optional[int] = None) -> jax.Array:
    """Per-request-baseline curves; the h0 row gather runs inside the
    kernel via scalar prefetch (h0 is (s, g), strata (b,) int rows)."""
    cfg = _blocks("survival_curves_strat", block_g is not None,
                  b=eta.shape[0], g=h0.shape[1])
    if block_g is None:
        block_g = cfg["block_g"]
    return _survival_curves_strat_kernel(eta, h0, strata, block_g=block_g)


def cd_sweep_path(x: jax.Array) -> str:
    """``"kernel"`` where the one-kernel coordinate sweep takes a design
    matrix ``x`` (n, p), else ``"jnp"``; counted under
    ``cd_sweep_path_total`` with the reason: ``fits``, or ``dtype`` (not
    float32: a bfloat16 fit keeps computing in bfloat16) or ``vmem`` (n
    past the kernel's VMEM budget)."""
    if x.dtype != jnp.float32:
        path, reason = "jnp", "dtype"
    elif not _cd_sweep.fits(x.shape[0]):
        path, reason = "jnp", "vmem"
    else:
        path, reason = "kernel", "fits"
    _M_SWEEP.inc(path=path, reason=reason)
    return path


def ssd_path(h: int, hd: int, g: int, n: int, chunk: int) -> str:
    """``"kernel"`` where the SSD kernel takes H heads of width hd over
    G groups of state size N in chunks of ``chunk``, else ``"jnp"``,
    counted now under ``ssd_path_total`` with the reason ``shape``. Where
    the shapes tile, the platform decides when the program is lowered:
    ``ssd_lowered`` counts that."""
    if _ssd.tiles(h, hd, g, n, chunk):
        return "kernel"
    _M_SSD.inc(path="jnp", reason="shape")
    return "jnp"


# identity on one array; lowering it counts the path of the SSD it marks
_SSD_LOWERED = Primitive("ssd_lowered")
_SSD_LOWERED.def_impl(lambda v, **_: v)
_SSD_LOWERED.def_abstract_eval(lambda v, **_: v)
ad.primitive_jvps[_SSD_LOWERED] = \
    lambda primals, tangents, **kw: (_SSD_LOWERED.bind(*primals, **kw),
                                     tangents[0])
batching.primitive_batchers[_SSD_LOWERED] = \
    lambda args, dims, **kw: (_SSD_LOWERED.bind(*args, **kw), dims[0])


def _count_lowered(ctx, v, *, path, reason):
    _M_SSD.inc(path=path, reason=reason)
    return [v]


mlir.register_lowering(_SSD_LOWERED, _count_lowered)


def ssd_lowered(v: jax.Array, path: str, reason: str) -> jax.Array:
    """``v`` itself; each lowering of the program counts one SSD under
    ``ssd_path_total{path, reason}``. Put inside one branch of a
    ``lax.platform_dependent``, it counts only where that branch is
    lowered."""
    return _SSD_LOWERED.bind(v, path=path, reason=reason)
