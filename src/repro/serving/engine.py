"""jit-compiled batched scoring engine over a SurvivalModel artifact.

Three query types, all O(batch) jit calls over device-resident model state:

  * ``risk_scores``      exp(x beta)                       -> (b,)
  * ``survival_curves``  exp(-H0_s(t) exp(x beta))         -> (b, g)
  * ``median_survival``  first grid time with S(t|x) <= .5 -> (b,)

Sparse fast path: a beam-search model with support size k gathers only the
k support columns on the host (O(b k) transferred instead of O(b p)) and
scores with the gathered ``beta_support`` — per-request work is O(k), the
serving-side payoff of FastSurvival's cardinality-constrained models.

Shape bucketing: incoming batches are zero-padded up to the next power of
two, so the jit cache holds at most log2(max_batch) entries per query type
instead of one compilation per distinct batch size. Cache misses (i.e.
fresh compilations) are counted for the instrumentation in service.py.

The unstratified curve evaluation runs through the fused Pallas kernel
(kernels/survival_curves.py); the stratified path routes through the
scalar-prefetch variant (per-request baseline row selected by the kernel's
index map) on TPU and falls back to a jnp gather elsewhere, where Pallas
only interprets.

Data-parallel scoring: ``shard=k`` (or ``"auto"``) wraps every bucketed
query body in ``shard_map`` over a 1-D ``data`` mesh from
``launch/mesh.py`` — rows split over shards, model state replicated — and
bucketing becomes per-shard (bucket = shards * next_pow2(ceil(b /
shards))), so each shard sees a power-of-two block. ``shard=None`` (the
default) is the legacy single-device path, bit-identical to previous
behavior.
"""
from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..kernels import ops
from ..launch import mesh as launch_mesh
from ..launch import runtime as launch_runtime
from ..obs import events as obs_events
from ..obs import metrics as obs_metrics
from ..obs import trace
from .artifacts import SurvivalModel

_ETA_CLIP = 30.0

# shared across engines: compile blowups (a bucketing regression) show up
# as a climbing counter, bucket skew as a lopsided histogram
_M_COMPILES = obs_metrics.REGISTRY.counter(
    "engine_jit_compiles_total", "fresh jit-cache compilations",
    ("kind",))
_M_CALLS = obs_metrics.REGISTRY.counter(
    "engine_calls_total", "scoring calls", ("kind",))
_M_BUCKET = obs_metrics.REGISTRY.histogram(
    "engine_bucket_size", "padded power-of-two batch buckets hit",
    buckets=obs_metrics.POW2_BUCKETS)


def _next_pow2(b: int) -> int:
    return 1 << max(int(np.ceil(np.log2(max(b, 1)))), 0)


class ScoringEngine:
    """Batched scorer with a shape-bucketed jit cache."""

    def __init__(self, model: SurvivalModel, *, use_sparse: Optional[bool]
                 = None, max_sparse_k: int = 64, use_kernel: bool = True,
                 shard: Union[int, str, None] = None,
                 use_strat_kernel: Optional[bool] = None):
        self.model = model
        if use_sparse is None:
            use_sparse = (model.is_sparse
                          and model.k is not None and model.k <= max_sparse_k)
        self.use_sparse = bool(use_sparse and model.is_sparse)
        self.use_kernel = use_kernel
        # stratified scalar-prefetch kernel: native on TPU; elsewhere the
        # interpreted Pallas call loses to the jnp gather, so default off
        if use_strat_kernel is None:
            use_strat_kernel = jax.default_backend() == "tpu"
        self.use_strat_kernel = bool(use_strat_kernel and use_kernel)
        # shard=None -> legacy single-device path (bit-identical: no mesh,
        # no shard_map in the trace); "auto" -> $REPRO_DATA_SHARDS or one
        # shard per local device; int -> explicit, clamped to devices
        if shard is None:
            self.shard = 1
        elif shard == "auto":
            self.shard = (launch_runtime.data_shards()
                          or jax.local_device_count())
        else:
            self.shard = int(shard)
        self.shard = max(1, min(self.shard, jax.local_device_count()))
        self._mesh = (launch_mesh.make_data_mesh(self.shard)
                      if self.shard > 1 else None)
        self._support = (np.asarray(model.support)
                         if model.support is not None else None)
        beta = (model.beta_support if self.use_sparse else model.beta)
        self._beta = jnp.asarray(np.asarray(beta, np.float32))
        self._h0 = jnp.asarray(np.asarray(model.base_cumhaz, np.float32))
        self._grid = jnp.asarray(np.asarray(model.time_grid, np.float32))
        self._cache: dict = {}
        self.compiles = 0
        self.calls = 0

    # -- feature handling --------------------------------------------------

    @property
    def feature_dim(self) -> int:
        """Columns the jit'd matvec consumes (k on the sparse path)."""
        return (len(self._support) if self.use_sparse
                else self.model.p)

    def _gather(self, x: np.ndarray) -> np.ndarray:
        """Host-side support gather: accepts (b, p) full features or
        (b, k) pre-gathered ones on the sparse path."""
        x = np.atleast_2d(np.asarray(x, np.float32))
        if self.use_sparse and x.shape[1] == self.model.p:
            x = x[:, self._support]
        if x.shape[1] != self.feature_dim:
            raise ValueError(
                f"expected {self.feature_dim} or {self.model.p} features, "
                f"got {x.shape[1]}")
        return x

    def _pad(self, x: np.ndarray):
        b = x.shape[0]
        if self.shard > 1:
            # per-shard pow-2 bucketing: every shard sees a power-of-two
            # block, the jit cache stays log-sized per shard count
            bucket = self.shard * _next_pow2(-(-b // self.shard))
        else:
            bucket = _next_pow2(b)
        if bucket != b:
            x = np.pad(x, ((0, bucket - b), (0, 0)))
        return x, b, bucket

    def _fn(self, kind: str, bucket: int):
        key = (kind, bucket, self.feature_dim)
        fn = self._cache.get(key)
        if fn is None:
            self.compiles += 1
            _M_COMPILES.inc(kind=kind)
            obs_events.emit("engine.compile", query=kind, bucket=bucket,
                            feature_dim=self.feature_dim,
                            cache_entries=len(self._cache))
            fn = self._build(kind)
            self._cache[key] = fn
        return fn

    # -- jit'd query bodies ------------------------------------------------

    def _build(self, kind: str):
        h0 = self._h0
        grid = self._grid
        use_kernel = self.use_kernel and h0.shape[0] == 1
        use_strat = self.use_strat_kernel and h0.shape[0] > 1

        def lin(xb, beta):
            # f32 as the artifact promises: TPU's default matmul precision
            # rounds the operands to bf16
            return jnp.dot(xb, beta, precision=jax.lax.Precision.HIGHEST)

        def eta_of(xb, beta):
            return jnp.clip(lin(xb, beta), -_ETA_CLIP, _ETA_CLIP)

        def curves(xb, beta, strata):
            if use_kernel:
                return ops.survival_curves(lin(xb, beta), h0[0])
            if use_strat:
                # baseline-row gather folded into the kernel's index map
                return ops.survival_curves_stratified(lin(xb, beta), h0,
                                                      strata)
            if h0.shape[0] == 1:
                # single stratum: broadcast the one baseline row instead of
                # materializing a (b, g) gather panel
                hh = h0[0][None, :]
            else:
                hh = h0[strata]                  # (b, g) baseline gather
            return jnp.exp(-hh * jnp.exp(eta_of(xb, beta))[:, None])

        def median_of(s):
            below = s <= 0.5
            hit = jnp.any(below, axis=1)
            idx = jnp.argmax(below, axis=1)
            return jnp.where(hit, grid[idx], jnp.inf)

        if kind == "risk":
            def fn(xb, beta, strata):
                return jnp.exp(eta_of(xb, beta))
        elif kind == "curves":
            fn = curves
        elif kind == "median":
            def fn(xb, beta, strata):
                return median_of(curves(xb, beta, strata))
        elif kind in ("score", "score_curves"):
            # fused service query: one transfer + one curve panel per batch
            def fn(xb, beta, strata):
                s = curves(xb, beta, strata)
                out = (jnp.exp(eta_of(xb, beta)), median_of(s))
                return out + ((s,) if kind == "score_curves" else ())
        else:
            raise ValueError(kind)
        if self._mesh is not None:
            fn = self._shard_wrap(fn, kind)
        return jax.jit(fn)

    _OUT_SPECS = {
        "risk": P("data"),
        "curves": P("data", None),
        "median": P("data"),
        "score": (P("data"), P("data")),
        "score_curves": (P("data"), P("data"), P("data", None)),
    }

    def _shard_wrap(self, fn, kind: str):
        """Rows split over the ``data`` mesh, model state replicated.

        The bucketed batch is divisible by the shard count by
        construction (see ``_pad``), so every shard runs the same
        pow-2-shaped pure body; outputs concatenate along rows."""
        return jax.shard_map(
            fn, mesh=self._mesh,
            in_specs=(P("data"), P(), P("data")),
            out_specs=self._OUT_SPECS[kind], check_vma=False)

    def _run(self, kind: str, x, strata):
        with trace.span("engine.score", kind=kind) as sp_span:
            xh = self._gather(x)
            xp, b, bucket = self._pad(xh)
            sp = np.zeros(bucket, np.int32)
            if strata is not None:
                s = np.asarray(strata, np.int32)
                if s.size and (s.min() < 0 or s.max() >= self.model.n_strata):
                    # the jit'd gather would silently clamp out-of-range rows
                    raise ValueError(
                        f"stratum indices must be in [0, {self.model.n_strata})"
                        f", got range [{s.min()}, {s.max()}]")
                sp[:b] = s
            self.calls += 1
            _M_CALLS.inc(kind=kind)
            _M_BUCKET.observe(bucket)
            sp_span.set(b=b, bucket=bucket)
            if self._mesh is not None:
                # leave host arrays uncommitted: jnp.asarray would pin
                # them to device 0 and force a reshard copy on every call
                out = self._fn(kind, bucket)(xp, self._beta, sp)
            else:
                out = self._fn(kind, bucket)(jnp.asarray(xp), self._beta,
                                             jnp.asarray(sp))
            if isinstance(out, tuple):
                return tuple(np.asarray(o)[:b] for o in out)
            return np.asarray(out)[:b]

    # -- public API --------------------------------------------------------

    def risk_scores(self, x: np.ndarray) -> np.ndarray:
        """exp(x beta) for a (b, p) or pre-gathered (b, k) batch."""
        return self._run("risk", x, None)

    def survival_curves(self, x: np.ndarray,
                        strata: Optional[np.ndarray] = None) -> np.ndarray:
        """(b, g) S(t|x) on the model grid. ``strata`` are baseline row
        indices (positions in model.strata_labels), default stratum 0."""
        return self._run("curves", x, strata)

    def median_survival(self, x: np.ndarray,
                        strata: Optional[np.ndarray] = None) -> np.ndarray:
        """First grid time where S(t|x) drops to 1/2 (inf if never)."""
        return self._run("median", x, strata)

    def score(self, x: np.ndarray, strata: Optional[np.ndarray] = None,
              with_curves: bool = False):
        """Fused service query: (risk, median[, curves]) from a single jit
        call — one host->device transfer and one curve panel per batch."""
        return self._run("score_curves" if with_curves else "score",
                         x, strata)

    def prewarm(self, batch_sizes=(1, 64), kinds=("score",),
                strata: bool = False) -> int:
        """Compile (and execute once, on zeros) the jit buckets a service
        will hit, so the first live request after a hot-swap never pays a
        trace+compile. ``batch_sizes`` are rounded up to their pow-2
        buckets; duplicate buckets compile once. Returns the number of
        fresh compilations. Safe to call from a background thread — the
        registry pre-warms new models off the serving path."""
        before = self.compiles
        seen = set()
        for b in batch_sizes:
            _, _, bucket = self._pad(np.zeros((int(b), 1), np.float32))
            if bucket in seen:
                continue
            seen.add(bucket)
            x = np.zeros((bucket, self.feature_dim), np.float32)
            s = (np.zeros(bucket, np.int32)
                 if strata and self.model.n_strata > 1 else None)
            for kind in kinds:
                self._run(kind, x, s)
        return self.compiles - before

    def cache_info(self) -> dict:
        return {"entries": len(self._cache), "compiles": self.compiles,
                "calls": self.calls, "shard": self.shard}
