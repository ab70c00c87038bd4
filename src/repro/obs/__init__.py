"""Unified telemetry for the FastSurvival stack — dependency-free.

Three layers (stdlib + the jax/numpy already in the tree; nothing else):

``metrics.py``
    Counters / gauges / fixed-bucket histograms in a thread-safe
    ``Registry`` (process-global ``REGISTRY`` default, injectable
    instances for tests), a Prometheus-text exporter served by
    ``serve_metrics()``, and a JSON-able ``snapshot()`` embedded into
    ``BENCH_*.json`` by ``benchmarks/run.py --json``.

``events.py`` / ``trace.py``
    A JSONL event sink (``$REPRO_EVENTS_FILE``) and nested timed spans
    with per-trace ids (``$REPRO_TRACE_FILE``, held in memory and
    written in blocks), summarized into the per-stage latency-breakdown
    table by ``repro.analysis.report``. While ``jax.profiler`` traces,
    each span is also a ``TraceAnnotation`` in the profiler's trace.

``solver.py``
    ``TelemetryCallback`` — per-iteration (objective, grad norm, step
    norm, active set) records via ``jax.debug.callback``, plus the
    ``solver_monotonicity_violations_total`` counter that turns the
    paper's loss-decrease guarantee into a monitored invariant. Threaded
    through ``core/solvers.py`` and ``core/beam.py`` as a static jit
    argument: ``None`` (the default) stages nothing.

Instrumented call sites: ``serving/service.py`` (queue/batch/dispatch
spans, queue-depth gauge, shed/timeout counters), ``serving/engine.py``
(compile events, bucket-size histogram), ``kernels/ops.py`` (dispatch
counters with tuned/default tags), ``kernels/autotune.py`` (winner
events), ``launch/runtime.py`` (env snapshot event).

Everything is overhead-free when off: disabled sinks are one ``None``
check (spans one more: is the profiler tracing), disabled solver
telemetry traces the pre-telemetry graph, and
metric updates on always-on counters are single locked dict writes.
"""
from . import events, metrics, trace  # noqa: F401
from .metrics import REGISTRY, Registry, serve_metrics  # noqa: F401
from .solver import TelemetryCallback, emit_iter  # noqa: F401
from .trace import span  # noqa: F401
