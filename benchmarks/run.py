"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV; ``--json PATH`` additionally
writes structured records (the committed ``BENCH_*.json`` trajectory
artifacts) of the form::

    {bench, name, us_per_call, derived, [value,] backend, tuned_blocks,
     git_rev}

``--autotune`` runs the kernel block-size sweep first (winners persist to
``$REPRO_TUNE_CACHE``, default the committed
``benchmarks/tuned_blocks.json``, and every subsequent kernel dispatch
uses them). ``--only`` takes a comma-separated
subset, e.g. ``--only kernels,serving``.

``--json`` additionally appends a ``telemetry/metrics_snapshot`` record:
the full ``repro.obs`` registry snapshot (serving/kernel counters the
benches accumulated, plus an instrumented convergence smoke fit), so
each ``BENCH_*.json`` carries convergence-iteration counts and stage
histograms alongside timings.

``--smoke`` is the CI guard, all in this one process (CI runs the test
suite separately): a tiny autotune sweep into a throwaway cache, the
serving benchmark at tiny shapes with schema validation of its records,
a regression gate on ``serving/batch_speedup`` against the committed
``BENCH_*.json`` baseline when one exists, a telemetry gate — the
embedded metrics snapshot must validate against its schema and the
instrumented smoke fit must record **zero monotonicity violations** —
plus the PR-8 scale gates: a tiny ``fit_stream`` (zero violations on the
live counter) and schema validation of the committed ``BENCH_8.json``
when present. Sharded scoring parity runs on four chips in
``chip_smoke.py --chips 4``.
The PR-9 robustness gates ride along: a tiny open-loop overload run
(HIGH-priority p99 must stay bounded at 2x saturation, a live hot swap
must drop nothing, every submitted request must reach a terminal
outcome) and schema + zero-drop validation of the committed
``BENCH_9.json`` when present. The PR-10 deep-survival gate closes the
loop through the revived model zoo: a tiny backbone trains under the
exact CPH objective, the beam-search refit head exports as a serving
artifact, and that artifact must score through ModelRegistry/RiskService
with exactly the sparse head's risks (plus schema + headline validation
of the committed ``BENCH_10.json``).

Runnable both as ``python -m benchmarks.run`` (with ``PYTHONPATH=src``)
and directly as ``python benchmarks/run.py``.
"""
import argparse
import glob
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BENCH_KEYS = ("efficiency", "selection_f1", "selection_real", "kernels",
              "serving", "scale", "overload", "deep")

# the bench-record schema BENCH_*.json files are validated against
RECORD_REQUIRED = {
    "bench": str,
    "name": str,
    "us_per_call": (int, float),
    "derived": str,
    "backend": str,
    "tuned_blocks": dict,
    "git_rev": str,
}
RECORD_OPTIONAL = {"value": (int, float), "metrics": dict}

# smoke gate: fail when serving/batch_speedup drops below this fraction
# of the committed baseline
REGRESSION_FLOOR = 0.8


def _ensure_paths():
    """Script mode (`python benchmarks/run.py`) has neither the repo root
    nor src/ importable; module mode already does."""
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def _setup_runtime(verbose: bool = False):
    """Runtime env policy, before jax is pulled in."""
    _ensure_paths()
    from repro.launch import runtime
    runtime.apply()
    if verbose:
        runtime.log()
    return runtime


def _import_benches():
    try:
        from . import (bench_deep, bench_efficiency, bench_kernels,
                       bench_overload, bench_scale, bench_selection_f1,
                       bench_selection_real, bench_serving)
    except ImportError:
        from benchmarks import (bench_deep, bench_efficiency, bench_kernels,
                                bench_overload, bench_scale,
                                bench_selection_f1, bench_selection_real,
                                bench_serving)
    return {
        "efficiency": bench_efficiency.run,       # paper Fig. 1 + App. D.1
        "selection_f1": bench_selection_f1.run,   # paper Fig. 2
        "selection_real": bench_selection_real.run,  # paper Figs. 3/4
        "kernels": bench_kernels.run,             # Cor. 3.3 machinery
        "serving": bench_serving.run,             # inference subsystem
        "scale": bench_scale.run,                 # streaming + sharded n
        "overload": bench_overload.run,           # robustness under overload
        "deep": bench_deep.run,                   # FastCPH-style deep head
    }


# -- structured records -----------------------------------------------------

def _git_rev() -> str:
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"], cwd=ROOT, text=True,
            stderr=subprocess.DEVNULL).strip()
    except (OSError, subprocess.CalledProcessError):
        return "unknown"


def _run_metadata():
    """(backend, tuned_blocks-for-backend, git_rev) stamped on records."""
    import jax
    from repro.kernels import autotune
    backend = jax.default_backend()
    entries = autotune.load_cache(refresh=True)
    tuned = {k: dict(v.get("config", {})) for k, v in entries.items()
             if k.startswith(backend + "/")}
    return backend, tuned, _git_rev()


def make_records(bench, rows, backend, tuned, git_rev):
    recs = []
    for row in rows:
        rec = {"bench": bench, "name": row[0],
               "us_per_call": float(row[1]), "derived": str(row[2]),
               "backend": backend, "tuned_blocks": tuned,
               "git_rev": git_rev}
        if len(row) > 3 and row[3] is not None:
            rec["value"] = float(row[3])
        recs.append(rec)
    return recs


def validate_records(records):
    """Schema errors for a BENCH_*.json payload ([] when valid)."""
    if not isinstance(records, list) or not records:
        return ["payload must be a non-empty list of records"]
    errors = []
    for i, r in enumerate(records):
        if not isinstance(r, dict):
            errors.append(f"record {i}: not an object")
            continue
        label = r.get("name", f"record {i}")
        for k, t in RECORD_REQUIRED.items():
            if k not in r:
                errors.append(f"{label}: missing required key '{k}'")
            elif not isinstance(r[k], t):
                errors.append(f"{label}: key '{k}' has type "
                              f"{type(r[k]).__name__}")
        for k, t in RECORD_OPTIONAL.items():
            if k in r and not isinstance(r[k], t):
                errors.append(f"{label}: key '{k}' has type "
                              f"{type(r[k]).__name__}")
    return errors


def validate_metrics_snapshot(snap):
    """Schema errors for an obs Registry.snapshot() embedding ([] = valid).

    Shape: ``{"counters"|"gauges": {name: {label_str: number}},
    "histograms": {name: {"buckets": [num...], "series":
    {label_str: {"counts": [int...], "sum": num, "count": int}}}}``.
    """
    errors = []
    if not isinstance(snap, dict):
        return ["metrics snapshot must be an object"]
    for group in ("counters", "gauges", "histograms"):
        if group not in snap or not isinstance(snap[group], dict):
            errors.append(f"metrics: missing/invalid group '{group}'")
    for group in ("counters", "gauges"):
        series_by_name = snap.get(group)
        if not isinstance(series_by_name, dict):
            continue
        for name, series in series_by_name.items():
            if not isinstance(series, dict) or not all(
                    isinstance(v, (int, float)) for v in series.values()):
                errors.append(f"metrics: {group}/{name} series not "
                              "label->number")
    hists = snap.get("histograms")
    for name, h in (hists.items() if isinstance(hists, dict) else ()):
        if not isinstance(h, dict) or not isinstance(h.get("buckets"), list):
            errors.append(f"metrics: histograms/{name} missing buckets")
            continue
        series = h.get("series")
        for label, s in (series.items() if isinstance(series, dict) else ()):
            ok = (isinstance(s, dict) and isinstance(s.get("counts"), list)
                  and isinstance(s.get("sum"), (int, float))
                  and isinstance(s.get("count"), int)
                  and len(s["counts"]) == len(h["buckets"]) + 1)
            if not ok:
                errors.append(
                    f"metrics: histograms/{name}[{label!r}] malformed")
    return errors


def _solver_violations(snap) -> float:
    counters = snap.get("counters", {})
    series = counters.get("solver_monotonicity_violations_total", {})
    return sum(series.values()) if isinstance(series, dict) else 0.0


def _telemetry_record(backend, tuned, git_rev, n_iters=25):
    """Instrumented smoke fit + full registry snapshot as a bench record.

    Runs ``fit_cd_tol`` on a small synthetic problem with a
    ``TelemetryCallback``, so the embedded snapshot carries convergence
    iteration counts and the monotonicity-violation counter alongside
    whatever serving/kernel metrics the benches accumulated.
    """
    import jax

    from repro.core import cox, solvers
    from repro.data.synthetic import SyntheticSpec, make_correlated_survival
    from repro.obs import REGISTRY, TelemetryCallback

    x, t, delta, _ = make_correlated_survival(
        SyntheticSpec(n=200, p=20, k=4, rho=0.3, seed=0))
    data = cox.prepare(x, t, delta)
    tel = TelemetryCallback("cd_quad_smoke")
    res = solvers.fit_cd_tol(data, 0.1, 0.5, max_iters=n_iters,
                             telemetry=tel)
    res.beta.block_until_ready()
    jax.effects_barrier()          # flush the debug callbacks
    snap = REGISTRY.snapshot()
    return {
        "bench": "telemetry", "name": "metrics_snapshot",
        "us_per_call": 0.0,
        "derived": (f"smoke_fit_iters={tel.iterations} "
                    f"violations={tel.violations}"),
        "value": float(tel.violations),
        "backend": backend, "tuned_blocks": tuned, "git_rev": git_rev,
        "metrics": snap,
    }


def _baseline_record(bench, name):
    """Matching record from the newest committed BENCH_*.json, if any."""
    paths = sorted(glob.glob(os.path.join(ROOT, "BENCH_*.json")))
    if not paths:
        return None, None
    path = paths[-1]
    try:
        with open(path) as f:
            records = json.load(f)
    except (OSError, ValueError):
        return None, path
    for r in records if isinstance(records, list) else []:
        if (isinstance(r, dict) and r.get("bench") == bench
                and r.get("name") == name):
            return r, path
    return None, path


def _print_rows(rows):
    for row in rows:
        print(f"{row[0]},{row[1]:.1f},{row[2]}", flush=True)


# -- CI smoke gate ----------------------------------------------------------

def _smoke() -> int:
    """Tiny autotune sweep, tiny-shape serving bench with schema
    validation, speedup regression gate, then the telemetry, streaming,
    overload and deep gates — in this process, so no child ever needs a
    device the parent holds."""
    import tempfile

    from repro.kernels import autotune
    with tempfile.TemporaryDirectory() as td:
        winners = autotune.sweep(
            [("revcumsum", {"n": 256, "m": 8}),
             ("survival_curves", {"b": 32, "g": 32})],
            cache_file=os.path.join(td, "tuned.json"), reps=1)
    if len(winners) != 2 or not all(winners.values()):
        print("[smoke] FAILED: autotune sweep returned no winners")
        return 1
    print(f"[smoke] autotune sweep ok: "
          + "; ".join(f"{k} -> {v}" for k, v in winners.items()),
          flush=True)

    benches = _import_benches()
    print("name,us_per_call,derived")
    rows = list(benches["serving"](smoke=True))
    _print_rows(rows)
    speedup = next((row[3] for row in rows
                    if row[0] == "serving/batch_speedup" and len(row) > 3),
                   None)
    if speedup is None or speedup <= 1.0:
        print("[smoke] FAILED: batched serving slower than naive loop "
              f"(speedup={speedup})")
        return 1

    backend, tuned, rev = _run_metadata()
    records = make_records("serving_smoke", rows, backend, tuned, rev)
    errors = validate_records(records)
    if errors:
        print("[smoke] FAILED: bench records violate schema:")
        for e in errors:
            print(f"[smoke]   {e}")
        return 1
    print(f"[smoke] schema ok ({len(records)} records)")

    base, path = _baseline_record("serving_smoke", "serving/batch_speedup")
    if base is not None and "value" in base:
        floor = REGRESSION_FLOOR * base["value"]
        if speedup < floor:
            print(f"[smoke] FAILED: serving/batch_speedup x{speedup:.2f} "
                  f"regressed >20% vs baseline x{base['value']:.2f} "
                  f"({os.path.basename(path)})")
            return 1
        print(f"[smoke] speedup x{speedup:.2f} within 20% of baseline "
              f"x{base['value']:.2f} ({os.path.basename(path)})")
    else:
        print("[smoke] no committed BENCH_*.json baseline — "
              "regression gate skipped")

    # telemetry gate: an instrumented smoke fit must record zero
    # monotonicity violations, and its snapshot must satisfy the schema
    tel_rec = _telemetry_record(backend, tuned, rev)
    errors = (validate_records([tel_rec])
              + validate_metrics_snapshot(tel_rec["metrics"]))
    if errors:
        print("[smoke] FAILED: telemetry snapshot violates schema:")
        for e in errors:
            print(f"[smoke]   {e}")
        return 1
    violations = _solver_violations(tel_rec["metrics"])
    if violations > 0:
        print(f"[smoke] FAILED: {int(violations)} monotonicity "
              "violation(s) recorded during the smoke fit — the "
              "surrogate descent guarantee is broken")
        return 1
    print(f"[smoke] telemetry ok ({tel_rec['derived']})")

    # streaming-fit gate: a tiny fit_stream must descend monotonically
    # (zero violations on the live counter) through the same telemetry
    try:
        from . import bench_scale
    except ImportError:
        from benchmarks import bench_scale
    from repro.core import solvers
    from repro.obs import TelemetryCallback
    tel = TelemetryCallback("fit_stream_smoke")
    src = bench_scale.SyntheticChunkSource(1500, 8, 512, seed=0)
    res = solvers.fit_stream(src, lam2=0.05, n_epochs=3, telemetry=tel)
    if tel.violations > 0 or tel.iterations < 1:
        print(f"[smoke] FAILED: streaming fit recorded "
              f"{tel.violations} violation(s) over {tel.iterations} "
              "epoch(s)")
        return 1
    print(f"[smoke] streaming fit ok (epochs={tel.iterations} "
          f"violations={tel.violations} "
          f"objective={float(res.objective[-1]):.2f})")

    # BENCH_8 gate: when the scale artifact is committed it must satisfy
    # the record schema and carry the shard-speedup headline
    b8 = os.path.join(ROOT, "BENCH_8.json")
    if os.path.exists(b8):
        try:
            with open(b8) as f:
                b8_records = json.load(f)
        except (OSError, ValueError) as e:
            print(f"[smoke] FAILED: BENCH_8.json unreadable: {e}")
            return 1
        errors = validate_records(b8_records)
        if errors:
            print("[smoke] FAILED: BENCH_8.json violates schema:")
            for e in errors:
                print(f"[smoke]   {e}")
            return 1
        speedups = [r.get("value") for r in b8_records
                    if isinstance(r, dict) and "shard_speedup"
                    in str(r.get("name", ""))]
        if not speedups:
            print("[smoke] FAILED: BENCH_8.json has no shard_speedup record")
            return 1
        print(f"[smoke] BENCH_8.json ok ({len(b8_records)} records, "
              f"shard speedup x{max(speedups):.2f})")
    else:
        print("[smoke] no BENCH_8.json committed yet — scale gate skipped")

    # overload gate: a tiny open-loop run must keep HIGH-priority p99
    # bounded past saturation, drop nothing during a live hot swap, and
    # account for every submitted request (zero silent loss)
    rows = list(benches["overload"](smoke=True))
    _print_rows(rows)
    vals = {row[0]: row[3] for row in rows if len(row) > 3}
    p99_2x = vals.get("overload/p99_high@2x")     # milliseconds
    if p99_2x is None or not 0.0 < p99_2x <= 500.0:
        print("[smoke] FAILED: overload p99_high@2x unbounded or missing "
              f"({None if p99_2x is None else f'{p99_2x:.1f}ms'})")
        return 1
    if vals.get("overload/silent_loss", 1.0) != 0.0:
        print("[smoke] FAILED: overload run lost requests silently "
              f"({vals.get('overload/silent_loss')})")
        return 1
    if vals.get("overload/hot_swap_dropped", 1.0) != 0.0:
        print("[smoke] FAILED: hot swap under load dropped requests "
              f"({vals.get('overload/hot_swap_dropped')})")
        return 1
    print(f"[smoke] overload ok (p99_high@2x={p99_2x:.1f}ms, "
          "hot swap zero-drop)")

    # BENCH_9 gate: the committed overload artifact must satisfy the
    # record schema and carry a zero-drop hot swap + zero silent loss
    b9 = os.path.join(ROOT, "BENCH_9.json")
    if os.path.exists(b9):
        try:
            with open(b9) as f:
                b9_records = json.load(f)
        except (OSError, ValueError) as e:
            print(f"[smoke] FAILED: BENCH_9.json unreadable: {e}")
            return 1
        errors = validate_records(b9_records)
        if errors:
            print("[smoke] FAILED: BENCH_9.json violates schema:")
            for e in errors:
                print(f"[smoke]   {e}")
            return 1
        by_name = {r.get("name"): r.get("value")
                   for r in b9_records if isinstance(r, dict)}
        for key in ("overload/p99_high@2x", "overload/hot_swap_dropped",
                    "overload/silent_loss"):
            if key not in by_name:
                print(f"[smoke] FAILED: BENCH_9.json missing '{key}'")
                return 1
        if by_name["overload/hot_swap_dropped"] != 0.0:
            print("[smoke] FAILED: committed BENCH_9.json records a "
                  "lossy hot swap")
            return 1
        if by_name["overload/silent_loss"] != 0.0:
            print("[smoke] FAILED: committed BENCH_9.json records "
                  "silent request loss")
            return 1
        print(f"[smoke] BENCH_9.json ok ({len(b9_records)} records, "
              f"p99_high@2x={by_name['overload/p99_high@2x']:.1f}ms)")
    else:
        print("[smoke] no BENCH_9.json committed yet — overload gate on "
              "committed artifact skipped")

    # deep-survival gate: a tiny train -> refit -> export run must learn a
    # better-than-random deep head and the exported artifact must serve
    # through ModelRegistry/RiskService with exactly the sparse head's
    # risks (the zoo + solver + serving stack all meeting in one path —
    # the 41-test get_abstract_mesh break would fail here immediately)
    rows = list(benches["deep"](smoke=True))
    _print_rows(rows)
    vals = {row[0]: row[3] for row in rows if len(row) > 3}
    ci_deep = vals.get("deep/cindex_deep")
    if ci_deep is None or not 0.55 <= ci_deep <= 1.0:
        print("[smoke] FAILED: deep head c-index missing or ~random "
              f"({ci_deep})")
        return 1
    if vals.get("deep/served_match", 0.0) != 1.0:
        print("[smoke] FAILED: served risks diverge from the sparse "
              f"refit head (match={vals.get('deep/served_match')})")
        return 1
    print(f"[smoke] deep survival ok (cindex_deep={ci_deep:.3f}, "
          "served risks match)")

    # BENCH_10 gate: the committed deep artifact must satisfy the record
    # schema, carry the c-index headline, and record a clean serving match
    b10 = os.path.join(ROOT, "BENCH_10.json")
    if os.path.exists(b10):
        try:
            with open(b10) as f:
                b10_records = json.load(f)
        except (OSError, ValueError) as e:
            print(f"[smoke] FAILED: BENCH_10.json unreadable: {e}")
            return 1
        errors = validate_records(b10_records)
        if errors:
            print("[smoke] FAILED: BENCH_10.json violates schema:")
            for e in errors:
                print(f"[smoke]   {e}")
            return 1
        by_name = {r.get("name"): r.get("value")
                   for r in b10_records if isinstance(r, dict)}
        for key in ("deep/train", "deep/refit", "deep/cindex_deep",
                    "deep/cindex_linear", "deep/served_match"):
            if key not in by_name:
                print(f"[smoke] FAILED: BENCH_10.json missing '{key}'")
                return 1
        if by_name["deep/served_match"] != 1.0:
            print("[smoke] FAILED: committed BENCH_10.json records a "
                  "serving mismatch")
            return 1
        print(f"[smoke] BENCH_10.json ok ({len(b10_records)} records, "
              f"cindex_deep={by_name['deep/cindex_deep']:.3f} vs "
              f"linear={by_name['deep/cindex_linear']:.3f})")
    else:
        print("[smoke] no BENCH_10.json committed yet — deep gate on "
              "committed artifact skipped")
    print("[smoke] OK")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all",
                    help="comma-separated subset of "
                         f"{','.join(BENCH_KEYS)} (default: all)")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI guard: tiny benches + autotune sweep + "
                         "schema/regression gates")
    ap.add_argument("--json", metavar="PATH",
                    help="write structured bench records (BENCH_*.json)")
    ap.add_argument("--autotune", action="store_true",
                    help="run the default block-size sweep first; winners "
                         "persist to $REPRO_TUNE_CACHE and are used by "
                         "the benches")
    args = ap.parse_args()

    _setup_runtime(verbose=not args.smoke)
    if args.smoke:
        sys.exit(_smoke())

    selected = (set(BENCH_KEYS) if args.only == "all"
                else {s.strip() for s in args.only.split(",") if s.strip()})
    unknown = selected - set(BENCH_KEYS)
    if unknown:
        ap.error(f"unknown bench(es): {','.join(sorted(unknown))}")

    if args.autotune:
        from repro.kernels import autotune
        autotune.sweep(verbose=True)

    benches = _import_benches()
    backend, tuned, rev = _run_metadata()
    records = []
    print("name,us_per_call,derived")
    for key, fn in benches.items():
        if key not in selected:
            continue
        rows = list(fn())
        _print_rows(rows)
        records += make_records(key, rows, backend, tuned, rev)

    if args.json:
        if "serving" in selected:
            # a tiny-shape serving pass rides along so --smoke has an
            # apples-to-apples baseline for its regression gate
            rows = list(benches["serving"](smoke=True))
            records += make_records("serving_smoke", rows, backend, tuned,
                                    rev)
        # embed the metrics snapshot (serving/kernel counters accumulated
        # by the benches + an instrumented convergence smoke fit)
        tel_rec = _telemetry_record(backend, tuned, rev)
        merrors = validate_metrics_snapshot(tel_rec["metrics"])
        if merrors:
            for e in merrors:
                print(f"[json] schema error: {e}", file=sys.stderr)
            sys.exit(1)
        records.append(tel_rec)
        errors = validate_records(records)
        if errors:
            for e in errors:
                print(f"[json] schema error: {e}", file=sys.stderr)
            sys.exit(1)
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"[json] wrote {len(records)} records -> {args.json}")


if __name__ == "__main__":
    main()
