"""The beam-search cell driven end to end on the CPU at a small size, past
the harness's look for a chip: a sound run comes out correct, a traced
run reports only its own per-layer metrics, a run with the search broken
underneath comes out not correct for each fault, and the control (the
searches through the program's bfloat16 path) reads above a limit.

The cell is not in ``BENCHMARK.json`` yet: ``bench/pending_beam.json``
holds the entries that admit it, and the tests run it under the spec
with those entries merged in."""
import contextlib
import json
import os
import sys
import time

import numpy as np
import pytest

import faults
import harness

CELL = "appc.beam_k15"
SIZE = {"n": 200, "p": 32, "k": 4}
LOAD = {"search": {"k": 4, "beam_width": 2, "n_expand": 3, "lam2": 0.001,
                   "score_steps": 4, "finetune_sweeps": 60},
        "trace_seconds": 0.2}
SECONDS = 0.5


def pending_spec(pending=False):
    """``BENCHMARK.json`` with ``pending_beam.json`` merged in: an entry
    whose name is there already gains its ``workloads``, any other is
    appended to its list."""
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    extra = harness.load_json(os.path.join(harness.BENCH,
                                           "pending_beam.json"))
    for group in ("workloads", "end_to_end", "per_layer"):
        have = {e["name"]: e for e in spec[group]}
        for entry in extra.get(group, []):
            if entry["name"] in have:
                have[entry["name"]]["workloads"] += entry["workloads"]
            else:
                spec[group].append(entry)
    return spec


@pytest.fixture(autouse=True)
def admitted(monkeypatch):
    monkeypatch.setattr(harness, "load_spec", pending_spec)


@pytest.fixture
def fresh(f32):
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


def run(seed=3, trace=False):
    return harness.run(CELL, seed, SECONDS, trace, time.perf_counter(),
                       require_chip=False, config_override=SIZE,
                       traffic_override=LOAD, pending=False, log=sys.stderr)


def over_limit(checks):
    return [k for k, c in checks.items() if not c["value"] <= c["limit"]]


def test_sound_run_is_correct(fresh):
    out = run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert set(out["checks"]) == {"loss_gap", "kkt_support", "support_off"}
    assert out["checks"]["support_off"]["value"] == 0
    assert set(out["metrics"]) == {"solve_s", "setup_s"}
    assert list(out)[-1] == "checks"
    json.dumps(out)


def test_traced_run_reports_its_own_per_layer_metrics(fresh):
    out = run(trace=True)
    assert out["correct"], out["checks"]
    assert out["device"]["window_s"] > 0
    names = {m["name"] for m in harness.Cell(harness.load_spec(),
                                             CELL).per_layer}
    assert names == {"beam.score_share", "beam.finetune_share",
                     "device_idle.beam"}
    assert set(out["metrics"]) <= names
    # the spans are read on the CPU too; their shares are of one window
    score = out["metrics"]["beam.score_share"]["value"]
    tune = out["metrics"]["beam.finetune_share"]["value"]
    assert 0 < score and 0 < tune and score + tune <= 100.0


@contextlib.contextmanager
def planted(kind):
    """``faults.KINDS`` under the search: every finetune leaves the
    coefficients at zero; the search sees the first half of the cohort;
    the returned loss is altered by 0.1%."""
    import jax
    import jax.numpy as jnp

    from repro.core import beam, cox

    if kind == "state_unchanged":
        def stuck(data, support_idx, support_mask, lam2, k_max,
                  n_sweeps=60):
            eta = jnp.zeros(data.n, data.x.dtype)
            return (jnp.zeros(k_max, data.x.dtype), eta,
                    cox.loss_from_eta(data, eta))

        patch = faults._patched(beam, "finetune", stuck)
    else:
        original = beam.beam_search

        def broken(data, **kw):
            if kind == "half_batch":
                m = data.n // 2
                idx = jnp.arange(m, dtype=jnp.int32)
                data = cox.CoxData(x=data.x[:m], delta=data.delta[:m],
                                   risk_start=idx, tie_end=idx)
            out = original(data, **kw)
            if kind == "answer_altered":
                out.losses[-1] *= 1.001
            return out

        patch = faults._patched(beam, "beam_search", broken)
    jax.clear_caches()
    try:
        with patch:
            yield
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("kind", faults.KINDS)
def test_fault_is_caught(f32, kind):
    with planted(kind):
        out = run()
    assert not out["correct"]
    assert over_limit(out["checks"])


def test_control_fails_the_check(fresh):
    cell = harness.Cell(harness.load_spec(), CELL)
    drv = cell.driver().Driver({**cell.config, **SIZE},
                               {**cell.traffic, **LOAD}, 3,
                               harness.devices_for(1, False), log=sys.stderr)
    drv.setup()
    drv.window(SECONDS, None)
    drv.release()
    rep = drv.check()
    assert rep["correct"], rep["checks"]
    control = drv.control()
    limits = {k: c["limit"] for k, c in rep["checks"].items()}
    assert [k for k, v in control.items() if not v <= limits[k]], control
    assert np.isfinite(list(control.values())).all()
