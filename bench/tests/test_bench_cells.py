"""Each cell driven end to end on the CPU at a small size, past the
harness's look for a chip: a sound run comes out correct, and a run with
the timed path broken underneath comes out not correct, once for each
fault the cell can have. The controls (the reference in the program's
place one precision lower) read above the cell's limits.

The serving cell runs at the configuration's own width (p = 1200), the
width at which its control is measured; the others at small sizes."""
import json
import os
import sys
import time

import numpy as np
import pytest

import faults
import harness

FIT_SIZE = {"n": 200, "p": 32, "k": 4}
SERVE_SIZE = {"n": 400, "serve": {"grid": 512, "request_pool": 256,
                                  "coefficients": {"support_scale": 0.25,
                                                   "dense_scale": 0.02}}}
SERVE_LOAD = {"rate_per_s": 100}
TRAIN_SIZE = {"n_layer": 2, "d_model": 64, "vocab_size": 512, "d_state": 16,
              "headdim": 16, "chunk_size": 16}
TRAIN_LOAD = {"batch": 8, "seq_len": 32}
CELLS = {"appc.fit": (FIT_SIZE, {}, 0.5),
         "appc.serve": (SERVE_SIZE, SERVE_LOAD, 1.0),
         "mamba2.train": (TRAIN_SIZE, TRAIN_LOAD, 1.0)}


@pytest.fixture
def fresh(f32):
    """No compiled program carries a planted fault into another test."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


def run(workload, seed=3, trace=False):
    cfg, load, seconds = CELLS[workload]
    return harness.run(workload, seed, seconds, trace, time.perf_counter(),
                       require_chip=False, config_override=cfg,
                       traffic_override=load, pending=True, log=sys.stderr)


def drive(workload, seed=3):
    """Set-up, window, release and check of one cell; returns the driver
    for its control."""
    cfg, load, seconds = CELLS[workload]
    spec = harness.load_spec(pending=True)
    cell = harness.Cell(spec, workload)
    drv = cell.driver().Driver({**cell.config, **cfg},
                               {**cell.traffic, **load}, seed,
                               harness.devices_for(1, False), log=sys.stderr)
    drv.setup()
    drv.window(seconds, None)
    drv.release()
    return drv, drv.check()


def over_limit(checks):
    return [k for k, c in checks.items() if not c["value"] <= c["limit"]]


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_sound_run_is_correct(fresh, workload):
    out = run(workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    json.dumps(out)


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_traced_run_reports_per_layer_metrics(fresh, workload):
    out = run(workload, trace=True)
    assert out["correct"], out["checks"]
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    spec = harness.load_spec(pending=True)
    names = {m["name"] for m in harness.Cell(spec, workload).per_layer}
    # the CPU has no device plane and no peaks entry: the shares of a
    # roofline or a peak stay silent, the rest are read
    assert set(out["metrics"]) <= names
    assert any(not n.endswith("roofline") and "mfu" not in n
               for n in out["metrics"])


# -- faults --------------------------------------------------------------------

DRIVER = {"appc.fit": "fit", "appc.serve": "serve", "mamba2.train": "train"}


@pytest.mark.parametrize("kind", faults.KINDS)
@pytest.mark.parametrize("workload", sorted(CELLS))
def test_fault_is_caught(f32, workload, kind):
    with faults.planted(DRIVER[workload], kind):
        out = run(workload)
    assert not out["correct"]
    assert over_limit(out["checks"])


def test_serve_unanswered_requests_are_failures(fresh, monkeypatch):
    """Half of each batch of the window dropped without an answer: the
    run waits for them past the close, then counts them as failed."""
    from repro.serving import service

    original = service.RiskService._form_batch
    warm = 192    # requests the set-up sends through the service

    def dropping(self):
        reqs, expired, abandoned = original(self)
        return ([r for r in reqs if r.rid < warm or r.rid % 2 == 0],
                expired, abandoned)

    monkeypatch.setattr(service.RiskService, "_form_batch", dropping)
    monkeypatch.setattr(harness.Cell, "driver", _short_wait)
    out = run("appc.serve")
    assert not out["correct"]
    assert 0 < out["failed"] <= out["attempted"] // 2 + 1


def _short_wait(cell):
    mod = harness.load_module(os.path.join(
        harness.BENCH, "drivers", cell.traffic["driver"] + ".py"),
        "bench_driver_short_wait")
    mod.WAIT_AFTER_CLOSE_S = 0.5
    return mod


# -- controls ------------------------------------------------------------------

@pytest.mark.parametrize("workload", sorted(CELLS))
def test_control_fails_the_check(fresh, workload):
    drv, rep = drive(workload)
    assert rep["correct"], rep["checks"]
    control = drv.control()
    limits = {k: c["limit"] for k, c in rep["checks"].items()}
    assert [k for k, v in control.items() if not v <= limits[k]], control
