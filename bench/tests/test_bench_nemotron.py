"""The Nemotron-H train cell driven end to end on the CPU at a small size,
past the harness's look for a chip: a sound run comes out correct, a
traced run reports only its own per-layer metrics (the routed-pair
counter among them), a run with the train step broken underneath comes
out not correct for each fault, and the control (the reference in the
program's place in bfloat16) reads above a limit."""
import json
import sys
import time

import pytest

import faults
import harness

CELL = "nemotron3.train"
SIZE = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 8,
        "mamba_head_dim": 16, "n_groups": 4, "ssm_state_size": 16,
        "chunk_size": 16, "moe_intermediate_size": 32,
        "moe_shared_expert_intermediate_size": 48, "n_routed_experts": 4,
        "num_experts_per_tok": 2, "experts_first": 2, "vocab_size": 512}
LOAD = {"batch": 8, "seq_len": 32}
SECONDS = 1.0


def size():
    cell = harness.Cell(harness.load_spec(), CELL)
    c = cell.config
    return dict(SIZE, published=dict(c["published"], n_routed_experts=8),
                limits={"train": dict(c["limits"]["train"], segment=8,
                                      block_rows=4)})


@pytest.fixture
def fresh(f32):
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


def run(seed=3, trace=False):
    return harness.run(CELL, seed, SECONDS, trace, time.perf_counter(),
                       require_chip=False, config_override=size(),
                       traffic_override=LOAD, pending=False, log=sys.stderr)


def over_limit(checks):
    return [k for k, c in checks.items() if not c["value"] <= c["limit"]]


def test_sound_run_is_correct(fresh):
    out = run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["checks"]) == {"loss_gap", "grad_gap", "update_gap"}
    assert list(out)[-1] == "checks"
    json.dumps(out)


def test_traced_run_reports_its_own_per_layer_metrics(fresh):
    out = run(trace=True)
    assert out["correct"], out["checks"]
    assert out["device"]["window_s"] > 0
    names = {m["name"] for m in harness.Cell(harness.load_spec(),
                                             CELL).per_layer}
    assert names == {"nemo3.mfu", "nemo3.moe_ms", "nemo3.experts_roofline",
                     "nemo3.attn_ms", "nemo3.ssd_ms",
                     "nemo3.expert_imbalance", "device_idle.nemo3"}
    # the CPU has no device plane and no peaks entry: shares of a peak or
    # roofline and the device's scope times stay silent; the counter reads
    assert set(out["metrics"]) <= names
    assert out["metrics"]["nemo3.expert_imbalance"]["value"] >= 1.0


@pytest.mark.parametrize("kind", faults.KINDS)
def test_fault_is_caught(f32, kind):
    with faults.planted("train", kind):
        out = run()
    assert not out["correct"]
    assert over_limit(out["checks"])


def test_control_fails_the_check(fresh):
    cell = harness.Cell(harness.load_spec(), CELL)
    drv = cell.driver().Driver({**cell.config, **size()},
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
