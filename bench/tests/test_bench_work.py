"""Operation and byte counts, and the peaks table."""
import json
import os

import pytest

import work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_peaks_table_is_keyed_by_device_kind():
    v5e = work.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_cd_sweep_is_memory_bound_on_v5e():
    w = work.cd_sweep(1200, 1200)
    assert w["flops"] == 17 * 1200 * 1200
    assert w["bytes"] == 4 * (1200 * 1200 + 4 * 1200)
    t, bound = work.least_seconds(w, work.peaks("TPU v5 lite"))
    assert bound == "memory"
    assert t == pytest.approx(4 * (1200 * 1200 + 4800) / 819e9)


def test_score_batch_counts_no_panel_traffic():
    w = work.score_batch(64, 1200, 512)
    assert w["flops"] == 2 * 64 * 1200 + 64 + 3 * 64 * 512
    assert w["bytes"] == 4 * (64 * 1200 + 1200 + 512 + 2 * 64)
    # linear in the batch, past the model's own bytes
    d = work.score_batch(2, 1200, 512)["bytes"] - work.score_batch(
        1, 1200, 512)["bytes"]
    assert d == 4 * (1200 + 2)


def test_mamba2_flops_per_token_at_published_widths():
    with open(os.path.join(BENCH, "configs", "mamba2-130m.json")) as f:
        cfg = json.load(f)
    d, e, n, h, p = 768, 1536, 128, 24, 64
    layer = (2 * d * (2 * e + 2 * n + h) + 2 * 4 * (e + 2 * n)
             + 5 * h * p * n + 2 * h * p + 2 * e * d)
    want = 3 * 24 * layer + 3 * 2 * d / 512
    assert work.mamba2_flops_per_token(cfg, 512) == pytest.approx(want)
    # about 6 flops per non-embedding parameter, plus the recurrence
    params = 24 * (d * (2 * e + 2 * n + h) + 4 * (e + 2 * n) + e * d)
    assert 6 * params < want < 6.9 * params


def test_arrivals_are_a_fixed_count_in_seeded_order():
    import numpy as np

    import arrivals

    traffic = {"rate_per_s": 250}
    offs, rows = arrivals.schedule(traffic, 4.0, 2**33 + 5, pool=10)
    assert len(offs) == len(rows) == 1000
    assert np.all(np.diff(offs) >= 0) and 0 <= offs[0] and offs[-1] < 4.0
    assert rows.min() >= 0 and rows.max() < 10
    again, _ = arrivals.schedule(traffic, 4.0, 2**33 + 5, pool=10)
    other, _ = arrivals.schedule(traffic, 4.0, 6, pool=10)
    assert np.array_equal(offs, again) and not np.array_equal(offs, other)
    burst = dict(traffic, burst={"every_s": 1.0, "size": 8})
    offs_b, _ = arrivals.schedule(burst, 4.0, 6, pool=10)
    assert len(offs_b) == 1000 + 3 * 8
    assert np.sum(offs_b == 2.0) == 8


class _Drv:
    """The counters a traced run leaves on its driver."""

    def __init__(self, cfg, traffic, counters):
        self.cfg, self.traffic, self.counters = cfg, traffic, counters


def _ctx(drv, peak, busy_s=0.5, window_s=1.0):
    return {"driver": drv, "peak": peak, "work": work,
            "trace": {"busy_s": busy_s, "window_s": window_s}}


def _read(name, ctx):
    import harness

    return harness.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                               "t_" + name.replace(".", "_")).read(ctx)


def _cells():
    with open(os.path.join(BENCH, "configs", "appc_n1200_p1200.json")) as f:
        appc = json.load(f)
    with open(os.path.join(BENCH, "configs", "mamba2-130m.json")) as f:
        mamba = json.load(f)
    fit = _Drv(appc, {}, {"sweep_s": 0.028})
    serve = _Drv(appc, {}, {"dispatch_batches": [8, 16, 64],
                            "features": 1200})
    train = _Drv(mamba, {"seq_len": 512}, {"tokens_per_s": 33000.0})
    return {"fit_cd_roofline": fit, "fit_cd.mfu": fit,
            "score_roofline": serve, "score.mfu": serve, "train.mfu": train}


@pytest.mark.parametrize("name", sorted(_cells()))
def test_shares_read_within_a_peak(name):
    """Each share of a roofline or a peak reads above 0 and at most 100%
    on v5e at plausible counters, and stays silent without a peak."""
    drv = _cells()[name]
    v = _read(name, _ctx(drv, work.peaks("TPU v5 lite")))
    assert 0.0 < v <= 100.0
    assert _read(name, _ctx(drv, None)) is None


def test_train_mfu_is_flops_times_rate_over_peak():
    drv = _cells()["train.mfu"]
    peak = work.peaks("TPU v5 lite")
    want = 100.0 * work.mamba2_flops_per_token(drv.cfg, 512) * 33000.0 \
        / peak["bf16_flops_per_s"]
    assert _read("train.mfu", _ctx(drv, peak)) == pytest.approx(want)
