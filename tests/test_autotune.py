"""Autotuner: shape-bucket edges, cache round-trip (no re-timing), default
fallback, ops dispatch through a tuned cache, and parity of every candidate
block config against the jnp references — including ragged/padded shapes."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, ops, ref


# -- shape buckets ----------------------------------------------------------

def test_bucket_edges():
    assert autotune.bucket(1) == 1
    assert autotune.bucket(2) == 2
    assert autotune.bucket(3) == 4
    assert autotune.bucket(512) == 512
    assert autotune.bucket(513) == 1024
    assert autotune.bucket(0) == 1   # degenerate guard


def test_bucket_key_shape_and_backend():
    k = autotune.bucket_key("revcumsum", {"n": 1000, "m": 3}, backend="cpu")
    assert k == "cpu/revcumsum/n=1024,m=4"
    # every n in (512, 1024] lands in the same bucket
    assert autotune.bucket_key("revcumsum", {"n": 600, "m": 4},
                               backend="cpu") == k
    assert autotune.bucket_key("revcumsum", {"n": 1000, "m": 3},
                               backend="tpu") != k


def test_candidates_pruned_to_bucket_but_default_kept():
    default = autotune.DEFAULT_CONFIGS["survival_curves"]
    cands = autotune.candidates_for("survival_curves", {"b": 32, "g": 32})
    assert default in cands
    floor_b = min(c["block_b"]
                  for c in autotune.CANDIDATES["survival_curves"])
    for cfg in cands:
        if cfg != default:
            assert cfg["block_b"] <= max(32, floor_b)


# -- cache round-trip -------------------------------------------------------

def test_cache_roundtrip_no_retiming(tmp_path, monkeypatch):
    path = str(tmp_path / "tuned.json")
    shape = {"n": 96, "m": 4}
    cfg = autotune.autotune("revcumsum", shape, cache_file=path, reps=1)
    assert set(cfg) == {"block_n"}
    with open(path) as f:
        data = json.load(f)
    assert len(data["entries"]) == 1
    (entry,) = data["entries"].values()
    assert entry["config"] == cfg
    assert entry["default_config"] == autotune.DEFAULT_CONFIGS["revcumsum"]
    assert entry["us"] <= entry["default_us"] + 1e-9

    def boom(*a, **k):
        raise AssertionError("cached bucket was re-timed")

    monkeypatch.setattr(autotune, "_time_call", boom)
    # same bucket (n=70 -> 128, m=3 -> 4 just like n=96, m=4): cache hit
    assert autotune.autotune("revcumsum", {"n": 70, "m": 3},
                             cache_file=path) == cfg
    # a fresh process state reloads the same winners from disk
    autotune._LOADED.clear()
    assert autotune.autotune("revcumsum", shape, cache_file=path) == cfg


def test_lookup_falls_back_to_default(tmp_path, monkeypatch):
    monkeypatch.setenv(autotune.CACHE_ENV, str(tmp_path / "missing.json"))
    autotune._LOADED.clear()
    for kernel, default in autotune.DEFAULT_CONFIGS.items():
        shape = {a: 64 for a in autotune.SHAPE_AXES[kernel]}
        assert autotune.lookup(kernel, **shape) == default


def test_lookup_returns_tuned_winner(tmp_path, monkeypatch):
    path = str(tmp_path / "tuned.json")
    monkeypatch.setenv(autotune.CACHE_ENV, path)
    key = autotune.bucket_key("revcumsum", {"n": 100, "m": 2})
    autotune.save_cache({key: {"config": {"block_n": 64}}}, path)
    assert autotune.lookup("revcumsum", n=100, m=2) == {"block_n": 64}
    # a different bucket still falls back to the default
    assert autotune.lookup("revcumsum", n=100_000, m=2) == \
        autotune.DEFAULT_CONFIGS["revcumsum"]


def test_ops_dispatch_consults_tuned_cache(tmp_path, monkeypatch):
    """ops.revcumsum with a tuned block produces reference results."""
    path = str(tmp_path / "tuned.json")
    monkeypatch.setenv(autotune.CACHE_ENV, path)
    n = 200
    key = autotune.bucket_key("revcumsum", {"n": n, "m": 1})
    autotune.save_cache({key: {"config": {"block_n": 64}}}, path)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(n), jnp.float32)
    np.testing.assert_allclose(np.asarray(ops.revcumsum(x)),
                               np.asarray(ref.revcumsum_ref(x)),
                               rtol=1e-4, atol=1e-4)


def test_autotune_registers_into_roofline(tmp_path):
    from repro.analysis import roofline
    path = str(tmp_path / "tuned.json")
    shape = {"b": 16, "g": 16}
    autotune.autotune("survival_curves", shape, cache_file=path, reps=1)
    key = autotune.bucket_key("survival_curves", shape)
    assert key in roofline.TUNED_KERNELS
    assert "default_us" in roofline.TUNED_KERNELS[key]


# -- parity of every candidate config against the jnp references ------------

RAGGED_SHAPES = {
    "revcumsum": {"n": 333, "m": 5},
    "cox_batch": {"n": 261, "p": 19},
    "survival_curves": {"b": 77, "g": 33},
    "survival_curves_strat": {"b": 77, "g": 33},
}


def _reference(kernel, inputs):
    if kernel == "revcumsum":
        return ref.revcumsum_ref(*inputs)
    if kernel == "cox_batch":
        return ref.cox_batch_ref(*inputs)
    if kernel == "survival_curves_strat":
        return ref.survival_curves_stratified_ref(*inputs)
    return ref.survival_curves_ref(*inputs)


@pytest.mark.parametrize("kernel", sorted(autotune.CANDIDATES))
def test_every_candidate_matches_ref_on_ragged_shapes(kernel):
    """All candidates (pruned or not — blocks larger than the shape stress
    the padding paths) agree with the oracle at a ragged shape."""
    shape = RAGGED_SHAPES[kernel]
    inputs = autotune._build_inputs(kernel, shape, seed=3)
    expect = [np.asarray(a, np.float32)
              for a in jax.tree_util.tree_leaves(_reference(kernel, inputs))]
    configs = [autotune.DEFAULT_CONFIGS[kernel]] + autotune.CANDIDATES[kernel]
    seen = []
    for cfg in configs:
        if cfg in seen:
            continue
        seen.append(cfg)
        got = autotune.run_config(kernel, inputs, cfg, interpret=True)
        leaves = [np.asarray(a, np.float32)
                  for a in jax.tree_util.tree_leaves(got)]
        assert len(leaves) == len(expect)
        for a, b in zip(leaves, expect):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4,
                                       err_msg=f"{kernel} {cfg}")


def test_tuned_winner_matches_ref_end_to_end(tmp_path):
    """autotune -> cache -> lookup -> run at the winning config == oracle."""
    path = str(tmp_path / "tuned.json")
    shape = {"b": 48, "g": 20}
    cfg = autotune.autotune("survival_curves", shape, cache_file=path,
                            reps=1)
    inputs = autotune._build_inputs("survival_curves", shape, seed=11)
    got = autotune.run_config("survival_curves", inputs, cfg, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.survival_curves_ref(*inputs)),
                               rtol=2e-5, atol=2e-5)
