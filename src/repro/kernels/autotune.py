"""Block-size autotuner for the Pallas kernels.

Per kernel (``revcumsum``, ``cox_batch``, ``survival_curves``) and per
shape bucket (power-of-two buckets on the kernel's shape axes, matching
the serving engine's batch bucketing),
``autotune()`` times a small candidate grid of block configs with
``block_until_ready``, picks the winner, and persists it to a JSON cache
keyed by ``backend/kernel/bucket``. ``ops.py`` calls ``lookup()`` on every
dispatch — a pure dict read that falls back to the static defaults when a
bucket is untuned, so production paths never pay a timing cost. Winners
are also registered into the roofline registry (``analysis/roofline.py``)
so the report's tuned-blocks table shows tuned vs default.

Cache location: ``$REPRO_TUNE_CACHE`` when set, else the committed
``benchmarks/tuned_blocks.json`` of this checkout, so kernel blocks never
come from a file outside the repository.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import events as obs_events
from .cox_batch import cox_batch
from .revcumsum import revcumsum
from .survival_curves import survival_curves, survival_curves_stratified

CACHE_ENV = "REPRO_TUNE_CACHE"
DEFAULT_CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    "benchmarks", "tuned_blocks.json")
CACHE_VERSION = 1

# the static fallbacks — identical to the historical hard-coded blocks, so
# untuned deployments behave exactly as before
DEFAULT_CONFIGS: Dict[str, Dict[str, int]] = {
    "revcumsum": {"block_n": 512},
    "cox_batch": {"block_n": 512, "block_p": 256},
    "survival_curves": {"block_b": 256, "block_g": 128},
    "survival_curves_strat": {"block_g": 128},
}

# shape axes that key a bucket, in display order
SHAPE_AXES: Dict[str, Tuple[str, ...]] = {
    "revcumsum": ("n", "m"),
    "cox_batch": ("n", "p"),
    "survival_curves": ("b", "g"),
    "survival_curves_strat": ("b", "g"),
}

# config key -> the shape axis it tiles (used to prune candidates that are
# grossly oversized for a bucket; the default config always survives)
BLOCK_AXES: Dict[str, Dict[str, str]] = {
    "revcumsum": {"block_n": "n"},
    "cox_batch": {"block_n": "n", "block_p": "p"},
    "survival_curves": {"block_b": "b", "block_g": "g"},
    "survival_curves_strat": {"block_g": "g"},
}

# candidate grids: small on purpose (autotuning cost is linear in their
# size) and all TPU-tileable (multiples of the (8, 128) f32 tile)
CANDIDATES: Dict[str, List[Dict[str, int]]] = {
    "revcumsum": [{"block_n": b} for b in (256, 512, 1024, 2048)],
    "cox_batch": [
        {"block_n": 512, "block_p": 256},
        {"block_n": 1024, "block_p": 256},
        {"block_n": 2048, "block_p": 128},
        {"block_n": 1024, "block_p": 512},
    ],
    "survival_curves": [
        {"block_b": 128, "block_g": 128},
        {"block_b": 256, "block_g": 128},
        {"block_b": 512, "block_g": 128},
        {"block_b": 1024, "block_g": 128},
        {"block_b": 256, "block_g": 256},
        {"block_b": 1024, "block_g": 512},
    ],
    "survival_curves_strat": [{"block_g": b} for b in (128, 256, 512)],
}

# shapes swept by ``benchmarks/run.py --autotune``: the bench_kernels
# shapes plus the default serving curve shapes (engine grid_size=128)
DEFAULT_SWEEP: List[Tuple[str, Dict[str, int]]] = [
    ("revcumsum", {"n": 65536, "m": 128}),
    ("cox_batch", {"n": 100_000, "p": 64}),
    ("survival_curves", {"b": 256, "g": 128}),
    ("survival_curves", {"b": 1024, "g": 128}),
]

_KERNEL_FNS = {
    "revcumsum": revcumsum,
    "cox_batch": cox_batch,
    "survival_curves": survival_curves,
    "survival_curves_strat": survival_curves_stratified,
}


# -- buckets and cache keys -------------------------------------------------

def bucket(v: int) -> int:
    """Next power of two >= v (>= 1), same policy as the engine's batches."""
    return 1 << max(int(np.ceil(np.log2(max(int(v), 1)))), 0)


def bucket_key(kernel: str, shape: Dict[str, int],
               backend: Optional[str] = None) -> str:
    backend = backend or jax.default_backend()
    dims = ",".join(f"{a}={bucket(shape[a])}" for a in SHAPE_AXES[kernel])
    return f"{backend}/{kernel}/{dims}"


# -- JSON cache -------------------------------------------------------------

def cache_path() -> str:
    return os.environ.get(CACHE_ENV) or os.path.normpath(DEFAULT_CACHE)

_LOADED: Dict[str, Dict[str, dict]] = {}   # path -> entries (lazy, per file)


def load_cache(path: Optional[str] = None,
               refresh: bool = False) -> Dict[str, dict]:
    path = path or cache_path()
    if refresh or path not in _LOADED:
        try:
            with open(path) as f:
                data = json.load(f)
            entries = data.get("entries", {}) if isinstance(data, dict) else {}
        except (OSError, ValueError):
            entries = {}
        _LOADED[path] = entries
    return _LOADED[path]


def save_cache(entries: Dict[str, dict], path: Optional[str] = None) -> str:
    path = path or cache_path()
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"version": CACHE_VERSION, "entries": entries}, f,
                  indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    _LOADED[path] = entries
    return path


def lookup_tagged(kernel: str, cache_file: Optional[str] = None,
                  **shape: int) -> Tuple[Dict[str, int], str]:
    """(config, provenance) for ``kernel`` at ``shape`` — the dispatch read.

    Provenance is ``"tuned"`` when the bucket has a cached winner and
    ``"default"`` on the static fallback; ``ops.py`` tags its per-kernel
    dispatch counters with it, so an untuned fleet shows up in metrics
    rather than silently running default blocks. Never times anything.
    """
    entry = load_cache(cache_file).get(bucket_key(kernel, shape))
    if entry and isinstance(entry.get("config"), dict):
        return dict(entry["config"]), "tuned"
    return dict(DEFAULT_CONFIGS[kernel]), "default"


def lookup(kernel: str, cache_file: Optional[str] = None,
           **shape: int) -> Dict[str, int]:
    """Tuned block config (``DEFAULT_CONFIGS`` fallback); see lookup_tagged."""
    return lookup_tagged(kernel, cache_file, **shape)[0]


# -- timing -----------------------------------------------------------------

def _build_inputs(kernel: str, shape: Dict[str, int], seed: int = 0):
    """Random inputs honoring the kernel's contract (sorted/tie-free not
    required: these kernels only assume the precomputed-vector algebra)."""
    rng = np.random.default_rng(seed)
    if kernel == "revcumsum":
        n, m = shape["n"], shape["m"]
        return (jnp.asarray(rng.standard_normal((n, m)), jnp.float32),)
    if kernel == "cox_batch":
        n, p = shape["n"], shape["p"]
        x = jnp.asarray(rng.standard_normal((n, p)), jnp.float32)
        eta = jnp.asarray(rng.standard_normal(n) * 0.3, jnp.float32)
        d = jnp.asarray((rng.uniform(size=n) < 0.7).astype(np.float32))
        w = jnp.exp(eta - jnp.max(eta))
        inv_s0 = 1.0 / jax.lax.cumsum(w, axis=0, reverse=True)
        wa = w * jnp.cumsum(d * inv_s0)
        return (x, w, wa - d, wa, d, inv_s0)
    if kernel == "survival_curves":
        b, g = shape["b"], shape["g"]
        return (jnp.asarray(rng.standard_normal(b) * 0.5, jnp.float32),
                jnp.asarray(np.linspace(0.0, 2.0, g), jnp.float32))
    if kernel == "survival_curves_strat":
        b, g = shape["b"], shape["g"]
        s = 8
        h0 = np.cumsum(rng.uniform(0.0, 0.05, size=(s, g)), axis=1)
        return (jnp.asarray(rng.standard_normal(b) * 0.5, jnp.float32),
                jnp.asarray(h0, jnp.float32),
                jnp.asarray(rng.integers(0, s, size=b), jnp.int32))
    raise KeyError(f"unknown kernel {kernel!r}")


def run_config(kernel: str, inputs: tuple, config: Dict[str, int],
               interpret: Optional[bool] = None):
    """One kernel call at an explicit block config (tuning / parity tests)."""
    return _KERNEL_FNS[kernel](*inputs, **config, interpret=interpret)


def _time_call(fn, reps: int = 3) -> float:
    """Mean wall microseconds per call, after a compile/warm-up call."""
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def candidates_for(kernel: str, shape: Dict[str, int]) -> List[Dict[str, int]]:
    """Candidate grid pruned to the shape bucket (a block dim larger than
    the padded bucket only adds padding); the default always survives so
    the winner is by construction >= as fast as the fixed blocks."""
    axes = BLOCK_AXES[kernel]
    floor = {k: min(c[k] for c in CANDIDATES[kernel]) for k in axes}
    default = DEFAULT_CONFIGS[kernel]
    out: List[Dict[str, int]] = [dict(default)]
    for cfg in CANDIDATES[kernel]:
        if cfg in out:
            continue
        if any(cfg[k] > max(bucket(shape[ax]), floor[k])
               for k, ax in axes.items()):
            continue
        out.append(dict(cfg))
    return out


def _cfg_key(cfg: Dict[str, int]) -> str:
    return ",".join(f"{k}={cfg[k]}" for k in sorted(cfg))


def _register(key: str, entry: dict) -> None:
    from ..analysis import roofline
    roofline.register_tuned(key, entry)


def autotune(kernel: str, shape: Dict[str, int], *,
             cache_file: Optional[str] = None, reps: int = 3,
             force: bool = False, interpret: Optional[bool] = None,
             verbose: bool = False) -> Dict[str, int]:
    """Tune one (kernel, bucket): time candidates, persist + return winner.

    A cached bucket is returned without re-timing unless ``force``.
    """
    path = cache_file or cache_path()
    key = bucket_key(kernel, shape)
    entries = load_cache(path, refresh=True)
    cached = entries.get(key)
    if cached is not None and not force and isinstance(
            cached.get("config"), dict):
        _register(key, cached)
        return dict(cached["config"])

    inputs = _build_inputs(kernel, shape)
    timings: Dict[str, dict] = {}
    for cfg in candidates_for(kernel, shape):
        us = _time_call(
            lambda cfg=cfg: run_config(kernel, inputs, cfg, interpret),
            reps=reps)
        timings[_cfg_key(cfg)] = {"config": cfg, "us": us}
        if verbose:
            print(f"[autotune] {key} {_cfg_key(cfg)} {us:.1f}us",
                  flush=True)
    best = min(timings.values(), key=lambda e: e["us"])
    entry = {
        "kernel": kernel,
        "backend": key.split("/", 1)[0],
        "shape": {a: int(shape[a]) for a in SHAPE_AXES[kernel]},
        "config": dict(best["config"]),
        "us": best["us"],
        "default_config": dict(DEFAULT_CONFIGS[kernel]),
        "default_us": timings[_cfg_key(DEFAULT_CONFIGS[kernel])]["us"],
        "candidates": {k: v["us"] for k, v in timings.items()},
        "reps": reps,
    }
    entries[key] = entry
    save_cache(entries, path)
    _register(key, entry)
    obs_events.emit("autotune.winner", key=key, config=best["config"],
                    us=best["us"], default_us=entry["default_us"])
    if verbose:
        print(f"[autotune] {key} winner {_cfg_key(best['config'])} "
              f"({best['us']:.1f}us vs default "
              f"{entry['default_us']:.1f}us)", flush=True)
    return dict(best["config"])


def sweep(shapes: Optional[Sequence[Tuple[str, Dict[str, int]]]] = None,
          **kwargs) -> Dict[str, Dict[str, int]]:
    """Autotune a list of (kernel, shape) pairs; defaults to DEFAULT_SWEEP."""
    winners: Dict[str, Dict[str, int]] = {}
    for kernel, shape in (shapes if shapes is not None else DEFAULT_SWEEP):
        winners[bucket_key(kernel, shape)] = autotune(kernel, shape, **kwargs)
    return winners
