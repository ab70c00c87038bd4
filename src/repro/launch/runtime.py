"""Runtime launch policy: env / XLA-flag tuning idioms.

The HomebrewNLP-style recipe (SNIPPETS.md): tcmalloc preload, silenced
TF/XLA logging and merged (never clobbered) ``XLA_FLAGS``. ``apply()``
setdefaults the policy into ``os.environ`` and must run **before** jax is
imported —
``scripts/launch.sh`` applies the same policy from the shell, which is the
only place the tcmalloc ``LD_PRELOAD`` can happen (a running process
cannot re-preload its allocator; ``apply()`` just reports availability).

``apply()`` also gives JAX a persistent compilation cache: the directory
``$JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads it itself),
else ``.jax_cache`` at the root of this checkout. The path is part of the
cache key, so it is fixed, never temporary.

Used by ``benchmarks/run.py``, ``chip_smoke.py`` and
``examples/serve_risk_api.py``; they log the effective environment via
``log()`` so every recorded benchmark is attributable to a concrete
runtime configuration.
"""
from __future__ import annotations

import os
import sys
from typing import Dict, Iterable, Optional

TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
    "/usr/lib/libtcmalloc_minimal.so.4",
)

ENV_DEFAULTS: Dict[str, str] = {
    "TF_CPP_MIN_LOG_LEVEL": "4",               # silence TF/XLA chatter
    "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
}

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
COMPILE_CACHE_DEFAULT = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))

# deployment-specific XLA flags go here (merged into $XLA_FLAGS, existing
# user flags win); empty by default — the CPU container needs none
XLA_FLAG_DEFAULTS: tuple = ()

# large-n scale-out knobs (PR 8): how many data shards the scoring engine
# spreads a batch over (0 = auto: one shard per local device), and the row
# count of one streaming-fit chunk (the working-set bound of fit_stream)
DATA_SHARDS_ENV = "REPRO_DATA_SHARDS"
STREAM_CHUNK_ENV = "REPRO_STREAM_CHUNK"
STREAM_CHUNK_DEFAULT = 65536


def data_shards() -> int:
    """``$REPRO_DATA_SHARDS`` as an int; 0 means auto (per-device)."""
    try:
        return max(int(os.environ.get(DATA_SHARDS_ENV, "0")), 0)
    except ValueError:
        return 0


def stream_chunk() -> int:
    """``$REPRO_STREAM_CHUNK`` rows per streaming-fit chunk (>= 1)."""
    try:
        return max(int(os.environ.get(STREAM_CHUNK_ENV,
                                      str(STREAM_CHUNK_DEFAULT))), 1)
    except ValueError:
        return STREAM_CHUNK_DEFAULT


def find_tcmalloc() -> Optional[str]:
    for p in TCMALLOC_PATHS:
        if os.path.exists(p):
            return p
    return None


def tcmalloc_active() -> bool:
    return "tcmalloc" in os.environ.get("LD_PRELOAD", "")


def apply(extra_env: Optional[Dict[str, str]] = None,
          xla_flags: Iterable[str] = XLA_FLAG_DEFAULTS) -> Dict[str, str]:
    """Setdefault the runtime policy into the environment.

    Returns the keys actually set (existing values are never overridden).
    Call before importing jax; a late call is detected and flagged in the
    returned dict under ``"_late"`` since env-derived config (the compile
    cache directory, XLA flags) is read at import/backend-init time.
    """
    applied: Dict[str, str] = {}
    defaults = {**ENV_DEFAULTS, COMPILE_CACHE_ENV: COMPILE_CACHE_DEFAULT,
                **(extra_env or {})}
    for k, v in defaults.items():
        if k not in os.environ:
            os.environ[k] = v
            applied[k] = v
    merged = [f for f in xla_flags
              if f not in os.environ.get("XLA_FLAGS", "")]
    if merged:
        flags = (os.environ.get("XLA_FLAGS", "") + " " + " ".join(merged))
        os.environ["XLA_FLAGS"] = flags.strip()
        applied["XLA_FLAGS"] = os.environ["XLA_FLAGS"]
    if applied and "jax" in sys.modules:
        applied["_late"] = "jax already imported; defaults may not apply"
    return applied


def describe() -> Dict[str, object]:
    """The effective runtime environment (imports jax lazily)."""
    import jax

    tc = find_tcmalloc()
    return {
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "jax_version": jax.__version__,
        "ld_preload": os.environ.get("LD_PRELOAD", ""),
        "tcmalloc": ("active" if tcmalloc_active()
                     else f"available:{tc}" if tc else "absent"),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "env": {k: os.environ.get(k, "") for k in ENV_DEFAULTS},
        "tune_cache": os.environ.get("REPRO_TUNE_CACHE", "(default)"),
        "compile_cache": os.environ.get(COMPILE_CACHE_ENV, "(off)"),
        "data_shards": data_shards() or "(auto)",
        "stream_chunk": stream_chunk(),
    }


def log(prefix: str = "[runtime]") -> Dict[str, object]:
    """Print and return the effective environment, one line per field.

    Also emits a ``runtime.env`` snapshot event to the JSONL sink (when
    ``$REPRO_EVENTS_FILE`` is on), so every recorded trace/benchmark
    stream opens with the runtime configuration that produced it.
    """
    d = describe()
    for k, v in d.items():
        print(f"{prefix} {k}={v}", flush=True)
    if not tcmalloc_active() and find_tcmalloc():
        print(f"{prefix} note: tcmalloc present but not preloaded — "
              "launch via scripts/launch.sh to enable it", flush=True)
    from ..obs import events as obs_events

    obs_events.emit("runtime.env", **{k: v for k, v in d.items()})
    return d
