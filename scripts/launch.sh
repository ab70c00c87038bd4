#!/usr/bin/env bash
# Runtime-tuned launcher (the SNIPPETS.md / HomebrewNLP recipe).
#
# Applies the same policy as src/repro/launch/runtime.py plus the one
# thing Python cannot do for itself: preloading tcmalloc. Existing env
# values always win (every export below is a default, not an override).
#
#   scripts/launch.sh -m benchmarks.run --smoke
#   scripts/launch.sh -m benchmarks.run --only kernels,serving --autotune --json BENCH_6.json
#   scripts/launch.sh examples/serve_risk_api.py
set -euo pipefail

ROOT="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"

# faster malloc, when the container ships it
if [ -z "${LD_PRELOAD:-}" ]; then
  for so in /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
            /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4 \
            /usr/lib/libtcmalloc.so.4 \
            /usr/lib/libtcmalloc_minimal.so.4; do
    if [ -f "$so" ]; then
      export LD_PRELOAD="$so"
      break
    fi
  done
fi

export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD="${TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD:-60000000000}"
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"      # no TF/XLA chatter
export XLA_FLAGS="${XLA_FLAGS:-}"                             # deployment flags slot
export PYTHONPATH="$ROOT/src${PYTHONPATH:+:$PYTHONPATH}"

exec /usr/bin/env python "$@"
