"""Device time by the program's own named scopes, for programs whose
scopes ``scopes.SCOPES`` does not list: the layer-pattern hybrid's
expert layer (``moe.route``, ``moe.dispatch``, ``moe.experts``,
``moe.combine``, ``moe.shared``) and attention (``attn.proj``,
``attn.core``), beside the Mamba-2, model, head and optimizer scopes it
shares with the Mamba-2 stack.

The reading is ``scopes``'s: each device operation's self time
(``scopes.op_seconds``) credited to the innermost listed scope of its
instruction's ``op_name`` in the compiled program (``scopes.op_names``).
It is kept in the readers' ``ctx`` under its own key, so that a cell
read by both modules never mixes their lists.
"""
from __future__ import annotations

import sys
from typing import Callable, Dict, Optional

import scopes
import tracing

HYBRID = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine",
          "moe.shared", "attn.proj", "attn.core")
SCOPES = scopes.SCOPES + HYBRID
KEY = "scopes_hybrid"


def breakdown(ctx, hlo: Callable) -> Optional[Dict[str, float]]:
    """{scope: device seconds} over the traced window, read once; None
    where the trace has no device plane. Raises where the device was
    busy but no trace file can be found. Prints each scope's share."""
    if KEY in ctx:
        return ctx[KEY]
    path = scopes.trace_path(ctx)
    if path is None:
        if ctx["trace"].get("busy_s", 0.0) > 0:
            raise RuntimeError("a traced run with device work, but no trace "
                               "file for the scope metrics")
        ctx[KEY] = None
        return None
    devices, host = tracing.read_events(path)
    if not any(devices.values()):
        ctx[KEY] = None
        return None
    times = scopes.op_seconds(devices, host, 1)
    names, inherited = scopes.op_names(hlo(ctx["driver"]))
    out = scopes.credit(times, names, SCOPES)
    total = sum(times.values())
    shares = {k: 100.0 * t / total for k, t in
              sorted(out.items(), key=lambda kv: -kv[1])}
    passed: Dict[str, float] = {}
    for ins in inherited & set(times):
        scope = scopes.scope_of(names[ins], SCOPES)
        passed[scope] = passed.get(scope, 0.0) + times[ins]
    print(f"scopes: {total!r} device s credited; % of it {shares}; "
          f"through an inherited op_name (s) {passed}", file=sys.stderr)
    ctx[KEY] = out
    return out


def ms_per_step(ctx, *names: str) -> Optional[float]:
    """Device milliseconds per train step under the named scopes in the
    traced window; None where none of them was found."""
    got = breakdown(ctx, scopes.train_hlo)
    steps = ctx["driver"].counters.get("steps")
    if not got or not steps:
        return None
    t = sum(got.get(n, 0.0) for n in names)
    return 1e3 * t / steps if t > 0 else None
