"""Device time by the program's own named scopes.

The program names its device work with ``jax.named_scope``: ``cd.stats``
and ``cd.update`` in the coordinate sweep, ``ssm.*``, ``model.*``,
``cox.head`` and ``optim.adamw`` in the train step. The names reach each
HLO instruction's ``op_name`` metadata, wrapped by the transforms that
made the instruction: a recomputed operation of the backward pass reads
``jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/
rematted_computation/ssm.ssd/exp``. So the backward and the recomputed
operations are credited to the part they belong to.

``op_seconds`` gives each device operation's self time by instruction
(``tracing.summarize``, the one reduction, fed whole picoseconds, so
that back-to-back operations never read as nested through rounding);
``credit`` gives it to the innermost of a set of scope names among the
``/``-separated parts of the operation's ``op_name``, transform wrappers
stripped first and whole parts matched. Time that matches no scope goes
under ``unscoped``; an operation whose ``op_name`` cannot be found goes
under ``unknown``.

Where the ``op_name`` comes from: a TPU operation event of the
profiler's trace is named by its instruction's HLO text, without the
metadata, and carries no ``op_name`` among its stats (only
``device_offset_ps``, ``device_duration_ps`` and ``Time Scale
Multiplier``, on a v5e with JAX 0.9.0). So each event's instruction is
looked up in the compiled program's own HLO text
(``jitted.lower(*args).compile().as_text()``, ``metadata={op_name=...}``),
which the readers obtain after the window of a traced run, from the
cell's driver (``fit_hlo``, ``train_hlo``). Instruction names are unique
within a program, and each of these cells runs one program in its traced
window. The TPU compiler's own rewrites carry no name stack (the suffix
sums of a ``cumsum`` become ``reduce-window`` and ``slice`` instructions
with no ``op_name``, or one that reads ``reduce_window_sum``): such an
instruction takes the ``op_name`` of the nearest instruction of its
computation that has one, its users first, then its operands
(``op_names``). That is a guess, so each traced run prints, per scope,
how much of its time came through an inherited ``op_name`` and from
which kinds of instruction. On the CPU there is no device plane and the
readers read nothing.
"""
from __future__ import annotations

import re
import sys
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import tracing

SCOPES = ("cd.stats", "cd.update", "cd.objective",
          "ssm.in_proj", "ssm.conv", "ssm.ssd", "ssm.gated_norm",
          "ssm.out_proj", "model.embed", "model.norm", "cox.head",
          "optim.adamw")
UNSCOPED = "unscoped"
UNKNOWN = "unknown"

# ``transpose(jvp(ssm.ssd))`` -> ``jvp(ssm.ssd)`` -> ``ssm.ssd``
_WRAPPER = re.compile(r"^[A-Za-z_][\w.-]*\((.*)\)$")
# one instruction of HLO text: its name and the rest of its line
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$", re.M)
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_REFERENCE = re.compile(r"%([\w.-]+)")
# ``broadcast.74.clone`` -> ``broadcast``: an instruction's kind
_NUMBER = re.compile(r"\.\d+.*$")


def parts(op_name: str) -> List[str]:
    """The ``/``-separated parts of an ``op_name``, each stripped of the
    transform wrappers around it."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPER.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPER.match(part)
        out.append(part)
    return out


def scope_of(op_name: Optional[str], scopes: Sequence[str] = SCOPES) -> str:
    """The innermost of ``scopes`` on the path, ``unscoped`` where none
    is, ``unknown`` where there is no ``op_name``."""
    if not op_name:
        return UNKNOWN
    for part in reversed(parts(op_name)):
        if part in scopes:
            return part
    return UNSCOPED


def op_names(hlo_text: str) -> Tuple[Dict[str, str], Set[str]]:
    """({instruction: op_name}, the instructions whose op_name was
    inherited) from a compiled program's HLO text.

    An instruction whose ``op_name`` is missing or carries no name stack
    (no ``/``) takes the op_name of the nearest instruction with one,
    breadth first through its users, then through its operands; one with
    no such neighbour is left out."""
    own: Dict[str, Optional[str]] = {}
    refs: Dict[str, List[str]] = {}
    for name, rest in _INSTRUCTION.findall(hlo_text):
        m = _OP_NAME.search(rest)
        own[name] = m.group(1) if m and "/" in m.group(1) else None
        refs[name] = _REFERENCE.findall(rest.split(", metadata=", 1)[0])
    operands = {n: [r for r in rs if r in own and r != n]
                for n, rs in refs.items()}
    users: Dict[str, List[str]] = {n: [] for n in own}
    for n, ops in operands.items():
        for o in ops:
            users[o].append(n)
    names = {n: o for n, o in own.items() if o}
    inherited = set()
    for n, o in own.items():
        if o:
            continue
        for graph in (users, operands):
            found = _nearest(n, graph, own)
            if found:
                names[n] = found
                inherited.add(n)
                break
    return names, inherited


def _nearest(start: str, graph: Dict[str, List[str]],
             own: Dict[str, Optional[str]]) -> Optional[str]:
    """The op_name of the nearest instruction with one, breadth first
    from ``start`` along ``graph``."""
    seen, queue = {start}, deque(graph[start])
    while queue:
        n = queue.popleft()
        if n in seen:
            continue
        seen.add(n)
        if own[n]:
            return own[n]
        queue.extend(graph[n])
    return None


def op_seconds(devices: Dict[str, List[tracing.Event]],
               host: Sequence[tracing.Event], n_devices: int
               ) -> Dict[str, float]:
    """{instruction: seconds} of device self time in the traced window,
    per chip, from ``tracing.read_events``'s events: ``summarize``'s
    breakdown of every operation, on times rounded to whole picoseconds
    and events named by their instruction alone."""
    def ps(t: float) -> int:
        return round(t * 1e12)

    devs = {plane: [(n.partition(" ")[0], ps(s), ps(e)) for n, s, e in evs]
            for plane, evs in devices.items()}
    window = [(n, ps(s), ps(e)) for n, s, e in host if n == tracing.WINDOW]
    got = tracing.summarize(devs, window, n_devices, top=None)
    return {n: t * 1e-12 for n, t in got["device_ops"]}


def credit(times: Dict[str, float], names: Dict[str, str],
           scopes: Sequence[str] = SCOPES) -> Dict[str, float]:
    """{scope: seconds} from {instruction: seconds} and the program's
    {instruction: op_name}."""
    out: Dict[str, float] = {}
    for ins, t in times.items():
        scope = scope_of(names.get(ins), scopes)
        out[scope] = out.get(scope, 0.0) + t
    return out


def trace_path(ctx) -> Optional[str]:
    """The traced run's ``.xplane.pb``: ``ctx["trace_path"]`` where the
    harness gives it, else the path of the harness's own ``capture``,
    found up the call stack (it is removed once the readers have run).
    The stack walk stands in until ``harness.run`` puts the path into
    ``ctx``; ``breakdown`` fails where it finds nothing."""
    if ctx.get("trace_path"):
        return ctx["trace_path"]
    frame = sys._getframe(1)
    while frame is not None:
        cap = frame.f_locals.get("capture")
        if isinstance(cap, tracing.Capture):
            return cap.path
        frame = frame.f_back
    return None


def fit_hlo(drv) -> str:
    """The compiled HLO text of the fit driver's solve, as traced."""
    return drv.solvers.fit_cd_tol.lower(drv.data[drv.order[0]],
                                        **drv.kw).compile().as_text()


def train_hlo(drv) -> str:
    """The compiled HLO text of the train driver's step, as traced."""
    return drv.step.lower(drv.state,
                          drv.batch(drv.next_step)).compile().as_text()


def breakdown(ctx, hlo: Callable) -> Optional[Dict[str, float]]:
    """{scope: device seconds} over the traced window of this run, read
    once and kept in ``ctx`` for the other readers; None where the trace
    has no device plane. ``hlo`` gives the compiled program's HLO text
    from the driver. Raises where the device was busy but no trace file
    can be found, so that a metric is never dropped without a word.

    The reading is also printed to standard error: each scope's share of
    the time credited, and per scope the seconds credited through an
    inherited op_name, by kind of instruction."""
    if "scopes" in ctx:
        return ctx["scopes"]
    path = trace_path(ctx)
    if path is None:
        if ctx["trace"].get("busy_s", 0.0) > 0:
            raise RuntimeError("a traced run with device work, but no trace "
                               "file for the scope metrics: give the "
                               "readers ctx['trace_path']")
        ctx["scopes"] = None
        return None
    devices, host = tracing.read_events(path)
    if not any(devices.values()):
        ctx["scopes"] = None
        return None
    times = op_seconds(devices, host, 1)
    names, inherited = op_names(hlo(ctx["driver"]))
    out = credit(times, names)
    total = sum(times.values())
    shares = {k: 100.0 * t / total for k, t in
              sorted(out.items(), key=lambda kv: -kv[1])}
    passed: Dict[str, Dict[str, float]] = {}
    for ins in inherited & set(times):
        kinds = passed.setdefault(scope_of(names[ins]), {})
        kind = _NUMBER.sub("", ins)
        kinds[kind] = kinds.get(kind, 0.0) + times[ins]
    through = {scope: {"s": sum(kinds.values()),
                       "kinds": dict(sorted(kinds.items(),
                                            key=lambda kv: -kv[1])[:6])}
               for scope, kinds in passed.items()}
    print(f"scopes: {total!r} device s credited; % of it {shares}; "
          f"through an inherited op_name {through}", file=sys.stderr)
    ctx["scopes"] = out
    return out


def seconds(ctx, hlo: Callable, *names: str) -> Optional[float]:
    """Device seconds under the named scopes in the traced window; None
    where none of them was found (a program that does not name them)."""
    got = breakdown(ctx, hlo)
    if not got:
        return None
    t = sum(got.get(n, 0.0) for n in names)
    return t if t > 0 else None
