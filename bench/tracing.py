"""Device traces: capture with the JAX profiler, reduce to numbers.

``Capture`` records a profiler trace into a fresh temporary directory,
removed once ``reduce_trace`` has read it. The reduction works on
plain event tuples, so a test can feed it a small synthetic trace:

* busy: the union of the intervals in which any operation ran on a
  device, clipped to the traced window, averaged over the devices;
* window: the host annotation ``bench.window`` that the driver opens
  around the traced part of a run;
* device_ops: device self time by operation name (less the operations
  nested in it), most first;
* idle_gaps: the gaps in the busy union, each named by the innermost of
  the benchmark's own host annotations (``bench.*``) that covers it,
  totalled by name, longest first.
"""
from __future__ import annotations

import contextlib
import glob
import os
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
HOST_PREFIX = "bench."
DEVICE_PLANE = "/device:TPU:"
# the line of a TPU plane that holds the operations themselves; its
# other lines hold one event per program or step around them
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]        # (start_s, end_s)
Event = Tuple[str, float, float]      # (name, start_s, end_s)


def annotate(name: str):
    """A host span in the profiler's trace (free when nothing traces)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Capture:
    """Trace of one stretch of a run: ``start``/``stop``, then ``path``."""

    def __init__(self):
        self.dir: Optional[str] = None
        self.running = False

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        # host annotations yes, a record of every Python call no: the
        # Python tracer slowed a traced serving window fourfold
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.running = True

    def stop(self) -> None:
        import jax

        if self.running:
            jax.profiler.stop_trace()
            self.running = False

    @property
    def path(self) -> Optional[str]:
        if self.dir is None:
            return None
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        return found[0] if found else None

    def cleanup(self) -> None:
        self.stop()
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None


@contextlib.contextmanager
def window(capture: Optional[Capture]):
    """Annotate the traced stretch of a run as the window (nothing when
    the run is not traced)."""
    if capture is None:
        yield
        return
    with annotate(WINDOW):
        yield


# -- reading ------------------------------------------------------------------

def op_name(text: str) -> str:
    """A device operation's name and result shape from its HLO text:
    ``%fusion.2 = f32[1024,8,128]{0,1,2:T(8,128)} fusion(...)`` gives
    ``fusion.2 f32[1024,8,128]``; a tuple result keeps its first 40
    characters."""
    name, _, rest = text.partition(" = ")
    name = name.strip().lstrip("%")
    if not rest:
        return name
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{name} {shape[:40]}"


def read_events(path: str):
    """(device_events_by_plane, host_events) from an ``.xplane.pb``.

    Device events are the operations of each TPU plane (its ``XLA Ops``
    line). Host events are every event of the other planes. Times are
    seconds on the trace's common clock.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        lines = list(plane.lines)
        if plane.name.startswith(DEVICE_PLANE):
            evs = devices.setdefault(plane.name, [])
            for ln in (ln for ln in lines if ln.name == OPS_LINE):
                for e in ln.events:
                    s = e.start_ns * 1e-9
                    evs.append((op_name(e.name), s,
                                s + e.duration_ns * 1e-9))
        elif not plane.name.startswith("/device:"):
            for ln in lines:
                for e in ln.events:
                    s = e.start_ns * 1e-9
                    host.append((e.name, s, s + e.duration_ns * 1e-9))
    return devices, host


# -- reduction ----------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals into a sorted disjoint list."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of a sorted disjoint list within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_gaps(gap_list: Sequence[Interval], spans: Sequence[Event]
              ) -> List[str]:
    """Name each gap by the innermost host annotation that covers its
    midpoint (``host.unannotated`` where none does). Both lists are
    sorted by start; one sweep, so millions of gaps stay cheap."""
    names, active, i = [], [], 0
    for gs, ge in gap_list:
        mid = 0.5 * (gs + ge)
        while i < len(spans) and spans[i][1] <= mid:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[2] >= mid]
        if active:
            names.append(min(active, key=lambda sp: sp[2] - sp[1])[0])
        else:
            names.append("host.unannotated")
    return names


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Device time by operation name, each event less the events nested
    in it (a loop's event spans the operations of its body)."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [end, name, child time]

    def close(frame):
        out[frame[1]] = out.get(frame[1], 0.0) + frame[3] - frame[2]

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += min(e, stack[-1][0]) - s
        stack.append([e, name, 0.0, e - s])
    while stack:
        close(stack.pop())
    return out


def summarize(devices: Dict[str, List[Event]], host: Sequence[Event],
              n_devices: int, top: int = 10) -> dict:
    """Busy, window and breakdown from device and host events.

    ``n_devices`` is the number of chips the cell uses; busy time is the
    mean over them (a chip with no event counts as idle throughout).
    """
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    window_s = hi - lo
    planes = sorted(devices)[:n_devices]
    busy_total, op_time = 0.0, {}
    idle = {}
    spans = [ev for ev in host
             if ev[0].startswith(HOST_PREFIX) and ev[0] != WINDOW
             and ev[2] > lo and ev[1] < hi]
    spans.sort(key=lambda ev: ev[1])
    for plane in planes:
        evs = [(n, max(s, lo), min(e, hi)) for n, s, e in devices[plane]
               if e > lo and s < hi]
        busy = union((s, e) for _, s, e in evs)
        busy_total += sum(e - s for s, e in busy)
        for n, t in self_times(evs).items():
            op_time[n] = op_time.get(n, 0.0) + t
        idle_list = gaps(busy, lo, hi)
        for g, name in zip(idle_list, name_gaps(idle_list, spans)):
            idle[name] = idle.get(name, 0.0) + (g[1] - g[0])
    busy_s = busy_total / max(n_devices, 1)
    scale = 1.0 / max(n_devices, 1)
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "device_ops": [[n, t * scale] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, t * scale] for n, t in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
    }


def idle_percent(summary: dict) -> Optional[float]:
    """Share of the traced window with no operation on the device."""
    if summary["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])


def reduce_trace(path: str, n_devices: int) -> dict:
    devices, host = read_events(path)
    return summarize(devices, host, n_devices)
