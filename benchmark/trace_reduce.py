"""From a jax.profiler xplane file to numbers: device busy union, idle share,
module times, collective time, the ops that took most time, and the idle gaps
labelled by what the benchmark's host spans say the host was doing.

Read with `jax.profiler.ProfileData` (JAX alone, no TensorFlow). The
arithmetic on op events is scripts/trace_ops.py's (the `op_kind` collapse,
async `-start` windows kept out of occupancy); the busy union, the window and
the gap labels are new here. Every function below `load` takes plain
(name, start_ns, duration_ns) tuples, so tests drive them with a recorded
trace (fixtures/) and with hand-made events alike.

A device has one plane `/device:TPU:<i>` with the lines `XLA Ops` (what ran,
back to back), `XLA Modules` (one event per executed program) and
`Async XLA Ops` (the windows of async copies and collectives, overlapping
compute). Host threads are lines of `/host:CPU`; a
`jax.profiler.TraceAnnotation` lands there under its own name.
"""

from __future__ import annotations

import collections
import re
import statistics
from dataclasses import dataclass, field

Event = tuple[str, float, float]  # name, start_ns, duration_ns

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/traced_window"
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all")
NO_SPAN = "(no benchmark span open)"


@dataclass
class Trace:
    devices: dict[int, dict[str, list[Event]]] = field(default_factory=dict)
    host_spans: list[Event] = field(default_factory=list)  # bench/* annotations

    @property
    def window(self) -> tuple[float, float] | None:
        """The traced stretch on the profiler's clock: the harness's
        bench/traced_window annotation, else first to last device event."""
        for name, start, dur in self.host_spans:
            if name == WINDOW_SPAN:
                return start, start + dur
        ops = [e for lines in self.devices.values() for e in lines.get(OPS_LINE, [])]
        if not ops:
            return None
        return min(s for _, s, _ in ops), max(s + d for _, s, d in ops)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            trace.devices[int(m.group(1))] = {
                line.name: [(e.name, e.start_ns, e.duration_ns) for e in line.events]
                for line in plane.lines}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                trace.host_spans += [(e.name, e.start_ns, e.duration_ns) for e in line.events
                                     if e.name.startswith(SPAN_PREFIX)]
    return trace


def op_name(event_name: str) -> str:
    """'%fusion.233 = f32[64]{0} fusion(...)' -> 'fusion.233'."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def op_kind(name: str) -> str:
    """Collapse op numbering: 'fusion.123' -> 'fusion' (trace_ops.py's rule)."""
    return re.split(r"[.\d]", op_name(name), maxsplit=1)[0]


def is_async_start(name: str) -> bool:
    return op_kind(name).endswith("-start")


def clip(events: list[Event], window: tuple[float, float] | None) -> list[Event]:
    if window is None:
        return list(events)
    lo, hi = window
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def busy_intervals(events: list[Event]) -> list[tuple[float, float]]:
    """Merged [start, end) intervals in which any operation ran."""
    merged: list[list[float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if dur <= 0:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return [(a, b) for a, b in merged]


def busy_s(events: list[Event], window: tuple[float, float] | None = None) -> float:
    return sum(b - a for a, b in busy_intervals(clip(events, window))) / 1e9


def device_busy(trace: Trace) -> tuple[float, float] | None:
    """(busy_s averaged over the traced chips, window_s), or None where no
    device plane was traced (a CPU rehearsal)."""
    window = trace.window
    per_device = [busy_s(lines.get(OPS_LINE, []), window) for lines in trace.devices.values()]
    if not per_device or window is None:
        return None
    return sum(per_device) / len(per_device), (window[1] - window[0]) / 1e9


def idle_share_pct(trace: Trace, device: int = 0) -> float | None:
    """100 x (1 - union of op intervals on one device / traced wall)."""
    window = trace.window
    if device not in trace.devices or window is None:
        return None
    busy = busy_s(trace.devices[device].get(OPS_LINE, []), window)
    return 100.0 * (1.0 - busy / ((window[1] - window[0]) / 1e9))


def _program_runs(trace: Trace, device: int, match: str | None, whole: bool = False) -> list[Event]:
    """One program's executions on a device inside the window (`whole`: only
    those not cut by its edges): the program whose name contains `match`, by
    default the one that took most of the device's time (the step)."""
    events = trace.devices.get(device, {}).get(MODULES_LINE, [])
    if trace.window is not None:
        lo, hi = trace.window
        events = [e for e in events
                  if (e[1] >= lo and e[1] + e[2] <= hi if whole else e[1] < hi and e[1] + e[2] > lo)]
    if match is None:
        total: collections.Counter = collections.Counter()
        for name, _, dur in events:
            total[name] += dur
        match = total.most_common(1)[0][0] if total else ""
    return [e for e in events if match in e[0]]


def module_median_ms(trace: Trace, device: int = 0, match: str | None = None) -> float | None:
    """Median duration of one program's whole executions on a device."""
    durs = [d for _, _, d in _program_runs(trace, device, match, whole=True)]
    return statistics.median(durs) / 1e6 if durs else None


def collective_ms_per_step(trace: Trace, device: int = 0, match: str | None = None) -> float | None:
    """Time the core spent in collective ops, per executed step: every
    all-reduce, all-gather, reduce-scatter, collective-permute and all-to-all
    event on the ops line except the `-start` halves, whose windows overlap
    compute (a `-done` is the wait for one, so it counts). 0.0 on one chip."""
    runs = _program_runs(trace, device, match, whole=True)
    if not runs:
        return None
    # counted over the whole executions only, so that steps and ops cover the same stretch
    span = min(s for _, s, _ in runs), max(s + d for _, s, d in runs)
    total = sum(d for n, _, d in clip(trace.devices[device].get(OPS_LINE, []), span)
                if op_kind(n).startswith(COLLECTIVE_KINDS) and not is_async_start(n))
    return total / len(runs) / 1e6


def top_ops(trace: Trace, device: int = 0, n: int = 10) -> list[list]:
    """[[name, seconds], ...]: op kinds and single ops with most device time,
    kinds written `kind:<kind>`, async `-start` halves left out."""
    ops = clip(trace.devices.get(device, {}).get(OPS_LINE, []), trace.window)
    by_kind: collections.Counter = collections.Counter()
    by_name: collections.Counter = collections.Counter()
    for name, _, dur in ops:
        if is_async_start(name):
            continue
        by_kind["kind:" + op_kind(name)] += dur
        by_name[op_name(name)] += dur
    half = n // 2
    rows = by_kind.most_common(half) + by_name.most_common(n - half)
    return [[k, v / 1e9] for k, v in rows]


def idle_gaps(trace: Trace, device: int = 0, n: int = 5) -> list[list]:
    """[[label, seconds], ...]: the longest stretches with no op on the
    device, each labelled by the benchmark's host spans open at its middle
    (joined by '+', the window's own span left out)."""
    window = trace.window
    if device not in trace.devices or window is None:
        return []
    busy = busy_intervals(clip(trace.devices[device].get(OPS_LINE, []), window))
    edges = [window[0]] + [t for iv in busy for t in iv] + [window[1]]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i]) for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:n]
    out = []
    for length, start in gaps:
        mid = start + length / 2
        open_spans = sorted({name[len(SPAN_PREFIX):] for name, s, d in trace.host_spans
                             if name != WINDOW_SPAN and s <= mid <= s + d})
        out.append(["+".join(open_spans) or NO_SPAN, length / 1e9])
    return out


def breakdown(trace: Trace, device: int = 0) -> dict:
    return {"device_ops": top_ops(trace, device, 10), "idle_gaps": idle_gaps(trace, device, 5)}
