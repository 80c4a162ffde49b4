"""Profiler capture and the reduction from a trace to numbers.

A traced run records a slice of its window with ``jax.profiler``; the
``.xplane.pb`` file is read back with ``jax.profiler.ProfileData`` into
plain tuples (``Event``), and everything after that is pure Python over
those tuples, so it is tested on a trace recorded on the chip
(``tests/bench/fixtures``).

On a TPU the device plane is ``/device:TPU:<n>``; its ``XLA Ops`` line
holds one event per executed HLO instruction (named by the instruction's
text, e.g. ``%lss_topk_pallas.1 = (...) custom-call(...)``), its
``XLA Modules`` line one event per program execution (named
``jit_<function>(<fingerprint>)``).  Host planes are ``/host:CPU``;
their events (runtime calls, Python functions) say what the host was
doing while the device waited.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Iterable, NamedTuple

from bench.stats import union_length

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start: float                 # seconds, on the trace's clock
    dur: float                   # seconds


def xplane_file(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_events(path: str) -> list[Event]:
    """Every event of every device and host plane, as plain tuples."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not (plane.name.startswith("/device:") or
                plane.name.startswith("/host:CPU")):
            continue
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 e.start_ns * 1e-9, e.duration_ns * 1e-9))
    return out


def device_planes(events: Iterable[Event]) -> list[str]:
    return sorted({e.plane for e in events if e.plane.startswith("/device:")
                   and e.line == OPS_LINE})


def ops(events: Iterable[Event], plane: str | None = None) -> list[Event]:
    return [e for e in events if e.line == OPS_LINE
            and e.plane.startswith("/device:")
            and (plane is None or e.plane == plane)]


def modules(events: Iterable[Event], prefix: str) -> list[Event]:
    """Program executions whose module name starts with ``prefix``
    (``jit__prefill_jit`` matches ``jit__prefill_jit(1234...)``)."""
    return [e for e in events if e.line == MODULES_LINE
            and e.name.startswith(prefix)]


def busy_s(events: Iterable[Event]) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    evs = list(events)
    planes = device_planes(evs)
    if not planes:
        return 0.0
    return sum(union_length([(e.start, e.start + e.dur)
                             for e in ops(evs, p)]) for p in planes) / len(planes)


def kernel_events(events: Iterable[Event], kernel: str) -> list[Event]:
    """Executions of a named kernel: the HLO instruction ``%<kernel>``
    or ``%<kernel>.<n>``."""
    pat = re.compile(rf"^%{re.escape(kernel)}(\.\d+)? = ")
    return [e for e in ops(events) if pat.match(e.name)]


_HLO = re.compile(r"^%?([\w.\-]+) = (\([^=]*?\)|\S+) ([\w\-]+)\(")


def short_op(name: str) -> str:
    """``%pad.19 = f32[1024,384,1024]{...} pad(...)`` ->
    ``pad.19 pad f32[1024,384,1024]``: instruction, opcode, shape."""
    m = _HLO.match(name)
    if not m:
        return name[:96]
    shape = re.sub(r"\{[^}]*\}", "", m.group(2))
    return f"{m.group(1)} {m.group(3)} {shape}"[:160]


def self_times(evs: list[Event]) -> dict[str, float]:
    """Self seconds per op name on one line: an event's duration less the
    part of it that events nested inside it cover (a loop and its body)."""
    out: dict[str, float] = {e.name: 0.0 for e in evs}
    stack: list[list] = []          # [end, name, duration, child time]
    for e in sorted(evs, key=lambda x: (x.start, -x.dur)):
        while stack and stack[-1][0] <= e.start:
            _end, nm, dur, child = stack.pop()
            out[nm] += dur - child
        if stack:
            stack[-1][3] += e.dur
        stack.append([e.start + e.dur, e.name, e.dur, 0.0])
    while stack:
        _end, nm, dur, child = stack.pop()
        out[nm] += dur - child
    return out


def top_device_ops(events: Iterable[Event], n: int = 10
                   ) -> list[list]:
    """The ``n`` device operations with the most self time, summed over
    devices and executions: ``[[short name, seconds], ...]``."""
    total: dict[str, float] = {}
    evs = list(events)
    for p in device_planes(evs):
        for name, s in self_times(ops(evs, p)).items():
            k = short_op(name)
            total[k] = total.get(k, 0.0) + s
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: Iterable[Event], n: int = 10,
              min_gap_s: float = 50e-6) -> list[list]:
    """The ``n`` longest gaps between device operations (first device),
    each named by what the host was doing: the shortest host event that
    covers at least half of the gap.  ``[["<host event> @<ms>", s], ...]``
    where ``<ms>`` is the gap's start from the trace's first operation."""
    evs = list(events)
    planes = device_planes(evs)
    if not planes:
        return []
    dev = sorted((e.start, e.start + e.dur) for e in ops(evs, planes[0]))
    if not dev:
        return []
    t0 = dev[0][0]
    gaps = []
    end = dev[0][1]
    for s, e in dev[1:]:
        if s - end >= min_gap_s:
            gaps.append((end, s))
        end = max(end, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    gaps = gaps[:n]
    host = [e for e in evs if e.plane.startswith("/host:")]
    out = []
    for g0, g1 in gaps:
        need = 0.5 * (g1 - g0)
        best = None
        for h in host:
            ov = min(h.start + h.dur, g1) - max(h.start, g0)
            if ov >= need and (best is None or h.dur < best.dur):
                best = h
        label = best.name if best is not None else "no host event"
        out.append([f"{label} @{(g0 - t0) * 1e3:.3f}ms", g1 - g0])
    return out
