"""What the per-layer readers of the program's own spans share: host
spans picked from a trace by their exact name, and mean durations; and
how much of the device's idle time lies inside the program's spans.

The program's scoped spans (``repro.obs.span``) enter a
``jax.profiler.TraceAnnotation`` of the same fixed name, so a traced run
finds them on the host planes (``/host:CPU``), on the device trace's
clock.  A program without a span reads as none: the readers then give
``None``.
"""

from __future__ import annotations

from typing import Iterable

from bench import trace
from bench.stats import union_length
from bench.trace import Event

PROGRAM_SPANS = ("runtime.", "decode.")     # the program's scoped spans


def host_spans(events: Iterable[Event], name: str) -> list[Event]:
    """Every host event named exactly ``name``."""
    return [e for e in events if e.plane.startswith("/host:")
            and e.name == name]


def mean_ms(events: list[Event]) -> float | None:
    """Mean duration in ms; None for no events."""
    if not events:
        return None
    return 1e3 * sum(e.dur for e in events) / len(events)


def program_spans(events: Iterable[Event]) -> list[Event]:
    """Every host event that is one of the program's scoped spans."""
    return [e for e in events if e.plane.startswith("/host:")
            and e.name.startswith(PROGRAM_SPANS)]


def device_gaps(events: list[Event]) -> list[tuple[float, float]]:
    """The idle intervals between operations of the first device, from
    its first operation to its last."""
    planes = trace.device_planes(events)
    if not planes:
        return []
    gaps, end = [], None
    for s, e in sorted((o.start, o.start + o.dur)
                       for o in trace.ops(events, planes[0])):
        if end is not None and s > end:
            gaps.append((end, s))
        end = e if end is None else max(end, e)
    return gaps


def _inside(gaps, spans) -> float:
    """Seconds of ``gaps`` that ``spans`` cover, overlaps counted once."""
    return sum(union_length([(max(a, e.start), min(b, e.start + e.dur))
                             for e in spans
                             if e.start < b and e.start + e.dur > a])
               for a, b in gaps)


def idle_in_spans(events: Iterable[Event]) -> dict:
    """The device's idle seconds between operations, the part of them
    inside any program span, and the part inside each span name (a gap
    inside a ``decode.admit`` inside a ``decode.tick`` counts for both
    names, once for the whole)."""
    evs = list(events)
    gaps = device_gaps(evs)
    spans = program_spans(evs)
    names = sorted({e.name for e in spans})
    return {"idle_s": sum(b - a for a, b in gaps),
            "in_spans_s": _inside(gaps, spans),
            "by_name_s": {n: _inside(gaps, [e for e in spans if e.name == n])
                          for n in names}}


def gaps_by_span(events: Iterable[Event], n: int = 10,
                 min_gap_s: float = 50e-6) -> list[list]:
    """``trace.idle_gaps``' ``n`` longest gaps, each named by the
    innermost program span that covers at least half of it (there the
    breakdown names the shortest host event of any kind, often a runtime
    call inside the span): ``[["<span> @<ms>", s], ...]``."""
    evs = list(events)
    gaps = [g for g in device_gaps(evs) if g[1] - g[0] >= min_gap_s]
    if not gaps:
        return []
    t0 = min(o.start for o in trace.ops(evs, trace.device_planes(evs)[0]))
    spans = program_spans(evs)
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        over = [e for e in spans
                if min(e.start + e.dur, b) - max(e.start, a) >= 0.5 * (b - a)]
        name = (min(over, key=lambda e: e.dur).name if over
                else "no program span")
        out.append([f"{name} @{(a - t0) * 1e3:.3f}ms", b - a])
    return out
