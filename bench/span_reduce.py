"""Split a profiler trace's device-idle time by the program span open on
the host.

The program's ``repro.obs.trace`` spans, while a tracer is installed, also
open profiler annotations of their names with ``span_id``, ``parent_id`` and
``trace_id`` arguments. So they land in the profiler's host trace, on the
device trace's clock, as events that carry a ``span_id`` stat; the
benchmark's own ``bench.*`` annotations carry none.

* The window and each device's idle time are those of
  ``bench.trace_reduce``: the span of ``bench.window``, less the union of
  the device's ``XLA Ops`` intervals.
* Each idle interval is cut wherever the innermost open program span
  changes, and each piece goes to that span's name, or to ``"none"`` where
  no program span is open. The split is exact: its values add up to the
  window's idle time. The innermost span is the open one that began last
  (the larger id on a tie), on any host thread.
* Seconds are averaged over the devices that ran anything, as
  ``trace_reduce.reduce`` averages busy time. Every program span name seen
  in the window is a key, at 0 where the device never idled under it.

``bench/tools/span_split.py`` runs a cell with the split put into
``cell.layer["idle_spans"]``, where the idle readers of ``bench/metrics/``
look for it.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Callable, NamedTuple

from bench import trace_reduce

NO_SPAN = "none"


class Span(NamedTuple):
    name: str
    start: int       # ns
    end: int         # ns
    span_id: int
    parent_id: int
    trace_id: int


def load_spans(path: str) -> list[Span]:
    """The program spans of one ``.xplane.pb`` file, from every host
    thread."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(trace_reduce.HOST_PLANE_PREFIX):
            continue
        for ln in plane.lines:
            for e in ln.events:
                st = dict(e.stats)
                if "span_id" in st:
                    out.append(Span(e.name, int(e.start_ns),
                                    int(e.start_ns + e.duration_ns),
                                    int(st["span_id"]),
                                    int(st.get("parent_id", 0)),
                                    int(st.get("trace_id", 0))))
    return out


def segments(spans, lo: int, hi: int) -> list[tuple[int, int, str]]:
    """``[lo, hi]`` cut where the innermost open span changes: disjoint,
    sorted ``(start, end, name)`` pieces that cover it."""
    pts = sorted({lo, hi, *(min(max(t, lo), hi)
                            for s in spans for t in (s.start, s.end))})
    order = sorted(spans, key=lambda s: s.start)
    active: list[Span] = []
    out: list[tuple[int, int, str]] = []
    j = 0
    for a, b in zip(pts, pts[1:]):
        while j < len(order) and order[j].start <= a:
            active.append(order[j])
            j += 1
        active = [s for s in active if s.end > a]
        name = (max(active, key=lambda s: (s.start, s.span_id)).name
                if active else NO_SPAN)
        if out and out[-1][2] == name:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def idle_by_span(trace: trace_reduce.Trace, spans,
                 window: str = "window") -> dict[str, float]:
    """Device-idle seconds inside the window, keyed by the innermost open
    program span's name or ``"none"``."""
    wins = [e for e in trace.host if e.name == trace_reduce.HOST_PREFIX + window]
    if not wins:
        raise ValueError(f"trace holds no "
                         f"{trace_reduce.HOST_PREFIX + window!r} annotation")
    lo, hi = min(e.start for e in wins), max(e.end for e in wins)
    inside = [s for s in spans if s.end > lo and s.start < hi]
    segs = segments(inside, lo, hi)
    idle_ns: dict[str, int] = defaultdict(int, {s.name: 0 for s in inside})
    n = 0
    for dev in trace.devices.values():
        busy = trace_reduce.union(((e.start, e.end) for e in dev.ops), lo, hi)
        if not busy:
            continue
        n += 1
        i = 0
        for s, e in trace_reduce.gaps(busy, lo, hi):
            while segs[i][1] <= s:
                i += 1
            k = i
            while k < len(segs) and segs[k][0] < e:
                idle_ns[segs[k][2]] += min(e, segs[k][1]) - max(s, segs[k][0])
                k += 1
    if not n:
        raise ValueError("no device operation ran inside the window")
    return {k: v / 1e9 / n for k, v in idle_ns.items()}


def idle_ms_per_run(cell, keep: Callable[[str], bool]) -> float | None:
    """Idle milliseconds per query run under the spans whose names ``keep``
    accepts; None where the traced window holds no program span."""
    split, runs = cell.layer.get("idle_spans"), cell.layer.get("runs")
    if not runs or not split or set(split) <= {NO_SPAN}:
        return None
    return sum(v for k, v in split.items() if keep(k)) * 1e3 / runs
