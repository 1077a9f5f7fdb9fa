"""Reduce a profiler trace (``.xplane.pb``) to device busy, idle and
per-program time, and to the breakdown the result line carries.

Only the benchmark reads traces, and every number it reports from one comes
from here, so every PR computes it the same way.

* The window is the span of the host annotation named ``window`` (the
  benchmark opens ``bench.window`` around its measured loop).
* Busy time of a device is the union of its ``XLA Ops`` intervals inside the
  window; idle is the rest of the window. Busy seconds are averaged over the
  devices that ran anything.
* Program time is the sum of ``XLA Modules`` intervals inside the window,
  grouped by program name with the trailing ``(<id>)`` removed, averaged over
  devices.
* Op time is keyed ``<program>/<op>``, the op's name without operands.
* Each idle gap is put down to the innermost ``bench.*`` host annotation
  open at its midpoint (``host:none`` where none is).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE_PREFIX = "/host:"
HOST_PREFIX = "bench."
_ID_SUFFIX = re.compile(r"\(\d+\)$")


class Event(NamedTuple):
    name: str
    start: int   # ns
    end: int     # ns


class Device(NamedTuple):
    ops: list[Event]
    modules: list[Event]


class Trace(NamedTuple):
    devices: dict[str, Device]
    host: list[Event]        # bench.* annotations from every host thread


class Reduction(NamedTuple):
    window_s: float
    busy_s: float                         # mean over devices that ran ops
    n_devices: int
    programs: dict[str, float]            # seconds per program, per device
    program_calls: dict[str, float]       # executions per program, per device
    ops: dict[str, float]                 # seconds per op name, per device
    idle_gaps: dict[str, float]           # idle seconds by host activity
    host: dict[str, float]                # seconds per bench.* annotation


def _events(line) -> list[Event]:
    return [Event(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def load(path: str) -> Trace:
    """Read the device and host events of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, Device] = {}
    host: list[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
            mods = (_events(lines[MODULES_LINE]) if MODULES_LINE in lines
                    else [])
            devices[plane.name] = Device(ops, mods)
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for ln in plane.lines:
                host.extend(e for e in _events(ln)
                            if e.name.startswith(HOST_PREFIX))
    return Trace(devices, host)


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Disjoint, sorted union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[tuple[int, int]], lo: int, hi: int):
    """The complement of a disjoint sorted union inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def program_name(name: str) -> str:
    return _ID_SUFFIX.sub("", name).strip()


def op_name(name: str) -> str:
    """An HLO op event's name without its operands: ``%fusion.3 = f32[..]
    fusion(...)`` -> ``%fusion.3``."""
    return name.split(" = ", 1)[0].strip()


def _host_activity(host: list[Event], t: int) -> str:
    best = None
    for ev in host:
        if ev.start <= t < ev.end and ev.name != HOST_PREFIX + "window" and (
                best is None or ev.start >= best.start):
            best = ev
    return best.name if best is not None else "host:none"


def reduce(trace: Trace, window: str = "window") -> Reduction:
    wins = [e for e in trace.host if e.name == HOST_PREFIX + window]
    if not wins:
        raise ValueError(f"trace holds no {HOST_PREFIX + window!r} annotation")
    lo, hi = min(e.start for e in wins), max(e.end for e in wins)
    active = {n: d for n, d in trace.devices.items()
              if union(((e.start, e.end) for e in d.ops), lo, hi)}
    if not active:
        raise ValueError("no device operation ran inside the window")
    n = len(active)
    busy_total = 0
    programs: dict[str, float] = defaultdict(float)
    calls: dict[str, float] = defaultdict(float)
    ops: dict[str, float] = defaultdict(float)
    idle: dict[str, float] = defaultdict(float)
    for dev in active.values():
        busy = union(((e.start, e.end) for e in dev.ops), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for e in dev.modules:
            s, t = max(e.start, lo), min(e.end, hi)
            if t > s:
                name = program_name(e.name)
                programs[name] += (t - s) / 1e9 / n
                calls[name] += 1 / n
        mods = sorted(dev.modules, key=lambda m: m.start)
        starts = [m.start for m in mods]
        for e in dev.ops:
            s, t = max(e.start, lo), min(e.end, hi)
            if t > s:
                i = bisect.bisect_right(starts, e.start) - 1
                prog = (program_name(mods[i].name) + "/"
                        if i >= 0 and mods[i].end >= e.start else "")
                ops[prog + op_name(e.name)] += (t - s) / 1e9 / n
        for s, e in gaps(busy, lo, hi):
            idle[_host_activity(trace.host, (s + e) // 2)] += (e - s) / 1e9 / n
    host: dict[str, float] = defaultdict(float)
    for e in trace.host:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            host[e.name] += (t - s) / 1e9
    return Reduction(window_s=(hi - lo) / 1e9, busy_s=busy_total / 1e9 / n,
                     n_devices=n, programs=dict(programs),
                     program_calls=dict(calls), ops=dict(ops),
                     idle_gaps=dict(idle), host=dict(host))


def top(d: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(r: Reduction) -> dict:
    """The result line's ``breakdown``: the ten device operations that took
    most time, and the ten host activities under which the device idled
    longest."""
    return {"device_ops": top(r.ops), "idle_gaps": top(r.idle_gaps)}


def program_seconds(r: Reduction, patterns) -> float | None:
    """Summed device seconds of the programs whose name matches any regex
    in ``patterns``; None where no such program ran in the window."""
    regs = [re.compile(p) for p in patterns]
    hit = [v for k, v in r.programs.items() if any(x.search(k) for x in regs)]
    return sum(hit) if hit else None
