"""Reduce a profiler trace to device busy time, per-program device time,
top device operations and idle gaps attributed to host spans.

The trace is the `.xplane.pb` the JAX profiler writes. `load` turns it into
plain intervals (seconds on the trace's own clock); `reduce` works on those
alone, so it is tested on hand-made intervals with a known answer.

Device planes are those named `/device:<accelerator>:<n>`; their `XLA Ops`
line holds one event per operation and their `XLA Modules` line one per
compiled program run (named after the jitted function, e.g.
`jit__engine_step_impl(...)`). Host spans are the `TraceAnnotation`s the
benchmark opens (`bench.*`, `engine.*`); the window is the `bench.traced`
span.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.traced"
HOST_PREFIXES = ("bench.", "engine.")


@dataclass
class Interval:
    name: str
    start: float
    end: float
    group: str = ""          # op: the program it ran in


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # plane -> {"ops", "modules"}
    host: list = field(default_factory=list)      # [Interval]


def load(logdir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    tr = Trace()
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            dev = plane.name.startswith("/device:") and ":CPU" not in plane.name
            for line in plane.lines:
                if dev and line.name in ("XLA Ops", "XLA Modules"):
                    key = "ops" if line.name == "XLA Ops" else "modules"
                    out = tr.devices.setdefault(plane.name,
                                                {"ops": [], "modules": []})
                    for ev in line.events:
                        stats = dict(ev.stats) if key == "ops" else {}
                        out[key].append(Interval(
                            ev.name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9,
                            str(stats.get("hlo_module", ""))))
                elif plane.name.startswith("/host:"):
                    for ev in line.events:
                        if ev.name.startswith(HOST_PREFIXES):
                            tr.host.append(Interval(
                                ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9))
    return tr


def union(intervals, lo, hi):
    """Merged [start, end) pieces of `intervals`, clipped to [lo, hi)."""
    pieces = sorted((max(i.start, lo), min(i.end, hi)) for i in intervals
                    if i.end > lo and i.start < hi)
    out = []
    for s, e in pieces:
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window(tr: Trace):
    spans = [h for h in tr.host if h.name == WINDOW_SPAN]
    if spans:
        return spans[0].start, spans[0].end
    every = [i for d in tr.devices.values() for i in d["ops"]] + tr.host
    if not every:
        return None
    return min(i.start for i in every), max(i.end for i in every)


CONTAINERS = ("while", "conditional", "call")


def _container(name: str) -> bool:
    """Loops and calls span the operations inside them: ranking them too
    would count that time twice."""
    return name.lstrip("%").split(".")[0].split(" ")[0] in CONTAINERS


def _op_label(op: Interval, modules, starts) -> str:
    """`program:op output-type`, e.g. `jit__prefill_impl:fusion.180
    f32[64,4,3,1024,1024]`. An op's event is named by its HLO text; its
    program is the module run that holds its start (or its own stat).
    `modules` is sorted by start, `starts` their starts."""
    head, _, rest = op.name.partition(" = ")
    out = rest.split("{")[0].split(" ")[0].lstrip("(")
    group = op.group
    k = bisect.bisect_right(starts, op.start) - 1
    if not group and k >= 0 and op.start < modules[k].end:
        group = modules[k].name.split("(")[0]
    label = f"{head.lstrip('%')} {out}".strip()
    return f"{group}:{label}" if group else label


def _host_label(tr: Trace, t: float) -> str:
    """The innermost benchmark span open at time t."""
    open_ = [h for h in tr.host if h.start <= t < h.end
             and h.name != WINDOW_SPAN]
    if not open_:
        return "no span"
    return min(open_, key=lambda h: h.end - h.start).name


def reduce(tr: Trace, top: int = 10) -> dict | None:
    """Busy and idle time over the traced window, averaged over devices.

    Returns None when the trace holds no device operation. Keys:
      window_s, busy_s        busy = union of op intervals, mean over chips
      modules                 {program name: [calls, device seconds]}
                              (calls that start in the window; seconds
                              clipped to it; summed over chips)
      device_ops              top `top` [label, seconds] by summed duration
      idle_gaps               top `top` [host span, seconds]: idle device
                              time summed by the host span open at each
                              gap's midpoint (first chip)
    """
    win = window(tr)
    devs = [d for d in tr.devices.values() if d["ops"]]
    if win is None or not devs:
        return None
    lo, hi = win
    busy, mods, ops = [], defaultdict(lambda: [0, 0.0]), defaultdict(float)
    for d in devs:
        u = union(d["ops"], lo, hi)
        busy.append(sum(e - s for s, e in u))
        for m in d["modules"]:
            if lo <= m.start < hi:
                name = m.name.split("(")[0]
                mods[name][0] += 1
                mods[name][1] += min(m.end, hi) - m.start
        ms = sorted(d["modules"], key=lambda m: m.start)
        starts = [m.start for m in ms]
        for o in d["ops"]:
            if o.end > lo and o.start < hi and not _container(o.name):
                ops[_op_label(o, ms, starts)] += \
                    min(o.end, hi) - max(o.start, lo)
    gaps = defaultdict(float)
    prev = lo
    for s, e in union(devs[0]["ops"], lo, hi) + [[hi, hi]]:
        if s > prev:
            gaps[_host_label(tr, (prev + s) / 2)] += s - prev
        prev = max(prev, e)
    rank = lambda d: sorted(([k, v] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {"window_s": hi - lo, "busy_s": sum(busy) / len(busy),
            "modules": {k: list(v) for k, v in mods.items()},
            "device_ops": rank(ops), "idle_gaps": rank(gaps)}


def module_time(red: dict, name: str):
    """(calls, device seconds) of the programs whose name contains `name`,
    or None when the trace holds none."""
    hits = [v for k, v in red["modules"].items() if name in k]
    if not hits:
        return None
    return sum(h[0] for h in hits), sum(h[1] for h in hits)
