"""Reduction of a profiler trace to device busy time and program time.

A trace is read with ``jax.profiler.ProfileData`` into plain
``(name, start_ns, end_ns)`` intervals; every number the per-layer readers
report is computed here from those intervals, so the arithmetic is the same
for every change and is tested on a small recorded trace (``bench/tests``).

On a TPU each chip is a plane ``/device:TPU:<i>``: its line ``XLA Ops``
holds one event per HLO operation executed, ``XLA Modules`` one per program
execution, named after the jitted function (``jit__openloop_scan(...)``).
The host's ``TraceAnnotation`` spans sit on the ``/host:CPU`` plane on the
same clock.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

def load(trace_dir: str):
    """The planes of the newest ``.xplane.pb`` under ``trace_dir`` as
    ``{plane: {line: [(name, start_ns, end_ns), ...]}}``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        lines = {}
        for line in plane.lines:
            lines[line.name] = [(e.name, float(e.start_ns),
                                 float(e.start_ns) + float(e.duration_ns))
                                for e in line.events]
        planes[plane.name] = lines
    return planes


def device_planes(planes: dict) -> list[str]:
    """The chips' planes (a TPU trace also has ``/device:CUSTOM:...``
    planes, which are not chips)."""
    return sorted(p for p in planes if p.startswith(("/device:TPU:", "/device:GPU:")))


def op_name(event_name: str) -> str:
    """``fusion.12`` from a TPU op event named ``%fusion.12 = s32[...] ...``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


# control-flow ops enclose the ops they run: counting them would count
# their contents twice
ENCLOSING = ("while", "conditional", "call")


def host_span(planes: dict, name: str) -> tuple[float, float] | None:
    """[start, end] ns of the first host annotation called ``name``."""
    for pname, lines in planes.items():
        if not pname.startswith("/host:"):
            continue
        for evs in lines.values():
            for n, s, e in evs:
                if n == name:
                    return s, e
    return None


def host_annotations(planes: dict, names) -> list[tuple[str, float, float]]:
    """Host annotation spans whose name is in ``names``."""
    names = set(names)
    out = []
    for pname, lines in planes.items():
        if pname.startswith("/host:"):
            for evs in lines.values():
                out.extend(ev for ev in evs if ev[0] in names)
    return sorted(out, key=lambda ev: ev[1])


def clip(intervals, lo: float, hi: float):
    out = []
    for n, s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((n, s, e))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merged [start, end] spans covered by at least one interval."""
    spans = sorted((s, e) for _, s, e in intervals)
    merged: list[list[float]] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def op_events(lines: dict):
    """The per-operation events of one device plane."""
    return lines.get("XLA Ops") or lines.get("XLA Modules") or []


def module_events(lines: dict, fn_name: str):
    """Executions of the program jitted from function ``fn_name``."""
    tag = f"jit_{fn_name}"
    return [ev for ev in lines.get("XLA Modules", [])
            if ev[0] == tag or ev[0].startswith(tag + "(")
            or ev[0].startswith(tag + ".")]


def op_totals(intervals) -> dict[str, float]:
    """Nanoseconds per op name, leaving out ops that enclose others."""
    tot: dict[str, float] = defaultdict(float)
    for n, s, e in intervals:
        name = op_name(n)
        if not name.startswith(ENCLOSING):
            tot[name] += e - s
    return tot


def gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    """Idle [start, end] spans of [lo, hi] between busy spans."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def label_gaps(idle, spans) -> dict[str, float]:
    """Idle seconds by the innermost host span that covers each gap's
    midpoint (``"other"`` where none does)."""
    out: dict[str, float] = defaultdict(float)
    for s, e in idle:
        mid = 0.5 * (s + e)
        best = None
        for n, hs, he in spans:
            if hs <= mid <= he and (best is None or he - hs < best[1]):
                best = (n, he - hs)
        out[best[0] if best else "other"] += (e - s) * 1e-9
    return out


def summarize(planes: dict, window: str = "bench_window",
              host_spans=()) -> dict:
    """Busy and idle time per chip inside the host span ``window``, the
    top device operations and the idle gaps by host activity."""
    span = host_span(planes, window)
    if span is None:
        raise ValueError(f"no host span {window!r} in the trace")
    lo, hi = span
    chips = device_planes(planes)
    busy, ops, idle = [], defaultdict(float), defaultdict(float)
    spans = host_annotations(planes, host_spans)
    for p in chips:
        evs = clip(op_events(planes[p]), lo, hi)
        merged = union(evs)
        busy.append(sum(e - s for s, e in merged))
        for n, t in op_totals(evs).items():
            ops[n] += t * 1e-9 / len(chips)
        for n, t in label_gaps(gaps(merged, lo, hi), spans).items():
            idle[n] += t / len(chips)
    return {
        "window": (lo, hi),
        "window_s": (hi - lo) * 1e-9,
        "chips": chips,
        "busy_s": (sum(busy) / len(busy) * 1e-9) if busy else 0.0,
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1])[:10],
    }


def program_time(planes: dict, fn_name: str, lo: float, hi: float):
    """(executions, device seconds per chip) of program ``fn_name`` whose
    executions have their midpoint inside [lo, hi] (the device's clock and
    the host's agree to about a millisecond), averaged over chips."""
    chips = device_planes(planes)
    if not chips:
        return 0, 0.0
    counts, secs = [], []
    for p in chips:
        evs = [ev for ev in module_events(planes[p], fn_name)
               if lo <= 0.5 * (ev[1] + ev[2]) <= hi]
        counts.append(len(evs))
        secs.append(sum(e - s for _, s, e in evs) * 1e-9)
    return min(counts), sum(secs) / len(secs)

