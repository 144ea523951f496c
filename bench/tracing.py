"""From a profiler trace to device busy time, idle gaps and kernel time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps two
things, on the trace's one clock: the device operations (the ``XLA Ops``
line of every ``/device:TPU:<n>`` plane) and the harness's host spans (its
``TraceAnnotation`` scopes). ``reduce`` works on those plain records only,
so a test can feed it a synthetic trace.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench_window"
HOST_SPANS = ("sample", "round", "telemetry", "eval")


@dataclass
class Event:
    name: str
    start: float  # ns, trace clock
    end: float
    stats: dict = field(default_factory=dict)
    device: int = 0


@dataclass
class Trace:
    ops: list  # device operations, all chips
    spans: list  # host spans (HOST_SPANS and WINDOW_SPAN)
    devices: int


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    ops, spans, devices = [], [], set()
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = int(plane.name.rsplit(":", 1)[1])
            devices.add(dev)
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Event(ev.name, ev.start_ns, ev.end_ns,
                                     {k: str(v) for k, v in ev.stats}, dev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS or ev.name == WINDOW_SPAN:
                        spans.append(Event(ev.name, ev.start_ns, ev.end_ns))
    return Trace(ops, spans, max(len(devices), 1))


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def window(trace: Trace) -> tuple:
    """``(start, end)`` of the harness's traced window, in trace ns."""
    w = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if not w:
        raise ValueError("the trace holds no bench_window span")
    return w[-1].start, w[-1].end


def reduce(trace: Trace, is_kernel, top: int = 10) -> dict:
    """Busy and idle time of the devices inside the window, the kernel's
    device time, the operations that took most time, and the longest idle
    gaps named by the host span that overlapped them most.

    Busy time is the union of one chip's operation intervals, averaged over
    the chips. ``is_kernel(event)`` picks the kernel's events."""
    lo, hi = window(trace)
    per_dev = {}
    for op in trace.ops:
        per_dev.setdefault(op.device, []).append((op.start, op.end))
    busy_ns = 0.0
    merged0 = []
    for dev, iv in sorted(per_dev.items()):
        merged = union(_clip(iv, lo, hi))
        busy_ns += sum(e - s for s, e in merged)
        if not merged0:
            merged0 = merged
    busy_ns /= max(trace.devices, 1)

    by_name, kernel_ns, kernel_events = {}, 0.0, 0
    for op in trace.ops:
        s, e = max(op.start, lo), min(op.end, hi)
        if e <= s:
            continue
        by_name[op.name] = by_name.get(op.name, 0.0) + (e - s)
        if is_kernel(op):
            kernel_ns += e - s
            kernel_events += 1
    kernel_ns /= max(trace.devices, 1)

    gaps, cursor = [], lo
    for s, e in merged0 + [[hi, hi]]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    host = [sp for sp in trace.spans if sp.name in HOST_SPANS]

    def label(gap):
        best, name = 0.0, "other"
        for sp in host:
            ov = min(gap[1], sp.end) - max(gap[0], sp.start)
            if ov > best:
                best, name = ov, sp.name
        return name

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    ops_top = sorted(by_name.items(), key=lambda kv: kv[1], reverse=True)[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "idle_share": 1.0 - busy_ns / max(hi - lo, 1e-9),
        "kernel_s": kernel_ns * 1e-9,
        "kernel_events": kernel_events,
        "device_ops": [[n, v * 1e-9] for n, v in ops_top],
        "idle_gaps": [[label(g), (g[1] - g[0]) * 1e-9] for g in gaps[:top]],
    }
