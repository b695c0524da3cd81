"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle gaps
and the device operations that took the most time.

Busy time is the union of the intervals in which an operation ran on a
device, clipped to the traced window; the idle share is 1 minus busy over
the window. Each idle gap is attributed to the host span (a
``jax.profiler.TraceAnnotation`` the harness opened) that covers the gap's
midpoint, so the breakdown says what the host was doing while the device
waited.

The window is the span named ``WINDOW_SPAN`` on the host plane; the
harness opens it around the traced part of the run.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("engine.step", "submit", "wait")
DEVICE_PREFIX = "/device:TPU:"
# the line of a device plane that holds the operations themselves, and the
# line of the programs (jitted functions) they belong to
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def merge(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                 float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float):
    """Complement of the merged ``busy`` intervals inside [lo, hi)."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def op_name(hlo: str, module: str) -> str:
    """A short, stable name for a device operation: its program's name and
    the HLO instruction's kind, e.g. ``jit_chunk/fusion`` for
    ``%fusion.12 = bf16[...] fusion(...)`` inside ``jit_chunk(123)``."""
    inst = hlo.split(" = ", 1)[0].lstrip("%").strip()
    inst = inst.rstrip("0123456789").rstrip(".") or inst
    return f"{module.split('(', 1)[0]}/{inst}" if module else inst


def _with_modules(ops, modules):
    """Name each op by the program (module event) that encloses it."""
    modules = sorted((s, e, n) for n, s, e in modules)
    starts = [m[0] for m in modules]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = modules[i][2] if i >= 0 and modules[i][1] >= s else ""
        out.append((op_name(name, mod), s, e))
    return out


def read(path: str):
    """(device ops per device plane, host spans by name), times in ns."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    host: Dict[str, List[Tuple[float, float]]] = collections.defaultdict(list)
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: list(_events(line)) for line in plane.lines}
            ops = lines.get(OP_LINE, [])
            if ops:
                devices[plane.name] = _with_modules(
                    ops, lines.get(MODULE_LINE, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == WINDOW_SPAN or name in HOST_SPANS:
                        host[name].append((s, e))
    return devices, dict(host)


def reduce(devices: Dict[str, List[Tuple[str, float, float]]],
           host: Dict[str, List[Tuple[float, float]]],
           window: Optional[Tuple[float, float]] = None, top: int = 10):
    """Busy seconds (mean over devices), window seconds, the top device
    operations by total time and the idle seconds by host span."""
    if window is None:
        spans = host.get(WINDOW_SPAN)
        if not spans:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
        window = (min(s for s, _ in spans), max(e for _, e in spans))
    lo, hi = window
    if not devices:
        raise ValueError("trace has no device operations")
    busy_total = 0.0
    op_time: Dict[str, float] = collections.Counter()
    idle_by: Dict[str, float] = collections.Counter()
    labelled = sorted((s, e, n) for n in HOST_SPANS
                      for s, e in host.get(n, []))
    for ops in devices.values():
        busy = merge(clip([(s, e) for _, s, e in ops], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        for name, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                op_time[name] += e - s
        for g0, g1 in gaps(busy, lo, hi):
            idle_by[_label((g0 + g1) / 2, labelled)] += g1 - g0
    n = len(devices)
    ns = 1e-9
    return {
        "busy_s": busy_total / n * ns,
        "window_s": (hi - lo) * ns,
        "devices": n,
        "device_ops": [[k, v / n * ns] for k, v in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / n * ns] for k, v in
                      sorted(idle_by.items(), key=lambda kv: -kv[1])[:top]],
    }


def _label(t: float, labelled) -> str:
    """Name of the host span covering t; the harness's spans do not
    overlap, so it is the last one to start before t, if it ends after."""
    i = bisect.bisect_right(labelled, (t, float("inf"), "")) - 1
    if i >= 0 and labelled[i][1] >= t:
        return labelled[i][2]
    return "other"


def reduce_file(path: str, top: int = 10):
    devices, host = read(path)
    return reduce(devices, host, top=top)
