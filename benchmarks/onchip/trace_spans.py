"""The trace reduction of ``trace_reduce`` extended to the program's own
spans, programs and named scopes.

* Operation times are self times: each instant of an operation's run counts
  once, for the innermost operation running then, so a ``while`` loses the
  time of its body's operations and one device's times sum to its busy
  time.
* Idle time is labelled in two steps. Where a program (an ``XLA Modules``
  event) was running on the device, the idle piece is ``in:<program>``:
  idle the host did not cause. The rest goes, piece by piece, to the
  innermost host span covering it: the harness's spans (``submit``,
  ``engine.step``, ``wait``) and the engine's program spans (``serve.*``,
  ``py.gc``), so the harness's ``engine.step`` keeps only its own self
  time.
* Each program's device time and runs, each named scope's device self time
  inside a program (``SCOPES``), and the ``SLOW_STEPS`` longest engine
  steps broken down by span and program. An operation's scope is read from
  the ``op_name`` metadata of its instruction in the program's compiled HLO
  (``scopes_from_hlo``, ``program_scopes``): the trace's op events carry
  only the instruction's text.

Busy time and the window are ``trace_reduce``'s, to the nanosecond. The
harness (``reduce_trace``) keeps ``trace_reduce``'s readings and adds this
reduction's per-program and per-scope times, which metric readers take.
"""
from __future__ import annotations

import bisect
import collections
import heapq
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, \
    Tuple

import trace_reduce as TR
from trace_reduce import clip, gaps, merge

# the engine's program spans (``StageTelemetry.span``) and GC pauses
PROGRAM_SPAN_PREFIXES = ("serve.", "py.gc")
# the model's named scopes whose device time is reported per program
SCOPES = ("paged_attention", "kv_write")
# the engine's step span, and how many of the slowest are broken down
STEP_SPAN = "serve.step"
SLOW_STEPS = 3


class Op(NamedTuple):
    """One device operation: ``<program>/<instruction kind>``, its start
    and end (ns), and the named scope of its instruction ('' if none)."""
    name: str
    start: float
    end: float
    scope: str = ""


def innermost(items: Iterable[Tuple[float, float, object]]
              ) -> List[Tuple[float, float, object]]:
    """Disjoint, sorted ``(start, end, label)`` pieces of the union of the
    items, each labelled by the innermost item covering it: the last to
    start, and of two that start together the shorter."""
    order = sorted((s, -e, i, lab) for i, (s, e, lab) in enumerate(items)
                   if e > s)
    out: List[Tuple[float, float, object]] = []
    active: List[Tuple[int, float, object]] = []   # (-rank, end, label)
    t = None

    def advance(until: float) -> None:
        nonlocal t
        while active and t < until:
            _, end, lab = active[0]
            if end <= t:
                heapq.heappop(active)
                continue
            stop = min(end, until)
            if out and out[-1][1] == t and out[-1][2] == lab:
                out[-1] = (out[-1][0], stop, lab)
            else:
                out.append((t, stop, lab))
            t = stop
        if t < until:
            t = until

    for rank, (s, neg_e, _, lab) in enumerate(order):
        if t is None:
            t = s
        advance(s)
        heapq.heappush(active, (-rank, -neg_e, lab))
    if t is not None:
        advance(float("inf"))
    return out


def overlay(parts: Sequence[Tuple[float, float]],
            pieces: Sequence[Tuple[float, float, object]]):
    """Split each of the sorted, disjoint ``parts`` by the sorted, disjoint
    labelled ``pieces``: yields ``(start, end, label)``, with label None
    where no piece covers."""
    j = 0
    for a, b in parts:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        t, k = a, j
        while t < b:
            if k < len(pieces) and pieces[k][0] < b:
                s, e, lab = pieces[k]
                if s > t:
                    yield t, s, None
                    t = s
                stop = min(e, b)
                yield t, stop, lab
                t = stop
                if e <= b:
                    k += 1
                else:
                    break
            else:
                yield t, b, None
                t = b


def instruction(hlo: str) -> str:
    """The instruction's name in an op event's HLO text, e.g. ``fusion.12``
    for ``%fusion.12 = bf16[8]{0} fusion(...)``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def program(module: str) -> str:
    """A module event's program name, without its run id:
    ``jit_decode_step`` for ``jit_decode_step(1234)``."""
    return module.split("(", 1)[0]


_HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_HLO_INST = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?'
                       r'metadata=\{[^}]*?op_name="([^"]*)"')


def scopes_from_hlo(texts: Iterable[str]) -> Dict[str, Dict[str, str]]:
    """``{program: {instruction: scope}}`` for the instructions whose
    ``op_name`` metadata lies under one of ``SCOPES``, from compiled HLO
    texts (``Compiled.as_text()``). A program compiled for several shapes
    gives the union of its texts."""
    out: Dict[str, Dict[str, str]] = collections.defaultdict(dict)
    for text in texts:
        m = _HLO_MODULE.match(text)
        if m is None:
            continue
        table = out[m.group(1)]
        for line in text.splitlines():
            if not any(s in line for s in SCOPES):
                continue
            hit = _HLO_INST.match(line)
            if hit is None:
                continue
            path = hit.group(2).split("/")
            scope = next((s for s in SCOPES if s in path), "")
            if scope:
                table[hit.group(1)] = scope
    return dict(out)


def _with_modules(ops, modules, scopes):
    """Name each op by the program (module event) that encloses it, and
    give it its instruction's scope there."""
    modules = sorted((s, e, n) for n, s, e in modules)
    starts = [m[0] for m in modules]
    out = []
    for hlo, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = modules[i][2] if i >= 0 and modules[i][1] >= s else ""
        scope = scopes.get(program(mod), {}).get(instruction(hlo), "")
        out.append(Op(TR.op_name(hlo, mod), s, e, scope))
    return out


def _host_span(name: str) -> Optional[str]:
    """The span's name without the metadata a ``TraceAnnotation`` may
    append (``serve.admit#rid=3#``), if the reduction reads it."""
    base = name.split("#", 1)[0]
    if base == TR.WINDOW_SPAN or base in TR.HOST_SPANS \
            or base.startswith(PROGRAM_SPAN_PREFIXES):
        return base
    return None


def read(path: str, scopes: Optional[Dict[str, Dict[str, str]]] = None):
    """(device ops per device plane, host spans by name, program runs per
    device plane), times in ns. ``scopes`` is ``scopes_from_hlo`` of the
    traced programs."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    programs: Dict[str, List[Tuple[str, float, float]]] = {}
    host: Dict[str, List[Tuple[float, float]]] = collections.defaultdict(list)
    for plane in data.planes:
        if plane.name.startswith(TR.DEVICE_PREFIX):
            lines = {line.name: list(TR._events(line))
                     for line in plane.lines}
            ops = lines.get(TR.OP_LINE, [])
            if ops:
                modules = lines.get(TR.MODULE_LINE, [])
                devices[plane.name] = _with_modules(ops, modules,
                                                    scopes or {})
                programs[plane.name] = [(program(n), s, e)
                                        for n, s, e in modules]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in TR._events(line):
                    base = _host_span(name)
                    if base is not None:
                        host[base].append((s, e))
    return devices, dict(host), programs


def reduce(devices: Dict[str, List[Tuple]],
           host: Dict[str, List[Tuple[float, float]]],
           window: Optional[Tuple[float, float]] = None, top: int = 10,
           programs: Optional[Dict[str, List[Tuple[str, float, float]]]]
           = None):
    """Busy seconds (mean over devices), window seconds, the top device
    operations by self time, the idle seconds by label, each program's
    device seconds and runs, each named scope's device self seconds
    (``<program>/<scope>``), and the ``SLOW_STEPS`` longest engine steps
    with the host seconds of each span inside (self time) and the device
    seconds of each program run in them."""
    if window is None:
        spans = host.get(TR.WINDOW_SPAN)
        if not spans:
            raise ValueError(f"trace has no {TR.WINDOW_SPAN!r} span")
        window = (min(s for s, _ in spans), max(e for _, e in spans))
    lo, hi = window
    if not devices:
        raise ValueError("trace has no device operations")
    programs = programs or {}
    busy_total = 0.0
    op_time: Dict[str, float] = collections.Counter()
    scope_time: Dict[str, float] = collections.Counter()
    prog_time: Dict[str, float] = collections.Counter()
    prog_runs: Dict[str, float] = collections.Counter()
    idle_by: Dict[str, float] = collections.Counter()
    labels = innermost(
        (s, e, n) for n, spans in host.items() if n != TR.WINDOW_SPAN
        for s, e in spans)
    steps = sorted(clip(host.get(STEP_SPAN, []), lo, hi),
                   key=lambda se: se[0] - se[1])[:SLOW_STEPS]
    step_progs: List[Dict[str, float]] = [collections.Counter()
                                          for _ in steps]
    for dev, ops in devices.items():
        ops = [Op(*op) for op in ops]
        busy = merge(clip([(op.start, op.end) for op in ops], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        clipped = [(max(op.start, lo), min(op.end, hi), op) for op in ops]
        for s, e, op in innermost(clipped):
            op_time[op.name] += e - s
            if op.scope:
                scope_time[f"{op.name.split('/', 1)[0]}/{op.scope}"] += e - s
        runs = [(max(s, lo), min(e, hi), n)
                for n, s, e in programs.get(dev, []) if e > lo and s < hi]
        for s, e, n in runs:
            prog_time[n] += e - s
            prog_runs[n] += 1
        for step, got in zip(steps, step_progs):
            for s, e, n in overlay([step], innermost(runs)):
                if n is not None:
                    got[n] += e - s
        outside = []
        for s, e, n in overlay(gaps(busy, lo, hi), innermost(runs)):
            if n is None:
                outside.append((s, e))
            else:
                idle_by["in:" + n] += e - s
        for s, e, n in overlay(outside, labels):
            idle_by[n or "other"] += e - s
    n = len(devices)
    ns = 1e-9

    def ranked(d, k=None):
        return [[name, v / n * ns] for name, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:k]]

    return {
        "busy_s": busy_total / n * ns,
        "window_s": (hi - lo) * ns,
        "devices": n,
        "device_ops": ranked(op_time, top),
        "idle_gaps": ranked(idle_by),
        "programs": [[name, t / n * ns, prog_runs[name] / n] for name, t in
                     sorted(prog_time.items(), key=lambda kv: -kv[1])],
        "scopes": ranked(scope_time),
        "slow_steps": [[(e - s) * ns, _span_time([(s, e)], labels),
                        ranked(got)] for (s, e), got in zip(steps,
                                                            step_progs)],
    }


def _span_time(parts, labels) -> List[List]:
    """Seconds of ``parts`` under each innermost label, largest first."""
    got: Dict[str, float] = collections.Counter()
    for s, e, n in overlay(parts, labels):
        got[n or "other"] += e - s
    return [[k, v * 1e-9] for k, v in sorted(got.items(),
                                             key=lambda kv: -kv[1])]


def reduce_file(path: str, top: int = 10,
                scopes: Optional[Dict[str, Dict[str, str]]] = None):
    devices, host, programs = read(path, scopes)
    return reduce(devices, host, top=top, programs=programs)


def program_scopes(eng) -> Dict[str, Dict[str, str]]:
    """The named scopes of the instructions of every program in a serving
    engine's AOT ledger, from their compiled HLO."""
    texts = (c.as_text() for fn in eng.aot.fns.values()
             for c in fn.compiled.values())
    return scopes_from_hlo(texts)
