#!/usr/bin/env python3
"""Record a small profiler trace of a tiny paged engine on a TPU, the
fixture of the trace reduction's tests:

    python3 benchmarks/onchip/tests/record_engine_trace.py [--out DIR]

A few engine steps of the ``tiny`` configuration (``data/tiny.json``) with
the harness's spans around them (``bench.window``, ``submit``,
``engine.step``, ``wait``), with the Python tracer off and the trace
trimmed to what the reductions read (``trim``) so the file stays small.
Writes ``engine.xplane.pb`` and
``engine.scopes.json`` (the named scopes of the engine's compiled programs,
``trace_spans.program_scopes``) to ``--out`` (default ``data/``) and prints
the trace's reduction by ``trace_spans``. Exits non-zero without a TPU.
"""
import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
sys.path.insert(0, os.path.dirname(HERE))

import harness  # noqa: E402
import jax  # noqa: E402
import trace_reduce  # noqa: E402
import trace_spans  # noqa: E402

SEED = 2**31 + 13
STEPS = 6

# field numbers of the profiler's XSpace protobuf (tsl's xplane.proto)
SPACE_PLANE = 1
PLANE_NAME, PLANE_LINE, PLANE_EVENT_METADATA = 2, 3, 4
LINE_EVENT = 4
EVENT_METADATA_ID, EVENT_STAT = 1, 4
ENTRY_KEY, ENTRY_VALUE = 1, 2
METADATA_NAME, METADATA_STAT = 2, 5
HLO_PLANE = b"/host:metadata"
SPANS = tuple(s.encode() for s in (
    trace_reduce.WINDOW_SPAN, *trace_reduce.HOST_SPANS,
    *trace_spans.PROGRAM_SPAN_PREFIXES))


def _varint(b: bytes, i: int):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b: bytes):
    """(field number, value, raw bytes) of each field of a message: the
    value is an int for a varint, the payload for a length-delimited
    field, None for a fixed-width one."""
    i = 0
    while i < len(b):
        start = i
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(b, i)
        elif kind == 2:
            n, i = _varint(b, i)
            value, i = b[i:i + n], i + n
        else:
            value, i = None, i + (8 if kind == 1 else 4)
        yield key >> 3, value, b[start:i]


def _field(number: int, payload: bytes) -> bytes:
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    return varint(number << 3 | 2) + varint(len(payload)) + payload


def _first(b: bytes, number: int, default=None):
    return next((v for f, v, _ in _fields(b) if f == number), default)


def _without(b: bytes, number: int) -> bytes:
    return b"".join(raw for f, _, raw in _fields(b) if f != number)


def _plane(b: bytes, host: bool) -> bytes:
    """A plane without event and metadata stats, without the metadata no
    event names, and on a host plane without the threads that hold no span
    the reductions read."""
    names = {}
    for f, v, _ in _fields(b):
        if f == PLANE_EVENT_METADATA:
            names[_first(v, ENTRY_KEY)] = _first(
                _first(v, ENTRY_VALUE, b""), METADATA_NAME, b"")
    lines, used = [], set()
    for f, v, _ in _fields(b):
        if f != PLANE_LINE:
            continue
        ids = {_first(e, EVENT_METADATA_ID)
               for g, e, _ in _fields(v) if g == LINE_EVENT}
        if host and not any(names.get(i, b"").split(b"#")[0]
                            .startswith(SPANS) for i in ids):
            continue
        used |= ids
        lines.append(_field(PLANE_LINE, b"".join(
            _field(LINE_EVENT, _without(e, EVENT_STAT)) if g == LINE_EVENT
            else raw for g, e, raw in _fields(v))))
    out = []
    for f, v, raw in _fields(b):
        if f == PLANE_LINE:
            out.extend(lines)
            lines = []
        elif f == PLANE_EVENT_METADATA:
            if _first(v, ENTRY_KEY) in used:
                out.append(_field(f, b"".join(
                    _field(g, _without(m, METADATA_STAT))
                    if g == ENTRY_VALUE else r
                    for g, m, r in _fields(v))))
        else:
            out.append(raw)
    return b"".join(out)


def trim(space: bytes) -> bytes:
    """The trace without what the reductions do not read: the HLO protos'
    plane, the stats of events and of their metadata, metadata no event
    names, and host threads that hold no span the reductions read. Event
    names and times, and the device's lines, stay as recorded."""
    out = []
    for f, v, raw in _fields(space):
        if f != SPACE_PLANE:
            out.append(raw)
            continue
        name = _first(v, PLANE_NAME, b"")
        if name != HLO_PLANE:
            out.append(_field(f, _plane(v, name.startswith(b"/host:"))))
    return b"".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=DATA)
    args = ap.parse_args(argv)
    try:
        harness.check_devices(1)
    except harness.NoChip as e:
        print(f"record_engine_trace.py: {e}", file=sys.stderr)
        return 2
    config = harness.load_json(os.path.join(DATA, "tiny.json"))
    mix = harness.load_json(os.path.join(DATA, "tiny-chat.json"))
    cell = harness.Cell("tiny.chat", 1, config, mix, [], [])
    m = harness.model_dims(config)
    params = harness.weights_of(cell).init(m, SEED)
    eng = harness.build_engine(cell, params, m)
    eng.warmup()
    chunk = mix["engine"]["prefill_chunk"]
    prompts = [list(range(1, 13)), list(range(5, 25)),
               list(range(7, 7 + 2 * chunk + 6))]   # the last is chunked
    trace_dir = os.path.join(harness.RUN_DIR, "record-engine-trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        for k in range(STEPS):
            with jax.profiler.TraceAnnotation("submit"):
                if k < len(prompts):
                    eng.submit(prompts[k], 6)
            with jax.profiler.TraceAnnotation("engine.step"):
                eng.step()
            with jax.profiler.TraceAnnotation("wait"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    with open(trace_reduce.find_xplane(trace_dir), "rb") as f:
        space = f.read()
    os.makedirs(args.out, exist_ok=True)
    out_pb = os.path.join(args.out, "engine.xplane.pb")
    with open(out_pb, "wb") as f:
        f.write(trim(space))
    scopes = trace_spans.program_scopes(eng)
    with open(os.path.join(args.out, "engine.scopes.json"), "w") as f:
        json.dump(scopes, f, indent=1, sort_keys=True)
        f.write("\n")
    shutil.rmtree(trace_dir, ignore_errors=True)
    devices, host, programs = trace_spans.read(out_pb, scopes)
    red = trace_spans.reduce(devices, host, top=1000, programs=programs)
    scoped = sum(1 for ops in devices.values() for op in ops if op.scope)
    print(json.dumps({
        "bytes": os.path.getsize(out_pb), "scoped_ops": scoped,
        "host_spans": {n: len(v) for n, v in sorted(host.items())},
        "spans": eng.telemetry.span_totals(), "reduce": red}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
