"""The program's own spans and the join point of each device operation, as
a traced run's profiler trace holds them.

`read_xspace(path)` returns plain lists:
  host     the program's host spans (`serve.*`, `pages.*`, the
           `repro.monitor.spans` recorder) and the benchmark's own
           (`bench.*`), each [name, start_ns, end_ns, attrs];
  devices  per TPU, its XLA operations, each [name, start_ns, end_ns,
           scope] where scope is the operation's name stack (the
           `jax.named_scope` path of the DSL's join points, e.g.
           `jit(decode_step)/blocks0/while/body/closed_call/block/attn/
           dot_general`), and its XLA modules, [name, start_ns, end_ns].

The device operations come from the `.xplane.pb` itself: JAX's
`ProfileData` gives no event's metadata, and the name stack is a stat of
an operation's metadata (`SCOPE_STAT`).  So the device planes are read
with a small protobuf wire reader; the host planes through `ProfileData`.

On those lists, `scope_time` sums the device time of the operations under
one join point within calls of one jitted step, and `idle_by_span` puts
each idle nanosecond of a window down to the innermost program span open
at that time.  `for_run` finds and reads the trace of a harness run, on
the trace's clock; `run.win.trace_offset` puts it on the host's.
"""

from __future__ import annotations

import bisect
import functools
import os
import re
from pathlib import Path

from bench import tracing

BENCH = Path(__file__).resolve().parent
PROGRAM = ("serve.", "pages.")
HOST_PREFIXES = PROGRAM + ("bench.",)
# the stat of an XLA operation's metadata that holds its name stack, as
# "jit(decode_step)/blocks0/while/body/closed_call/block/attn/dot_general:"
# (a TPU v5e trace, JAX 0.9)
SCOPE_STAT = "tf_op"
NEXT_SPAN = "bench.serve_stream.next"
HARNESS = "harness"
# the attention join point: `attn` (decoder), `self_attn`, `cross_attn`
ATTENTION = re.compile(r"(^|/)(\w+_)?attn(/|:|$)")


# -- reading a trace -------------------------------------------------------------


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i, end):
    """(field, value) of one protobuf message: a length-delimited field's
    value is its (start, end) in `buf`, a varint's its number."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} in the trace")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat_names(buf, plane_fields) -> dict[int, str]:
    names = {}
    for num, entry in plane_fields:
        if num != 5:  # stat_metadata: map<int64, XStatMetadata>
            continue
        for f, v in _fields(buf, *entry):
            if f == 2:
                sid = name = None
                for g, w in _fields(buf, *v):
                    if g == 1:
                        sid = w
                    elif g == 2:
                        name = _text(buf, w)
                names[sid] = name
    return names


def _event_metadata(buf, plane_fields, stat_names):
    """Event metadata id -> its name, and -> the name stack its
    `SCOPE_STAT` holds (for the operations that have one)."""
    names: dict[int, str] = {}
    scopes: dict[int, str] = {}
    for num, entry in plane_fields:
        if num != 4:  # event_metadata: map<int64, XEventMetadata>
            continue
        for f, v in _fields(buf, *entry):
            if f != 2:
                continue
            mid = name = scope = None
            for g, w in _fields(buf, *v):
                if g == 1:
                    mid = w
                elif g == 2:
                    name = _text(buf, w)
                elif g == 5:  # XStat
                    sid = value = None
                    for h, x in _fields(buf, *w):
                        if h == 1:
                            sid = x
                        elif h == 5:
                            value = _text(buf, x)
                        elif h == 7:  # an interned string
                            value = stat_names.get(x)
                    if stat_names.get(sid) == SCOPE_STAT and value:
                        scope = value
            names[mid] = name
            if scope:
                scopes[mid] = scope
    return names, scopes


def _device_plane(buf, plane_fields) -> dict:
    names, scopes = _event_metadata(buf, plane_fields,
                                    _stat_names(buf, plane_fields))
    dev: dict = {"ops": [], "modules": []}
    for num, line in plane_fields:
        if num != 3:  # XLine
            continue
        key, t0, events = None, 0, []
        for f, v in _fields(buf, *line):
            if f == 2:
                key = {tracing.OPS_LINE: "ops",
                       tracing.MODULES_LINE: "modules"}.get(_text(buf, v))
            elif f == 3:
                t0 = _signed(v)
            elif f == 4:
                events.append(v)
        if key is None:
            continue
        for ev in events:
            mid, off, dur = 0, 0, 0
            for f, v in _fields(buf, *ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = _signed(v)
                elif f == 3:
                    dur = v
            a = t0 + off / 1000.0
            row = [names.get(mid, ""), a, a + dur / 1000.0]
            if key == "ops":
                row.append(scopes.get(mid, ""))
            dev[key].append(row)
    return dev


def read_xspace(path: str) -> dict:
    """{"host": [[name, a, b, attrs]], "devices": [{"name", "ops",
    "modules"}]}, times in the trace's nanoseconds."""
    from jax.profiler import ProfileData

    buf = memoryview(Path(path).read_bytes())
    out: dict = {"host": [], "devices": []}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        fields = list(_fields(buf, *plane))
        name = next((_text(buf, v) for f, v in fields if f == 2), "")
        if tracing.DEVICE_PLANE.match(name):
            out["devices"].append({"name": name,
                                   **_device_plane(buf, fields)})
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name, e.start_ns, e.start_ns + e.duration_ns,
                     {k: v for k, v in e.stats}]
                    for e in line.events if e.name.startswith(HOST_PREFIXES))
    return out


@functools.lru_cache(maxsize=1)
def _read_once(path: str, mtime_ns: int) -> dict:
    return read_xspace(path)


def for_run(run) -> dict | None:
    """The trace of a traced harness run (the file the harness reduced),
    read once for all the metrics that read it; None for a run without."""
    if not getattr(run, "trace", None):
        return None
    files = sorted((BENCH / "out" / "trace" / run.cell.name).rglob(
        "*.xplane.pb"))
    if not files:
        return None
    path = str(files[-1])
    return _read_once(path, os.stat(path).st_mtime_ns)


def window(run) -> tuple[float, float]:
    """The run's measured window in trace nanoseconds."""
    lo = (run.t_start - run.win.trace_offset) * 1e9
    return lo, lo + run.seconds * 1e9


# -- device time by join point ------------------------------------------------------


def scope_time(trace: dict, win: tuple[float, float], module: str,
               scope: re.Pattern) -> tuple[int, float]:
    """(calls, seconds): the calls of jitted `module` that lie inside
    `win`, and the device time of the operations inside them whose name
    stack matches `scope`, averaged over the devices that ran any."""
    w0, w1 = win
    per_dev = []
    for dev in trace["devices"]:
        calls = sorted((a, b) for n, a, b in dev["modules"]
                       if tracing.module_base(n) == module
                       and a >= w0 and b <= w1)
        if not calls:
            continue
        starts = [a for a, _ in calls]
        total = 0.0
        for name, a, b, sc in dev["ops"]:
            if not sc or tracing.CONTAINER.match(name) \
                    or not scope.search(sc):
                continue
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and a < calls[i][1]:
                total += b - a
        per_dev.append((len(calls), total))
    if not per_dev:
        return 0, 0.0
    calls = sum(c for c, _ in per_dev) / len(per_dev)
    return round(calls), sum(t for _, t in per_dev) / len(per_dev) / 1e9


# -- idle time by program span ------------------------------------------------------


def innermost(host) -> list[tuple[float, float, str]]:
    """Disjoint, sorted segments of host time, each labelled with the
    innermost span open in it: a program span, else the benchmark's call
    into the stream (`NEXT_SPAN`).  Spans must nest."""
    spans = sorted(((a, b, n) for n, a, b, _ in host
                    if n.startswith(PROGRAM) or n == NEXT_SPAN),
                   key=lambda s: (s[0], -s[1]))
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, float, str]] = []
    t = None

    def emit(until):
        nonlocal t
        if stack and until > t:
            out.append((t, until, stack[-1][2]))
        t = until

    for a, b, n in spans:
        while stack and stack[-1][1] <= a:
            emit(stack[-1][1])
            stack.pop()
        if t is None or not stack:
            t = a
        emit(a)
        stack.append((a, b, n))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def idle_by_span(trace: dict, win: tuple[float, float]) -> dict:
    """Idle nanoseconds of the window's device time by the innermost
    program span open on the host (else `NEXT_SPAN`, program code outside
    every span, else `HARNESS`), and each idle gap with its labels."""
    w0, w1 = win
    segs = innermost(trace["host"])
    starts = [s[0] for s in segs]
    by: dict[str, float] = {}
    gaps = []
    for dev in trace["devices"]:
        clipped = [(max(a, w0), min(b, w1)) for _, a, b, _ in dev["ops"]
                   if b > w0 and a < w1]
        merged = tracing.union(clipped)
        if not merged:
            continue
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for i in range(0, len(edges), 2):
            a, b = edges[i], edges[i + 1]
            if b > a:
                labels = _label(segs, starts, a, b)
                for name, ns in labels.items():
                    by[name] = by.get(name, 0.0) + ns
                gaps.append((a, b, labels))
    gaps.sort(key=lambda g: g[0] - g[1])
    return {"idle_ns": sum(by.values()), "by_span": by, "gaps": gaps}


def _label(segs, starts, a: float, b: float) -> dict[str, float]:
    """Nanoseconds of [a, b) under each label of `segs` (whose starts are
    `starts`), the rest under `HARNESS`."""
    out: dict[str, float] = {}
    i = max(0, bisect.bisect_right(starts, a) - 1)
    covered = 0.0
    while i < len(segs) and segs[i][0] < b:
        s0, s1, name = segs[i]
        lo, hi = max(a, s0), min(b, s1)
        if hi > lo:
            out[name] = out.get(name, 0.0) + hi - lo
            covered += hi - lo
        i += 1
    if b - a > covered:
        out[HARNESS] = out.get(HARNESS, 0.0) + (b - a - covered)
    return out
