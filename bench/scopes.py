"""Readings of the program's own device scopes and host spans in a trace.

The program names four disjoint parts of its training step with
``jax.named_scope`` (``lmc.agg``, ``lmc.halo``, ``lmc.store``,
``lmc.dense``); each device operation's ``op_name`` path starts with the
scope it was traced under, and a transposed operation's path reads
``transpose(jvp(<scope>))``. On the TPU the path is the ``tf_op``
statistic of the operation's metadata in the trace file, which
``bench.trace`` does not keep; :func:`op_paths` reads it from the file, by
operation name. An operation's text in a hand-built ``Trace`` may carry
its path too. The host spans (``pipeline.wait``, ``pipeline.h2d``,
``pipeline.build``, ...) are ``host_events`` by name.

Every reading here returns ``None`` where the trace holds nothing of the
kind: no device operations, no scoped operation, no such span.
"""
from __future__ import annotations

import glob
import os
from pathlib import Path
from typing import Optional

from bench import trace as tracing

PATH_STAT = "tf_op"   # the metadata statistic that holds an op's op_name

# the parts of the step, each with the text its operations carry; a text
# holding several belongs to the one that starts first, so a transposed
# aggregation ("transpose(jvp(lmc.agg...") is not counted as a forward one
PARTS = {"aggregate_t": "transpose(jvp(lmc.agg",
         "aggregate": "lmc.agg",
         "compensate": "lmc.halo",
         "store_refresh": "lmc.store",
         "dense": "lmc.dense"}


def part_of(text: str) -> Optional[str]:
    """The part of the step whose scope ``text`` names first, or None."""
    found = [(text.find(tag), part) for part, tag in PARTS.items()
             if tag in text]
    return min(found)[1] if found else None


def _text(op, paths: dict) -> str:
    """An operation's path (where ``paths`` has it) before its own text."""
    own = op[3] if len(op) > 3 else op[0].lower()
    return f"{paths.get(op[0], '')} {own}"


# ---------------------------------------------------------- trace file
def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a protobuf message; a
    length-delimited value is a memoryview of its bytes."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            val, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            val, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, val


def _map_entry(buf) -> tuple[int, memoryview]:
    f = dict(_fields(buf))
    return f.get(1, 0), f.get(2, memoryview(b""))


def paths_in_xspace(data: bytes) -> dict:
    """{operation name: op_name path} of the device planes of a serialized
    ``XSpace`` (tsl/profiler/protobuf/xplane.proto: XSpace.planes = 1;
    XPlane.name = 2, event_metadata = 4, stat_metadata = 5, both maps with
    key = 1 and value = 2; XEventMetadata.name = 2, stats = 5;
    XStatMetadata.name = 2; XStat.metadata_id = 1, str_value = 5,
    ref_value = 7)."""
    out = {}
    for num, plane in _fields(memoryview(data)):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for n, v in fields if n == 2), "")
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for n, v in fields:
            if n == 5:
                key, md = _map_entry(v)
                stat_names[key] = next(
                    (bytes(x).decode() for m, x in _fields(md) if m == 2), "")
        for n, v in fields:
            if n != 4:
                continue
            md = list(_fields(_map_entry(v)[1]))
            op = next((bytes(x).decode() for m, x in md if m == 2), None)
            for m, stat in md:
                if m != 5:
                    continue
                st = dict(_fields(stat))
                if stat_names.get(st.get(1)) != PATH_STAT:
                    continue
                if 5 in st:
                    path = bytes(st[5]).decode()
                else:
                    path = stat_names.get(st.get(7), "")
                if op is not None:
                    out[op] = path.lower()
    return out


_PATHS_CACHE: dict = {}


def op_paths(reader_file: str) -> dict:
    """{operation name: op_name path} from the newest trace file of the run,
    which the harness writes under ``.trace`` beside the ``metrics``
    directory that holds ``reader_file``; empty where there is none."""
    log_dir = Path(reader_file).resolve().parents[1] / ".trace"
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        return {}
    newest = max(files, key=os.path.getmtime)
    key = (newest, os.stat(newest).st_mtime_ns)
    if key not in _PATHS_CACHE:
        _PATHS_CACHE.clear()
        _PATHS_CACHE[key] = paths_in_xspace(Path(newest).read_bytes())
    return _PATHS_CACHE[key]


def _seconds(intervals) -> float:
    return sum(b - a for a, b in intervals) / 1e9


# ------------------------------------------------------------- readings
def part_seconds(trace: tracing.Trace, lo: float, hi: float,
                 paths: Optional[dict] = None) -> dict:
    """Device seconds in [lo, hi] of each part (the union of its operations'
    intervals), averaged over the devices; empty where no operation carries
    a scope. ``paths``: op_name paths by operation name."""
    if not trace.device_ops:
        return {}
    paths = paths or {}
    tot = dict.fromkeys(PARTS, 0.0)
    scoped = False
    for ops in trace.device_ops:
        by_part = {part: [] for part in PARTS}
        for op in ops:
            part = part_of(_text(op, paths))
            if part is not None:
                by_part[part].append(op)
                scoped = True
        for part, hits in by_part.items():
            tot[part] += _seconds(tracing.busy_intervals(hits, lo, hi))
    if not scoped:
        return {}
    n = len(trace.device_ops)
    return {part: s / n for part, s in tot.items()}


def scoped_share(trace: tracing.Trace, lo: float, hi: float,
                 paths: Optional[dict] = None) -> Optional[float]:
    """Share of the device's busy time in [lo, hi] in operations that carry
    a scope, in %; None where none does."""
    paths = paths or {}
    scoped = busy = 0.0
    for ops in trace.device_ops:
        hits = [op for op in ops if part_of(_text(op, paths)) is not None]
        scoped += _seconds(tracing.busy_intervals(hits, lo, hi))
        busy += _seconds(tracing.busy_intervals(ops, lo, hi))
    if scoped <= 0.0 or busy <= 0.0:
        return None
    return 100.0 * scoped / busy


def _overlap(xs, ys) -> float:
    """Length of the intersection of two sorted, merged interval lists."""
    tot, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            tot += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_under(trace: tracing.Trace, name: str, lo: float, hi: float
               ) -> Optional[float]:
    """Device idle seconds in [lo, hi] while the host is inside a span
    ``name``, averaged over the devices; None without device operations or
    without such a span."""
    spans = tracing.busy_intervals(
        [e for e in trace.host_events if e[0] == name], lo, hi)
    if not trace.device_ops or not spans:
        return None
    tot = 0.0
    for ops in trace.device_ops:
        idle, t = [], lo
        for a, b in tracing.busy_intervals(ops, lo, hi):
            if a > t:
                idle.append((t, a))
            t = max(t, b)
        if hi > t:
            idle.append((t, hi))
        tot += _overlap(idle, spans)
    return tot / len(trace.device_ops) / 1e9


def mean_span(trace: tracing.Trace, name: str, lo: float, hi: float
              ) -> Optional[float]:
    """Mean duration in seconds of the spans ``name`` that start in
    [lo, hi], on any host thread; None where there is none."""
    durs = [float(e[2]) for e in trace.host_events
            if e[0] == name and lo <= float(e[1]) <= hi]
    return sum(durs) / len(durs) / 1e9 if durs else None


def per_step_part(ctx, part: str, reader_file: str) -> Optional[float]:
    """Device seconds a step of one part of the step, or None."""
    secs = part_seconds(ctx.trace, ctx.lo, ctx.hi, op_paths(reader_file))
    return secs[part] / ctx.steps if secs else None


def per_step_idle(ctx, name: str) -> Optional[float]:
    """Device idle seconds a step under the host span ``name``, or None."""
    idle = idle_under(ctx.trace, name, ctx.lo, ctx.hi)
    return None if idle is None else idle / ctx.steps
