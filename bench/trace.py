"""Reduction of a profiler trace to device busy time, kernel time and the
breakdown that goes into the result line.

A trace is reduced through :class:`Trace`: the operations each device ran
(``device_ops``, one list per device, from the ``XLA Ops`` line of each
``/device:`` plane) and the host's events (``host_events``, every line of
the ``/host:CPU`` plane, where the benchmark's own spans are). Events are
``(name, start_ns, duration_ns)`` on the profiler's common clock; a device
operation may carry a fourth field, the text its kernel is recognised by
(its name and the start of each of its text statistics, such as the HLO
op and its module). Tests build a ``Trace`` by hand; :func:`from_xplane`
reads one that ``jax.profiler`` wrote.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict

Event = tuple  # (name, start_ns, duration_ns[, text])
STAT_CHARS = 300  # of each text statistic kept for recognising kernels

WINDOW_SPAN = "bench.window"
# the benchmark's host spans that label an idle gap, most specific first
GAP_LABELS = ("bench.batch_wait", "bench.step_dispatch",
              "bench.update_dispatch", "bench.step")


@dataclasses.dataclass
class Trace:
    device_ops: list    # one list of Events per device
    host_events: list   # Events of every host thread

    def window(self) -> tuple[float, float]:
        """(start, end) of the benchmark's window span, in ns."""
        spans = [e for e in self.host_events if e[0] == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
        s = max(spans, key=lambda e: e[2])
        return float(s[1]), float(s[1] + s[2])


def from_xplane(log_dir: str) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    device_ops, host = [], []
    for plane in pd.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:"):
            ops = [ln for ln in lines if ln.name == "XLA Ops"]
            if ops:
                device_ops.append([(e.name, e.start_ns, e.duration_ns,
                                    _text(e)) for ln in ops
                                   for e in ln.events])
        elif plane.name.startswith("/host:CPU"):
            host.extend((e.name, e.start_ns, e.duration_ns)
                        for ln in lines for e in ln.events)
    return Trace(device_ops=device_ops, host_events=host)


def _text(e) -> str:
    stats = [str(v)[:STAT_CHARS] for _, v in e.stats if isinstance(v, str)]
    return " ".join([e.name] + stats).lower()


def _clipped(events, lo, hi):
    for name, start, dur, *_ in events:
        a, b = max(float(start), lo), min(float(start) + float(dur), hi)
        if b > a:
            yield name, a, b


def busy_intervals(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of the operations' intervals inside [lo, hi], merged, sorted."""
    merged = []
    for _, a, b in sorted(_clipped(ops, lo, hi), key=lambda t: t[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    if not trace.device_ops:
        return 0.0
    per_dev = [sum(b - a for a, b in busy_intervals(ops, lo, hi))
               for ops in trace.device_ops]
    return sum(per_dev) / len(per_dev) / 1e9


def op_seconds(trace: Trace, lo: float, hi: float, match) -> float:
    """Summed device time of the operations whose text (or, lacking one,
    lower-case name) ``match`` accepts, averaged over the devices."""
    if not trace.device_ops:
        return 0.0
    tot = 0.0
    for ops in trace.device_ops:
        hits = [e for e in ops if match(e[3] if len(e) > 3 else e[0].lower())]
        tot += sum(b - a for _, a, b in _clipped(hits, lo, hi))
    return tot / len(trace.device_ops) / 1e9


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10) -> list:
    """The ``k`` operation names that took most device time, with seconds
    (averaged over the devices)."""
    by_name = defaultdict(float)
    for ops in trace.device_ops:
        for name, a, b in _clipped(ops, lo, hi):
            by_name[name] += (b - a) / 1e9
    n = max(len(trace.device_ops), 1)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:k]
    return [[name, s / n] for name, s in top]


def idle_gaps(trace: Trace, lo: float, hi: float, k: int = 10) -> list:
    """Device idle time inside [lo, hi] by what the host was doing: each gap
    of the first device is labelled with the most specific benchmark span
    among ``GAP_LABELS`` that covers over half of it, else with the one that
    covers most of it (``"other"`` where none does); returns the ``k``
    labels with the most idle seconds. Spans of one label do not nest."""
    if not trace.device_ops:
        return []
    busy = busy_intervals(trace.device_ops[0], lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = {}
    for lab in GAP_LABELS:
        ev = sorted((float(e[1]), float(e[1]) + float(e[2]))
                    for e in trace.host_events if e[0] == lab)
        spans[lab] = ([s for s, _ in ev], ev)

    def cover(lab, a, b):
        starts, ev = spans[lab]
        tot, j = 0.0, bisect.bisect_left(starts, b) - 1
        while j >= 0 and ev[j][1] > a:
            tot += min(ev[j][1], b) - max(ev[j][0], a)
            j -= 1
        return tot

    by_label = defaultdict(float)
    for a, b in gaps:
        cov = {lab: cover(lab, a, b) for lab in GAP_LABELS}
        half = [lab for lab in GAP_LABELS if cov[lab] > 0.5 * (b - a)]
        best = max(GAP_LABELS, key=lambda lab: cov[lab])
        label = half[0] if half else (best if cov[best] > 0 else "other")
        by_label[label] += (b - a) / 1e9
    top = sorted(by_label.items(), key=lambda kv: -kv[1])[:k]
    return [[name, sec] for name, sec in top]
