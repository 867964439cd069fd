"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

Device planes are the planes named ``/device:...`` that carry an ``XLA
Ops`` line: one event per operation the chip ran, with its start and
duration on the profiler's clock. The host planes carry the benchmark's
own spans (``bench.*``, written with ``jax.profiler.TraceAnnotation``)
and JAX's, on the same clock. Everything is clipped to the window span.

- busy: the union of the device's op intervals (averaged over chips);
- kernel: the summed time of Pallas kernels, told from XLA's own ops by
  their custom-call events (``is_kernel``); op times are self times, a
  loop's op less the ops nested in it;
- idle gaps: the device's idle intervals, each put down to the host event
  that covers most of it; gaps under ``MIN_GAP_NS`` are summed apart.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MIN_GAP_NS = 50_000
SHORT_GAPS = f"gaps under {MIN_GAP_NS // 1000} us"


@dataclass
class TraceSummary:
    window_ns: float
    busy_ns: float                 # union of op intervals, mean over chips
    kernel_ns: float               # Pallas kernel time, mean over chips
    xla_ns: float                  # other ops' time, mean over chips
    n_devices: int
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stats(event) -> Dict[str, object]:
    try:
        return dict(event.stats)
    except Exception:  # noqa: BLE001 - a stat the reader cannot decode
        return {}


def is_kernel(name: str, stats: Dict[str, object]) -> bool:
    """True for a Pallas kernel: XLA lowers it to a custom call, which the
    trace names as such in the op's category or its HLO text."""
    category = stats.get("hlo_category")
    if isinstance(category, str) and category:
        return "custom" in category.lower()
    text = " ".join(str(v) for v in (name, stats.get("long_name", "")))
    return "custom-call" in text or "custom_call" in text


def op_name(name: str) -> str:
    """The op's HLO name: the events carry the instruction's whole text
    (``%while.13 = (s32[] ...) while(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(events) -> List[float]:
    """Each event's time less that of the events nested in it on the same
    line (a loop's op holds its body's ops), so that no time counts twice.
    ``events`` are ``(start, end, ...)`` tuples."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [e[1] - e[0] for e in events]
    stack: List[int] = []
    for i in order:
        s, e = events[i][0], events[i][1]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _host_events(planes):
    out = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name))
    return out


def window_of(host_events, span: str = WINDOW_SPAN) -> Tuple[float, float]:
    for s, e, name in host_events:
        if name == span:
            return s, e
    raise ValueError(f"the trace holds no {span!r} span")


def _label_gaps(gaps, host_events, window_lo):
    """Sum the idle gaps by the host event that covers most of each."""
    spans = sorted((s, e, n) for s, e, n in host_events
                   if n != WINDOW_SPAN and e > window_lo)
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)
    by_label: Dict[str, float] = defaultdict(float)
    for gs, ge in gaps:
        if ge - gs < MIN_GAP_NS:
            by_label[SHORT_GAPS] += ge - gs
            continue
        best, best_key = "host idle (no span)", None
        lo = bisect.bisect_left(starts, gs - longest)
        hi = bisect.bisect_right(starts, ge)
        for s, e, n in spans[lo:hi]:
            cover = min(e, ge) - max(s, gs)
            if cover <= 0:
                continue
            key = (cover, -(e - s))
            if best_key is None or key > best_key:
                best, best_key = n, key
        by_label[best] += ge - gs
    return by_label


def summarize(path: str, span: str = WINDOW_SPAN,
              top: int = 10) -> TraceSummary:
    """Busy, kernel and op time and the labelled idle gaps of the device
    planes in the window span of the trace at ``path``."""
    from jax.profiler import ProfileData  # noqa: PLC0415 - heavy import

    data = ProfileData.from_file(path)
    planes = list(data.planes)
    host = _host_events(planes)
    lo, hi = window_of(host, span)
    window = hi - lo
    busy_total = kernel_total = xla_total = 0.0
    op_time: Dict[str, float] = defaultdict(float)
    gap_time: Dict[str, float] = defaultdict(float)
    n_dev = 0
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        ops = [ln for ln in plane.lines if ln.name == OPS_LINE]
        if not ops:
            continue
        n_dev += 1
        events = []
        for ev in ops[0].events:
            c = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
            if c is not None:
                events.append((c[0], c[1], ev.name,
                               is_kernel(ev.name, _stats(ev))))
        for (s, e, name, kernel), own in zip(events, self_times(events)):
            op_time[op_name(name)] += own
            if kernel:
                kernel_total += own
            else:
                xla_total += own
        merged = _union([(s, e) for s, e, _, _ in events])
        busy_total += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for label, t in _label_gaps(gaps, host, lo).items():
            gap_time[label] += t
    if not n_dev:
        raise ValueError(f"no device plane with an {OPS_LINE!r} line in "
                         f"{path}")

    def ranked(d):
        return [[k, v / n_dev / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return TraceSummary(window_ns=window, busy_ns=busy_total / n_dev,
                        kernel_ns=kernel_total / n_dev,
                        xla_ns=xla_total / n_dev, n_devices=n_dev,
                        top_ops=ranked(op_time), idle_gaps=ranked(gap_time))


def describe(path: str, limit: int = 12) -> str:
    """A plain listing of the trace's planes, lines and first events, to
    read a trace by hand before writing code against it."""
    from jax.profiler import ProfileData  # noqa: PLC0415

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for ev in evs[:limit]:
                out.append(f"    {ev.name!r} start={ev.start_ns} "
                           f"dur={ev.duration_ns} stats={_stats(ev)}")
    return "\n".join(out)
