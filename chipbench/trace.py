"""Reduction of a profiler trace to what the per-layer metrics read.

``read_xplane`` turns the ``.xplane.pb`` file that ``jax.profiler`` writes
into device operations (the "XLA Ops" line of each device plane) and the
harness's own host spans (``chipbench.*`` trace annotations).  The rest are
plain functions of those lists, so a fixed event list tests them:

* ``busy``: the union of the device operations' intervals in a window;
* ``kernel``: the count and summed device time of the operations whose
  name or metadata matches a kernel's pattern;
* ``idle_gaps``: the gaps between busy intervals, each named by the
  harness span that covers most of it;
* ``top_ops``: device time by operation name.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

HOST_PREFIX = "chipbench."
NO_SPAN = "outside the harness spans (train_loop's own host work)"


class Event(NamedTuple):
    name: str
    start: float  # seconds on the trace's clock
    end: float
    text: str = ""  # name and metadata, for matching kernels
    plane: str = ""


class Span(NamedTuple):
    name: str
    start: float
    end: float


def _stats_text(ev) -> str:
    parts = []
    for item in getattr(ev, "stats", ()) or ():
        try:
            key, val = item
        except (TypeError, ValueError):
            key, val = "", item
        parts.append(f"{key}={val}")
    return " ".join(parts)


def find_xplane(root: str) -> str:
    files = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return max(files, key=os.path.getmtime)


def read_xplane(path: str) -> Tuple[List[Event], List[Span]]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    events: List[Event] = []
    spans: List[Span] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    start = ev.start_ns * 1e-9
                    events.append(Event(
                        ev.name, start, start + ev.duration_ns * 1e-9,
                        f"{ev.name} {_stats_text(ev)}", plane.name,
                    ))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        start = ev.start_ns * 1e-9
                        spans.append(Span(ev.name, start,
                                          start + ev.duration_ns * 1e-9))
    return events, spans


def window(spans: Iterable[Span], name: str) -> Tuple[float, float]:
    """(start, end) of the one host span called ``name``."""
    found = [s for s in spans if s.name == name]
    if len(found) != 1:
        raise ValueError(f"{len(found)} host spans named {name!r}")
    return found[0].start, found[0].end


def _clipped(events: Iterable[Event], t0: float, t1: float):
    for e in events:
        a, b = max(e.start, t0), min(e.end, t1)
        if b > a:
            yield a, b


def intervals(events: Iterable[Event], t0: float, t1: float
              ) -> List[Tuple[float, float]]:
    """Union of the events' intervals inside [t0, t1], sorted, disjoint."""
    merged: List[List[float]] = []
    for a, b in sorted(_clipped(events, t0, t1)):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy(events: Iterable[Event], t0: float, t1: float) -> float:
    """Seconds of [t0, t1] in which some device operation ran, averaged
    over the device planes present."""
    by_plane: Dict[str, List[Event]] = defaultdict(list)
    for e in events:
        by_plane[e.plane].append(e)
    if not by_plane:
        return 0.0
    return sum(sum(b - a for a, b in intervals(evs, t0, t1))
               for evs in by_plane.values()) / len(by_plane)


def kernel(events: Iterable[Event], pattern: str, t0: float, t1: float
           ) -> Tuple[int, float]:
    """(count, summed seconds) of the operations inside [t0, t1] whose
    name or metadata matches ``pattern``."""
    rx = re.compile(pattern)
    n, total = 0, 0.0
    for e in events:
        if e.start >= t0 and e.end <= t1 and rx.search(e.text or e.name):
            n += 1
            total += e.end - e.start
    return n, total


def idle_gaps(events: Iterable[Event], spans: Iterable[Span], t0: float,
              t1: float) -> List[Tuple[str, float]]:
    """Every gap between busy intervals in [t0, t1] as (name, seconds),
    longest first.  A gap is named by the harness span (other than the
    window's own) that overlaps it most; of two that overlap it alike the
    shorter, the inner one, names it."""
    evs = list(events)
    planes = {e.plane for e in evs}
    if len(planes) > 1:  # gaps of the first device
        first = sorted(planes)[0]
        evs = [e for e in evs if e.plane == first]
    busy_iv = intervals(evs, t0, t1)
    gaps, cur = [], t0
    for a, b in busy_iv:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        gaps.append((cur, t1))
    inner = [s for s in spans if not s.name.startswith(HOST_PREFIX + "window")]
    out = []
    for a, b in gaps:
        best: Optional[Tuple[float, float, str]] = None
        for s in inner:
            ov = min(b, s.end) - max(a, s.start)
            if ov <= 0:
                continue
            cand = (ov, -(s.end - s.start), s.name)
            if best is None or cand > best:
                best = cand
        out.append((best[2] if best else NO_SPAN, b - a))
    out.sort(key=lambda x: -x[1])
    return out


def short_name(name: str, width: int = 160) -> str:
    """A device operation's name as the trace gives it is its whole HLO
    instruction; keep its head."""
    return name if len(name) <= width else name[:width - 3] + "..."


def top_ops(events: Iterable[Event], t0: float, t1: float, n: int = 10
            ) -> List[Tuple[str, float]]:
    """Device seconds by operation name inside [t0, t1], largest first."""
    total: Dict[str, float] = defaultdict(float)
    for e in events:
        if e.start >= t0 and e.end <= t1:
            total[e.name] += e.end - e.start
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [(short_name(k), v) for k, v in top]


def roofline_share(calls: int, least_s_per_call: float, seconds: float
                   ) -> Optional[float]:
    """Percent of the roofline: the least time the chip could take for the
    calls' work over the time they took.  None when nothing ran."""
    if calls <= 0 or seconds <= 0:
        return None
    return 100.0 * calls * least_s_per_call / seconds


def least_seconds(flops: float, bytes_: float, peaks: Dict[str, float]
                  ) -> Tuple[float, str]:
    """The larger of FLOPs over peak and bytes over bandwidth, and which
    bound it is."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
