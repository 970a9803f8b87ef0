"""The serving program's own spans in a profiler trace, and what they
say about the device's idle time.

The serving engine (``src/repro/serve/``) marks its dispatch path with
``jax.profiler.TraceAnnotation`` spans whose names start with
``serve.``; the profiler writes them into its host plane. This module
collects those that lie inside the benchmark's window (the
``bench.window`` annotation), with their nesting depth and arguments,
and reduces them per dispatch. It splits a device's idle time by the
innermost program span open over each stretch of it, and names each
idle gap by the innermost span over the gap's midpoint.

The device planes of a TPU trace are not on the host plane's clock to
the millisecond: on a v5e their events come out about 2 ms early (a
program's run ends before the host's ``serve.wait`` for it begins). So
the device's times are first moved by ``device_offset_ns``, taken from
the ends of the host's waits against the ends of the device's runs.

    python3 bench/spans.py <window.xplane.pb> [--device 0]

prints one JSON object: the window, the device's busy time, each span's
count and seconds, the host milliseconds per dispatch and the device's
idle time split by span. ``bench/record_trace.py`` writes such a file.
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import pathlib
import statistics
import sys
from typing import Optional

PREFIX = "serve."
DISPATCH = "serve.dispatch"
WAIT = "serve.wait"
D2H = "serve.d2h"
NO_SPAN = "no program span"


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    depth: int               # serve.* spans open around it on its thread
    args: dict
    parent: Optional[int]    # index of the enclosing serve.* span

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@dataclasses.dataclass
class Spans:
    window: tuple            # (start, end) of bench.window, trace ns
    items: list              # Span, by start time

    def of(self, name: str) -> list:
        return [s for s in self.items if s.name == name]

    def count(self, name: str) -> int:
        return len(self.of(name))

    def seconds(self, name: str) -> Optional[float]:
        """Seconds under spans called ``name``; None when there are none."""
        hits = self.of(name)
        return sum(s.seconds for s in hits) if hits else None

    def host_seconds_per_dispatch(self) -> Optional[float]:
        """Mean over dispatches of the dispatch's time less the time its
        host spent waiting for the device (its ``serve.wait``)."""
        dispatches = [i for i, s in enumerate(self.items)
                      if s.name == DISPATCH]
        if not dispatches:
            return None
        waited = dict.fromkeys(dispatches, 0.0)
        for s in self.items:
            if s.name == WAIT and s.parent in waited:
                waited[s.parent] += s.seconds
        return sum(self.items[i].seconds - waited[i]
                   for i in dispatches) / len(dispatches)

    def seconds_per_dispatch(self, name: str) -> Optional[float]:
        n = self.count(DISPATCH)
        took = self.seconds(name)
        if not n or took is None:
            return None
        return took / n

    def timeline(self) -> list:
        """``(start, end, span)`` pieces that tile the window, each with
        the innermost span open over it (None where none is). Spans are
        taken to nest, as one thread's do."""
        w0, w1 = self.window
        out: list = []
        stack: list = []
        t = w0

        def close(until):
            nonlocal t
            while stack and stack[-1].end_ns <= until:
                top = stack.pop()
                out.append((t, top.end_ns, top))
                t = top.end_ns

        for s in self.items:
            close(s.start_ns)
            out.append((t, s.start_ns, stack[-1] if stack else None))
            t = s.start_ns
            stack.append(s)
        close(w1)
        out.append((t, w1, None))
        return [p for p in out if p[1] > p[0]]

    def innermost(self, points: list) -> list:
        """For each time in ``points`` (trace ns), the innermost span
        open over it, or None."""
        pieces = self.timeline()
        starts = [a for a, _, _ in pieces]
        out = []
        for t in points:
            k = bisect.bisect_right(starts, t) - 1
            out.append(pieces[k][2] if k >= 0 and t < pieces[k][1] else None)
        return out

    def overlap(self, intervals: list) -> dict:
        """Seconds of ``intervals`` (ascending, disjoint, inside the
        window) under each innermost span name, ``NO_SPAN`` where no
        span is open."""
        pieces = self.timeline()
        out: dict = {}
        k = 0
        for a, b in intervals:
            while k < len(pieces) and pieces[k][1] <= a:
                k += 1
            j = k
            while j < len(pieces) and pieces[j][0] < b:
                s, e, sp = pieces[j]
                name = sp.name if sp else NO_SPAN
                out[name] = out.get(name, 0.0) + (min(b, e) - max(a, s)) * 1e-9
                j += 1
        return out


def _window(data, name: str) -> tuple:
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    return ev.start_ns, ev.start_ns + ev.duration_ns
    raise ValueError(f"no {name!r} span in the trace")


def collect(data) -> Spans:
    """The ``serve.*`` spans of a ``ProfileData`` that lie inside the
    window. Spans on one thread nest; ``depth`` and ``parent`` follow
    that nesting."""
    from bench.trace import WINDOW_SPAN

    w0, w1 = _window(data, WINDOW_SPAN)
    items: list = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = sorted(
                ((ev.start_ns, -ev.duration_ns, ev) for ev in line.events
                 if ev.name.startswith(PREFIX)
                 and w0 <= ev.start_ns
                 and ev.start_ns + ev.duration_ns <= w1),
                key=lambda e: e[:2])
            open_: list = []       # (end, index) of the enclosing spans
            for start, neg_dur, ev in events:
                end = start - neg_dur
                while open_ and open_[-1][0] <= start:
                    open_.pop()
                items.append(Span(
                    name=ev.name, start_ns=start, end_ns=end,
                    depth=len(open_), args={k: v for k, v in ev.stats},
                    parent=open_[-1][1] if open_ else None))
                open_.append((end, len(items) - 1))
    # one thread's spans are contiguous and by start; keep that order
    # across threads too, with parents re-pointed
    order = sorted(range(len(items)), key=lambda i: items[i].start_ns)
    where = {old: new for new, old in enumerate(order)}
    spans = [items[i] for i in order]
    for s in spans:
        if s.parent is not None:
            s.parent = where[s.parent]
    return Spans(window=(w0, w1), items=spans)


def _device_plane(data, device: int):
    plane = data.find_plane_with_name(f"/device:TPU:{device}")
    if plane is None:
        raise ValueError(f"no /device:TPU:{device} plane in the trace")
    return plane


def device_offset_ns(data, spans: Spans, device: int = 0) -> Optional[float]:
    """What to add to the device's times to put them on the host
    plane's clock (see ``offset_from``). None without waits or runs."""
    runs: dict = {}
    for line in _device_plane(data, device).lines:
        if line.name == "XLA Modules":
            for ev in line.events:
                runs.setdefault(ev.name, []).append(
                    ev.start_ns + ev.duration_ns)
    return offset_from([s.end_ns for s in spans.of(WAIT)], runs)


def offset_from(waits: list, runs: dict) -> Optional[float]:
    """The median, over dispatches, of the end of the host's
    ``serve.wait`` less the end of the device's program run it waited
    for. ``runs`` maps each program's name to its runs' ends: a mesh
    also runs programs that move the input, so the serving program is
    the one whose ends keep the steadiest distance to the waits'. The
    profiler stops right after the window, so the window's last
    dispatch is the program's last run: runs and waits are paired from
    the end."""
    best = None
    for ends in runs.values():
        k = min(len(waits), len(ends))
        if k < 2:
            continue
        diffs = sorted(w - r for w, r in zip(waits[-k:], sorted(ends)[-k:]))
        spread = diffs[(3 * k) // 4] - diffs[k // 4]
        if best is None or spread < best[0]:
            best = (spread, statistics.median(diffs))
    return None if best is None else best[1]


def device_gaps(data, window: tuple, device: int = 0,
                offset_ns: float = 0.0) -> list:
    """The idle gaps ``(start, end)`` of ``/device:TPU:<device>`` inside
    the window, its times moved by ``offset_ns``: where no op of its
    ``XLA Ops`` line ran."""
    from bench.trace import _union

    w0, w1 = window
    busy = [(max(ev.start_ns + offset_ns, w0),
             min(ev.start_ns + ev.duration_ns + offset_ns, w1))
            for line in _device_plane(data, device).lines
            if line.name == "XLA Ops" for ev in line.events]
    merged = _union([(s, e) for s, e in busy if e > s])
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    return [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]


def label_gaps(spans: Spans, gaps: list) -> list:
    """``(seconds, label)`` of each gap, longest first: the innermost
    program span over its midpoint, or ``NO_SPAN``."""
    over = spans.innermost([(s + e) / 2 for s, e in gaps])
    out = [((e - s) * 1e-9, sp.name if sp else NO_SPAN)
           for (s, e), sp in zip(gaps, over)]
    return sorted(out, key=lambda g: -g[0])


def summary(data, device: int = 0) -> dict:
    spans = collect(data)
    w0, w1 = spans.window
    offset = device_offset_ns(data, spans, device)
    gaps = device_gaps(data, spans.window, device, offset or 0.0)
    labelled = label_gaps(spans, gaps)
    idle = sum(s for s, _ in labelled)
    by_label = spans.overlap(gaps)
    names = sorted({s.name for s in spans.items})
    dispatches = spans.count(DISPATCH)

    def ms(x):
        return None if x is None else x * 1e3

    return {
        "window_s": (w1 - w0) * 1e-9,
        "device_offset_ms": None if offset is None else offset * 1e-6,
        "busy_s": (w1 - w0) * 1e-9 - idle,
        "idle_s": idle,
        "dispatches": dispatches,
        "spans": {n: {"count": spans.count(n), "seconds": spans.seconds(n),
                      "ms_per_dispatch": ms(spans.seconds_per_dispatch(n))}
                  for n in names},
        "host_ms_per_dispatch": ms(spans.host_seconds_per_dispatch()),
        "d2h_ms_per_dispatch": ms(spans.seconds_per_dispatch(D2H)),
        "idle_by_span_s": dict(sorted(by_label.items(),
                                      key=lambda kv: -kv[1])),
        "idle_share_under_spans": (
            1.0 - by_label.get(NO_SPAN, 0.0) / idle if idle else None),
        "idle_gaps": [[what, s] for s, what in labelled[:10]],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--device", type=int, default=0)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData

    data = ProfileData.from_file(args.xplane)
    print(json.dumps(summary(data, args.device), indent=1))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    sys.exit(main())
