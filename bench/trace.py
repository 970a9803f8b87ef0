"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

A run with ``--trace 1`` starts the profiler just before its measured
window and stops it just after. This module reads what the profiler
wrote and reduces it on the device's own clock:

- busy time: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
  clipped to the window and averaged over the chips the cell uses;
- per-operation device time, keyed by the operation's name, with the
  text of its stats kept so a reader can find a kernel by the name the
  program gave it;
- idle gaps between busy intervals, each attributed to what the host
  was doing then, from spans the benchmark recorded around its calls
  into the program.

The window is the span of the benchmark's ``bench.window`` annotation,
found in the host plane; host spans recorded on ``time.perf_counter``
are placed on the trace clock through it.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Optional

WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class OpTime:
    seconds: float = 0.0
    count: int = 0
    text: str = ""        # the op's name and custom-call target, for lookups


@dataclasses.dataclass
class TraceSummary:
    window_s: float                       # traced window, host clock
    busy_s: float                         # mean over chips of busy time
    chips: int                            # device planes reduced
    ops: dict                             # op name -> OpTime (all chips)
    gaps: list                            # (seconds, host activity), longest first

    def op_seconds(self, pattern: str) -> Optional[float]:
        """Device seconds of every op whose name or custom-call target match
        ``pattern`` (a regular expression), summed over chips; None when
        no op matches."""
        rx = re.compile(pattern)
        hits = [o.seconds for o in self.ops.values() if rx.search(o.text)]
        return sum(hits) if hits else None

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1].seconds)[:n]
        return {
            "device_ops": [[name, o.seconds] for name, o in top],
            "idle_gaps": [[what, s] for s, what in self.gaps[:n]],
        }


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {log_dir}, found {len(paths)}")
    return paths[0]


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def _event_text(key: str, ev) -> str:
    """What a name lookup searches: the instruction's name and, for a
    custom call, its target (``tpu_custom_call`` for a Pallas kernel) —
    not its operands, which name the ops that feed it."""
    parts = [key]
    m = _TARGET.search(ev.name)
    if m:
        parts.append(m.group(1))
    return " ".join(parts)


def reduce(path: str, *, devices: Optional[list] = None,
           host_spans: Optional[list] = None,
           window_perf: Optional[tuple] = None) -> TraceSummary:
    """Reduce the xplane file at ``path``.

    ``devices``: TPU ids to reduce (default: every TPU plane).
    ``host_spans``: ``(name, t0, t1)`` on ``time.perf_counter`` seconds;
    ``window_perf``: the window's ``(t0, t1)`` on the same clock, which
    ties those spans to the ``bench.window`` annotation.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    win = None
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    win = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if win is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    w0, w1 = win

    ops: dict = {}
    busy_per_chip = []
    merged_all: list = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m or (devices is not None and int(m.group(1)) not in devices):
            continue
        intervals = []
        for line in plane.lines:
            if line.name != _OPS_LINE:
                continue
            for ev in line.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e <= s:
                    continue
                intervals.append((s, e))
                # a TPU op's event name is its HLO instruction text;
                # keyed by the instruction's name, searched by the text
                key = ev.name.split(" = ")[0]
                o = ops.get(key)
                if o is None:
                    o = ops[key] = OpTime(text=_event_text(key, ev))
                o.seconds += (e - s) * 1e-9
                o.count += 1
        merged = _union(intervals)
        busy_per_chip.append(sum(e - s for s, e in merged) * 1e-9)
        merged_all.append(merged)
    if not busy_per_chip:
        raise ValueError(f"no TPU device plane with {_OPS_LINE!r} in {path}")

    # Idle gaps of the first chip, named by the host span over each.
    gaps = []
    spans = []
    if host_spans and window_perf:
        off = w0 - window_perf[0] * 1e9
        spans = sorted((t0 * 1e9 + off, t1 * 1e9 + off, name)
                       for name, t0, t1 in host_spans)
    starts = [a for a, _, _ in spans]
    merged = merged_all[0]
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e - s <= 0:
            continue
        mid = (s + e) / 2
        k = bisect.bisect_right(starts, mid) - 1
        what = spans[k][2] if k >= 0 and spans[k][1] >= mid else "no host span"
        gaps.append(((e - s) * 1e-9, what))
    gaps.sort(key=lambda g: -g[0])

    return TraceSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(busy_per_chip) / len(busy_per_chip),
        chips=len(busy_per_chip),
        ops=ops,
        gaps=gaps,
    )
