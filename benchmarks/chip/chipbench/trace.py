"""From a profiler trace to device busy time, span attribution and idle gaps.

The JAX profiler writes one ``.xplane.pb`` per traced window. In it the host
plane (``/host:CPU``) carries the benchmark's own spans
(``jax.profiler.TraceAnnotation``: ``job``, ``sample``, ``combine``) on the
line of the Python thread that ran them, and each chip's plane
(``/device:TPU:<n>``) carries one event per XLA operation that ran there,
on its ``XLA Ops`` line, on the same clock.

Busy time is the union of a chip's operation intervals; the idle share of a
stretch of time is the part of it no operation covers. Everything here works
on plain ``(name, start_ns, end_ns)`` tuples, so the reduction can be checked
on a recorded trace without a chip.
"""

from __future__ import annotations

import bisect
import gzip
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # (name, start_ns, end_ns)
Interval = Tuple[float, float]

SPAN_NAMES = ("job", "sample", "combine")
HOST_PLANE = "/host:CPU"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


class Trace(NamedTuple):
    spans: List[Event]  # the benchmark's spans, in start order
    host: List[Event]  # every event on the spans' thread (for gap labels)
    devices: List[List[Event]]  # per chip, its operations in start order


def find_xplane(log_dir: Path) -> Path:
    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``: the HLO
    instruction's name, without its text."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _device_index(plane_name: str) -> Optional[int]:
    tail = plane_name[len(DEVICE_PREFIX):]
    return int(tail) if tail.isdigit() else None


def load(path: Path, chips: int) -> Trace:
    """Read the spans, host events and the first ``chips`` chips' ops.

    ``path`` is an ``.xplane.pb`` file, or one compressed with gzip."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.suffix == ".gz":
        data = ProfileData.from_serialized_xspace(gzip.decompress(path.read_bytes()))
    else:
        data = ProfileData.from_file(str(path))
    spans: List[Event] = []
    host: List[Event] = []
    devices: Dict[int, List[Event]] = {}
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                events = [(ev.name, float(ev.start_ns), float(ev.end_ns))
                          for ev in line.events]
                mine = [e for e in events if e[0] in SPAN_NAMES]
                if mine:
                    spans.extend(mine)
                    host.extend(events)
        elif plane.name.startswith(DEVICE_PREFIX):
            idx = _device_index(plane.name)
            if idx is None:
                continue
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(
                        (op_name(ev.name), float(ev.start_ns), float(ev.end_ns))
                        for ev in line.events
                    )
            devices[idx] = sorted(ops, key=lambda e: e[1])
    chosen = [devices[i] for i in sorted(devices)[:chips]]
    return Trace(sorted(spans, key=lambda e: e[1]), sorted(host, key=lambda e: e[1]), chosen)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals into disjoint ones, in order."""
    merged: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the disjoint ``merged`` intervals cover."""
    total = 0.0
    start = max(0, bisect.bisect_left([b for _, b in merged], lo))
    for a, b in merged[start:]:
        if a >= hi:
            break
        total += max(0.0, min(b, hi) - max(a, lo))
    return total


def busy(trace: Trace) -> List[List[Interval]]:
    """Per chip, the union of its operation intervals."""
    return [union((s, e) for _, s, e in ops) for ops in trace.devices]


def window(trace: Trace) -> Optional[Interval]:
    """From the first job's start to the last job's end."""
    jobs = [(s, e) for name, s, e in trace.spans if name == "job"]
    if not jobs:
        return None
    return jobs[0][0], max(e for _, e in jobs)


def idle_share(trace: Trace, name: Optional[str] = None) -> Optional[float]:
    """Share of the window (``name=None``) or of the spans called ``name``
    in which no operation ran, averaged over the chips."""
    merged = busy(trace)
    if not merged or not any(merged):
        return None
    if name is None:
        win = window(trace)
        stretches = [win] if win else []
    else:
        stretches = [(s, e) for n, s, e in trace.spans if n == name]
    length = sum(e - s for s, e in stretches)
    if length <= 0:
        return None
    shares = [
        1.0 - sum(covered(m, s, e) for s, e in stretches) / length for m in merged
    ]
    return sum(shares) / len(shares)


def busy_seconds(trace: Trace) -> Optional[Tuple[float, float]]:
    """``(busy_s, window_s)``: device-busy seconds inside the window,
    averaged over the chips, and the window's length."""
    win = window(trace)
    merged = busy(trace)
    if win is None or not merged:
        return None
    busy_ns = sum(covered(m, *win) for m in merged) / len(merged)
    return busy_ns * 1e-9, (win[1] - win[0]) * 1e-9


def span_seconds(trace: Trace, name: str) -> List[float]:
    return [(e - s) * 1e-9 for n, s, e in trace.spans if n == name]


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The device operations that took most time inside the window,
    seconds per chip (summed over the chips, divided by their number)."""
    win = window(trace)
    if win is None or not trace.devices:
        return []
    total: Dict[str, float] = defaultdict(float)
    for ops in trace.devices:
        for name, s, e in ops:
            total[name] += max(0.0, min(e, win[1]) - max(s, win[0]))
    chips = len(trace.devices)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9 / chips] for name, ns in ranked if ns > 0]


def _label(trace: Trace, t: float) -> str:
    """The benchmark span and the innermost host event around instant ``t``."""
    span = "outside"
    for name, s, e in trace.spans:
        if s <= t <= e and name != "job":
            span = name
    inner = None
    for name, s, e in trace.host:
        if s > t:
            break
        if e >= t and name not in SPAN_NAMES:
            inner = name  # later starts nest inside earlier ones
    return f"{span}/{inner}" if inner else span


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """The longest idle gaps of the first chip inside the window, each
    labelled by what the host was doing at its midpoint."""
    win = window(trace)
    merged = busy(trace)
    if win is None or not merged:
        return []
    gaps = []
    cursor = win[0]
    for a, b in merged[0] + [(win[1], win[1])]:
        a, b = max(a, win[0]), min(b, win[1])
        if a > cursor:
            gaps.append((a - cursor, cursor, a))
        cursor = max(cursor, b)
    gaps.sort(reverse=True)
    return [[_label(trace, (s + e) / 2), length * 1e-9] for length, s, e in gaps[:n]]
