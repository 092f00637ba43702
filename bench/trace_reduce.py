"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

What it reads:

* device planes (``/device:TPU:<n>``), and on each the line of XLA operations
  (``XLA Ops``): every event there is an operation running on that chip;
* the benchmark's own host spans (``jax.profiler.TraceAnnotation`` named
  ``bench.call``), one around each timed call, on the host plane.

What it gives, per chip: busy seconds (the union of operation intervals
inside the traced window), seconds of collective-permute operations, and
the operations by total time; for the run: the window (first span's start
to last span's end), the number of spans, and the longest idle gaps on the
busiest chip, each named by the host event that overlaps it most.

Device and host clocks in a trace agree to about a millisecond, not
better (a TPU v5e trace shows a product's device module starting 0.9 ms
before its host span), so per-call device time is the window's busy time
over the spans, not busy time clipped to each span.
"""

from __future__ import annotations

import re

SPAN = "bench.call"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_PERMUTE = re.compile(r"collective-permute|ppermute", re.IGNORECASE)
_HLO = re.compile(r"^(%[\w.\-]+) = ([a-z0-9]+\[[0-9,]*\])")


def op_name(name: str) -> str:
    """An XLA op's trace name, which is its whole HLO line, cut to the
    instruction name and result shape (``%fusion.2 f32[1,5114,256,256]``)."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of half-open intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> float:
    """Total length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def reduce_planes(planes, span: str = SPAN, top: int = 10) -> dict | None:
    """Reduce ``planes`` (``jax.profiler.ProfileData(...).planes`` or any
    objects with ``name``, ``lines[].name`` and ``lines[].events[]`` having
    ``name``, ``start_ns``, ``duration_ns``).  None where the trace holds no
    span or no device operation."""
    spans, host = [], []
    devices = {}
    for plane in planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            ops = [(op_name(n), s, e) for line in plane.lines if line.name == _OPS_LINE
                   for n, s, e in _events(line)]
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in _events(line):
                    (spans if ev[0] == span else host).append(ev)
    devices = {d: ops for d, ops in devices.items() if ops}
    if not spans or not devices:
        return None
    w0 = min(s for _, s, _ in spans)
    w1 = max(e for _, _, e in spans)
    window = [(w0, w1)]
    chips = {}
    for d, ops in sorted(devices.items()):
        busy = merge([(max(s, w0), min(e, w1)) for _, s, e in ops])
        by_name: dict[str, float] = {}
        for name, s, e in ops:
            by_name[name] = by_name.get(name, 0.0) + max(0.0, min(e, w1) - max(s, w0))
        chips[d] = dict(
            busy_s=overlap(busy, window) * 1e-9,
            permute_s=sum(t for n, t in by_name.items() if _PERMUTE.search(n)) * 1e-9,
            ops={n: t * 1e-9 for n, t in by_name.items()},
            busy=busy,
        )
    busiest = max(chips, key=lambda d: chips[d]["busy_s"])
    return dict(window_s=(w1 - w0) * 1e-9, spans=len(spans), chips=chips,
                busiest=busiest,
                device_ops=_top_ops(chips, top),
                idle_gaps=_idle_gaps(chips[busiest]["busy"], (w0, w1), host, top))


def _top_ops(chips: dict, top: int) -> list[list]:
    tot: dict[str, float] = {}
    for c in chips.values():
        for n, t in c["ops"].items():
            tot[n] = tot.get(n, 0.0) + t / len(chips)
    return [[n, t] for n, t in sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def _idle_gaps(busy, window, host, top: int) -> list[list]:
    gaps, t = [], window[0]
    for s, e in busy + [(window[1], window[1])]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    out = []
    for g0, g1 in gaps:
        best, best_ov = "idle", 0.0
        for name, s, e in host:
            ov = min(e, g1) - max(s, g0)
            if ov > best_ov or (ov == best_ov and ov > 0 and name < best):
                best, best_ov = name, ov
        out.append([best, (g1 - g0) * 1e-9])
    return out


def reduce_file(path: str, span: str = SPAN, top: int = 10) -> dict | None:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, span=span, top=top)
