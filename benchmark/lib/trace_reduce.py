"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time, the operations that took most of it, named
programs' device durations, cross-chip copies, and the idle gaps by what
the host was doing. Read with nothing but JAX
(``jax.profiler.ProfileData``).

What the planes look like on a TPU v5e host (looked at by hand, PR 22;
PERF.md section 3 has the account): one plane ``/device:TPU:<i>`` a
chip, whose line ``XLA Ops`` holds one event per executed HLO operation
and whose line ``XLA Modules`` one per executed program, named
``jit_<fn>(<fingerprint>)``; the host is ``/host:CPU`` with one line a
thread, where the benchmark's ``TraceAnnotation`` spans appear under
their own names (``bench.*``). All planes share one clock.

    python benchmark/lib/trace_reduce.py <file.xplane.pb>   # dump by hand
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
UNATTRIBUTED = "host: outside the benchmark's spans (fabric or idle)"

Interval = Tuple[float, float]


class Trace:
    """Plain lists read from the file: {device index: {line name:
    [(name, start_ns, dur_ns)]}} and the host's benchmark spans."""

    def __init__(self, devices: Dict[int, Dict[str, list]],
                 host_spans: List[Tuple[str, float, float]]):
        self.devices = devices
        self.host_spans = host_spans

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        devices: Dict[int, Dict[str, list]] = {}
        host_spans = []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                lines = devices.setdefault(int(m.group(1)), {})
                for line in plane.lines:
                    if line.name in (OPS_LINE, MODULES_LINE):
                        lines[line.name] = [
                            (e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events]
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            host_spans.append((e.name, float(e.start_ns),
                                               float(e.duration_ns)))
        return cls(devices, host_spans)

    # --------------------------------------------------------- reductions
    def window(self) -> Optional[Interval]:
        """From the first to the last thing recorded on a device or in a
        benchmark span: the traced window."""
        starts, ends = [], []
        for lines in self.devices.values():
            for events in lines.values():
                for _n, s, d in events:
                    starts.append(s)
                    ends.append(s + d)
        for _n, s, d in self.host_spans:
            starts.append(s)
            ends.append(s + d)
        if not starts:
            return None
        return min(starts), max(ends)

    def busy_intervals(self, device: int) -> List[Interval]:
        """Union of the intervals in which an operation ran there."""
        events = self.devices.get(device, {}).get(OPS_LINE, [])
        return union([(s, s + d) for _n, s, d in events if d > 0])

    def busy_s(self, devices: List[int]) -> float:
        """Seconds an operation ran, averaged over ``devices``."""
        if not devices:
            return 0.0
        total = sum(sum(e - s for s, e in self.busy_intervals(i))
                    for i in devices)
        return total / len(devices) / 1e9

    def top_ops(self, devices: List[int], limit: int = 10) -> list:
        """[[operation, seconds]] summed over ``devices``, longest first."""
        sums: Dict[str, float] = {}
        for i in devices:
            for name, _s, d in self.devices.get(i, {}).get(OPS_LINE, []):
                name = short_op(name)
                sums[name] = sums.get(name, 0.0) + d
        top = sorted(sums.items(), key=lambda kv: -kv[1])[:limit]
        return [[name, ns / 1e9] for name, ns in top]

    def program_durations_us(self, program: str,
                             devices: List[int]) -> List[float]:
        """Device durations of every run of the jitted program ``jit_
        <program>`` (its ``XLA Modules`` events)."""
        want = re.compile(rf"^jit_{re.escape(program)}(\(|$)")
        return [d / 1e3 for i in devices
                for name, _s, d in self.devices.get(i, {}).get(
                    MODULES_LINE, []) if want.match(name)]

    def idle_gaps(self, devices: List[int], limit: int = 10) -> list:
        """[[what the host was doing, idle seconds]]: every idle gap of
        every device in ``devices`` inside the traced window, shared out
        over the benchmark's host spans that overlap it (innermost span
        wins where they nest); the rest is the host outside the
        benchmark's spans. Longest first."""
        win = self.window()
        if win is None:
            return []
        cover = _innermost_cover(self.host_spans)
        sums: Dict[str, float] = {}
        for i in devices:
            edge = win[0]
            for s, e in self.busy_intervals(i) + [(win[1], win[1])]:
                if s > edge:
                    _share_gap(edge, s, cover, sums)
                edge = max(edge, e)
        top = sorted(sums.items(), key=lambda kv: -kv[1])[:limit]
        return [[name, ns / 1e9 / max(1, len(devices))] for name, ns in top]


_OP_HEAD = re.compile(r"^(%?[\w.\-]+) = (\(?\w+\[[^\]]*\])")


def short_op(name: str) -> str:
    """An ``XLA Ops`` event carries the whole HLO line; keep the
    operation's name and its result's type and shape."""
    m = _OP_HEAD.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:120]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _innermost_cover(spans) -> List[Tuple[float, float, str]]:
    """Flatten possibly nested or concurrent spans into disjoint
    (start, end, name) pieces; where spans overlap, the one that started
    last names the piece."""
    points = sorted({p for _n, s, d in spans for p in (s, s + d)})
    if not points:
        return []
    by_start = sorted(spans, key=lambda sp: sp[1])
    out = []
    active: list = []
    idx = 0
    for a, b in zip(points, points[1:]):
        while idx < len(by_start) and by_start[idx][1] <= a:
            active.append(by_start[idx])
            idx += 1
        active = [sp for sp in active if sp[1] + sp[2] > a]
        if active:
            out.append((a, b, active[-1][0]))
    return out


def _share_gap(g0: float, g1: float, cover, sums: Dict[str, float]) -> None:
    import bisect

    left = g1 - g0
    # cover is sorted by start and disjoint
    lo = bisect.bisect_left(cover, (g0,)) - 1
    for a, b, name in cover[max(lo, 0):]:
        if a >= g1:
            break
        ov = min(b, g1) - max(a, g0)
        if ov > 0:
            sums[name] = sums.get(name, 0.0) + ov
            left -= ov
    if left > 0:
        sums[UNATTRIBUTED] = sums.get(UNATTRIBUTED, 0.0) + left


def dump(path: str, top: int = 12) -> None:
    """Planes, lines, event counts and the longest names: for looking at
    a trace by hand before trusting the reduction."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            sums: Dict[str, list] = {}
            n, t0, t1 = 0, None, None
            for e in line.events:
                n += 1
                row = sums.setdefault(e.name, [0, 0.0])
                row[0] += 1
                row[1] += e.duration_ns
                t0 = e.start_ns if t0 is None else min(t0, e.start_ns)
                t1 = max(t1 or 0, e.start_ns + e.duration_ns)
            print(f"  LINE {line.name!r}: {n} events, span "
                  f"{t0}..{t1} ns")
            for name, (cnt, ns) in sorted(sums.items(),
                                          key=lambda kv: -kv[1][1])[:top]:
                print(f"      {ns / 1e3:12.1f} us  x{cnt:<6} {name[:100]}")


if __name__ == "__main__":
    import sys
    dump(sys.argv[1])
