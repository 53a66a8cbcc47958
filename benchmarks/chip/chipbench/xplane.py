"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the benchmark's
device numbers: busy time, per-operation time, kernel time, and the idle
gaps with what the host was doing in each.

Device planes are named ``/device:TPU:<n>``; the operations the chip ran
are the events of their ``XLA Ops`` line.  Busy time is the union of those
intervals inside the traced window, which the harness marks with a host
annotation (``WINDOW_MARK``).  Host spans of the system under test, timed on
``time.perf_counter``, are moved onto the trace's clock through that mark.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import os

__all__ = [
    "Event", "Trace", "WINDOW_MARK", "OUTSIDE", "merge", "overlap_ns", "find_xplane", "label_gaps",
]

WINDOW_MARK = "chipbench_window"
#: Label of an idle gap in which no span of the system was open: the host
#: was in the harness or in untraced code of the system.
OUTSIDE = "outside spans"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns

    @property
    def op(self) -> str:
        """The HLO instruction's name without its ``%`` and ``.<n>`` suffix:
        a device event is named by its whole instruction text,
        ``%raycast_count_batch_kernel_call.1 = s32[...] custom-call(...)``."""
        head = self.name.split(" = ", 1)[0].lstrip("%")
        base, _, suffix = head.rpartition(".")
        return base if base and suffix.isdigit() else head


def find_xplane(log_dir: str) -> str:
    """The newest ``*.xplane.pb`` under a profiler log directory."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


class Trace:
    """Host events and each device's operations, on one clock (ns)."""

    def __init__(self, host: list[Event], devices: dict[str, list[Event]]):
        self.host = host
        self.devices = devices

    @classmethod
    def load(cls, path: str) -> "Trace":
        """Read an ``.xplane.pb`` file, or one compressed as ``.xplane.pb.gz``."""
        from jax.profiler import ProfileData

        if str(path).endswith(".gz"):
            with gzip.open(path, "rb") as f:
                data = ProfileData.from_serialized_xspace(f.read())
        else:
            data = ProfileData.from_file(str(path))
        host: list[Event] = []
        devices: dict[str, list[Event]] = {}
        for plane in data.planes:
            is_device = plane.name.startswith(DEVICE_PREFIX)
            is_host = plane.name.startswith("/host:CPU")
            if not (is_device or is_host):
                continue
            for line in plane.lines:
                if is_device and line.name != OPS_LINE:
                    continue
                events = [
                    Event(e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
                    for e in line.events
                ]
                if is_device:
                    devices.setdefault(plane.name, []).extend(events)
                else:
                    host.extend(events)
        return cls(host, devices)

    # ---- the window --------------------------------------------------------
    def window(self, mark: str = WINDOW_MARK) -> tuple[float, float]:
        """``(start_ns, end_ns)`` of the harness's window annotation."""
        found = [e for e in self.host if e.name == mark]
        if not found:
            raise ValueError(f"no host event {mark!r} in the trace")
        e = max(found, key=lambda e: e.dur_ns)
        return e.start_ns, e.end_ns

    # ---- device time -------------------------------------------------------
    def busy(self, device: str, lo: float, hi: float) -> list[tuple[float, float]]:
        """Merged intervals in which ``device`` ran an operation."""
        return _clip(merge((e.start_ns, e.end_ns) for e in self.devices[device]), lo, hi)

    def busy_s(self, lo: float, hi: float) -> float:
        """Busy seconds inside ``[lo, hi]``, averaged over the devices that
        ran anything in it."""
        per = [
            sum(e - s for s, e in self.busy(d, lo, hi)) for d in sorted(self.devices)
        ]
        per = [p for p in per if p > 0]
        return sum(per) / len(per) / 1e9 if per else 0.0

    def ops_in(self, lo: float, hi: float):
        for events in self.devices.values():
            for e in events:
                if e.end_ns > lo and e.start_ns < hi:
                    yield e

    def op_seconds(self, lo: float, hi: float) -> dict[str, float]:
        """Seconds per operation (:attr:`Event.op`) inside the window,
        summed over devices."""
        out: dict[str, float] = {}
        for e in self.ops_in(lo, hi):
            dur = min(e.end_ns, hi) - max(e.start_ns, lo)
            out[e.op] = out.get(e.op, 0.0) + dur / 1e9
        return out

    def kernel_seconds(self, op: str, lo: float, hi: float) -> tuple[float, int]:
        """``(seconds, events)`` of the operation ``op`` inside the window,
        summed over devices."""
        total, n = 0.0, 0
        for e in self.ops_in(lo, hi):
            if e.op == op:
                total += (min(e.end_ns, hi) - max(e.start_ns, lo)) / 1e9
                n += 1
        return total, n

    def busy_union(self, lo: float, hi: float) -> list[tuple[float, float]]:
        """Merged intervals in which any device ran an operation."""
        return merge(iv for d in self.devices for iv in self.busy(d, lo, hi))

    def idle_gaps(self, lo: float, hi: float) -> list[tuple[float, float]]:
        """Gaps inside the window in which no device ran an operation: the
        gaps of the union of every device's busy intervals."""
        edges = [lo] + [t for iv in self.busy_union(lo, hi) for t in iv] + [hi]
        return [
            (edges[i], edges[i + 1])
            for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]
        ]


def overlap_ns(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> float:
    """Total length of the intersection of two sorted, disjoint interval
    lists."""
    total = 0.0
    i = 0
    for s, e in a:
        while i < len(b) and b[i][1] <= s:
            i += 1
        j = i
        while j < len(b) and b[j][0] < e:
            total += min(e, b[j][1]) - max(s, b[j][0])
            j += 1
    return total


def label_gaps(
    gaps: list[tuple[float, float]], spans: list[tuple[str, float, float, int]]
) -> list[tuple[str, float]]:
    """``(label, seconds)`` per gap: the deepest host span (``(name, start_ns,
    end_ns, depth)``) open at the gap's midpoint, ``OUTSIDE`` where none is."""
    out = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        open_ = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        label = max(open_, key=lambda sp: sp[3])[0] if open_ else OUTSIDE
        out.append((label, (e - s) / 1e9))
    return out
