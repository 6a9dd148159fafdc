"""Reduce a ``jax.profiler`` trace to device busy time, idle gaps and
per-kernel time.

Two stages, so that the reduction can be checked on a stored trace:

  1. ``load_xplane`` reads the ``.xplane.pb`` the profiler writes into
     plain records: the device planes' operation events, and the host's
     spans (the benchmark's own ``bench.*`` annotations and the runtime's
     host events), all on the profiler's one clock in nanoseconds;
  2. ``reduce`` turns those records into the window, the union of device
     busy intervals inside it, the idle gaps labelled by what the host was
     doing, and the summed device time of each operation name.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from pathlib import Path

from harness import hlo

DEVICE_PREFIX = "/device:"
# the line of a device plane whose events are single operations
OPS_LINES = ("XLA Ops",)
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: float           # ns on the profiler's clock
    dur: float             # ns
    plane: str = ""

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Records:
    device: list           # Event: operations on a device
    host: list             # Event: host spans (annotations, runtime)

    def to_json(self) -> dict:
        return {"device": [dataclasses.asdict(e) for e in self.device],
                "host": [dataclasses.asdict(e) for e in self.host]}

    @classmethod
    def from_json(cls, d: dict) -> "Records":
        return cls([Event(**e) for e in d["device"]],
                   [Event(**e) for e in d["host"]])


def find_xplane(log_dir: str | os.PathLike) -> Path:
    found = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(found[-1])


def load_xplane(path: str | os.PathLike) -> Records:
    """Plain records of one profiler trace file. Device operations come
    from each device plane's ``XLA Ops`` line; host spans from every line
    of the host planes."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    device, host = [], []
    names: dict[str, str] = {}       # one copy of each long HLO name
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name not in OPS_LINES:
                    continue
                for e in line.events:
                    name = names.setdefault(e.name, e.name)
                    device.append(Event(name, float(e.start_ns),
                                        float(e.duration_ns), plane.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                where = f"{plane.name}|{line.name}"
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append(Event(e.name, float(e.start_ns),
                                          float(e.duration_ns), where))
    return Records(device, host)


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Reduction:
    window: tuple[float, float]      # ns
    busy: list                       # per device plane: disjoint intervals
    gaps: list                       # (start, end, host label), ns
    op_ns: dict                      # op name -> summed device ns
    devices: int
    self_ns: dict = dataclasses.field(default_factory=dict)
    #                                  op name -> device ns not covered by
    #                                  the operations nested inside it

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran operations."""
        tot = sum(e - s for iv in self.busy for s, e in iv)
        return tot * 1e-9 / max(self.devices, 1)

    def breakdown(self, n: int = 10) -> dict:
        """The operations with the most self time (a loop's time less the
        operations inside it), labelled by name, output shape and kind,
        and the idle time by what the host was doing."""
        by_op: dict[str, float] = {}
        for name, ns in self.self_ns.items():
            key = hlo.short(name)
            by_op[key] = by_op.get(key, 0.0) + ns
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:n]
        by_label: dict[str, float] = {}
        for s, e, label in self.gaps:
            by_label[label] = by_label.get(label, 0.0) + (e - s)
        gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v * 1e-9] for k, v in ops],
                "idle_gaps": [[k, v * 1e-9] for k, v in gaps]}


def label_gaps(host: list, times: list) -> list[str]:
    """For each time, the name of the innermost host span open then: a
    stack sweep over each thread's nested spans, and the shortest of the
    threads' innermost spans. ``bench.window`` itself is no label."""
    order = sorted(range(len(times)), key=times.__getitem__)
    best: list = [None] * len(times)
    threads: dict[str, list] = {}
    for e in host:
        if e.name != WINDOW_SPAN:
            threads.setdefault(e.plane, []).append(e)
    for evs in threads.values():
        evs.sort(key=lambda e: (e.start, -e.dur))
        stack: list = []
        i = 0
        for k in order:
            t = times[k]
            while i < len(evs) and evs[i].start <= t:
                while stack and stack[-1].end <= evs[i].start:
                    stack.pop()
                stack.append(evs[i])
                i += 1
            while stack and stack[-1].end <= t:
                stack.pop()
            if stack and (best[k] is None or stack[-1].dur < best[k].dur):
                best[k] = stack[-1]
    return [b.name if b is not None else "no host span" for b in best]


def reduce(rec: Records, window: tuple[float, float] | None = None
           ) -> Reduction:
    """Busy intervals, idle gaps and per-op device time inside the
    window: the ``bench.window`` host span, or the span of all device
    operations where the trace has none."""
    if window is None:
        spans = [e for e in rec.host if e.name == WINDOW_SPAN]
        if spans:
            window = (min(e.start for e in spans),
                      max(e.end for e in spans))
        elif rec.device:
            window = (min(e.start for e in rec.device),
                      max(e.end for e in rec.device))
        else:
            window = (0.0, 0.0)
    lo, hi = window
    planes = sorted({e.plane for e in rec.device})
    busy, op_ns = [], {}
    for p in planes:
        evs = [e for e in rec.device if e.plane == p]
        busy.append(union(clip([(e.start, e.end) for e in evs], lo, hi)))
    for e in rec.device:
        c = clip([(e.start, e.end)], lo, hi)
        if c:
            op_ns[e.name] = op_ns.get(e.name, 0.0) + (c[0][1] - c[0][0])
    self_ns = self_times([e for e in rec.device
                          if e.end > lo and e.start < hi], lo, hi)
    # idle gaps of the first device (one chip per cell today), labelled by
    # the host span that was open at their midpoint
    spans = []
    if busy:
        t = lo
        for s, e in busy[0] + [(hi, hi)]:
            if s > t:
                spans.append((t, s))
            t = max(t, e)
    labels = label_gaps(rec.host, [0.5 * (s + e) for s, e in spans])
    gaps = [(s, e, lab) for (s, e), lab in zip(spans, labels)]
    return Reduction((lo, hi), busy, gaps, op_ns, len(planes), self_ns)


def self_times(events: list, lo: float, hi: float) -> dict:
    """Per operation name, the device time inside [lo, hi] that no
    operation nested in it covers (a while loop's own time, not its
    body's)."""
    out: dict[str, float] = {}
    planes: dict[str, list] = {}
    for e in events:
        planes.setdefault(e.plane, []).append(e)
    for evs in planes.values():
        evs.sort(key=lambda e: (e.start, -e.dur))
        stack: list = []       # [event, clipped start, child ns]

        def close(item):
            e, s0, child = item
            own = min(e.end, hi) - s0 - child
            out[e.name] = out.get(e.name, 0.0) + max(own, 0.0)
            if stack:
                stack[-1][2] += min(e.end, hi) - s0

        for e in evs:
            while stack and stack[-1][0].end <= e.start:
                close(stack.pop())
            stack.append([e, max(e.start, lo), 0.0])
        while stack:
            close(stack.pop())
    return out
