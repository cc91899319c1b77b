"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, per-op
device time and the host span open in each idle gap.

Layout of a TPU trace as ``jax.profiler.ProfileData`` reads it: one plane
``/device:TPU:<n>`` per chip, whose line ``XLA Ops`` holds one event per
executed HLO operation (name = the HLO instruction text); ``/host:CPU``
holds the host threads when the host tracer is on, with any
``jax.profiler.TraceAnnotation`` spans named ``bench.*``. A benchmark run
traces the device only and hands the reducer the harness spans it kept on
the host's wall clock (``run.HostSpan``); the plane ``Task Environment``
gives that clock's reading at the trace's time 0 (``profile_start_time``).
``Async XLA Ops`` spans overlap the compute they hide behind and are not
counted as busy. ``tests/data/v5e_kd_matmul.xplane.pb`` is such a trace,
recorded on a TPU v5e by ``tools/record_trace.py``.

Host and device timestamps of one trace share a clock only to about a
millisecond, so gaps are attributed by their midpoint and only gaps of a
millisecond or more are listed.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
ENV_PLANE = "Task Environment"
MIN_GAP_NS = 1_000_000


def _union(intervals):
    """Merge [start, end) intervals; returns the merged, sorted list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(ops):
    """(name, self seconds) per op; an op nested in another's interval is
    that op's child."""
    out, stack = [], []             # stack: [end, index into out]
    for s, d, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= d * 1e-9
        out.append([name, d * 1e-9])
        stack.append([s + d, len(out) - 1])
    return out


def op_kind(name: str) -> str:
    """A stable short name for an HLO op event: ``%fusion.3 = f32[..]
    fusion(...), kind=kLoop`` -> ``fusion.3``."""
    m = re.match(r"%?([\w.\-]+)", name)
    return m.group(1) if m else name[:64]


@dataclass
class DeviceTrace:
    index: int
    ops: list = field(default_factory=list)       # (start_ns, dur_ns, name)

    def busy_intervals(self):
        return _union([s, s + d] for s, d, _ in self.ops if d > 0)

    def busy_ns(self) -> float:
        return float(sum(e - s for s, e in self.busy_intervals()))


@dataclass
class Trace:
    devices: list
    spans: list                                   # (start_ns, dur_ns, name)
    start_epoch_ns: int = 0          # the wall clock at the trace's time 0

    def add_host_spans(self, spans):
        """Add spans taken on the host's wall clock, (start ns since the
        epoch, duration ns, name), on the trace's time axis."""
        self.spans.extend((s - self.start_epoch_ns, d, n)
                          for s, d, n in spans)

    def op_time_s(self, match) -> float:
        """Summed device seconds of the ops whose name satisfies ``match``,
        over all devices."""
        return sum(d for dev in self.devices for _, d, n in dev.ops
                   if match(n)) * 1e-9

    def op_count(self, match) -> int:
        return sum(1 for dev in self.devices for _, _, n in dev.ops
                   if match(n))

    def busy_s(self) -> float:
        """Device busy seconds, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(d.busy_ns() for d in self.devices) / len(
            self.devices) * 1e-9

    def top_ops(self, n: int = 10):
        """The ``n`` op kinds with the most device self seconds (all
        devices): an op's time less that of the ops nested in it, so that
        a scan's ``while`` op does not count its body twice."""
        tot: dict = {}
        for dev in self.devices:
            for name, secs in _self_times(dev.ops):
                k = op_kind(name)
                tot[k] = tot.get(k, 0.0) + secs
        return sorted(([k, v] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10, min_gap_ns: float = MIN_GAP_NS):
        """The ``n`` longest idle gaps of device 0, each named by the
        innermost harness span open at its midpoint: ``[[span, seconds],
        ...]``."""
        if not self.devices:
            return []
        dev = self.devices[0]
        busy = dev.busy_intervals()
        gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)
                if busy[i + 1][0] - busy[i][1] >= min_gap_ns]
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = (s + e) / 2
            open_spans = [(ss, dd, nm) for ss, dd, nm in self.spans
                          if ss <= mid <= ss + dd]
            name = (min(open_spans, key=lambda x: x[1])[2]
                    if open_spans else "outside harness spans")
            out.append([name, (e - s) * 1e-9])
        return out

    def per_device_busy_s(self):
        return [d.busy_ns() * 1e-9 for d in self.devices]


def from_profile(pd) -> Trace:
    """Build a ``Trace`` from a ``jax.profiler.ProfileData``."""
    devices, spans, start = [], [], 0
    for plane in pd.planes:
        if plane.name == ENV_PLANE:
            start = int(dict(plane.stats).get("profile_start_time", 0))
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = DeviceTrace(int(m.group(2)))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops.extend((e.start_ns, e.duration_ns, e.name)
                                   for e in line.events)
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend((e.start_ns, e.duration_ns, e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    devices.sort(key=lambda d: d.index)
    return Trace(devices=devices, spans=spans, start_epoch_ns=start)


def load(path: str) -> Trace:
    import jax
    return from_profile(jax.profiler.ProfileData.from_file(path))
