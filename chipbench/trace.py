"""The reduction from a profiler trace to what the per-layer metrics read.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Device planes are named ``/device:TPU:<n>``; their ``XLA Ops``
line holds one event per operation that ran on that chip.  Host threads
live on the ``/host:CPU`` plane, where the benchmark's own
``TraceAnnotation`` spans (``chipbench.*``) sit on the same clock.

``Trace.window`` is the ``chipbench.window`` span.  Everything else is
clipped to it:

- busy: the union of a device's op intervals;
- per-op time: each op name's summed duration;
- idle gaps: the stretches of the window in which a device runs no op,
  each named by the innermost ``chipbench.*`` span that covers its middle
  (``host`` where none does);
- exposed collectives: the part of the collective ops' intervals in which
  no other op runs on that device.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

WINDOW_SPAN = "chipbench.window"
SPAN_PREFIX = "chipbench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
COLLECTIVE_MARKS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute", "send", "recv")

Interval = tuple[float, float]


@dataclass
class Op:
    name: str
    start: float
    end: float


@dataclass
class Trace:
    window: Interval
    devices: dict[str, list[Op]]            # plane name -> ops, clipped
    spans: list[tuple[str, float, float]] = field(default_factory=list)

    # -- building -----------------------------------------------------------

    @classmethod
    def from_dir(cls, log_dir: str) -> "Trace":
        paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        return cls.from_file(paths[-1])

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(path))

    @classmethod
    def from_profile(cls, data) -> "Trace":
        spans, devices = [], {}
        for plane in data.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                ops = []
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    for ev in line.events:
                        ops.append(Op(ev.name, ev.start_ns * 1e-9,
                                      ev.end_ns * 1e-9))
                devices[plane.name] = ops
            else:
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            spans.append((ev.name, ev.start_ns * 1e-9,
                                          ev.end_ns * 1e-9))
        windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
        if not windows:
            raise ValueError(f"the trace has no {WINDOW_SPAN} span")
        window = max(windows, key=lambda w: w[1] - w[0])
        return cls(window, {k: clip_ops(v, window)
                            for k, v in devices.items()}, spans)

    # -- reductions ---------------------------------------------------------

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(union_length([(o.start, o.end) for o in ops])
                   for ops in self.devices.values()) / len(self.devices)

    def op_seconds(self) -> dict[str, float]:
        """Summed device time per op name, averaged over the devices."""
        out: dict[str, float] = {}
        for ops in self.devices.values():
            for o in ops:
                out[o.name] = out.get(o.name, 0.0) + o.end - o.start
        n = max(len(self.devices), 1)
        return {k: v / n for k, v in out.items()}

    def matching_seconds(self, needle: str) -> tuple[float, int]:
        """Summed device time and count of the ops whose name contains
        ``needle`` (an op's name is its HLO text), averaged over the
        devices."""
        total, count = 0.0, 0
        for ops in self.devices.values():
            for o in ops:
                if needle in o.name:
                    total += o.end - o.start
                    count += 1
        n = max(len(self.devices), 1)
        return total / n, count

    def idle_gaps(self) -> list[tuple[str, float]]:
        """Idle seconds per host activity, averaged over the devices."""
        out: dict[str, float] = {}
        for ops in self.devices.values():
            idle = gaps([(o.start, o.end) for o in ops], self.window)
            names = self.host_activity([(s + e) / 2 for s, e in idle])
            for (s, e), name in zip(idle, names):
                out[name] = out.get(name, 0.0) + e - s
        n = max(len(self.devices), 1)
        return sorted(((k, v / n) for k, v in out.items()),
                      key=lambda kv: -kv[1])

    def host_activity(self, times: list[float]) -> list[str]:
        """The innermost benchmark span open at each of ``times`` (sorted),
        by one sweep: the spans come from one thread, so they nest."""
        spans = sorted((s for s in self.spans if s[0] != WINDOW_SPAN),
                       key=lambda s: (s[1], -s[2]))
        out, stack, i = [], [], 0
        for t in times:
            while i < len(spans) and spans[i][1] <= t:
                stack.append(spans[i])
                i += 1
            while stack and stack[-1][2] < t:
                stack.pop()
            # a closed span under an open one: drop it from the middle too
            stack = [s for s in stack if s[2] >= t] if stack else stack
            out.append(stack[-1][0] if stack else "host")
        return out

    def exposed_collective_s(self) -> float:
        """Collective time with no other op on the device, averaged."""
        total = 0.0
        for ops in self.devices.values():
            coll = [(o.start, o.end) for o in ops if is_collective(o.name)]
            other = [(o.start, o.end) for o in ops if not is_collective(o.name)]
            total += union_length(coll) - overlap_length(coll, other)
        return total / max(len(self.devices), 1)


class Tracer:
    """Profiles the window into a temporary directory and reads it back."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)

    @staticmethod
    def annotate(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def stop(self) -> Trace:
        import jax
        jax.profiler.stop_trace()
        return Trace.from_dir(self.log_dir)


def is_collective(name: str) -> bool:
    """By the instruction's own name, the head of its HLO text (the rest
    names its operands)."""
    low = name.split(" = ")[0].lower()
    return any(m in low for m in COLLECTIVE_MARKS)


def clip_ops(ops: list[Op], window: Interval) -> list[Op]:
    lo, hi = window
    return [Op(o.name, max(o.start, lo), min(o.end, hi))
            for o in ops if o.end > lo and o.start < hi]


def merge(intervals: list[Interval]) -> list[Interval]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_length(intervals: list[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def overlap_length(a: list[Interval], b: list[Interval]) -> float:
    """Length of (union of a) intersected with (union of b)."""
    ma, mb = merge(a), merge(b)
    i = j = 0
    total = 0.0
    while i < len(ma) and j < len(mb):
        s, e = max(ma[i][0], mb[j][0]), min(ma[i][1], mb[j][1])
        if e > s:
            total += e - s
        if ma[i][1] < mb[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(intervals: list[Interval], window: Interval) -> list[Interval]:
    """The stretches of ``window`` that no interval covers."""
    out, t = [], window[0]
    for s, e in merge(intervals):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return out
