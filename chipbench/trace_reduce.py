"""From the profiler's `.xplane.pb` to the numbers the benchmark
reports: the device's busy seconds in the window, the device operations
that took most time, and the idle time by what the host was doing.

The harness marks the window with a `TraceAnnotation` named
`chipbench.window`; everything here is clipped to it. Times inside are
the trace's own nanoseconds. A device plane is one named
`/device:TPU:<n>`; its operations are the events of its `XLA Ops` line
(the compute stream: the DMA of `Async XLA Ops` runs beside it and is
not counted as busy), each named `<program>/<operation>` after the
event of the `XLA Modules` line it lies in.
"""

from __future__ import annotations

import bisect
import collections
import re

from chipbench.spans import merge

WINDOW = "chipbench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
BETWEEN = "(harness, between operations)"


class Cover:
    """A disjoint union of intervals, asked often: how much of
    `[start, end)` it covers, and whether it holds a moment."""

    def __init__(self, intervals):
        merged = merge(intervals)
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.before = [0]       # length covered before interval i
        for s_, e in merged:
            self.before.append(self.before[-1] + e - s_)

    def _upto(self, t) -> int:
        """Length covered before the moment `t`."""
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0
        return self.before[i - 1] + min(t, self.ends[i - 1]) - self.starts[i - 1]

    def covered(self, start, end) -> int:
        return self._upto(end) - self._upto(start)

    def holds(self, t) -> bool:
        i = bisect.bisect_right(self.starts, t)
        return i > 0 and t < self.ends[i - 1]

    @property
    def length(self) -> int:
        return self.before[-1]


class Reduced:
    """`window`: (start, end); `events`: per device plane, in name
    order, its `(name, start, end)` clipped to the window; `busy`: per
    plane the `Cover` of them."""

    def __init__(self, window, events):
        self.window = window
        self.events = events
        self.busy = [Cover((s, e) for _, s, e in evs) for evs in events]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds an operation ran on the device, averaged over the
        chips."""
        if not self.busy:
            return 0.0
        return sum(b.length for b in self.busy) / len(self.busy) / 1e9

    def device_seconds(self, intervals) -> float:
        """Device time of chip 0's operations that start inside one of
        the `(start, end)` intervals (trace nanoseconds)."""
        if not self.events:
            return 0.0
        inside = Cover(intervals)
        return sum(e - s for _, s, e in self.events[0]
                   if inside.holds(s)) / 1e9

    def device_ops(self):
        """Chip 0's operations that took most time: [name, seconds]."""
        total = collections.Counter()
        for name, s, e in (self.events[0] if self.events else ()):
            total[name] += e - s
        return [[n, t / 1e9] for n, t in total.most_common(TOP)]

    def idle_by_host(self, host_spans):
        """Chip 0's idle seconds in the window by what the host was
        doing: `host_spans` are `(name, start, end)` of one thread, so
        properly nested, and each moment of idleness goes to the
        innermost span open then (`BETWEEN` where none is)."""
        w0, w1 = self.window
        busy = self.busy[0] if self.busy else Cover([])
        marks = []
        for name, s, e in host_spans:
            s, e = max(s, w0), min(e, w1)
            if e > s:
                marks.append((s, 1, name))
                marks.append((e, 0, name))
        marks.sort(key=lambda m: (m[0], m[1]))
        idle = collections.Counter()
        stack, at = [], w0

        def close(upto):
            if upto > at:
                label = stack[-1] if stack else BETWEEN
                idle[label] += (upto - at) - busy.covered(at, upto)

        for t, opening, name in marks:
            close(t)
            at = max(at, t)
            if opening:
                stack.append(name)
            else:
                for i in range(len(stack) - 1, -1, -1):
                    if stack[i] == name:
                        del stack[i]
                        break
        close(w1)
        return [[n, t / 1e9] for n, t in idle.most_common(TOP) if t > 0]


def reduce_planes(planes) -> Reduced:
    """`planes`: `(plane name, [(line name, [(event name, start ns,
    duration ns)])])`, as `read_xplane` gives them."""
    window = None
    for _, lines in planes:
        for _, events in lines:
            for name, start, dur in events:
                if name == WINDOW:
                    window = (start, start + dur)
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    w0, w1 = window
    device = []
    for plane, lines in sorted(planes, key=lambda p: p[0]):
        if not DEVICE_PLANE.match(plane):
            continue
        modules = sorted((s, s + d, name.split("(")[0])
                         for line, events in lines if line == MODULES_LINE
                         for name, s, d in events)
        starts = [m[0] for m in modules]

        def program(at):
            i = bisect.bisect_right(starts, at) - 1
            return modules[i][2] if i >= 0 and at < modules[i][1] else "?"

        evs = [(f"{program(s)}/{name.split(' = ')[0]}",
                max(s, w0), min(s + d, w1))
               for line, events in lines if line == OPS_LINE
               for name, s, d in events if s < w1 and s + d > w0]
        device.append(sorted(evs, key=lambda e: e[1]))
    return Reduced(window, device)


def read_xplane(path: str):
    """The planes of an `.xplane.pb` as plain lists: of a device plane
    the two lines read here, of any other the harness's annotations (a
    long window holds a million host events that nothing reads)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        on_device = DEVICE_PLANE.match(plane.name)
        lines = []
        for line in plane.lines:
            if on_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [(e.name, int(e.start_ns), int(e.duration_ns))
                      for e in line.events
                      if on_device or e.name.startswith("chipbench.")]
            if events:
                lines.append((line.name, events))
        planes.append((plane.name, lines))
    return planes
