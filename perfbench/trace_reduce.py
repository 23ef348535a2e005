"""From a profiler trace (`.xplane.pb`) to the numbers the per-layer metrics
read: device busy and idle time, time per device operation, collective time
and the part of it that nothing hides, kernel time, and the idle gaps with
the benchmark span that covered each.

Read with nothing but JAX (`jax.profiler.ProfileData`).  A TPU's plane is
named ``/device:TPU:<n>``.  Its line ``XLA Ops`` holds one event per HLO
operation the core executed, named by the whole HLO instruction; busy time,
idle gaps and time per operation come from that line alone.  The line
``Async XLA Ops`` holds, for each asynchronous pair, one event from the
start of `<op>-start` to the end of `<op>-done`: a transfer in flight, not
work of the core, read here only to know when a collective was under way.
Host spans are the `TraceAnnotation`s whose names start with
`harness.SPAN_PREFIX`; they share the trace's clock (on the v5e host the
device's events read about a millisecond early against them; PR 22).  All
times here are seconds on that clock.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
ASYNC_LINES = ("Async XLA Ops",)
COLLECTIVE = re.compile(
    r"^(all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all)"
    r"(-start|-done)?(\.\d+)?$")
STRING_STATS = ("hlo_category", "tf_op", "long_name", "name", "hlo_op",
                "kernel_details", "deduplicated_name")
# An event of the `XLA Ops` line is named by its whole HLO instruction:
# ``%fusion.7 = f32[8,128]{1,0:T(8,128)} fusion(%p0, %p1), kind=kLoop, ...``.
HLO_TEXT = re.compile(
    r"^%?(?P<name>[\w.\-]+) = \(?(?P<shape>[a-z0-9]+\[[0-9,]*\])?.*? "
    r"(?P<opcode>[a-z][a-z\-]*)\(")
FUSION_KIND = re.compile(r"kind=k(\w+)")


# -- intervals ----------------------------------------------------------------


def union(intervals) -> list:
    """Merged, sorted, non-overlapping intervals."""
    out: list = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """The part of merged intervals `a` that merged intervals `b` leave
    uncovered."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# -- the reduced trace --------------------------------------------------------


@dataclass
class Op:
    name: str
    start: float
    end: float
    category: str = ""
    shape: str = ""     # the first output's type and dimensions
    text: str = ""      # every string the trace holds about this event

    @property
    def collective(self) -> bool:
        return bool(COLLECTIVE.match(self.name)) or \
            "all-reduce" in self.category or "collective" in self.category


@dataclass
class DeviceTrace:
    index: int
    ops: list = field(default_factory=list)         # on the core, in order
    async_ops: list = field(default_factory=list)   # pairs in flight


@dataclass
class Trace:
    devices: list
    spans: list            # (name, start, end), benchmark spans only
    window: tuple          # (start, end): first span's start to last's end

    # .. busy and idle ........................................................

    def busy_intervals(self, dev: DeviceTrace) -> list:
        return clip(union((o.start, o.end) for o in dev.ops), *self.window)

    def busy_s(self) -> list:
        return [total(self.busy_intervals(d)) for d in self.devices]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def idle_pct(self) -> list:
        return [100.0 * (1.0 - b / self.window_s) for b in self.busy_s()]

    def idle_gaps(self, dev: "DeviceTrace | None" = None,
                  top: int = 5) -> list:
        """The longest idle gaps of one device (by default the one that was
        idle longest), each named after the benchmark span that covered
        most of it (`none` outside them all)."""
        if dev is None:
            if not self.devices:
                return []
            dev = min(self.devices,
                      key=lambda d: total(self.busy_intervals(d)))
        gaps = subtract([self.window], self.busy_intervals(dev))
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
            best, best_cover = "none", 0.0
            for name, ss, se in self.spans:
                cover = min(e, se) - max(s, ss)
                if cover > best_cover:
                    best, best_cover = name, cover
            out.append([best, e - s])
        return out

    # .. operations ...........................................................

    def op_seconds(self, dev: DeviceTrace) -> dict:
        """Seconds per operation inside the window, keyed by
        ``<category>:<name>`` as the trace prints them."""
        out: dict = {}
        lo, hi = self.window
        for o in dev.ops:
            d = min(o.end, hi) - max(o.start, lo)
            if d > 0:
                key = " ".join(x for x in (o.category + ":" + o.name,
                                           o.shape) if x).lstrip(":")
                out[key] = out.get(key, 0.0) + d
        return out

    def top_ops(self, top: int = 10) -> list:
        """The operations with most time, averaged over the devices."""
        acc: dict = {}
        for d in self.devices:
            for k, v in self.op_seconds(d).items():
                acc[k] = acc.get(k, 0.0) + v / len(self.devices)
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:top]]

    def kernel_seconds(self, names) -> list:
        """Per device, the summed duration of the events that hold one of
        the strings `names` anywhere in what the trace says of them."""
        lo, hi = self.window
        out = []
        for d in self.devices:
            out.append(sum(
                max(0.0, min(o.end, hi) - max(o.start, lo))
                for o in d.ops if any(n in o.text for n in names)))
        return out

    # .. collectives ..........................................................

    def collective_intervals(self, dev: DeviceTrace) -> list:
        """When a collective was under way on this device: each collective
        event, and for an asynchronous pair everything from the start of
        `<op>-start` to the end of its `<op>-done`."""
        spans, open_starts = [], {}
        for o in sorted(dev.ops + dev.async_ops, key=lambda o: o.start):
            if not o.collective:
                continue
            m = COLLECTIVE.match(o.name)
            kind = m.group(2) if m else None
            key = (m.group(1), m.group(3)) if m else None
            if kind == "-start":
                open_starts.setdefault(m.group(1), []).append(o.start)
                spans.append((o.start, o.end))
            elif kind == "-done" and open_starts.get(key[0]):
                spans.append((open_starts[key[0]].pop(0), o.end))
            else:
                spans.append((o.start, o.end))
        return clip(union(spans), *self.window)

    def collective_s(self) -> list:
        return [total(self.collective_intervals(d)) for d in self.devices]

    def collective_exposed_s(self) -> list:
        """Per device, the collective time during which no other operation
        ran on that device."""
        out = []
        for d in self.devices:
            other = clip(union((o.start, o.end) for o in d.ops
                               if not o.collective), *self.window)
            out.append(total(subtract(self.collective_intervals(d), other)))
        return out


# -- reading ------------------------------------------------------------------


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _op(event) -> Op:
    stats = {}
    for k, v in event.stats:
        if k in STRING_STATS and isinstance(v, str):
            stats[k] = v
    start = event.start_ns * 1e-9
    name, category, shape = event.name, stats.get("hlo_category", ""), ""
    m = HLO_TEXT.match(event.name)
    if m:
        name, shape = m.group("name"), m.group("shape") or ""
        if not category:
            kind = FUSION_KIND.search(event.name)
            category = m.group("opcode") + (
                f" {kind.group(1).lower()}" if kind else "")
    return Op(name=name, start=start, end=start + event.duration_ns * 1e-9,
              category=category, shape=shape,
              text=" ".join([event.name, *stats.values()]))


def reduce_trace(path: str, span_prefix: str = "pb:") -> Trace:
    """Read one `.xplane.pb` (or the directory `jax.profiler` wrote it
    under) into a `Trace`."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    return reduce_profile(ProfileData.from_file(path), span_prefix)


def reduce_profile(profile, span_prefix: str = "pb:") -> Trace:
    devices, spans = [], []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = DeviceTrace(index=int(m.group(1)))
            for line in plane.lines:
                if line.name == OP_LINE:
                    dev.ops = [_op(e) for e in line.events]
                elif line.name in ASYNC_LINES:
                    dev.async_ops = [_op(e) for e in line.events]
            if dev.ops:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        s = e.start_ns * 1e-9
                        spans.append((e.name[len(span_prefix):], s,
                                      s + e.duration_ns * 1e-9))
    spans.sort(key=lambda x: x[1])
    if spans:
        window = (spans[0][1], max(e for _, _, e in spans))
    else:
        every = [t for d in devices for o in d.ops for t in (o.start, o.end)]
        window = (min(every), max(every)) if every else (0.0, 0.0)
    devices.sort(key=lambda d: d.index)
    return Trace(devices=devices, spans=spans, window=window)
