"""Reduce a profiler trace (`.xplane.pb`) to device busy time, per-op device
time and idle gaps attributed to the benchmark's host spans.

Read with `jax.profiler.ProfileData` and nothing else. On a TPU the trace
holds, per chip, a plane `/device:TPU:<n>` whose `XLA Modules` line has one
event per program run (named `<module>(<fingerprint>)`) and whose `XLA Ops`
line has one event per HLO instruction run, named by the instruction's text
(`%<instruction> = <shape> <op>(...)`). Control-flow instructions (`while`,
`conditional`) are events of that line too, enclosing the instructions they
run: busy time is the union of all of them, and time per op is taken over
the innermost events only. An op's scope is the `op_name` metadata of its
instruction in the compiled program's HLO (where `jax.named_scope` names
such as `reuse_site:<site>` and a Pallas kernel's name appear), looked up by
module and instruction name. Host spans are the events the benchmark opened
with `TraceAnnotation`/`StepTraceAnnotation`, all named `bench:<what>`; the
profiler puts host and device events on one clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PREFIX = "bench:"
_META = re.compile(r'^\s*(?:ROOT )?%([^\s=]+) = .*?op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule ([^\s,]+)")


def hlo_scopes(hlo_text: str) -> dict:
    """{module: {instruction: op_name}} of one compiled program's HLO."""
    lines = hlo_text.splitlines()
    m = _MODULE.match(lines[0]) if lines else None
    if m is None:
        return {}
    table = {}
    for line in lines:
        hit = _META.match(line)
        if hit:
            table[hit.group(1)] = hit.group(2)
    return {m.group(1): table}


@dataclasses.dataclass(frozen=True)
class Op:
    """One device event, for building a trace by hand."""

    name: str
    scope: str
    start: int   # ns
    end: int


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int
    end: int
    depth: int   # nesting depth among bench spans on its thread


class Device:
    """One chip's op events as arrays, in start order."""

    def __init__(self, start, end, key, keys):
        start, end = np.asarray(start, np.int64), np.asarray(end, np.int64)
        order = np.lexsort((-end, start))
        self.start, self.end = start[order], end[order]
        self.key = np.asarray(key, np.int64)[order]
        self.keys = keys                       # [(instruction, scope)]
        # an event is a leaf unless the next one (in start order) begins
        # inside it, which on one line means it encloses that one
        self.leaf = np.ones(len(self.start), bool)
        if len(self.start) > 1:
            self.leaf[:-1] = self.start[1:] >= self.end[:-1]
        # merged busy intervals with prefix sums of their lengths
        if len(self.start):
            reach = np.maximum.accumulate(self.end)
            new = np.ones(len(self.start), bool)
            new[1:] = self.start[1:] > reach[:-1]
            first = np.flatnonzero(new)
            last = np.append(first[1:] - 1, len(self.start) - 1)
            self.iv_start, self.iv_end = self.start[first], reach[last]
        else:
            self.iv_start = self.iv_end = np.zeros(0, np.int64)
        self.prefix = np.concatenate(
            [[0], np.cumsum(self.iv_end - self.iv_start)])

    def busy_ns(self, lo: int, hi: int) -> int:
        i = max(int(np.searchsorted(self.iv_start, lo, "right")) - 1, 0)
        j = int(np.searchsorted(self.iv_start, hi, "left"))
        if j <= i:
            return 0
        total = int(self.prefix[j] - self.prefix[i])
        total -= max(0, min(int(self.iv_end[i]), lo) - int(self.iv_start[i]))
        total -= max(0, int(self.iv_end[j - 1])
                     - max(int(self.iv_start[j - 1]), hi))
        return total

    def leaves_in(self, spans: list) -> np.ndarray:
        """Mask of the leaf events that start inside one of `spans`."""
        if not spans:
            return np.zeros(len(self.start), bool)
        s0 = np.array([s.start for s in spans], np.int64)
        s1 = np.array([s.end for s in spans], np.int64)
        order = np.argsort(s0)
        s0, s1 = s0[order], s1[order]
        idx = np.searchsorted(s0, self.start, "right") - 1
        ok = idx >= 0
        ok[ok] &= self.start[ok] < s1[idx[ok]]
        return ok & self.leaf

    def op_ns(self, mask, match) -> int:
        want = np.array([bool(match(n, s)) for n, s in self.keys] or [False])
        sel = mask & want[self.key] if len(self.key) else mask
        return int(np.sum(self.end[sel] - self.start[sel]))


class Trace:
    def __init__(self, devices: dict, spans: list):
        self.devices = devices                 # plane name -> Device
        self.spans = sorted(spans, key=lambda s: s.start)
        self._inner = [s for s in self.spans if s.name != "bench:window"]
        self._inner_starts = [s.start for s in self._inner]

    @classmethod
    def of(cls, ops: dict, spans: list) -> "Trace":
        """A trace from hand-made `Op`s: {device: [Op]}."""
        devices = {}
        for dev, evs in ops.items():
            keys = sorted({(o.name, o.scope) for o in evs})
            index = {k: i for i, k in enumerate(keys)}
            devices[dev] = Device([o.start for o in evs], [o.end for o in evs],
                                  [index[(o.name, o.scope)] for o in evs],
                                  keys)
        return cls(devices, spans)

    # ------------------------------------------------------------ host
    def spans_named(self, name: str, within: Span | None = None) -> list:
        return [s for s in self.spans if s.name == name and (
            within is None
            or (s.start >= within.start and s.end <= within.end))]

    def window(self) -> Span | None:
        w = self.spans_named("bench:window")
        return w[0] if w else None

    def host_span_at(self, t: int) -> str:
        """The innermost benchmark span but the window open on the host at
        time `t`, or "no span"."""
        i = bisect.bisect_right(self._inner_starts, t) - 1
        for s in self._inner[max(i - 16, 0): i + 1][::-1]:
            if s.end > t:
                return s.name
        return "no span"

    # ------------------------------------------------------------ device
    def busy_intervals(self, device: str) -> list:
        d = self.devices[device]
        return [[int(a), int(b)] for a, b in zip(d.iv_start, d.iv_end)]

    def busy_ns(self, lo: int, hi: int) -> float:
        """Nanoseconds within [lo, hi] in which an operation ran, averaged
        over the devices."""
        if not self.devices:
            return 0.0
        return sum(d.busy_ns(lo, hi) for d in self.devices.values()) \
            / len(self.devices)

    def op_ns(self, spans: list, match) -> float:
        """Summed duration of the innermost ops that start inside one of
        `spans` and whose (instruction, scope) `match` accepts, averaged over
        the devices."""
        if not self.devices:
            return 0.0
        return sum(d.op_ns(d.leaves_in(spans), match)
                   for d in self.devices.values()) / len(self.devices)

    def top_ops(self, lo: int, hi: int, n: int = 10) -> list:
        """[name, seconds] of the innermost ops with the most device time
        within [lo, hi], by instruction (with the tail of its scope)."""
        per: dict = {}
        for d in self.devices.values():
            sel = d.leaves_in([Span("", lo, hi, 0)])
            sums = np.bincount(d.key[sel], weights=(d.end - d.start)[sel],
                               minlength=len(d.keys))
            for k in np.flatnonzero(sums):
                label = _label(*d.keys[k])
                per[label] = per.get(label, 0.0) + float(sums[k])
        scale = 1e-9 / max(len(self.devices), 1)
        top = sorted(per.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * scale] for k, v in top]

    def idle_gaps(self, lo: int, hi: int, n: int = 10) -> list:
        """[host span, seconds]: device idle time within [lo, hi], each gap
        summed whole under the innermost benchmark span open on the host at
        its middle, largest first."""
        per: dict = {}
        for dev in self.devices:
            edge = lo
            for a, b in self.busy_intervals(dev) + [[hi, hi]]:
                a, b = max(a, lo), min(b, hi)
                if a > edge:
                    name = self.host_span_at((edge + a) // 2)
                    per[name] = per.get(name, 0) + a - edge
                edge = max(edge, b)
        scale = 1e-9 / max(len(self.devices), 1)
        top = sorted(per.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * scale] for k, v in top]


def _label(instruction: str, scope: str) -> str:
    """An instruction with the part of its scope that says what it serves:
    its reuse site where it has one, else the last two scope levels."""
    parts = scope.split("/")
    site = [p for p in parts if p.startswith("reuse_site:")]
    return f"{instruction} {'/'.join(site or parts[-2:])}".strip()


def _instruction(event_name: str) -> str:
    if event_name.startswith("%"):
        cut = event_name.find(" ")
        return event_name[1:cut if cut > 0 else None]
    return event_name


def load(path: str, scopes: dict | None = None) -> Trace:
    """The trace at `path`. `scopes` ({module: {instruction: op_name}}, see
    `hlo_scopes`) names the scope of each op of those modules."""
    from jax.profiler import ProfileData

    scopes = scopes or {}
    data = ProfileData.from_file(path)
    devices: dict = {}
    spans: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            mods = sorted((int(e.start_ns), int(e.end_ns),
                           e.name.split("(", 1)[0])
                          for e in (lines[MODULES_LINE].events
                                    if MODULES_LINE in lines else ()))
            mod_starts = [m[0] for m in mods]
            start, end, key, keys, index = [], [], [], [], {}
            for e in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
                s, t = int(e.start_ns), int(e.end_ns)
                i = bisect.bisect_right(mod_starts, s) - 1
                module = mods[i][2] if i >= 0 and s < mods[i][1] else ""
                k = (module, _instruction(e.name))
                if k not in index:
                    index[k] = len(keys)
                    keys.append((k[1], scopes.get(module, {}).get(k[1], "")))
                start.append(s)
                end.append(t)
                key.append(index[k])
            devices[plane.name] = Device(start, end, key, keys)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                flat = sorted(
                    (Span(e.name, int(e.start_ns), int(e.end_ns), 0)
                     for e in line.events if e.name.startswith(HOST_PREFIX)),
                    key=lambda s: (s.start, -s.end))
                stack: list = []      # spans of one thread nest by time
                for s in flat:
                    while stack and stack[-1].end <= s.start:
                        stack.pop()
                    spans.append(dataclasses.replace(s, depth=len(stack)))
                    stack.append(s)
    return Trace(devices, spans)


def find(trace_dir: str) -> str:
    """The one `.xplane.pb` a profiler run wrote under `trace_dir`."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {found}")
    return found[0]
