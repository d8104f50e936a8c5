"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
per-kernel device time, and the ``breakdown`` of a result line.

Device operations are the leaf events of each TPU plane's "XLA Ops" line:
an event that holds others (a ``while`` or ``conditional`` running its
body) is left out, so busy time is the union of the operations that did
the work. Each operation is named by its HLO instruction (``fusion``,
``gather_swiglu``: a Pallas kernel's custom call carries the kernel's
name) and by the program it ran in (the "XLA Modules" line). The traced
window runs from the first to the last of the harness's own host spans
(``bench.*``), on the trace's clock; an idle gap is named by the innermost
harness span that covers its middle. A trace can lose device events; a
traced ``bench.step_block`` call with no device operation under it shows
the loss (``Reduced.lost_calls``), and the readers of ``bench/metrics/``
then withhold what they read from the trace.

    python3 bench/trace_reduce.py <dir or .xplane.pb>         # the reduction
    python3 bench/trace_reduce.py --dump <dir or .xplane.pb>  # planes, lines
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN = re.compile(r"^bench\.")
CALL_SPAN = "bench.step_block"
# the device clock reads about 1 ms behind the host's
CLOCK_SLACK_NS = 2_000_000
INSTRUCTION = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*\s*=")
MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")


@dataclasses.dataclass
class Op:
    device: int
    inst: str       # HLO instruction name without its numeric suffix
    module: str     # the jitted program it ran in
    text: str       # the event's full HLO text (shapes included)
    start: int      # ns
    dur: int        # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Reduced:
    t0: int
    t1: int
    ops: List[Op]                      # leaf device ops inside [t0, t1]
    spans: List[Tuple[str, int, int]]  # harness host spans, by start
    n_devices: int

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_intervals(self, device: int) -> List[Tuple[int, int]]:
        iv = sorted((max(o.start, self.t0), min(o.end, self.t1))
                    for o in self.ops if o.device == device)
        out: List[List[int]] = []
        for a, b in iv:
            if b <= a:
                continue
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return [(a, b) for a, b in out]

    def devices(self) -> List[int]:
        return sorted({o.device for o in self.ops}) or [0]

    @property
    def busy_s(self) -> float:
        tot = sum(b - a for d in self.devices()
                  for a, b in self.busy_intervals(d))
        return tot * 1e-9 / max(len(self.devices()), self.n_devices, 1)

    def lost_calls(self) -> int:
        """Traced ``step_block`` calls with no device operation under them:
        every call runs at least one program, so each is a lost stretch."""
        starts = sorted(o.start for o in self.ops)
        ends = sorted(o.end for o in self.ops)
        lost = 0
        for name, a, b in self.spans:
            if name != CALL_SPAN:
                continue
            # ops that start before the span's end, less those that end
            # before its start, overlap it
            n = (bisect.bisect_left(starts, b + CLOCK_SLACK_NS)
                 - bisect.bisect_right(ends, a - CLOCK_SLACK_NS))
            lost += n <= 0
        return lost

    @property
    def complete(self) -> bool:
        return bool(self.ops) and self.lost_calls() == 0

    def kernel_ops(self, inst: str) -> List[Op]:
        """Device ops of the instruction (kernel) named ``inst``."""
        return [o for o in self.ops if o.inst == inst]

    def breakdown(self, top: int = 10) -> dict:
        by: Dict[str, int] = {}
        for o in self.ops:
            key = f"{o.module}/{o.inst}"
            by[key] = by.get(key, 0) + o.dur
        ops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
        gaps: Dict[str, int] = {}
        for d in self.devices():
            iv = self.busy_intervals(d)
            edges = [self.t0] + [x for ab in iv for x in ab] + [self.t1]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    name = self.host_at((a + b) // 2)
                    gaps[name] = gaps.get(name, 0) + (b - a)
        idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v * 1e-9] for k, v in ops],
                "idle_gaps": [[k, v * 1e-9] for k, v in idle]}

    def host_at(self, t: int) -> str:
        inner = None
        for name, a, b in self.spans:
            if a <= t <= b and (inner is None or b - a < inner[2] - inner[1]):
                inner = (name, a, b)
        return inner[0] if inner else "outside harness spans"


def find(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


def _leaves(events: List[tuple]) -> List[tuple]:
    """Drop every event that contains another: (start, dur, ...) tuples."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    parent = [False] * len(events)
    stack: List[int] = []
    for i, (s, d, *_) in enumerate(events):
        while stack and events[stack[-1]][0] + events[stack[-1]][1] <= s:
            stack.pop()
        if stack and s + d <= events[stack[-1]][0] + events[stack[-1]][1]:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(events, parent) if not p]


def _module_at(mods: List[Tuple[int, int, str]], t: int) -> str:
    i = bisect.bisect_right([m[0] for m in mods], t) - 1
    if i >= 0 and t <= mods[i][1]:
        return mods[i][2]
    return "?"


def reduce(path: str) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find(path))
    ops: List[Op] = []
    spans: List[Tuple[str, int, int]] = []
    n_dev = 0
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = int(plane.name.rsplit(":", 1)[1])
            n_dev += 1
            lines = {line.name: line for line in plane.lines}
            mods = sorted(
                (int(e.start_ns), int(e.start_ns + e.duration_ns),
                 MODULE.match(e.name).group(1))
                for e in (lines[MODULES_LINE].events
                          if MODULES_LINE in lines else []))
            if OPS_LINE not in lines:
                continue
            evs = [(int(e.start_ns), int(e.duration_ns), e.name)
                   for e in lines[OPS_LINE].events]
            for s, d, text in _leaves(evs):
                m = INSTRUCTION.match(text)
                ops.append(Op(dev, m.group(1) if m else text[:40],
                              _module_at(mods, s), text, s, d))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if HOST_SPAN.match(ev.name):
                        a = int(ev.start_ns)
                        spans.append((ev.name, a, a + int(ev.duration_ns)))
    if not spans:
        raise ValueError("trace holds no harness spans (bench.*)")
    spans.sort(key=lambda s: s[1])
    t0 = min(a for _, a, _ in spans)
    t1 = max(b for _, _, b in spans)
    ops = [o for o in ops if o.start < t1 and o.end > t0]
    return Reduced(t0=t0, t1=t1, ops=ops, spans=spans, n_devices=n_dev)


def dump(path: str, per_line: int = 3) -> None:
    """Print every plane and line, with a few events and all their stats."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find(path))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:per_line]:
                print(f"    {ev.name[:200]!r} dur {ev.duration_ns} "
                      f"stats {[(k, str(v)[:80]) for k, v in ev.stats]}")


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv[0] == "--dump":
        dump(argv[1])
        return 0
    r = reduce(argv[0])
    print(json.dumps({"window_s": r.window_s, "busy_s": r.busy_s,
                      "n_ops": len(r.ops), "lost_calls": r.lost_calls(),
                      **r.breakdown()}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
