"""Reduce a profiler trace to device busy time, idle gaps and program time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, keeping three
things: each device's operations (the "XLA Ops" line of every
``/device:TPU:n`` plane), each device's programs ("XLA Modules"), and the
benchmark's own host spans (``TraceAnnotation`` names starting ``bench.``).
The rest is arithmetic on (name, start_ns, end_ns) triples, clipped to the
traced window: the span of the host annotation ``bench.traced``.
"""

from __future__ import annotations

import bisect
import gzip
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.traced"
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)+$")


@dataclass
class Trace:
    ops: list = field(default_factory=list)       # per device: [(name, s, e)]
    modules: list = field(default_factory=list)   # per device: [(name, s, e)]
    host: list = field(default_factory=list)      # [(name, s, e)]

    @staticmethod
    def from_json(d: dict) -> "Trace":
        conv = lambda evs: [tuple(e) for e in evs]  # noqa: E731
        return Trace([conv(x) for x in d["ops"]],
                     [conv(x) for x in d["modules"]], conv(d["host"]))


def program_name(name: str) -> str:
    """``jit__decode_fn(12)`` and ``jit__decode_fn.3`` -> ``jit__decode_fn``."""
    return _SUFFIX.sub("", name)


def load(path) -> Trace:
    """Read an ``.xplane.pb`` file, or a ``.json.gz`` of a reduced trace
    ({"ops", "modules", "host"}, as the tests' recorded trace is kept)."""
    path = Path(path)
    if path.name.endswith(".json.gz"):
        return Trace.from_json(json.loads(gzip.decompress(path.read_bytes())))
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, mods = [], []
            for line in plane.lines:
                dst = {OPS_LINE: ops, MODULES_LINE: mods}.get(line.name)
                if dst is None:
                    continue
                for ev in line.events:
                    dst.append((ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns)))
            tr.ops.append(sorted(ops, key=lambda e: e[1]))
            tr.modules.append(sorted(mods, key=lambda e: e[1]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        tr.host.append((ev.name, int(ev.start_ns),
                                        int(ev.start_ns + ev.duration_ns)))
    tr.host.sort(key=lambda e: e[1])
    return tr


def window(tr: Trace) -> tuple[int, int]:
    spans = [(s, e) for n, s, e in tr.host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    return spans[0]


def merged(events, t0: int, t1: int) -> list[tuple[int, int]]:
    """Union of the events' intervals, clipped to [t0, t1]."""
    out: list[list[int]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events, t0: int, t1: int) -> int:
    return sum(e - s for s, e in merged(events, t0, t1))


class Busy:
    """Busy time of a device between any two instants, in O(log n) a query
    after one pass over its events."""

    def __init__(self, events, t0: int, t1: int):
        self.spans = merged(events, t0, t1)
        self.starts = [s for s, _ in self.spans]
        self.before = [0]
        for s, e in self.spans:
            self.before.append(self.before[-1] + e - s)

    def _upto(self, t: int) -> int:
        """Busy ns in (-inf, t]."""
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0
        s, e = self.spans[i - 1]
        return self.before[i - 1] + min(e, t) - s

    def between(self, a: int, b: int) -> int:
        return self._upto(b) - self._upto(a) if b > a else 0


def gaps(events, t0: int, t1: int) -> list[tuple[int, int]]:
    """Intervals of [t0, t1] in which no event runs."""
    out, cur = [], t0
    for s, e in merged(events, t0, t1):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


def program_seconds(modules, t0: int, t1: int) -> dict[str, float]:
    """Device seconds of each program, clipped to the window."""
    out: dict[str, float] = {}
    for name, s, e in modules:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            key = program_name(name)
            out[key] = out.get(key, 0.0) + (e - s) / 1e9
    return out


def runs(modules, name: str, t0: int, t1: int) -> list[tuple[int, int]]:
    """The executions of one program that start inside the window."""
    return [(s, e) for n, s, e in modules
            if program_name(n) == name and t0 <= s < t1]


def idle_by_host_span(tr: Trace, device: int = 0) -> dict[str, float]:
    """Idle seconds of one device in the window, by the benchmark span the
    host was in at the middle of each gap ("none" between spans).  The
    harness's spans (ingest, tick, idle) follow one another, never nest."""
    t0, t1 = window(tr)
    spans = [h for h in tr.host if h[0] != WINDOW_SPAN]
    starts = [s for _, s, _ in spans]
    out: dict[str, float] = {}
    for s, e in gaps(tr.ops[device], t0, t1):
        mid = (s + e) // 2
        i = bisect.bisect_right(starts, mid) - 1
        key = spans[i][0] if i >= 0 and spans[i][2] > mid else "none"
        out[key] = out.get(key, 0.0) + (e - s) / 1e9
    return out
