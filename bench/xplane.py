"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to device busy
time, op times, and idle gaps labelled by what the host was doing.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per device operation. The host plane's Python thread line
(``python`` on the CPU, ``python3`` on a TPU host) holds the profiler's
Python spans: the harness's own ``TraceAnnotation``s
(``bench.step``, ``bench.client``) and the program's functions, named
``$<file>:<line> <function>``, which this module shortens to
``<file> <function>``. The traced window runs from the start of the first
``bench.step`` span to the end of the last.

A device op's name is its HLO instruction as text
(``%paged_attention_fwd.9 = bf16[42,1,48,128]{...} custom-call(...)``);
``op_label`` keeps the instruction's base name and its result shape. A
``while`` (the scan over layers) spans the ops of its body, so control ops
are left out of the op ranking.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from jax.profiler import ProfileData

Interval = Tuple[int, int]     # [start, end) in ns on the trace's clock
_PY = re.compile(r"^\$(?P<file>[^:]+):\d+ (?P<fn>.+)$")
CONTROL_OPS = {"while", "conditional", "call"}


def op_label(name: str) -> str:
    """``%fusion.12 = bf16[4,256]{1,0:T(4,128)} fusion(...)`` ->
    ``fusion bf16[4,256]``."""
    head, _, rest = name.partition(" = ")
    base = re.sub(r"\.\d+$", "", head.lstrip("%"))
    shape = re.sub(r"\{[^}]*\}", "", rest.split(" ")[0]) if rest else ""
    return f"{base} {shape}".strip()


@dataclasses.dataclass
class Op:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Trace:
    window: Interval
    ops: Dict[str, List[Op]]            # device plane -> ops in the window
    host: List[Tuple[str, int, int]]    # python spans (name, start, end)

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy(self, device: str) -> List[Interval]:
        return union([(o.start, o.end) for o in self.ops[device]])

    @property
    def busy_s(self) -> float:
        """Seconds with an op running, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(total(self.busy(d)) for d in self.ops) * 1e-9 / len(self.ops)

    def spans(self, fn: str) -> List[Interval]:
        """Host intervals of the program function (or annotation) ``fn``."""
        return union([(s, e) for n, s, e in self.host
                      if n == fn or n.endswith(" " + fn)])

    def ops_within(self, device: str, spans: List[Interval]) -> List[Op]:
        """Ops of ``device`` that start inside one of ``spans``."""
        out, j = [], 0
        for o in sorted(self.ops[device], key=lambda o: o.start):
            while j < len(spans) and spans[j][1] <= o.start:
                j += 1
            if j < len(spans) and spans[j][0] <= o.start:
                out.append(o)
        return out

    def label(self, t: int) -> str:
        """Innermost host span open at time t."""
        best = None
        for n, s, e in self.host:
            if s <= t < e and (best is None or e - s < best[1]):
                best = (n, e - s)
        return best[0] if best else "no host span"

    def device_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        """Ops with most device seconds, averaged over the devices."""
        acc: Dict[str, int] = {}
        for ops in self.ops.values():
            for o in ops:
                label = op_label(o.name)
                if label.split(" ")[0] in CONTROL_OPS:
                    continue
                acc[label] = acc.get(label, 0) + o.end - o.start
        n = max(len(self.ops), 1)
        return [(k, v * 1e-9 / n) for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:top]]

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """Longest idle gaps of the first device, each labelled by the
        innermost host span open in its middle."""
        if not self.ops:
            return []
        busy = self.busy(self.devices[0])
        edges = [self.window[0]] + [x for iv in busy for x in iv] \
            + [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [(self.label((s + e) // 2), (e - s) * 1e-9)
                for s, e in gaps[:top]]


def union(ivs: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(ivs: List[Interval]) -> int:
    return sum(e - s for s, e in ivs)


def _short(name: str) -> str:
    m = _PY.match(name)
    if not m:
        return name
    return f"{os.path.basename(m['file'])} {m['fn']}"


def newest(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def reduce(path: str, window_span: str = "bench.step") -> Optional[Trace]:
    """The trace at ``path``, cut to its traced window; None when it has
    no ``window_span`` span."""
    pd = ProfileData.from_file(path)
    host: List[Tuple[str, int, int]] = []
    device_ops: Dict[str, List[Op]] = {}
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                device_ops.setdefault(plane.name, []).extend(
                    Op(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if not line.name.startswith("python"):
                    continue
                host.extend((_short(e.name), int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                            for e in line.events)
    steps = [(s, e) for n, s, e in host if n == window_span]
    if not steps:
        return None
    win = (min(s for s, _ in steps), max(e for _, e in steps))
    ops = {d: [o for o in v if win[0] <= o.start < win[1]]
           for d, v in device_ops.items()}
    ops = {d: v for d, v in ops.items() if v}
    return Trace(win, ops, [h for h in host
                            if h[2] > win[0] and h[1] < win[1]])
