"""The engine's phase spans as the device trace shows them, shared by the
readers of engine.sample_ms, engine.schedule_ms and
device.idle_sample_share (bench/metrics/).

The engine opens each phase of its host loop through ``Telemetry.span``,
a ``jax.profiler.TraceAnnotation``: the span lands on the host's Python
line of the trace under the name it was opened with (``engine.step``,
``engine.decode.sample``, ...; the tree is in serving/engine/__init__.py),
on the clock of the device ops. A program without these spans leaves
every reader here with None.
"""
from typing import List, Optional

from bench.xplane import Interval, total, union

STEP = "engine.step"
SAMPLE = "engine.decode.sample"
# host time of a step that is not its bookkeeping: handing work to the
# device and waiting for it, and choosing tokens
DEVICE_PHASES = (".dispatch", ".fence")
TOKEN_CHOICE = (SAMPLE, "engine.chunk.first_token")


def intervals(tr, name: str) -> List[Interval]:
    """Every host span named ``name``, one interval each, in time order."""
    return sorted((s, e) for n, s, e in tr.host if n == name)


def clip(ivs: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in ivs if s < hi and e > lo]


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two unions (sorted, disjoint interval lists)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle(busy: List[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that ``busy`` (a union) leaves uncovered."""
    out, t = [], window[0]
    for s, e in clip(busy, *window):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < window[1]:
        out.append((t, window[1]))
    return out


def sample_ms(tr) -> Optional[float]:
    """Mean duration of ``engine.decode.sample``, in ms."""
    ivs = intervals(tr, SAMPLE) if tr is not None else []
    return total(ivs) / len(ivs) * 1e-6 if ivs else None


def schedule_ms(tr) -> Optional[float]:
    """Mean over the traced ``engine.step``s of the step's duration less
    the union of its dispatch, fence and token-choice spans, in ms."""
    steps = intervals(tr, STEP) if tr is not None else []
    if not steps:
        return None
    not_schedule = union([
        (s, e) for n, s, e in tr.host if n.startswith("engine.")
        and (n.endswith(DEVICE_PHASES) or n in TOKEN_CHOICE)])
    self_ns = [(e - s) - total(clip(not_schedule, s, e)) for s, e in steps]
    return sum(self_ns) / len(self_ns) * 1e-6


def idle_sample_share(tr) -> Optional[float]:
    """Share of the traced window in which the device runs no op while the
    host is inside ``engine.decode.sample``, averaged over the devices,
    in %."""
    if tr is None or not tr.ops or tr.window_s <= 0:
        return None
    samples = union(intervals(tr, SAMPLE))
    if not samples:
        return None
    ns = [total(intersect(idle(tr.busy(d), tr.window), samples))
          for d in tr.devices]
    return 100.0 * sum(ns) * 1e-9 / len(ns) / tr.window_s
