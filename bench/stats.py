"""End-to-end metrics of one measured window, from the harness's own clock.

The harness stamps every generated token with the host time at which it
became visible (the end of the ``Engine.step`` that produced it) and every
request with the time its client sent it. The window opens on a loop
that has been running: tokens visible at the open are not counted, and
TTFT is taken of the requests whose first token falls in the window.
Tails are taken over every sample of the window, never over medians of
parts of it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Window:
    t_open: float = 0.0
    t_close: float = 0.0
    sent: Dict[int, float] = dataclasses.field(default_factory=dict)
    prompt_len: Dict[int, int] = dataclasses.field(default_factory=dict)
    max_new: Dict[int, int] = dataclasses.field(default_factory=dict)
    origin: Dict[int, Tuple[int, int]] = dataclasses.field(
        default_factory=dict)                 # rid -> (client, k)
    token_t: Dict[int, List[float]] = dataclasses.field(default_factory=dict)
    finished: Dict[int, np.ndarray] = dataclasses.field(default_factory=dict)
    steps: List[Tuple[float, float]] = dataclasses.field(default_factory=list)
    before: Dict[int, int] = dataclasses.field(
        default_factory=dict)                 # rid -> tokens visible at open
    pre_steps: int = 0                        # engine steps before the open
    pre_done: int = 0                         # requests finished before it

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def stamp(self, rid: int, n_visible: int, t: float) -> None:
        """``rid`` has ``n_visible`` tokens visible at time ``t``."""
        ts = self.token_t.setdefault(rid, [])
        ts.extend([t] * (n_visible - self.before.get(rid, 0) - len(ts)))


def percentile(xs, q: float) -> Optional[float]:
    if not len(xs):
        return None
    return float(np.percentile(np.asarray(xs, np.float64), q))


def ttft_s(w: Window) -> List[float]:
    return [ts[0] - w.sent[rid] for rid, ts in w.token_t.items()
            if ts and not w.before.get(rid)]


def itl_s(w: Window) -> List[float]:
    out: List[float] = []
    for ts in w.token_t.values():
        out.extend(np.diff(ts).tolist())
    return out


def tokens(w: Window) -> int:
    return sum(len(ts) for ts in w.token_t.values())


def end_to_end(w: Window) -> Dict[str, Optional[float]]:
    p90 = percentile(ttft_s(w), 90)
    p95 = percentile(itl_s(w), 95)
    return {"ttft_p90_s": p90,
            "itl_p95_ms": None if p95 is None else p95 * 1e3,
            "output_tok_s": tokens(w) / w.seconds if w.seconds > 0 else None}
