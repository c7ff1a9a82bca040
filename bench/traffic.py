"""The one traffic generator. A traffic mix is a data file,
``bench/traffic/<name>.json``, that this module reads; nothing about a mix
lives in code.

A mix file holds::

    {"loop": "closed",
     "clients": "max_batch" | <int>,
     "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                "min": 64, "max": 3072},
     "output": {... the same keys ...},
     "why": "..."}

Closed loop: every client sends its next request the moment its previous
one has finished, with no think time. Lengths come in waves: a client's
k-th request belongs to wave k, and every wave holds the same set of
lengths, the stratified quantiles of the distribution (one per client),
in an order that is the same for every seed. Prompt and output lengths
are dealt independently. The seed deals these sequences of lengths to the
clients, so every seed offers the same work, sent in another order.
Token ids are uniform over ``[2, vocab)``, drawn from (seed, client, k)
alone, so a request's tokens do not depend on when it was sent.

A client's first request is cut to what would remain of it in a loop
that had long been running: the i-th sequence's first output is its drawn
length times (i + 1/2) / clients. So the first requests end spread out
in time, and the loop reaches its steady state soon after the first
prompts are in.
"""
from __future__ import annotations

import json
import math
import statistics
from pathlib import Path
from typing import Dict, Tuple

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str, root: Path = TRAFFIC_DIR) -> dict:
    path = root / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"bench: no traffic mix {path}")
    mix = json.loads(path.read_text())
    if mix.get("loop") != "closed":
        raise SystemExit(f"bench: traffic {name}: only closed loops are "
                         f"generated, got {mix.get('loop')!r}")
    return mix


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """The n stratified quantiles (i + 1/2)/n of a clipped lognormal."""
    if dist["dist"] != "lognormal":
        raise SystemExit(f"bench: unknown length distribution "
                         f"{dist['dist']!r}")
    z = statistics.NormalDist()
    mu = math.log(dist["median"])
    out = [math.exp(mu + dist["sigma"] * z.inv_cdf((i + 0.5) / n))
           for i in range(n)]
    return np.clip(np.rint(out), dist["min"], dist["max"]).astype(np.int64)


def _rng(seed: int, *words: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**63, seed >> 63, *words])


class ClosedLoopTraffic:
    """Requests of a closed loop: ``request(client, k)`` is the k-th
    request client ``client`` sends, as (prompt token ids, max_new)."""

    def __init__(self, mix: dict, clients: int, vocab: int, seed: int):
        self.mix, self.clients, self.vocab, self.seed = mix, clients, vocab, seed
        self._prompt = quantile_lengths(mix["prompt"], clients)
        self._output = quantile_lengths(mix["output"], clients)
        self._waves: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self.sequence = _rng(seed, 3).permutation(clients)  # client -> i

    def wave(self, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """(prompt lengths, output lengths) by sequence for wave k, the
        same for every seed."""
        if k not in self._waves:
            rng = np.random.default_rng([1, k])
            self._waves[k] = (rng.permutation(self._prompt),
                              rng.permutation(self._output))
        return self._waves[k]

    def request(self, client: int, k: int) -> Tuple[np.ndarray, int]:
        plen, olen = self.wave(k)
        i = int(self.sequence[client])
        toks = _rng(self.seed, 2, client, k).integers(
            2, self.vocab, int(plen[i]))
        n = int(olen[i])
        if k == 0:
            n = max(1, math.ceil(n * (i + 0.5) / self.clients))
        return toks.astype(np.int32), n
