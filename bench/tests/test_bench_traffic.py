"""The traffic generator: deterministic per seed, lengths inside their
clips, medians as stated, and every seed offering the same work."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.traffic import ClosedLoopTraffic, load_mix, quantile_lengths

MIXES = ["chat", "rag"]
SEED = 2**31 + 977


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = load_mix(name)
    a = ClosedLoopTraffic(mix, 40, 49155, SEED)
    b = ClosedLoopTraffic(mix, 40, 49155, SEED)
    for c, k in [(0, 0), (7, 0), (39, 3)]:
        pa, oa = a.request(c, k)
        pb, ob = b.request(c, k)
        assert oa == ob and np.array_equal(pa, pb)
    pa, _ = a.request(7, 0)
    pc, _ = ClosedLoopTraffic(mix, 40, 49155, SEED + 1).request(7, 0)
    assert len(pa) != len(pc) or not np.array_equal(pa, pc)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_clips_and_vocab(name):
    mix = load_mix(name)
    t = ClosedLoopTraffic(mix, 43, 1000, 3)
    for c in range(43):
        p, o = t.request(c, 2)
        assert mix["prompt"]["min"] <= len(p) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= o <= mix["output"]["max"]
        assert p.min() >= 2 and p.max() < 1000
        assert len(p) + o <= 4096


@pytest.mark.parametrize("name", MIXES)
def test_medians_as_stated(name):
    mix = load_mix(name)
    for key in ("prompt", "output"):
        q = quantile_lengths(mix[key], 1001)
        assert abs(np.median(q) - mix[key]["median"]) <= 1
    # sigma: the quartiles of a lognormal sit at median * exp(+-0.674 sigma)
    q = quantile_lengths(mix["prompt"], 1001)
    ratio = np.percentile(q, 75) / np.median(q)
    assert abs(np.log(ratio) / 0.674 - mix["prompt"]["sigma"]) < 0.05


@pytest.mark.parametrize("seed", [1, 2**31 + 5, 2**40])
def test_every_seed_same_lengths_in_another_order(seed):
    """Every seed sends the same sequences of lengths; the seed deals them
    to the clients, so the order of sending differs."""
    mix = load_mix("chat")
    ref = ClosedLoopTraffic(mix, 43, 256000, 0)
    t = ClosedLoopTraffic(mix, 43, 256000, seed)
    for k in range(3):
        assert sorted(t.wave(k)[0]) == sorted(ref.wave(k)[0])
        assert t.wave(k)[1].tolist() == ref.wave(k)[1].tolist()
    work = lambda tr: sorted(
        tuple((len(tr.request(c, k)[0]), tr.request(c, k)[1])
              for k in range(3)) for c in range(43))
    assert work(t) == work(ref)
    assert t.sequence.tolist() != ref.sequence.tolist()


def test_first_requests_cut_to_what_remains():
    """The i-th sequence's first output is its drawn length times
    (i + 1/2) / clients; later requests are served whole."""
    mix = load_mix("chat")
    t = ClosedLoopTraffic(mix, 42, 256000, SEED)
    olen = t.wave(0)[1]
    firsts = []
    for c in range(42):
        i = int(t.sequence[c])
        n = t.request(c, 0)[1]
        assert n == max(1, -(-int(olen[i]) * (2 * i + 1) // 84))
        assert t.request(c, 1)[1] == t.wave(1)[1][i]
        firsts.append(n)
    assert np.mean(firsts) < 0.6 * np.mean(olen)


def test_unknown_loop_refused(tmp_path):
    (tmp_path / "x.json").write_text(json.dumps({"loop": "open"}))
    with pytest.raises(SystemExit):
        load_mix("x", tmp_path)
