"""End-to-end metric arithmetic: tails over every sample of the window,
moved by one stall; tokens counted only as they became visible."""
import numpy as np

from bench import stats


def window(stall: float = 0.0):
    w = stats.Window(t_open=100.0)
    t = 100.0
    for rid in range(20):
        w.sent[rid] = 100.0
    for step in range(50):
        t += 0.1 + (stall if step == 30 else 0.0)
        w.steps.append((t - 0.1, t))
        for rid in range(20):
            w.stamp(rid, step + 1, t)
    w.t_close = t
    return w


def test_tails_over_all_samples():
    w = window()
    e = stats.end_to_end(w)
    assert np.isclose(e["ttft_p90_s"], 0.1)
    assert np.isclose(e["itl_p95_ms"], 100.0)
    assert e["output_tok_s"] == 20 * 50 / w.seconds
    assert len(stats.itl_s(w)) == 20 * 49


def test_a_stall_moves_the_tail():
    calm, stalled = stats.end_to_end(window()), \
        stats.end_to_end(window(stall=2.0))
    # one step in fifty stalls by 2 s: 20 of 980 gaps (2%) are slow, so
    # p95 stays put and the rate drops; a stall on six steps in a
    # hundred (6%) moves p95
    assert np.isclose(stalled["itl_p95_ms"], calm["itl_p95_ms"])
    assert stalled["output_tok_s"] < calm["output_tok_s"] * 0.75
    w = stats.Window(t_open=0.0)
    t = 0.0
    w.sent[0] = 0.0
    for step in range(100):
        t += 0.1 + (2.0 if step in (10, 40, 70, 80, 90, 95) else 0.0)
        w.stamp(0, step + 1, t)
    w.t_close = t
    assert stats.end_to_end(w)["itl_p95_ms"] > 1000.0


def test_tokens_counted_once_when_visible():
    w = stats.Window(t_open=0.0)
    w.sent[1] = 0.0
    w.stamp(1, 2, 0.5)          # two tokens visible at once
    w.stamp(1, 2, 0.7)          # nothing new
    w.stamp(1, 3, 0.9)
    w.t_close = 1.0
    assert w.token_t[1] == [0.5, 0.5, 0.9]
    assert stats.tokens(w) == 3
    assert stats.itl_s(w) == [0.0, 0.4]
    assert stats.end_to_end(w)["output_tok_s"] == 3.0


def test_empty_window_has_no_tail():
    w = stats.Window(t_open=0.0, t_close=1.0)
    e = stats.end_to_end(w)
    assert e["ttft_p90_s"] is None and e["itl_p95_ms"] is None


def test_window_opens_on_a_running_loop():
    """Tokens visible when the window opens are not counted, and TTFT is
    taken only of requests whose first token falls in the window."""
    w = stats.Window(t_open=10.0)
    w.sent.update({1: 2.0, 2: 9.0, 3: 10.5})
    w.before[1] = 40                       # decoding since before the open
    w.stamp(1, 41, 10.2)
    w.stamp(1, 42, 10.4)
    w.stamp(2, 1, 10.3)                    # sent before, first token inside
    w.stamp(3, 1, 11.0)
    w.t_close = 12.0
    assert stats.tokens(w) == 4
    assert np.allclose(sorted(stats.ttft_s(w)), [0.5, 1.3])
    assert np.allclose(stats.itl_s(w), [0.2])
