"""The readers of the engine's phase spans (bench/engine_spans.py and
bench/metrics/{engine.sample_ms, engine.schedule_ms,
device.idle_sample_share}.py): their interval arithmetic on hand-built
traces, None where the spans are missing, and the spans found in a real
jax.profiler trace of the engine on the CPU."""
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import engine_spans, harness, xplane
from bench.paged_kernel import kernel_ops
from bench.xplane import Op, Trace

READERS = ("engine.sample_ms", "engine.schedule_ms",
           "device.idle_sample_share")


def _trace():
    """Two steps in a 1000 ns window (times in ns):

    step A 100-900: a prompt chunk (dispatch 120-150, fence 150-250,
    first token 250-290, overlapped by a stray dispatch 140-160), a decode
    tick (dispatch 330-360, fence 360-600, sample 610-800), gauges;
    self time 800 - (170 + 270 + 190) = 170.
    step B 900-1000: a sample 920-960; self time 60.
    TPU:0 is busy 120-250, 330-600, 650-700 and 930-940: idle inside the
    samples 40 + 100 + 30 = 170. TPU:1 is busy all through."""
    host = [("bench.step", 0, 1000),
            ("engine.step", 100, 900),
            ("engine.chunk", 110, 300),
            ("engine.chunk.prepare", 110, 120),
            ("engine.chunk.dispatch", 120, 150),
            ("engine.prefill.dispatch", 140, 160),
            ("engine.chunk.fence", 150, 250),
            ("engine.chunk.first_token", 250, 290),
            ("engine.decode", 300, 850),
            ("engine.decode.grow", 300, 310),
            ("engine.decode.prepare", 310, 330),
            ("engine.decode.dispatch", 330, 360),
            ("engine.decode.fence", 360, 600),
            ("engine.decode.sample", 610, 800),
            ("array.py _value", 620, 700),
            ("engine.gauges", 860, 880),
            ("engine.step", 900, 1000),
            ("engine.decode.sample", 920, 960)]
    busy = [(120, 250), (330, 600), (650, 700), (930, 940)]
    ops = {"/device:TPU:0": [Op("op", s, e) for s, e in busy],
           "/device:TPU:1": [Op("op", 0, 1000)]}
    return Trace((0, 1000), ops, host)


def test_interval_helpers():
    assert engine_spans.intersect([(0, 10), (20, 30)], [(5, 25)]) == \
        [(5, 10), (20, 25)]
    assert engine_spans.intersect([(0, 10)], []) == []
    assert engine_spans.idle([(10, 20), (15, 30), (40, 60)], (0, 50)) == \
        [(0, 10), (30, 40)]
    assert engine_spans.idle([], (0, 50)) == [(0, 50)]
    assert engine_spans.clip([(0, 10), (20, 30), (40, 50)], 5, 25) == \
        [(5, 10), (20, 25)]


def test_sample_ms_is_the_mean_span():
    assert engine_spans.sample_ms(_trace()) == pytest.approx(115e-6)


def test_schedule_ms_is_the_steps_self_time():
    """Dispatch, fence and token choice come off each step once, however
    they overlap; the spans inside them (array.py) change nothing."""
    assert engine_spans.schedule_ms(_trace()) == pytest.approx(115e-6)


def test_idle_sample_share_is_idle_inside_samples():
    assert engine_spans.idle_sample_share(_trace()) == \
        pytest.approx(100.0 * 170 / 1000 / 2)


def test_readers_files_read_the_trace():
    ctx = types.SimpleNamespace(trace=_trace())
    got = [harness.load_reader(n)(ctx) for n in READERS]
    assert got == pytest.approx([115e-6, 115e-6, 8.5])


@pytest.mark.parametrize("trace", ["none", "no_spans", "no_ops"])
def test_readers_none_without_spans(trace):
    """A program without the spans (or a run without a trace) reads None
    everywhere; the device share also needs device ops."""
    tr = _trace()
    if trace == "none":
        tr = None
    elif trace == "no_spans":
        tr.host = [h for h in tr.host if not h[0].startswith("engine.")]
    else:
        tr.ops = {}
    ctx = types.SimpleNamespace(trace=tr)
    got = {n: harness.load_reader(n)(ctx) for n in READERS}
    if trace == "no_ops":
        assert got["device.idle_sample_share"] is None
        assert got["engine.sample_ms"] is not None
    else:
        assert set(got.values()) == {None}


def test_engine_spans_in_a_profiler_trace(tmp_path):
    """Two engine steps traced by jax.profiler inside ``bench.step``
    annotations: the reduction finds the engine's spans on the host's
    Python line, nested in the steps, and the readers read them."""
    from repro.configs import tiny_config
    from repro.models.api import build_model
    from repro.serving.engine import AdmissionPolicy, Engine, Request

    cfg = tiny_config("gemma2-2b")
    model = build_model(cfg)
    policy = AdmissionPolicy(
        hw_name="test", max_model_len=64, page_size=16, num_pages=64,
        max_batch=2, prefill_chunk=16, quant_bits=16, decode_slo_s=0.03,
        est_decode_s=0.0, est_prefill_s=0.0)
    engine = Engine(model, model.init(jax.random.PRNGKey(0)), policy)
    prompt = lambda n: np.arange(2, 2 + n, dtype=np.int32)
    engine.run([Request(rid=0, prompt=prompt(8), max_new=3)])   # compile
    engine.submit(Request(rid=1, prompt=prompt(8), max_new=4))
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.step"):
                engine.step()
    finally:
        jax.profiler.stop_trace()
    tr = xplane.reduce(xplane.newest(str(tmp_path)))
    assert tr is not None
    steps = tr.spans("engine.step")
    assert len(steps) == 2
    assert len(tr.spans("engine.chunk")) == 1
    samples = tr.spans("engine.decode.sample")
    assert len(samples) == 2
    for s, e in samples:
        assert any(a <= s and e <= b for a, b in steps)
    step_ms = sum(e - s for s, e in steps) / len(steps) * 1e-6
    assert 0 < engine_spans.sample_ms(tr) < step_ms
    assert 0 < engine_spans.schedule_ms(tr) < step_ms


SPAN_FIXTURE = Path(__file__).resolve().parent / "fixtures" / \
    "engine_tiny_spans.xplane.pb"


def test_readers_on_the_chip_fixture():
    """A trace recorded on a TPU v5e (record_span_fixture.py: three steps,
    each with a prompt chunk and a decode tick): the spans nest in the
    steps, the kernels start inside the spans of the phase that runs
    them, and the three readers read it."""
    tr = xplane.reduce(str(SPAN_FIXTURE))
    steps = engine_spans.intervals(tr, "engine.step")
    assert len(steps) == 3
    for name in ("engine.chunk", "engine.decode", "engine.decode.sample",
                 "engine.decode.fence", "engine.gauges"):
        ivs = engine_spans.intervals(tr, name)
        assert len(ivs) == 3, name
        assert all(any(a <= s and e <= b for a, b in steps)
                   for s, e in ivs), name
    dev = tr.devices[0]
    for span, fn in (("engine.decode", "_decode_tick"),
                     ("engine.chunk", "_run_prefill_chunk")):
        by_span = kernel_ops(tr.ops_within(dev, tr.spans(span)))
        assert by_span
        assert [o.name for o in by_span] == \
            [o.name for o in kernel_ops(tr.ops_within(dev, tr.spans(fn)))]
    ctx = types.SimpleNamespace(trace=tr)
    sample, schedule, idle = [harness.load_reader(n)(ctx) for n in READERS]
    step_ms = sum(e - s for s, e in steps) / len(steps) * 1e-6
    assert 0 < sample < step_ms and 0 < schedule < step_ms
    assert 0 < idle < 100.0 * (1 - tr.busy_s / tr.window_s)
