"""Engine telemetry (serving/telemetry): metrics instruments, the
recorder's tick/span/stall record, the engine's phase span tree,
roofline calibration, Chrome trace export, back-compat views (stall_log
/ first_token_s), and the engine integration — including the JitLRU
no-retrace steady-state guarantee and per-shard mesh tags."""

import contextlib
import itertools
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import tiny_config
from repro.models.api import build_model
from repro.serving.engine import AdmissionPolicy, Engine, Request
from repro.serving.telemetry import (MetricsRegistry, Telemetry, TickEvent,
                                     calibrate, chrome_trace, summarize,
                                     write_chrome_trace)


def _policy(**kw):
    base = dict(hw_name="test", max_model_len=64, page_size=16,
                num_pages=10_000, max_batch=4, prefill_chunk=16,
                quant_bits=16, decode_slo_s=0.03, est_decode_s=0.0,
                est_prefill_s=0.0)
    base.update(kw)
    return AdmissionPolicy(**base)


def _req(rid, S, gen, *, vocab=512):
    rng = np.random.default_rng(rid)
    return Request(rid=rid, prompt=rng.integers(2, vocab, S, dtype=np.int64)
                   .astype(np.int32), max_new=gen)


def _fake_clock():
    """Deterministic 1-second-per-call clock for recorder unit tests."""
    counter = itertools.count()
    return lambda: float(next(counter))


def _tick(kind="decode", step=1, t=0.0, measured=1.0, predicted=0.5,
          batch=2, padded=4, q_len=1, **kw):
    return TickEvent(kind=kind, step=step, t_start=t, measured_s=measured,
                     predicted_s=predicted, batch=batch, padded_batch=padded,
                     q_len=q_len, tokens=batch, **kw)


# ---------------------------------------------------------------- metrics --
def test_counter_gauge_histogram():
    m = MetricsRegistry()
    m.counter("c").inc()
    m.counter("c").inc(4)
    assert m.counter("c").value == 5

    g = m.gauge("g")
    for v in (3.0, 1.0, 7.0):
        g.set(v)
    assert (g.value, g.min, g.max) == (7.0, 1.0, 7.0)

    h = m.histogram("h")
    for v in range(1, 101):
        h.observe(float(v))
    assert h.percentile(50) == 50.0
    assert h.percentile(99) == 99.0
    assert h.count == 100 and h.mean == 50.5
    assert m.histogram("empty").percentile(50) == 0.0


def test_registry_reset_preserves_references():
    """A monitor holding an instrument across Engine.reset_stats must see
    zeroed state through the SAME object (create-on-use would silently
    fork it otherwise)."""
    m = MetricsRegistry()
    c, g, h = m.counter("c"), m.gauge("g"), m.histogram("h")
    c.inc(3)
    g.set(2.0)
    h.observe(1.0)
    m.reset()
    assert m.counter("c") is c and c.value == 0
    assert m.gauge("g") is g and g.value is None
    assert m.histogram("h") is h and h.count == 0


def test_histogram_maxlen_bound():
    from repro.serving.telemetry import Histogram
    h = Histogram(maxlen=8)
    for v in range(100):
        h.observe(float(v))
    assert len(h.samples) <= 8
    assert h.count == 100            # count/total keep the full history


# --------------------------------------------------------------- recorder --
def test_recorder_ticks_spans_and_views():
    tel = Telemetry(clock=_fake_clock())
    tel.start_clock()                         # t0 = 0.0
    tel.seq_event(7, "enqueue", prompt=8)     # t = 1.0
    tel.seq_event(7, "admit", slot=0)         # t = 2.0
    tel.tick(_tick(kind="chunk", q_len=16))
    tel.seq_event(7, "first_token", token=3)  # t = 3.0
    tel.stall(0.25, 0.125)
    tel.tick(_tick(kind="decode"))

    assert [e.kind for e in tel.ticks] == ["chunk", "decode"]
    assert tel.metrics.counter("ticks.decode").value == 1
    assert tel.metrics.counter("ticks.chunk").value == 1
    assert tel.stall_log_view() == [0.25]
    assert tel.first_token_view() == {7: 3.0}
    assert tel.queue_wait_seconds() == [1.0]
    span = tel.spans[7]
    assert [e.kind for e in span.events] == ["enqueue", "admit",
                                             "first_token"]

    tel.reset()
    assert not tel.ticks and not tel.spans and not tel.stalls
    assert tel.t0 is None
    assert tel.metrics.counter("ticks.decode").value == 0


def test_recorder_first_token_keeps_first_edge():
    """A preempted request re-prefills and emits a second first_token
    edge; the TTFT view must keep the first (the token was already
    served once)."""
    tel = Telemetry(clock=_fake_clock())
    tel.start_clock()
    tel.seq_event(0, "first_token", token=1)   # t = 1.0
    tel.seq_event(0, "preempt")
    tel.seq_event(0, "requeue")
    tel.seq_event(0, "first_token", token=1)   # t = 4.0 (recompute)
    assert tel.first_token_view() == {0: 1.0}
    assert tel.spans[0].count("first_token") == 2


def test_default_span_is_a_profiler_annotation():
    """Without a factory a span is a jax.profiler.TraceAnnotation, and the
    package itself stays importable without jax (the import is lazy)."""
    tel = Telemetry()
    span = tel.span("engine.step")
    assert isinstance(span, jax.profiler.TraceAnnotation)
    with span:
        pass
    code = ("import sys; sys.modules['jax'] = None; "
            "import repro.serving.telemetry as t; t.Telemetry()")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60,
                   env=env)


# -------------------------------------------------------------- calibrate --
def test_calibrate_recovers_scale():
    """measured = 2 * predicted exactly -> scale 2.0, rel_err 1.0."""
    ticks = [_tick(measured=2.0 * p, predicted=p, t=float(i))
             for i, p in enumerate((0.5, 1.0, 1.5))]
    report = calibrate(ticks)
    (g,) = report.groups
    assert g.kind == "decode" and g.n == 3
    assert g.scale == pytest.approx(2.0)
    assert g.rel_err == pytest.approx(1.0)
    assert report.scale_factors()["decode"] == pytest.approx(2.0)
    assert report.rel_err_by_kind()["decode"] == pytest.approx(1.0)
    assert "scale[decode]" in report.format()


def test_calibrate_unpredicted_group_is_none():
    """hw_name='test' policies predict 0.0 — measured percentiles still
    report, scale/rel_err must be None (not inf/nan)."""
    ticks = [_tick(measured=0.5, predicted=0.0),
             _tick(kind="chunk", q_len=16, measured=1.0, predicted=0.5)]
    report = calibrate(ticks)
    scales = report.scale_factors()
    assert scales["decode"] is None
    assert scales["chunk"] == pytest.approx(2.0)
    d = report.as_dict()
    # JSON-safe: the bench serializes this with allow_nan semantics
    json.dumps(d, allow_nan=False)


def test_calibrate_groups_by_shape():
    ticks = [_tick(padded=4, measured=1.0, predicted=1.0),
             _tick(padded=8, measured=2.0, predicted=1.0)]
    report = calibrate(ticks)
    assert {(g.batch, g.q_len) for g in report.groups} == {(4, 1), (8, 1)}
    # sample-weighted per-kind scale blends both groups
    assert report.scale_factors()["decode"] == pytest.approx(1.5)


# ----------------------------------------------------------------- engine --
@pytest.fixture(scope="module")
def gemma_tiny():
    cfg = tiny_config("gemma2-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def test_engine_records_ticks_and_spans(gemma_tiny, tmp_path):
    model, params = gemma_tiny
    engine = Engine(model, params, _policy())
    reqs = [_req(0, 20, 4), _req(1, 8, 6)]
    engine.run(reqs)

    tel = engine.telemetry
    kinds = {ev.kind for ev in tel.ticks}
    assert kinds == {"chunk", "decode"}       # chunked mode, no buckets
    assert all(ev.measured_s > 0.0 for ev in tel.ticks)
    # hw_name="test" is unknown to the roofline -> no prediction
    assert all(ev.predicted_s == 0.0 for ev in tel.ticks)
    decode = [ev for ev in tel.ticks if ev.kind == "decode"]
    assert all(ev.padded_batch == 4 for ev in decode)
    assert all(0 < ev.batch <= ev.padded_batch for ev in decode)
    assert sum(ev.tokens for ev in decode) == engine.stats["decode_tokens"]
    # the first chunk tick carries the admissions' page allocations
    chunks = [ev for ev in tel.ticks if ev.kind == "chunk"]
    assert chunks[0].pages_allocated > 0
    # every page returned by drain: lifetime counters agree
    a = engine.kv.allocator
    assert a.total_allocated == a.total_freed

    # spans: full lifecycle for both requests
    for r in reqs:
        span = tel.spans[r.rid]
        for kind in ("enqueue", "admit", "first_token", "finish",
                     "release"):
            assert span.count(kind) == 1, (r.rid, kind)
        assert span.count("chunk") == -(-len(r.prompt) // 16)
    assert set(engine.first_token_s) == {0, 1}
    assert all(t >= 0.0 for t in engine.first_token_s.values())
    assert engine.stall_log == tel.stall_log_view()

    # metrics rolled up
    m = tel.metrics
    assert m.counter("ticks.decode").value == engine.stats["decode_ticks"]
    assert m.gauge("pool.occupancy").value is not None
    assert m.gauge("pool.min_free").value is not None
    assert "telemetry summary" in summarize(tel)

    # reset drops the record (bench re-timing path)
    engine.reset_stats()
    assert not tel.ticks and not tel.spans and engine.stall_log == []
    assert engine.first_token_s == {}


def test_engine_chrome_trace_is_valid(gemma_tiny, tmp_path):
    model, params = gemma_tiny
    engine = Engine(model, params, _policy(max_batch=2))
    engine.run([_req(0, 20, 4), _req(1, 8, 3)])

    path = tmp_path / "trace.json"
    write_chrome_trace(engine.telemetry, str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert evs, "empty trace"
    # finite by construction: re-serialization with allow_nan=False holds
    json.dumps(doc, allow_nan=False)

    slices = [e for e in evs if e.get("ph") == "X"]
    assert slices and all(e["dur"] > 0.0 for e in slices)
    assert {e["name"] for e in slices} == {"chunk", "decode"}
    counters = [e for e in evs if e.get("ph") == "C"]
    assert {e["name"] for e in counters} == {"pool free pages",
                                             "queue depth"}
    # async request spans balance per id
    begins = [e["id"] for e in evs if e.get("ph") == "b"]
    ends = [e["id"] for e in evs if e.get("ph") == "e"]
    assert sorted(begins) == sorted(ends) == [0, 1]
    marks = [e for e in evs if e.get("ph") == "n"]
    assert {m["args"]["event"] for m in marks} >= {"admit", "chunk",
                                                   "first_token", "finish"}


def test_engine_preemption_span_and_ttft(gemma_tiny):
    """Forced preemption (pool too small for both lifetimes): the victim's
    span records preempt/requeue, its TTFT keeps the first served token,
    and the decode tick that preempted carries the page deltas."""
    model, params = gemma_tiny
    engine = Engine(model, params, _policy(max_batch=2, num_pages=7))
    engine.run([_req(0, 12, 44), _req(1, 12, 44)])
    assert engine.stats["preemptions"] >= 1

    tel = engine.telemetry
    victims = [rid for rid, s in tel.spans.items() if s.count("preempt")]
    assert victims
    for rid in victims:
        span = tel.spans[rid]
        assert span.count("requeue") == span.count("preempt")
        assert span.count("admit") == span.count("preempt") + 1
        if span.count("first_token") > 1:
            # TTFT pinned to the FIRST first_token edge
            first = span.first("first_token").t
            assert engine.first_token_s[rid] == tel.rel(first)
    preempt_ticks = [ev for ev in tel.ticks if ev.preempted]
    assert preempt_ticks and all(ev.kind == "decode"
                                 for ev in preempt_ticks)
    assert sum(ev.preempted for ev in tel.ticks) == \
        engine.stats["preemptions"]
    assert tel.metrics.counter("preemptions").value == \
        engine.stats["preemptions"]
    # low-water mark: the pool really was driven near empty
    assert tel.metrics.gauge("pool.min_free").value <= 1


def test_engine_steady_state_decode_never_retraces(gemma_tiny):
    """Satellite guarantee: after warmup, decode ticks reuse ONE compiled
    executable — the jit cache-size gauge stays at 1 and the per-shape
    LRUs see no new misses across a second identical run."""
    model, params = gemma_tiny
    engine = Engine(model, params, _policy(max_batch=2))
    reqs = [_req(0, 20, 6), _req(1, 8, 4)]
    engine.run(reqs)
    m = engine.telemetry.metrics
    decode_cache = m.gauge("jit.decode.cache_size").value
    chunk_cache = m.gauge("jit.chunk.cache_size").value
    if decode_cache >= 0:                 # PjitFunction exposes _cache_size
        assert decode_cache == 1.0
    if chunk_cache >= 0:
        assert chunk_cache == 1.0
    misses_before = engine._prefill_jits.misses
    writer_misses_before = engine.kv._write_jit.misses

    engine.reset_stats()
    engine.run(reqs)                      # steady state: same shapes
    if decode_cache >= 0:
        assert m.gauge("jit.decode.cache_size").value == 1.0
    assert engine._prefill_jits.misses == misses_before
    assert engine.kv._write_jit.misses == writer_misses_before
    # chunked mode: no padding-bucket jits at all, so misses stay 0 and
    # the hit/miss gauges report the same
    assert m.gauge("jit.prefill.misses").value == 0.0


def test_engine_mesh_tags_on_ticks(gemma_tiny):
    """A 1x1 mesh engine stamps every tick event with its shard layout
    (the multi-device CI job exercises real meshes; the tags ride the
    same path here on one device)."""
    from repro.launch.mesh import make_serving_mesh
    model, params = gemma_tiny
    mesh = make_serving_mesh(model=1, data=1)
    engine = Engine(model, params, _policy(max_batch=2), mesh=mesh)
    engine.run([_req(0, 8, 3)])
    assert engine.telemetry.ticks
    for ev in engine.telemetry.ticks:
        assert ev.tags["mesh_model"] == 1
        assert ev.tags["mesh_data"] == 1
        assert ev.tags["mesh_devices"] == 1
    # tags survive into the Chrome trace slice args
    doc = chrome_trace(engine.telemetry)
    x = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert all(e["args"]["mesh_model"] == 1 for e in x)


def test_engine_roofline_prediction_on_known_hw(gemma_tiny):
    """With a real hardware target the predictor prices every tick kind
    (> 0), predictions are constant per shape, and calibrate() fits a
    finite scale."""
    from repro.core.hardware_model import V5E_EDGE
    from repro.serving.engine import derive_policy
    import dataclasses
    model, params = gemma_tiny
    policy = derive_policy(model.cfg, V5E_EDGE, max_model_len=64,
                           param_bytes=model.param_bytes())
    policy = dataclasses.replace(policy, max_batch=2)
    engine = Engine(model, params, policy)
    engine.run([_req(0, 20, 4), _req(1, 8, 3)])
    tel = engine.telemetry
    assert all(ev.predicted_s > 0.0 for ev in tel.ticks)
    for kind in ("chunk", "decode"):
        preds = {ev.predicted_s for ev in tel.ticks if ev.kind == kind
                 and ev.padded_batch == 2}
        assert len(preds) <= 1            # memoized per shape
    report = calibrate(tel.ticks)
    for kind, scale in report.scale_factors().items():
        assert scale is not None and np.isfinite(scale) and scale > 0.0
    for kind, err in report.rel_err_by_kind().items():
        assert err is not None and np.isfinite(err)


# ------------------------------------------------------------ phase spans --
# the engine's span tree: each span and the spans it may open inside
SPAN_PARENTS = {
    "engine.step": {None},
    "engine.admit": {"engine.step"},
    "engine.prefill": {"engine.admit"},
    "engine.prefill.dispatch": {"engine.prefill"},
    "engine.prefill.fence": {"engine.prefill"},
    "engine.chunk": {"engine.step"},
    "engine.chunk.prepare": {"engine.chunk"},
    "engine.chunk.dispatch": {"engine.chunk"},
    "engine.chunk.fence": {"engine.chunk"},
    "engine.chunk.first_token": {"engine.chunk"},
    "engine.decode": {"engine.step"},
    "engine.decode.grow": {"engine.decode"},
    "engine.decode.prepare": {"engine.decode"},
    "engine.decode.dispatch": {"engine.decode"},
    "engine.decode.fence": {"engine.decode"},
    "engine.decode.sample": {"engine.decode"},
    "engine.finish": {"engine.step", "engine.admit"},
    "engine.gauges": {"engine.step"},
}


class SpanTree:
    """A span factory that records each span opened, with its parent."""

    def __init__(self):
        self.opened = []             # (name, parent name or None)
        self._stack = []

    @contextlib.contextmanager
    def __call__(self, name):
        self.opened.append((name, self._stack[-1] if self._stack else None))
        self._stack.append(name)
        try:
            yield
        finally:
            self._stack.pop()


def _spans_per_step(engine, tree):
    """The names of the spans each step opened, step by step."""
    steps = []
    while engine.scheduler.has_work():
        before = len(tree.opened)
        engine.step()
        steps.append(sorted(n for n, _ in tree.opened[before:]))
    return steps


@pytest.mark.parametrize("chunked", [True, False])
def test_engine_span_tree(gemma_tiny, chunked):
    """Every span the engine opens has a name of the tree and opens inside
    its parent; chunked mode opens every chunk and decode phase, and the
    whole-prompt mode its prefill phases inside admission."""
    model, params = gemma_tiny
    tree = SpanTree()
    engine = Engine(model, params, _policy(max_batch=2),
                    chunked_prefill=chunked,
                    telemetry=Telemetry(span_factory=tree))
    engine.run([_req(0, 20, 4), _req(1, 8, 1)])
    assert tree.opened and not tree._stack
    for name, parent in tree.opened:
        assert parent in SPAN_PARENTS[name], (name, parent)
    names = {n for n, _ in tree.opened}
    steps = sum(n == "engine.step" for n, _ in tree.opened)
    assert steps == engine._step_idx
    assert sum(n == "engine.gauges" for n, _ in tree.opened) == steps
    assert sum(n == "engine.finish" for n, _ in tree.opened) == 2
    decode = {n for n in SPAN_PARENTS if n.startswith("engine.decode")}
    assert decode <= names
    if chunked:
        chunk = {n for n in SPAN_PARENTS if n.startswith("engine.chunk")}
        assert chunk <= names
        assert not {n for n in names if n.startswith("engine.prefill")}
        assert sum(n == "engine.chunk.dispatch" for n, _ in tree.opened) \
            == engine.stats["prefill_chunks"]
    else:
        prefill = {n for n in SPAN_PARENTS if n.startswith("engine.prefill")}
        assert prefill <= names
        assert ("engine.finish", "engine.admit") in tree.opened
    assert sum(n == "engine.decode.sample" for n, _ in tree.opened) \
        == engine.stats["decode_ticks"]


def test_engine_spans_per_step_do_not_grow_with_batch(gemma_tiny):
    """No span is opened per batch slot or per sequence: one request opens
    the same spans in every step at max_batch 2 and 8, and a step that
    only decodes opens the same spans for one sequence as for six."""
    model, params = gemma_tiny

    def run(max_batch, reqs):
        tree = SpanTree()
        engine = Engine(model, params, _policy(max_batch=max_batch),
                        telemetry=Telemetry(span_factory=tree))
        for r in reqs:
            engine.submit(r)
        return _spans_per_step(engine, tree)

    one = run(2, [_req(0, 20, 5)])
    assert one == run(8, [_req(0, 20, 5)])
    assert len(one) >= 4

    def decode_only(steps):
        return {tuple(names) for names in steps
                if "engine.decode" in names and "engine.chunk" not in names
                and "engine.finish" not in names}

    six = run(8, [_req(i, 8 + 3 * i, 12) for i in range(6)])
    assert decode_only(one) and decode_only(one) == decode_only(six)


def test_occupancy_gauge_matches_page_walk():
    """The per-step occupancy gauge (kept from each sequence's count of
    trimmed pages) equals the walk over every page slot it replaced, on
    an all-local model whose pages are trimmed behind the window."""
    cfg = tiny_config("gemma2-2b").replace(attn_pattern=("local",))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(model, params, _policy(max_model_len=96, num_pages=100,
                                           max_batch=2))
    engine.submit(_req(0, 8, 70))
    engine.submit(_req(1, 30, 40))
    page = engine.kv.allocator.page_size
    seen = set()
    while engine.scheduler.has_work():
        engine.step()
        used = 0
        for seq in engine.scheduler.active.values():
            live = sum(p != 0 for p in seq.pages)
            trimmed = len(seq.pages) - live
            assert seq.trimmed == trimmed
            used += max(min(seq.pos - trimmed * page, live * page), 0)
            seen.add(trimmed)
        cap = engine.kv.allocator.num_allocated * page
        want = used / cap if cap else 0.0
        assert engine.telemetry.metrics.gauge("pool.occupancy").value == want
    assert max(seen) >= 2               # pages were trimmed on the way
