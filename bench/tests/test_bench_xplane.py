"""The trace reduction (bench/xplane.py) on a small trace recorded on a
TPU v5e (fixtures/engine_tiny.xplane.pb, made by record_trace_fixture.py:
three engine steps of a tiny dense model, each in a ``bench.step``
annotation), and its interval arithmetic."""
from pathlib import Path

import pytest

from bench import xplane
from bench.paged_kernel import kernel_ops

FIXTURE = Path(__file__).resolve().parent / "fixtures" / \
    "engine_tiny.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return xplane.reduce(str(FIXTURE))


def test_window_is_the_step_spans(trace):
    steps = trace.spans("bench.step")
    assert len(steps) == 3
    assert trace.window == (steps[0][0], steps[-1][1])
    assert trace.window_s > 0


def test_one_tpu_device_busy_inside_the_window(trace):
    assert trace.devices == ["/device:TPU:0"]
    assert 0 < trace.busy_s <= trace.window_s
    for o in trace.ops["/device:TPU:0"]:
        assert trace.window[0] <= o.start < trace.window[1]


def test_kernel_found_inside_engine_spans(trace):
    dev = trace.devices[0]
    dec = kernel_ops(trace.ops_within(dev, trace.spans("_decode_tick")))
    chunk = kernel_ops(trace.ops_within(
        dev, trace.spans("_run_prefill_chunk")))
    assert dec and chunk
    assert not set(map(id, dec)) & set(map(id, chunk))


def test_breakdown_lists(trace):
    ops = trace.device_ops()
    assert 0 < len(ops) <= 10
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    gaps = trace.idle_gaps()
    assert 0 < len(gaps) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in gaps)
    busy = trace.busy_s
    assert sum(s for _, s in gaps) <= trace.window_s - busy + 1e-9


def test_union_and_total():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xplane.total([(0, 3), (5, 8)]) == 6


def test_python_span_names_shortened():
    assert xplane._short("$/x/y/engine.py:474 _decode_tick") == \
        "engine.py _decode_tick"
    assert xplane._short("bench.step") == "bench.step"
