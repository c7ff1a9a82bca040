"""engine.schedule_ms: host bookkeeping of one traced ``Engine.step`` as
self time, in ms: the ``engine.step`` span less the union of the
``*.dispatch``, ``*.fence``, ``engine.decode.sample`` and
``engine.chunk.first_token`` spans inside it (admission, page growth,
input preparation, finishing requests, gauges), mean over the traced
steps (bench/engine_spans.py)."""
from bench.engine_spans import schedule_ms


def read(ctx):
    return schedule_ms(ctx.trace)
