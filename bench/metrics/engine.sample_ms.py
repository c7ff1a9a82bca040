"""engine.sample_ms: mean host time of ``engine.decode.sample`` in the
traced window, in ms: the decode tick's logits pulled to the host and a
token chosen for every row (bench/engine_spans.py)."""
from bench.engine_spans import sample_ms


def read(ctx):
    return sample_ms(ctx.trace)
