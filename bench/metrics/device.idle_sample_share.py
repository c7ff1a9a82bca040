"""device.idle_sample_share: share of the traced window in which no
operation runs on the device while the host is inside
``engine.decode.sample``, averaged over the chips, in %: the part of
``device.idle_share`` that token choice on the host costs
(bench/engine_spans.py)."""
from bench.engine_spans import idle_sample_share


def read(ctx):
    return idle_sample_share(ctx.trace)
