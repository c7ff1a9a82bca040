"""decode.step_ms: mean fenced host time of a decode tick
(``transformer.decode_step_paged`` under ``Engine._decode``), in ms, over
the ticks of the window that ran without the profiler."""


def read(ctx):
    ts = [t.measured_s for t in ctx.unprofiled("decode")]
    return sum(ts) / len(ts) * 1e3 if ts else None
