"""prefill.chunk_ms: mean fenced host time of a prompt-chunk tick
(``transformer.prefill_chunk_paged`` under ``Engine._chunk_prefill``), in
ms, over the ticks of the window that ran without the profiler."""


def read(ctx):
    ts = [t.measured_s for t in ctx.unprofiled("chunk")]
    return sum(ts) / len(ts) * 1e3 if ts else None
