"""prefill.mfu: model operations of the real prompt rows of every chunk
(padding rows do no model work) over the summed chunk tick seconds times
chips times the bf16 peak, in %. Ticks that ran under the profiler are
left out."""
from bench import costs


def read(ctx):
    ts = ctx.unprofiled("chunk")
    sec = sum(t.measured_s for t in ts)
    if not sec:
        return None
    flops = sum(costs.chunk_flops(ctx.model, *t.span) for t in ts)
    return 100.0 * flops / (sec * ctx.chips * ctx.peaks["bf16_flops_per_s"])
