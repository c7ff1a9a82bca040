"""prefill.mfu: model operations of the real prompt rows of every chunk
(padding rows do no model work; the family's ``chunk_flops``) over the
summed chunk tick seconds times chips times the bf16 peak, in %. Ticks
that ran under the profiler are left out."""


def read(ctx):
    ts = ctx.unprofiled("chunk")
    sec = sum(t.measured_s for t in ts)
    if not sec:
        return None
    flops = sum(ctx.family.chunk_flops(ctx.model, *t.span) for t in ts)
    return 100.0 * flops / (sec * ctx.chips * ctx.peaks["bf16_flops_per_s"])
