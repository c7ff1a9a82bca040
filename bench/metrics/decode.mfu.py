"""decode.mfu: model operations of the real decoded tokens (each at its
own context, with the unembedding; the family's ``decode_flops``) over
the summed decode tick seconds times chips times the bf16 peak, in %.
Padded batch slots do no model work. Ticks that ran under the profiler
are left out."""


def read(ctx):
    ts = ctx.unprofiled("decode")
    sec = sum(t.measured_s for t in ts)
    if not sec:
        return None
    flops = sum(ctx.family.decode_flops(ctx.model, t.contexts) for t in ts)
    return 100.0 * flops / (sec * ctx.chips * ctx.peaks["bf16_flops_per_s"])
