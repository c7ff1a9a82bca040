"""paged_attention.prefill_roofline: the same share as the decode one,
for the kernel inside the engine's prompt-chunk ticks: per chunk, the
real rows' score and weighted-sum operations and the K/V of the positions
they attend, read once."""
from bench.paged_kernel import roofline_share


def read(ctx):
    work = [ctx.family.chunk_attention_work(ctx.model, *t.span)
            for t in ctx.profiled_ticks("chunk")]
    return roofline_share(ctx, "_run_prefill_chunk", work)
