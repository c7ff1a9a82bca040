"""paged_attention.decode_roofline: the least time the paged-attention
kernel needs in the traced decode ticks (per tick, the larger of the
scores' and weighted sums' operations over the bf16 peak and the real
contexts' K/V bytes over the HBM bandwidth), over the kernel's device time
inside the engine's decode ticks, in %."""
from bench.paged_kernel import roofline_share


def read(ctx):
    work = [ctx.family.decode_attention_work(ctx.model, t.contexts)
            for t in ctx.profiled_ticks("decode")]
    return roofline_share(ctx, "_decode_tick", work)
