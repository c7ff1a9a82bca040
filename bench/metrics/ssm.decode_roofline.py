"""ssm.decode_roofline: the least time the Mamba-2 decode state update
needs in the traced decode ticks (per tick, the live sequences' states
read and written once with their inputs and outputs over the HBM
bandwidth, or their operations over the bf16 peak if larger; the family's
``ssm_decode_work``), over the device time of the pinned kernel
``ssm_decode_fwd`` inside the engine's decode ticks, in %. Idle batch
rows, which update only the scratch slot, are not the work. Nothing to
read where the family has no recurrent state or the trace has no such
kernel."""
from bench.ssm_kernel import roofline_share


def read(ctx):
    work_of = getattr(ctx.family, "ssm_decode_work", None)
    if work_of is None:
        return None
    work = [work_of(ctx.model, len(t.contexts))
            for t in ctx.profiled_ticks("decode")]
    return roofline_share(ctx, work)
