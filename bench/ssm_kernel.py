"""The Mamba-2 decode state-update kernel as the device trace shows it,
for its roofline reader (bench/metrics/ssm.decode_roofline.py).

The kernel's ops are found by their HLO instruction's base name, which
the Pallas call pins (``ssm_decode_fwd``), inside the engine's
``_decode_tick`` host spans (the engine fences every dispatch, so a decode
tick's device work starts inside its span), as bench/paged_kernel.py
finds the paged-attention kernel.
"""
from bench import costs
from bench.xplane import op_label

KERNEL = "ssm_decode_fwd"


def roofline_share(ctx, work):
    """(share in %, note) of the kernel inside the decode ticks' spans;
    None when the trace has no such op. ``work`` holds (operations,
    bytes) per traced decode tick, from the family's ``ssm_decode_work``."""
    tr = ctx.trace
    if tr is None or not work:
        return None
    spans = tr.spans("_decode_tick")
    ns = sum(o.end - o.start for d in tr.devices
             for o in tr.ops_within(d, spans)
             if op_label(o.name).split(" ")[0] == KERNEL)
    if ns <= 0:
        return None
    need, bounds = 0.0, {"flops": 0, "bytes": 0}
    for flops, nbytes in work:
        s, which = costs.roofline_seconds(flops, nbytes, ctx.peaks)
        need += s
        bounds[which] += 1
    bound = max(bounds, key=bounds.get)
    return 100.0 * need / (ns * 1e-9), (
        f"{len(work)} ticks, bound by {bound} in {bounds[bound]}; kernel "
        f"device time {ns * 1e-9:.6f}s, least time {need:.6f}s")
