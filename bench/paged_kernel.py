"""The paged-attention kernel as the device trace shows it, shared by its
two roofline readers (bench/metrics/paged_attention.*_roofline.py).

The kernel's ops are found by their HLO instruction's base name, which
the Pallas call takes from the kernel's entry function
(``paged_attention_fwd``, ``paged_prefill_fwd`` and their ``_quant``
twins); the engine function whose host span
encloses an op's start (``_decode_tick`` or ``_run_prefill_chunk``; the
engine fences every dispatch, so its device work starts inside it) says
which of the two uses it was.
"""
import re

from bench import costs
from bench.xplane import op_label

KERNEL = re.compile(r"paged_\w*fwd")


def kernel_ops(ops):
    return [o for o in ops
            if KERNEL.fullmatch(op_label(o.name).split(" ")[0])]


def roofline_share(ctx, engine_fn, work):
    """(share in %, note) of the kernel inside ``engine_fn``'s spans; None
    when the trace has no such kernel op. ``work`` holds (operations,
    bytes) per call, from the family's counts (``ctx.family``)."""
    tr = ctx.trace
    if tr is None or not work:
        return None
    spans = tr.spans(engine_fn)
    ns = sum(o.end - o.start for d in tr.devices
             for o in kernel_ops(tr.ops_within(d, spans)))
    if ns <= 0:
        return None
    need, bounds = 0.0, {"flops": 0, "bytes": 0}
    for flops, nbytes in work:
        s, which = costs.roofline_seconds(flops, nbytes, ctx.peaks)
        need += s
        bounds[which] += 1
    bound = max(bounds, key=bounds.get)
    return 100.0 * need / (ns * 1e-9), (
        f"{len(work)} calls, bound by {bound} in {bounds[bound]}; kernel "
        f"device time {ns * 1e-9:.6f}s, least time {need:.6f}s")
