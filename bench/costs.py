"""What every model family's operation and byte counts share: the bytes
of one K or V element at the configurations' stated precision, and the
roofline's least time on one chip.

The counts themselves (``decode_flops``, ``chunk_flops``,
``decode_attention_work``, ``chunk_attention_work``) depend on the
model's structure and are the family's: bench/families/<family>.py, which
the readers reach through ``ctx.family``. They are taken from the
configuration's shapes, never from what an implementation happens to
move: a later change to a kernel or to the dtype it moves is then
measured against the same work.
"""
from __future__ import annotations

from typing import Tuple

KV_BYTES = 2      # bfloat16 K/V, as the configuration states


def roofline_seconds(flops: int, nbytes: int, peaks: dict) -> Tuple[float, str]:
    """Least time on one chip and which bound sets it."""
    tf = flops / peaks["bf16_flops_per_s"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
