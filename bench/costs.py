"""Operations and bytes the served work needs, from the configuration's
shapes at its stated precision (bfloat16 weights and K/V). Never from what
an implementation happens to move: a later change to a kernel or to the
dtype it moves is then measured against the same work.

One function set per model family; the configuration's ``family`` picks it.
A multiply-add counts as two operations.
"""
from __future__ import annotations

from typing import Iterable, Tuple

KV_BYTES = 2      # bfloat16 K/V, as the configuration states


def _dims(m: dict):
    d, H, K = m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or d // H
    return d, H, K, hd, m["d_ff"], m["num_layers"], m["vocab_size"]


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies per layer (attention projections and
    the feed-forward)."""
    d, H, K, hd, F, _, _ = _dims(m)
    gated = m["activation"] in ("swiglu", "geglu")
    return d * H * hd + 2 * d * K * hd + H * hd * d + d * F * (3 if gated else 2)


def attention_flops(m: dict, keys: int) -> int:
    """Scores and weighted sum of one query over ``keys`` keys, all
    layers: 2 * H * hd for q.k and as much for p.v, per key."""
    d, H, K, hd, F, L, V = _dims(m)
    return 4 * H * hd * keys * L


def kv_bytes(m: dict, tokens: int) -> int:
    """K and V of ``tokens`` positions, all layers."""
    d, H, K, hd, F, L, V = _dims(m)
    return 2 * K * hd * tokens * L * KV_BYTES


def decode_flops(m: dict, contexts: Iterable[int]) -> int:
    """Model operations of one decode token per sequence, each attending
    over its context (keys including itself), with the unembedding."""
    d, H, K, hd, F, L, V = _dims(m)
    per_tok = 2 * layer_matmul_params(m) * L + 2 * d * V
    return sum(per_tok + attention_flops(m, c) for c in contexts)


def chunk_flops(m: dict, start: int, end: int) -> int:
    """Model operations of the real prompt rows ``start .. end-1`` of one
    prefill chunk (no unembedding: the chunk program returns hidden
    states). Row p attends over keys 0..p."""
    n = end - start
    keys = (start + 1 + end) * n // 2
    return 2 * layer_matmul_params(m) * m["num_layers"] * n \
        + attention_flops(m, keys)


def decode_attention_work(m: dict, contexts: Iterable[int]) -> Tuple[int, int]:
    """(operations, bytes) the paged-attention kernel needs in one decode
    tick: each sequence reads its context's K/V once."""
    cs = list(contexts)
    return attention_flops(m, sum(cs)), kv_bytes(m, sum(cs))


def chunk_attention_work(m: dict, start: int, end: int) -> Tuple[int, int]:
    """(operations, bytes) the kernel needs for one chunk's real rows:
    the scores over keys 0..p for each row p, and the K/V of positions
    0..end-1 read once."""
    n = end - start
    return attention_flops(m, (start + 1 + end) * n // 2), kv_bytes(m, end)


def roofline_seconds(flops: int, nbytes: int, peaks: dict) -> Tuple[float, str]:
    """Least time on one chip and which bound sets it."""
    tf = flops / peaks["bf16_flops_per_s"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
