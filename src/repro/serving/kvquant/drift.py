"""Greedy-drift measurement for quantized KV pools.

A quantized pool cannot promise token-identical greedy outputs — it
promises *bounded logit drift*. The right measurement is teacher-forced:
replay one fixed token stream through an fp pool and a quantized pool and
compare the per-step logits. Under teacher forcing both runs see identical
contexts, so the logit gap is exactly the KV-quantization error — no
argmax-flip cascade pollutes it.

The token-level statement this licenses (asserted in tests/test_kvquant.py
and reported by benchmarks/bench_engine_throughput.py): a greedy quantized
run is token-identical to the fp run until the first step whose fp top-2
logit margin is within 2x the measured drift — any flip beyond that margin
would need a logit error larger than the bound.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.transformer import to_page_layout
from repro.serving.engine.pool import quiet_donation
from repro.serving.kvquant.quantize import quantize_pool


def _identity_pool(cache, max_len: int, page: int):
    """B=1 identity-mapped page pool from a full-layout prefill cache:
    logical block i at physical page 1 + i (page 0 stays scratch), the same
    layout launch.serve's sequential baseline decodes through."""
    ppseq = -(-max_len // page)
    span = ppseq * page
    pt = np.arange(1, ppseq + 1, dtype=np.int32)[None]

    def to_pages(c):                     # (G, 1, S, K, hd) full layout
        pad = [(0, 0)] * c.ndim
        pad[2] = (0, span - c.shape[2])
        c = to_page_layout(jnp.pad(c, pad)[:, 0], page)
        pool = jnp.zeros((c.shape[0], ppseq + 1) + c.shape[2:], c.dtype)
        return pool.at[:, 1:].set(c)

    return jax.tree.map(to_pages, cache), jnp.asarray(pt)


def teacher_forced_logits(model, params, tokens, prompt_len: int, *,
                          page_size: int = 16, kv_bits=None,
                          kernel: str = "auto") -> np.ndarray:
    """Replay ``tokens`` (prompt + continuation) through a paged pool,
    feeding the given continuation instead of sampling, and return the fp32
    logits the model emits for every continuation position —
    ``out[i]`` predicts ``tokens[prompt_len + i]``.

    ``kv_bits=None`` replays through the fp pool; otherwise the prefill
    cache is converted with the writers' per-token quantization mapping and
    decode quantizes on write, so the replay exercises exactly the serving
    path (fused-dequant walk included)."""
    tokens = np.asarray(tokens, np.int32)
    T = len(tokens)
    assert 0 < prompt_len < T, (prompt_len, T)
    logits, cache = model.prefill(
        params, {"tokens": jnp.asarray(tokens[None, :prompt_len])},
        cache_layout="full")
    pool, pt = _identity_pool(cache, T, page_size)
    if kv_bits is not None:
        pool = quantize_pool(pool, model.cfg, kv_bits)
    decode = jax.jit(
        lambda p, pool, pt, t, pos: model.decode_step_paged(
            p, pool, pt, t, pos, kernel=kernel),
        donate_argnums=(1,))
    out = [np.asarray(logits[0, -1], np.float32)]
    for t in range(prompt_len, T - 1):
        with quiet_donation():
            logits, pool = decode(params, pool, pt,
                                  jnp.asarray(tokens[None, t:t + 1]),
                                  jnp.asarray([t], jnp.int32))
        out.append(np.asarray(logits[0, 0], np.float32))
    return np.stack(out)


def greedy_drift(model, params, tokens, prompt_len: int, *,
                 kv_bits, page_size: int = 16, kernel: str = "auto",
                 fp_logits: np.ndarray = None) -> dict:
    """Max-abs teacher-forced logit drift of a KV bit policy vs the fp pool
    over one token stream, plus the top-2 fp margin at every step (what a
    flip must beat). Keys: ``max_abs`` drift, ``margins`` (n,) fp top-2
    gaps, ``flip_steps`` indices where the quantized argmax differs,
    ``fp_logits`` — pass the latter back in to compare several bit
    policies against one fp replay instead of re-running it."""
    fp = fp_logits if fp_logits is not None else \
        teacher_forced_logits(model, params, tokens, prompt_len,
                              page_size=page_size, kernel=kernel)
    qq = teacher_forced_logits(model, params, tokens, prompt_len,
                               page_size=page_size, kv_bits=kv_bits,
                               kernel=kernel)
    drift = float(np.max(np.abs(fp - qq)))
    top2 = np.sort(fp, axis=-1)[:, -2:]
    margins = top2[:, 1] - top2[:, 0]
    flips = np.nonzero(np.argmax(fp, -1) != np.argmax(qq, -1))[0]
    return {"max_abs": drift, "margins": margins,
            "flip_steps": flips.tolist(), "fp_logits": fp}
