"""GQA attention: full / sliding-window (local) / cross, train + prefill +
decode paths, with full-cache and ring-buffer (local) KV caches.

Layout conventions:
  activations x          (B, S, D)
  q                      (B, S, H, hd)
  k, v                   (B, S, K, hd)     H = K * G (GQA groups)
  full KV cache          (B, S_max, K, hd)
  ring KV cache (local)  (B, W, K, hd)     slot = position % W
Attention logits are computed in fp32; RoPE is applied at cache-write time
(absolute positions), which keeps ring-buffer decode exact.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.models import flash as flash_lib
from repro.models.layers import apply_rope, softcap
from repro.models.params import PDef

F32 = jnp.float32
NEG_INF = -2.0 ** 30  # large-but-finite; avoids NaNs for fully-masked rows


def attn_defs(d_model: int, n_heads: int, n_kv: int, head_dim: int):
    return {
        "wq": PDef((d_model, n_heads, head_dim),
                   ("embed", "heads", "head_dim"), "scaled"),
        "wk": PDef((d_model, n_kv, head_dim),
                   ("embed", "kv_heads", "head_dim"), "scaled"),
        "wv": PDef((d_model, n_kv, head_dim),
                   ("embed", "kv_heads", "head_dim"), "scaled"),
        "wo": PDef((n_heads, head_dim, d_model),
                   ("heads", "head_dim", "embed"), "scaled"),
    }


def qkv(p, x, theta: float, positions, *, dot=None):
    """Project and rope. positions: (B, S) absolute positions (or None)."""
    if dot is None:
        dot = lambda a, w, name: jnp.einsum(
            "bsd,dnh->bsnh", a, w)
    q = dot(x, p["wq"], "attn_q")
    k = dot(x, p["wk"], "attn_k")
    v = dot(x, p["wv"], "attn_v")
    if theta > 0 and positions is not None:
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v


def _attend(q, k, v, mask, cap: float, *, ac=None):
    """Dense attention (short sequences / decode). KV repeated to H heads so
    the heads axis shards cleanly even when TP > n_kv (see flash.py).
    mask broadcastable to (B,H,S,T). Returns (B,S,H,hd).

    `ac` (decode path): sequence-parallel hints — q replicated over the model
    axis, kv/scores sharded over cache-seq; softmax and the PV contraction
    then partition over the cache with only tiny combine collectives."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    if ac is not None:
        q = ac(q, "decode_q")
        k = ac(k, "decode_kv")
        v = ac(v, "decode_kv")
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(F32), k.astype(F32))
    if ac is not None:
        s = ac(s, "decode_scores")
    s = softcap(s * (hd ** -0.5), cap)
    s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(F32))
    return o.astype(q.dtype)


def causal_mask(S: int, T: int, q_offset=0):
    i = jnp.arange(S)[:, None] + q_offset
    j = jnp.arange(T)[None, :]
    return (j <= i)[None, None]


def local_mask(S: int, T: int, window: int, q_offset=0):
    i = jnp.arange(S)[:, None] + q_offset
    j = jnp.arange(T)[None, :]
    return ((j <= i) & (j > i - window))[None, None]


def attention_fwd(p, x, kind: str, cfg, positions, *, dot=None,
                  segment_ids=None, ring: bool = True
                  ) -> Tuple[jax.Array, dict]:
    """Training/prefill attention. Returns (out (B,S,D), cache_entry).

    kind: "global" | "local" | "bidir".
    cache_entry holds roped k/v ready for decode (ring layout for local;
    ``ring=False`` keeps local caches in chronological full layout so the
    paged serving engine can copy them into its page pool).
    """
    B, S, D = x.shape
    q, k, v = qkv(p, x, cfg.rope_theta, positions, dot=dot)
    W = cfg.window_size
    if S >= flash_lib.FLASH_MIN and segment_ids is None:
        o = flash_lib.flash_attention(q, k, v, kind, W, cfg.attn_softcap)
    else:
        if kind == "local":
            mask = local_mask(S, S, W)
        elif kind == "bidir":
            mask = jnp.ones((1, 1, S, S), bool)
        else:
            mask = causal_mask(S, S)
        if segment_ids is not None:  # block packed-sequence cross-talk
            seg = (segment_ids[:, :, None] == segment_ids[:, None, :])
            mask = mask & seg[:, None]
        o = _attend(q, k, v, mask, cfg.attn_softcap)
    dot_o = dot or (lambda a, w, name: jnp.einsum(
        "bsnh,nhd->bsd", a, w))
    out = dot_o(o, p["wo"], "attn_o")
    cache = {"k": k, "v": v}
    if ring and kind == "local" and S >= W:
        cache = {"k": _last_window_ring(k, W), "v": _last_window_ring(v, W)}
    return out, cache


def _last_window_ring(k: jax.Array, W: int) -> jax.Array:
    """Rearrange the last W cached positions into ring layout (slot=pos%W)."""
    S = k.shape[1]
    last = jax.lax.slice_in_dim(k, S - W, S, axis=1)  # positions S-W..S-1
    # slot s holds position S-W + ((s - (S-W)) % W)
    inv = np.array([(s - (S - W)) % W for s in range(W)])
    return last[:, inv]


def _cache_write(cache: jax.Array, new: jax.Array, idx) -> jax.Array:
    """Write `new` (B,1,K,hd) at seq position idx. Uses a scatter (.at.set)
    rather than dynamic_update_slice: the SPMD partitioner keeps a scatter
    with replicated scalar indices LOCAL on a seq-sharded cache, whereas a
    dynamic-update-slice at a traced offset falls back to all-gathering the
    whole cache shard per layer (observed 87 GB/device/token on the
    decode_32k dry-run — see EXPERIMENTS.md §Perf iteration D2)."""
    return cache.at[:, idx].set(new[:, 0], mode="promise_in_bounds")


def attention_decode(p, x, cache_k, cache_v, pos, kind: str, cfg, *,
                     dot=None, ac=None
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode. x (B,1,D); pos scalar int32 (current position).

    Returns (out (B,1,D), new_cache_k, new_cache_v).
    """
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k_new, v_new = qkv(p, x, cfg.rope_theta, positions, dot=dot)
    T = cache_k.shape[1]
    if kind == "local" and T == cfg.window_size:
        slot = jnp.mod(pos, T)
        cache_k = _cache_write(cache_k, k_new, slot)
        cache_v = _cache_write(cache_v, v_new, slot)
        # absolute position held by each slot (after this write)
        s = jnp.arange(T)
        abs_pos = pos - jnp.mod(pos - s, T)
        mask = (abs_pos >= 0)[None, None, None, :]
    else:
        cache_k = _cache_write(cache_k, k_new, pos)
        cache_v = _cache_write(cache_v, v_new, pos)
        j = jnp.arange(T)
        valid = j <= pos
        if kind == "local":
            valid &= j > pos - cfg.window_size
        mask = valid[None, None, None, :]
    o = _attend(q, cache_k, cache_v, mask, cfg.attn_softcap, ac=ac)
    dot_o = dot or (lambda a, w, name: jnp.einsum(
        "bsnh,nhd->bsd", a, w))
    out = dot_o(o, p["wo"], "attn_o")
    return out, cache_k, cache_v


def attention_decode_paged(p, x, pool_k, pool_v, page_table, positions,
                           kind: str, cfg, *, dot=None, ac=None,
                           kernel: str = "auto"):
    """Slot-indexed one-token decode against a paged KV pool.

    x           (B, 1, D)   one new token's activations per sequence
    pool_k/v    (P, K, page, hd)  this layer's physical page pool
    page_table  (B, n_pages) int32 physical page ids per logical block;
                unused tail entries must point at the scratch page 0
    positions   (B,) int32  absolute position of the incoming token (== the
                number of tokens already cached for that sequence)
    kernel      "auto" | "pallas" | "ref" — kernels/ops.py::paged_attention
                dispatch: the Pallas page-walk kernel on TPU, the pure-JAX
                block walk elsewhere. Neither path materializes the dense
                chronological (B, n_pages*page, K, hd) KV view, and local
                layers walk only the window's pages instead of masking a
                full-length gather.

    The new k/v are scattered into page ``page_table[b, pos // page]`` at
    slot ``pos % page``; attention then walks the sequence's pages in
    chronological order, masking columns beyond ``positions[b]`` (and
    outside the sliding window for local layers). Because RoPE is applied
    at cache-write time with absolute positions, the page walk matches a
    dense chronological cache to fp32-accumulation precision.

    Quantized pools (serving/kvquant): ``pool_k``/``pool_v`` may instead be
    ``{"q": int8 pages, "scale": fp32 (P, K, page)}`` dicts — the stored
    bitwidth (int8, or int4 packed along head_dim) is inferred from the
    stored minor-dim size. The incoming token's k/v are quantized on write
    (per-token per-head symmetric scales, the same mapping the engine's
    prefill writer uses), and attention runs the fused-dequant walk — no
    dense fp KV view is materialized on either path.

    ``ac`` (sequence-parallel decode hints) applies to the dense decode
    path only; the paged walk ignores it. Sharded paged decode instead
    rides shard_map (serving/engine/sharded.py): the pool arrives as a
    local kv-head slice and this function runs unchanged per shard — the
    walk is embarrassingly parallel over heads, and the ``dot`` hook
    (sharded.tp_dot) all-gathers the per-head outputs before the
    out-projection so the contraction keeps its 1-device reduction order.

    Returns (out (B,1,D), pool_k, pool_v).
    """
    quantized = isinstance(pool_k, dict)
    page = (pool_k["q"] if quantized else pool_k).shape[2]
    q, k_new, v_new = qkv(p, x, cfg.rope_theta, positions[:, None], dot=dot)
    pids = jnp.take_along_axis(page_table, (positions // page)[:, None],
                               axis=1)[:, 0]
    slots = positions % page
    window = cfg.window_size if kind == "local" else 0
    if quantized:
        hd = q.shape[-1]
        bits = kref.kv_bits_of(pool_k["q"], hd)

        def write(pool, new):                        # new: (B, K, hd)
            qv, sc = kref.quantize_kv(new, bits)
            return {"q": pool["q"].at[pids, :, slots].set(
                        qv, mode="promise_in_bounds"),
                    "scale": pool["scale"].at[pids, :, slots].set(
                        sc, mode="promise_in_bounds")}

        pool_k = write(pool_k, k_new[:, 0])
        pool_v = write(pool_v, v_new[:, 0])
        o = kops.paged_attention_quant(
            q[:, 0], pool_k["q"], pool_k["scale"], pool_v["q"],
            pool_v["scale"], page_table, positions, window=window,
            cap=cfg.attn_softcap, mode=kernel)[:, None]
    else:
        pool_k = pool_k.at[pids, :, slots].set(k_new[:, 0],
                                               mode="promise_in_bounds")
        pool_v = pool_v.at[pids, :, slots].set(v_new[:, 0],
                                               mode="promise_in_bounds")
        o = kops.paged_attention(q[:, 0], pool_k, pool_v, page_table,
                                 positions, window=window,
                                 cap=cfg.attn_softcap, mode=kernel)[:, None]
    dot_o = dot or (lambda a, w, name: jnp.einsum(
        "bsnh,nhd->bsd", a, w))
    return dot_o(o, p["wo"], "attn_o"), pool_k, pool_v


def attention_prefill_paged(p, x, pool_k, pool_v, page_table, positions,
                            kind: str, cfg, *, dot=None, kernel: str = "auto"):
    """Chunked prefill against a paged KV pool (prefill-with-cache).

    x           (B, Sq, D)  one prompt chunk's activations per sequence
    pool_k/v    (P, K, page, hd)  this layer's physical page pool (or the
                quantized ``{"q", "scale"}`` dicts, see below)
    page_table  (B, n_pages) int32; unused tails -> scratch page 0
    positions   (B,) int32  absolute position of each chunk's FIRST token
                (== the number of prompt tokens already resident in the
                pool for that sequence)

    The chunk's roped k/v are scattered into their pages first — token t
    at page ``page_table[b, (pos+t) // page]`` slot ``(pos+t) % page`` —
    then attention walks the sequence's pages with the chunked-prefill
    kernel: query t attends causally to every pool slot at
    ``kpos <= positions[b] + t``, i.e. the resident prompt prefix plus the
    chunk itself. No dense chronological prompt KV view is materialized on
    any path, and the final chunk's padding garbage stays behind the
    causal mask exactly like bucket padding did (overwritten by decode in
    position order).

    Quantized pools quantize the chunk on write (per-token per-head
    scales, the same mapping as the decode scatter) and run the
    fused-dequant prefill walk.

    Returns (out (B, Sq, D), pool_k, pool_v).
    """
    quantized = isinstance(pool_k, dict)
    page = (pool_k["q"] if quantized else pool_k).shape[2]
    B, Sq, _ = x.shape
    n_blocks = page_table.shape[1]
    abs_pos = positions[:, None] + jnp.arange(Sq, dtype=jnp.int32)[None, :]
    q, k_new, v_new = qkv(p, x, cfg.rope_theta, abs_pos, dot=dot)
    # A final chunk padded past the page-table width routes its overflow
    # rows to the scratch page explicitly: an unclamped gather fills OOB
    # indices with INT_MIN, which the promise_in_bounds scatter below
    # would treat as undefined behaviour.
    blocks = abs_pos // page                                    # (B, Sq)
    pids = jnp.take_along_axis(page_table,
                               jnp.minimum(blocks, n_blocks - 1), axis=1)
    pids = jnp.where(blocks < n_blocks, pids, 0)
    slots = abs_pos % page
    window = cfg.window_size if kind == "local" else 0
    if quantized:
        hd = q.shape[-1]
        bits = kref.kv_bits_of(pool_k["q"], hd)

        def write(pool, new):                        # new: (B, Sq, K, hd)
            qv, sc = kref.quantize_kv(new, bits)
            return {"q": pool["q"].at[pids, :, slots].set(
                        qv, mode="promise_in_bounds"),
                    "scale": pool["scale"].at[pids, :, slots].set(
                        sc, mode="promise_in_bounds")}

        pool_k = write(pool_k, k_new)
        pool_v = write(pool_v, v_new)
        o = kops.paged_attention_prefill_quant(
            q, pool_k["q"], pool_k["scale"], pool_v["q"], pool_v["scale"],
            page_table, positions, window=window, cap=cfg.attn_softcap,
            mode=kernel)
    else:
        pool_k = pool_k.at[pids, :, slots].set(k_new,
                                               mode="promise_in_bounds")
        pool_v = pool_v.at[pids, :, slots].set(v_new,
                                               mode="promise_in_bounds")
        o = kops.paged_attention_prefill(q, pool_k, pool_v, page_table,
                                         positions, window=window,
                                         cap=cfg.attn_softcap, mode=kernel)
    dot_o = dot or (lambda a, w, name: jnp.einsum(
        "bsnh,nhd->bsd", a, w))
    return dot_o(o, p["wo"], "attn_o"), pool_k, pool_v


def cross_attention(p, x, mem_k, mem_v, cfg, *, dot=None) -> jax.Array:
    """Decoder cross-attention against precomputed encoder k/v (no mask)."""
    B, S, D = x.shape
    if dot is None:
        dot = lambda a, w, name: jnp.einsum(
            "bsd,dnh->bsnh", a, w)
    q = dot(x, p["wq"], "xattn_q")
    T = mem_k.shape[1]
    if S >= flash_lib.FLASH_MIN or T >= 4 * flash_lib.FLASH_MIN:
        o = flash_lib.flash_attention(q, mem_k, mem_v, "bidir", 0,
                                      cfg.attn_softcap)
    else:
        mask = jnp.ones((1, 1, S, T), bool)
        o = _attend(q, mem_k, mem_v, mask, cfg.attn_softcap)
    dot_o = lambda a, w, name: jnp.einsum(
        "bsnh,nhd->bsd", a, w)
    return dot_o(o, p["wo"], "xattn_o")


def cross_kv(p, mem, *, dot=None):
    """Precompute encoder-side k/v for cross attention (no rope)."""
    if dot is None:
        dot = lambda a, w, name: jnp.einsum(
            "bsd,dnh->bsnh", a, w)
    return dot(mem, p["wk"], "xattn_k"), dot(mem, p["wv"], "xattn_v")


def cache_len_for(kind: str, cfg, seq_len: int) -> int:
    if kind == "local":
        return min(cfg.window_size, seq_len)
    return seq_len
