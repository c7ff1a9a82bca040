"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

These define the semantics; the kernels must match them on every
shape/dtype sweep in tests/test_kernels_*.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


# --------------------------------------------------------- quant matmul ----
def quantize_w8(w: jax.Array):
    """Per-output-channel symmetric int8. Returns (q int8 (K,N), scale (N,))."""
    amax = jnp.max(jnp.abs(w.astype(F32)), axis=0)
    scale = amax / 127.0 + 1e-12
    q = jnp.clip(jnp.round(w.astype(F32) / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(F32)


def quantize_w4_packed(w: jax.Array):
    """Per-channel symmetric int4, two values packed per int8 along K.
    Returns (packed int8 (K//2, N), scale (N,))."""
    K = w.shape[0]
    assert K % 2 == 0, K
    amax = jnp.max(jnp.abs(w.astype(F32)), axis=0)
    scale = amax / 7.0 + 1e-12
    q = jnp.clip(jnp.round(w.astype(F32) / scale), -7, 7).astype(jnp.int8)
    lo = q[0::2] & 0x0F
    hi = (q[1::2] & 0x0F) << 4
    return (lo | hi).astype(jnp.int8), scale.astype(F32)


def unpack_w4(packed: jax.Array) -> jax.Array:
    """Inverse of the int4 packing: (K//2, N) int8 -> (K, N) int8 in [-7,7]."""
    lo = packed.astype(jnp.int8) << 4
    lo = lo >> 4                     # arithmetic shift sign-extends
    hi = packed.astype(jnp.int8) >> 4
    K2, N = packed.shape
    out = jnp.zeros((K2 * 2, N), jnp.int8)
    out = out.at[0::2].set(lo)
    out = out.at[1::2].set(hi)
    return out


def quantize_a8(x: jax.Array):
    """Per-tensor symmetric int8 activations. Returns (q int8, scale ())."""
    amax = jnp.max(jnp.abs(x.astype(F32))) + 1e-12
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x.astype(F32) / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(F32)


def quant_matmul_w8a16(x: jax.Array, w_q: jax.Array, scale: jax.Array):
    """x (M,K) bf16/f32, w_q (K,N) int8, scale (N,) -> (M,N) x.dtype."""
    out = jnp.einsum("mk,kn->mn", x.astype(F32), w_q.astype(F32))
    return (out * scale[None, :]).astype(x.dtype)


def quant_matmul_w4a16(x: jax.Array, packed: jax.Array, scale: jax.Array):
    return quant_matmul_w8a16(x, unpack_w4(packed), scale)


def quant_matmul_w8a8(x_q: jax.Array, x_scale: jax.Array, w_q: jax.Array,
                      w_scale: jax.Array, out_dtype=jnp.bfloat16):
    """int8 x int8 -> int32 accumulate -> rescale (the int8 MXU path)."""
    acc = jnp.einsum("mk,kn->mn", x_q.astype(jnp.int32),
                     w_q.astype(jnp.int32))
    return (acc.astype(F32) * x_scale * w_scale[None, :]).astype(out_dtype)


# ----------------------------------------------------- KV-cache quant ------
def kv_qmax(bits: int) -> float:
    """Symmetric integer range for a KV bitwidth (int8 -> 127, int4 -> 7)."""
    if bits not in (4, 8):
        raise ValueError(f"KV cache bits must be 4 or 8, got {bits}")
    return 2.0 ** (bits - 1) - 1.0


def pack_int4_hd(q: jax.Array) -> jax.Array:
    """Pack int4 codes two-per-byte along head_dim (the minor axis):
    element i of the first half rides the low nibble of byte i, element
    i + hd/2 its high nibble — so unpacking is two shifts and one
    concatenation, with no lane interleave for the TPU kernel to lower.
    (..., hd) int8 in [-7, 7] -> (..., hd//2) int8."""
    h = q.shape[-1] // 2
    assert q.shape[-1] == 2 * h, q.shape
    lo = q[..., :h] & 0x0F
    hi = (q[..., h:] & 0x0F) << 4
    return (lo | hi).astype(jnp.int8)


def unpack_int4_hd(packed: jax.Array) -> jax.Array:
    """Inverse of pack_int4_hd: (..., hd//2) int8 -> (..., hd) int32 in
    [-7, 7]. Arithmetic shifts on int32 sign-extend the nibbles; int32 is
    what the Pallas kernel computes in as well."""
    x = packed.astype(jnp.int32)
    return jnp.concatenate([(x << 28) >> 28, x >> 4], axis=-1)


def quantize_kv(x: jax.Array, bits: int, *, granularity: str = "token"):
    """Symmetric per-head KV quantization (the pool-write semantics).

    x (..., hd) — any number of leading axes; for ``granularity="page"``
    it is a pool tile (..., K, page, hd), the pool's own layout.

    granularity:
      "token" — one scale per leading index: amax over hd only. This is
                what the paged pool stores (each page carries a
                (K, page_size) fp32 scale tile), because decode writes one
                token at a time and must never re-scale a page in place.
      "page"  — one scale per (page, K) pair: amax over (slot, hd). Coarser;
                kept for the scale-granularity error-bound study
                (tests/test_kvquant.py) and offline pool conversion.

    Returns (stored, scale): stored int8, packed along hd when bits == 4;
    scale fp32 with the reduced axes dropped ("token" -> x.shape[:-1],
    "page" -> x.shape[:-2])."""
    qmax = kv_qmax(bits)
    xf = x.astype(F32)
    if granularity == "token":
        amax = jnp.max(jnp.abs(xf), axis=-1)
        scale = amax / qmax + 1e-12
        div = scale[..., None]
    elif granularity == "page":
        amax = jnp.max(jnp.abs(xf), axis=(-2, -1))           # (..., K)
        scale = amax / qmax + 1e-12
        div = scale[..., None, None]
    else:
        raise ValueError(f"unknown scale granularity {granularity!r}")
    q = jnp.clip(jnp.round(xf / div), -qmax, qmax).astype(jnp.int8)
    if bits == 4:
        q = pack_int4_hd(q)
    return q, scale.astype(F32)


def dequantize_kv(stored: jax.Array, scale: jax.Array, bits: int, *,
                  granularity: str = "token") -> jax.Array:
    """Inverse of quantize_kv -> f32. Exact inverse of the storage mapping;
    |x - dequantize_kv(*quantize_kv(x, bits))| <= scale/2 elementwise."""
    q = unpack_int4_hd(stored) if bits == 4 else stored
    if granularity == "token":
        return q.astype(F32) * scale[..., None]
    if granularity == "page":
        return q.astype(F32) * scale[..., None, None]
    raise ValueError(f"unknown scale granularity {granularity!r}")


def kv_bits_of(stored: jax.Array, hd: int) -> int:
    """Infer the stored KV bitwidth from the minor-axis size (int4 packs two
    codes per byte along hd, so the shape itself encodes the bitwidth —
    static under tracing)."""
    if stored.shape[-1] == hd:
        return 8
    if stored.shape[-1] * 2 == hd:
        return 4
    raise ValueError(
        f"stored KV minor dim {stored.shape[-1]} matches neither int8 ({hd}) "
        f"nor packed int4 ({hd // 2})")


# ------------------------------------------------------ paged attention ----
def _paged_block_walk(q, load_k, load_v, K, hd, page, n_blocks, positions, *,
                      window, cap):
    """Shared block-walk body for the fp and quantized pure-JAX paged
    attention refs — the semantics both must agree on exactly, kept in one
    place (the Pallas entry points share one kernel body the same way).
    ``load_k``/``load_v`` map a block index to its fp32 (B, K, page, hd)
    tile — a pool gather for the fp path, gather + dequant for the
    quantized one.

    q is (B, Sq, H, hd): Sq == 1 is the decode walk, Sq > 1 the
    chunked-prefill walk — query t of sequence b sits at absolute position
    ``positions[b] + t`` and attends causally to every pool slot at or
    before it (the resident prompt prefix plus the chunk's own already-
    written K/V).

    Walks `lax.fori_loop` over the data-dependent block range —
    ``[min(first qpos) - window + 1, max(last qpos)]`` across the batch —
    so the dense chronological (B, n_blocks*page, K, hd) KV view is never
    built and local-window layers do window-trimmed walks instead of
    full-length masking. Scores are staged per-block into a (B,K,G,Sq,T)
    fp32 buffer so the softmax itself is a single full-row pass, matching
    the dense path's normalization exactly."""
    B, Sq, H, _ = q.shape
    G = H // K
    T = n_blocks * page
    scale = hd ** -0.5
    NEG = -2.0 ** 30
    # (B, Sq, K, G, hd) -> (B, K, G, Sq, hd): head h = k*G + g, matching the
    # decode reshape convention.
    qf = jnp.moveaxis(q.astype(F32).reshape(B, Sq, K, G, hd), 1, 3)
    qpos = positions[:, None] + jnp.arange(Sq, dtype=jnp.int32)  # (B, Sq)

    # blocks any query needs; a final chunk padded past the page-table
    # width must not walk past it (the overrun blocks hold only padding
    # queries, which are garbage by contract) — without the clamp the
    # staging offset saturates at T-page and clobbers the last real
    # block's scores.
    hi = jnp.minimum((jnp.max(positions) + Sq - 1) // page + 1, n_blocks)
    if window:
        lo = jnp.maximum((jnp.min(positions) - window + 1) // page, 0)
    else:
        lo = jnp.zeros((), jnp.int32)

    def score_block(i, s_buf):
        s = jnp.einsum("bkgsd,bkpd->bkgsp", qf, load_k(i)) * scale
        if cap:
            s = cap * jnp.tanh(s / cap)
        kpos = i * page + jnp.arange(page)
        valid = kpos[None, None, :] <= qpos[:, :, None]          # (B, Sq, p)
        if window:
            valid &= kpos[None, None, :] > qpos[:, :, None] - window
        s = jnp.where(valid[:, None, None], s, NEG)
        return jax.lax.dynamic_update_slice(s_buf, s, (0, 0, 0, 0, i * page))

    s_buf = jnp.full((B, K, G, Sq, T), NEG, F32)
    s_buf = jax.lax.fori_loop(lo, hi, score_block, s_buf)
    w = jax.nn.softmax(s_buf, axis=-1)

    def pv_block(i, acc):
        wb = jax.lax.dynamic_slice(w, (0, 0, 0, 0, i * page),
                                   (B, K, G, Sq, page))
        return acc + jnp.einsum("bkgsp,bkpd->bkgsd", wb, load_v(i))

    o = jax.lax.fori_loop(lo, hi, pv_block,
                          jnp.zeros((B, K, G, Sq, hd), F32))
    return jnp.moveaxis(o, 3, 1).reshape(B, Sq, H, hd).astype(q.dtype)


def paged_attention_ref(q, pool_k, pool_v, page_table, positions, *,
                        window=0, cap=0.0):
    """Block-walking paged decode attention (the CPU serving fallback and
    the semantics oracle for kernels/paged_attention.py).

    q (B, H, hd) one query token per sequence; pool_k/v (P, K, page, hd);
    page_table (B, n_blocks) int32, unused tails pointing at scratch page 0;
    positions (B,) int32 absolute position of the query token (== index of
    the newest cached token). H = K*G (GQA). Walk semantics in
    _paged_block_walk."""
    return paged_prefill_ref(q[:, None], pool_k, pool_v, page_table,
                             positions, window=window, cap=cap)[:, 0]


def paged_prefill_ref(q, pool_k, pool_v, page_table, positions, *,
                      window=0, cap=0.0):
    """Block-walking chunked-prefill attention (the CPU serving fallback and
    the semantics oracle for paged_prefill_fwd).

    q (B, Sq, H, hd) one prompt chunk per sequence, whose K/V have already
    been written into the pool; pool_k/v (P, K, page, hd); page_table
    (B, n_blocks) int32 with unused tails on scratch page 0; positions (B,)
    int32 absolute position of each chunk's FIRST token (the resident
    prefix length). Query t attends causally to pool slots at
    kpos <= positions[b] + t — the prompt prefix resident in the pool plus
    the chunk itself. Walk semantics in _paged_block_walk."""
    hd = q.shape[-1]
    _, K, page, _ = pool_k.shape
    return _paged_block_walk(
        q, lambda i: pool_k[page_table[:, i]].astype(F32),
        lambda i: pool_v[page_table[:, i]].astype(F32),
        K, hd, page, page_table.shape[1], positions, window=window, cap=cap)


def chronological_kv(pool, page_table):
    """The dense chronological view of a page pool: (P, K, page, hd) gathered
    through page_table (B, n_blocks) -> (B, n_blocks*page, K, hd). Test-only
    — exactly the view the walks exist to avoid."""
    g = pool[page_table]                          # (B, n, K, page, hd)
    B, n, K, page, hd = g.shape
    return jnp.swapaxes(g, 2, 3).reshape(B, n * page, K, hd)


def paged_attention_dense_ref(q, pool_k, pool_v, page_table, positions, *,
                              window=0, cap=0.0):
    """Dense oracle: gather pages chronologically, mask, softmax. Test-only —
    this materializes exactly the (B, T, K, hd) view the kernel exists to
    avoid."""
    B, H, hd = q.shape
    k = chronological_kv(pool_k, page_table)
    v = chronological_kv(pool_v, page_table)
    K = k.shape[2]
    T = k.shape[1]
    G = H // K
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(F32), k.astype(F32))
    s = s * (hd ** -0.5)
    if cap:
        s = cap * jnp.tanh(s / cap)
    j = jnp.arange(T)[None, :]
    valid = j <= positions[:, None]
    if window:
        valid &= j > positions[:, None] - window
    s = jnp.where(valid[:, None, :], s, -2.0 ** 30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", w, v.astype(F32))
    return out.astype(q.dtype)


def paged_prefill_dense_ref(q, pool_k, pool_v, page_table, positions, *,
                            window=0, cap=0.0):
    """Dense chunked-prefill oracle: gather pages chronologically, mask each
    chunk query causally at its absolute position, softmax. Test-only —
    materializes exactly the (B, T, K, hd) view the prefill walk avoids.
    q (B, Sq, H, hd); positions (B,) chunk-start positions."""
    B, Sq, H, hd = q.shape
    k = chronological_kv(pool_k, page_table)
    v = chronological_kv(pool_v, page_table)
    K = k.shape[2]
    T = k.shape[1]
    G = H // K
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bshd,bkhd->bhsk", q.astype(F32), k.astype(F32))
    s = s * (hd ** -0.5)
    if cap:
        s = cap * jnp.tanh(s / cap)
    qpos = positions[:, None] + jnp.arange(Sq)[None, :]          # (B, Sq)
    j = jnp.arange(T)
    valid = j[None, None, :] <= qpos[:, :, None]                 # (B, Sq, T)
    if window:
        valid &= j[None, None, :] > qpos[:, :, None] - window
    s = jnp.where(valid[:, None], s, -2.0 ** 30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhsk,bkhd->bshd", w, v.astype(F32))
    return out.astype(q.dtype)


def paged_attention_quant_ref(q, pool_k, k_scale, pool_v, v_scale,
                              page_table, positions, *, window=0, cap=0.0):
    """Block-walking paged decode attention over a *quantized* page pool
    (the CPU serving fallback and the semantics oracle for the fused-dequant
    Pallas kernel).

    q (B, H, hd) fp; pool_k/v (P, K, page, hd_store) int8 — hd_store == hd
    for int8 KV, hd // 2 for int4 packed along head_dim (pack_int4_hd);
    k_scale/v_scale (P, K, page) fp32 per-page-slot, per-kv-head scales;
    page_table (B, n_blocks) int32 with unused tails on scratch page 0;
    positions (B,) int32.

    Pages are dequantized one block at a time inside the walk — each block
    materializes only a (B, K, page, hd) fp tile; the dense chronological
    (B, n_blocks*page, K, hd) fp KV view is never built (asserted on the
    decode jaxpr in tests/test_kvquant.py). Walk semantics shared with the
    fp ref via _paged_block_walk."""
    return paged_prefill_quant_ref(q[:, None], pool_k, k_scale, pool_v,
                                   v_scale, page_table, positions,
                                   window=window, cap=cap)[:, 0]


def paged_prefill_quant_ref(q, pool_k, k_scale, pool_v, v_scale,
                            page_table, positions, *, window=0, cap=0.0):
    """Chunked-prefill walk over a *quantized* page pool: the chunk's K/V
    are already quantized into the pool, and each block is dequantized
    inside the walk exactly as in paged_attention_quant_ref. q (B, Sq, H,
    hd) fp; positions (B,) int32 chunk-start positions (see
    paged_prefill_ref)."""
    hd = q.shape[-1]
    _, K, page, _ = pool_k.shape
    bits = kv_bits_of(pool_k, hd)

    def loader(pool, scales):
        def load(i):
            pids = page_table[:, i]
            return dequantize_kv(pool[pids], scales[pids], bits)
        return load

    return _paged_block_walk(
        q, loader(pool_k, k_scale), loader(pool_v, v_scale),
        K, hd, page, page_table.shape[1], positions, window=window, cap=cap)


# ------------------------------------------------------ flash attention ----
def flash_attention_ref(q, k, v, *, causal=True, window=0, cap=0.0):
    """Dense attention oracle. q (B,S,H,hd), k/v (B,T,K,hd) GQA."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(F32), k.astype(F32))
    s = s * (hd ** -0.5)
    if cap:
        s = cap * jnp.tanh(s / cap)
    i = jnp.arange(S)[:, None]
    j = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= j <= i
    if window:
        mask &= j > i - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(F32))
    return out.astype(q.dtype)


# -------------------------------------------------- ssm decode update -----
def ssm_decode_ref(state, rows, x, dt, a, Bm, Cm, d_skip):
    """Oracle of kernels/ssm_decode.py: the Mamba-2 one-token update of the
    states in rows ``rows`` of ``state`` (R, H, P, N) f32. x (B, H, P),
    dt (B, H) after softplus, a (H,) = -exp(a_log), Bm/Cm (B, G, N),
    d_skip (H,). Returns (y (B, H, P) f32 with the D skip, state)."""
    H, G = x.shape[1], Bm.shape[1]
    Bh = jnp.repeat(Bm, H // G, axis=1)[:, :, None, :]       # (B,H,1,N)
    Ch = jnp.repeat(Cm, H // G, axis=1)[:, :, None, :]
    decay = jnp.exp(dt * a[None, :])[:, :, None, None]
    new = state[rows] * decay + (dt[:, :, None] * x)[..., None] * Bh
    y = jnp.sum(new * Ch, axis=-1) + x * d_skip[None, :, None]
    return y, state.at[rows].set(new, mode="promise_in_bounds")
