"""Pallas TPU kernels: paged attention over the serving page pool.

Decode and chunked prefill, over bf16 and quantized pools, walk
``page_table[b]`` with an online softmax (flash-style running max/sum),
fusing the page gather, the causal/local-window mask and the attention
itself, so the dense chronological ``(B, n_blocks*page, K, hd)`` KV view is
never materialized in HBM. ``_paged_call`` picks the walk from the query
length ``Sq``.

Layout: the pool is kv-head-major within a page, ``(num_pages, K, page,
hd)``: a page's ``(K, page, hd)`` tile is contiguous, and one kv head's
``(page, hd)`` tile in it is a block whose last two dims are whole array
dims — the tiling the TPU compiler requires. Quantized pools carry
``(num_pages, K, page)`` fp32 scale tiles. ``page_table``/``positions``
ride in as scalar-prefetch operands (``PrefetchScalarGridSpec``). Blocks
a sequence does not need — past its last query or, for local layers,
wholly below the window (``_block_range``) — are neither copied nor
computed, which makes local-window walks O(window), not O(T).

Decode over a bf16 pool (``Sq == 1``): the grid is ``(B, ceil(n_blocks /
ppb))`` with ``ppb = 128 // page`` pages (at most ``n_blocks``), so one
grid step is one sequence, all K kv heads and 128 tokens. On the chip a
page walk costs what its grid steps cost, far more than its bytes. The
pools stay in HBM (``memory_space=pl.ANY``); each live page's whole
``(K, page, hd)`` tile is one DMA into a double-buffered VMEM buffer of
ppb pages, started one live step ahead — within the sequence, or the next
sequence's first live step while this one's last computes. Per kv head a
step takes the ``(G, 128)`` scores, masks them and updates that head's
fp32 (m, l, acc) scratch; the output is written on the sequence's last
grid step.

Chunked prefill, and decode over a quantized pool: the grid is ``(B, K,
n_blocks)``, one (sequence, kv head)'s ``Sq*G`` query rows against one
``(page, hd)`` tile per step. The BlockSpec index maps resolve logical
block ``i`` to page ``page_table[b, i]`` and clamp skipped blocks onto an
already-resident page (no new copy is pipelined in) while ``pl.when``
skips their FLOPs; the block axis is innermost, so the (m, l, acc) scratch
carries along it and the output tile is written once on the final block.
Prefill keeps this walk because all heads at once would multiply its
``Sq*G``-row q block by K in VMEM. Quantized decode keeps it because the
TPU compiler refuses a manual DMA out of a pool whose minor dim is under
128 lanes: the ``(K, page)`` scale tiles, and int4 codes packed to hd/2.

Quantized pools (serving/kvquant) arrive int8 (int4 packed along head_dim)
and are dequantized inside the block loop: the per-token scales multiply
the score columns (K) and the probability columns (V), so the only fp KV
ever built is one (page, hd) tile in VMEM.

Forward-only by design. Validated against the dense oracles in
tests/test_kernels.py and tests/test_kvquant.py (interpret mode) and
compiled for a described TPU v5e in tests/test_tpu_compile.py; the
pure-JAX block-walk twins live in kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

F32 = jnp.float32
NEG = -1e30
LANES = 128  # scratch minor dim, aligned to the VPU lane width


def _block_range(pos, page, window, span):
    """(lo, hi) inclusive block range the queries at ``pos .. pos+span-1``
    must walk. ``lo`` is the first query's window start — later queries
    only look higher, and the per-row mask handles the rest."""
    hi = (pos + span - 1) // page          # last block holding a live token
    lo = jnp.maximum((pos - window + 1) // page, 0) if window else 0
    return lo, hi


def _kv_index_map(page, window, span):
    """Page-table walk for the kv tiles: clamp skipped blocks onto an
    in-range (already fetched) page so no fresh DMA is pipelined for them;
    pl.when skips their compute."""
    def kv_map(b, k, i, pt, pos):
        lo, hi = _block_range(pos[b], page, window, span)
        ic = jnp.clip(i, lo, hi) if window else jnp.minimum(i, hi)
        return (pt[b, ic], k, 0, 0)
    return kv_map


def _paged_kernel(pt_ref, pos_ref, q_ref, *refs, page, Sq, G, bits, window,
                  cap, scale, n_blocks):
    # q_ref: (Sq*G, hd) — one (sequence, kv-head)'s queries, row r = s*G + g
    # holds query s; kv refs: one (page, hd_store) tile of this kv head;
    # scale refs (quantized pools): the page's whole (K, page) scale tile.
    if bits == 16:
        k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        (k_ref, ks_ref, v_ref, vs_ref, o_ref, m_ref, l_ref,
         acc_ref) = refs
    b = pl.program_id(0)
    kh = pl.program_id(1)
    i = pl.program_id(2)
    pos = pos_ref[b]
    lo, hi = _block_range(pos, page, window, Sq)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((i >= lo) & (i <= hi))
    def _block():
        rows = Sq * G
        q = q_ref[...].astype(F32) * scale
        k = k_ref[...]
        v = v_ref[...]
        if bits == 4:
            k = ref.unpack_int4_hd(k)
            v = ref.unpack_int4_hd(v)
        s = jax.lax.dot_general(q, k.astype(F32), (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)    # (rows, page)
        if bits != 16:
            # this kv head's per-token scales as a (1, page) row: scaling
            # the score columns equals dequantizing K before the dot
            row = jax.lax.broadcasted_iota(jnp.int32, ks_ref.shape, 0) == kh
            s = s * jnp.sum(jnp.where(row, ks_ref[...], 0.0), axis=0,
                            keepdims=True)
        if cap:
            s = cap * jnp.tanh(s / cap)
        kpos = i * page + jax.lax.broadcasted_iota(jnp.int32, (rows, page), 1)
        qpos = pos + jax.lax.broadcasted_iota(jnp.int32, (rows, page), 0) // G
        valid = kpos <= qpos
        if window:
            valid &= kpos > qpos - window
        s = jnp.where(valid, s, NEG)

        m_prev = m_ref[:, :1]                                  # (rows, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = l_ref[...] * corr + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        if bits != 16:
            # V's per-token scales fold into the probability columns
            row = jax.lax.broadcasted_iota(jnp.int32, vs_ref.shape, 0) == kh
            p = p * jnp.sum(jnp.where(row, vs_ref[...], 0.0), axis=0,
                            keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v.astype(F32), (((1,), (0,)), ((), ())),
            preferred_element_type=F32)

    @pl.when(i == n_blocks - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[...] = out.astype(o_ref.dtype)


def _decode_kernel(pt_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
                   sem, slot_ref, m_ref, l_ref, acc_ref, *, page, ppb, window,
                   cap, scale, n_blocks):
    # Grid step (b, j): sequence b's logical pages j*ppb .. j*ppb+ppb-1, all
    # K kv heads. q_ref/o_ref: (K, G, hd). The pools stay in HBM; each live
    # page's whole (K, page, hd) tile is copied into slot ``slot_ref[0]`` of
    # the double-buffered (2, ppb, K, page, hd) VMEM page buffers, one live
    # step ahead of its use.
    B, n_steps = pl.num_programs(0), pl.num_programs(1)
    K, G, _ = q_ref.shape
    T = ppb * page
    b, j = pl.program_id(0), pl.program_id(1)

    def live_blocks(bb):
        lo, hi = _block_range(pos_ref[bb], page, window, 1)
        return lo, jnp.minimum(hi, n_blocks - 1)

    def for_live_pages(bb, jj, slot, act):
        """act(copy) for the K and V copy of every live page of step jj of
        sequence bb into ``slot``; a page outside [lo, hi] starts none."""
        lo, hi = live_blocks(bb)
        for i in range(ppb):
            blk = jj * ppb + i

            @pl.when((blk >= lo) & (blk <= hi))
            def _():
                pid = pt_ref[bb, blk]
                for src, dst in ((k_hbm, k_buf), (v_hbm, v_buf)):
                    act(pltpu.make_async_copy(src.at[pid], dst.at[slot, i],
                                              sem.at[slot]))

    lo, hi = live_blocks(b)
    first, last = lo // ppb, hi // ppb     # this sequence's live steps

    @pl.when((b == 0) & (j == 0))
    def _zero_buffers():
        # a live step's pages that it does not fetch hold what an earlier
        # step left there, which the mask zeroes in p; never uninitialized
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((j >= first) & (j <= last))
    def _step():
        @pl.when((b == 0) & (j == first))
        def _first_fetch():
            slot_ref[0] = 0
            for_live_pages(b, j, 0, lambda c: c.start())

        slot = slot_ref[0]

        # start the next live step's pages, of this sequence or the next
        @pl.when(j < last)
        def _next_step():
            for_live_pages(b, j + 1, 1 - slot, lambda c: c.start())

        @pl.when((j == last) & (b + 1 < B))
        def _next_seq():
            for_live_pages(b + 1, live_blocks(b + 1)[0] // ppb, 1 - slot,
                           lambda c: c.start())

        for_live_pages(b, j, slot, lambda c: c.wait())
        pos = pos_ref[b]
        kpos = j * T + jax.lax.broadcasted_iota(jnp.int32, (G, T), 1)
        valid = kpos <= pos
        if window:
            valid &= kpos > pos - window
        for kh in range(K):
            q = q_ref[kh]                                       # (G, hd)
            k = k_buf[slot, :, kh].reshape(T, -1).astype(q.dtype)
            v = v_buf[slot, :, kh].reshape(T, -1).astype(F32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=F32) * scale
            if cap:
                s = cap * jnp.tanh(s / cap)
            s = jnp.where(valid, s, NEG)

            m_prev = m_ref[kh][:, :1]                           # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            m_ref[kh] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[kh] = l_ref[kh] * corr + jnp.broadcast_to(
                jnp.sum(p, axis=-1, keepdims=True), l_ref.shape[1:])
            acc_ref[kh] = acc_ref[kh] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32)
        slot_ref[0] = 1 - slot

    @pl.when(j == n_steps - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[:, :, :1], 1e-30)
        o_ref[...] = out.astype(o_ref.dtype)


def _decode_call(q, pool_k, pool_v, page_table, positions, *, name, window,
                 cap, interpret):
    """The decode pallas_call over an unquantized pool. q (B, H, hd);
    pool_k/v (P, K, page, hd). Returns (B, H, hd)."""
    B, H, hd = q.shape
    _, K, page, _ = pool_k.shape
    G = H // K
    n_blocks = page_table.shape[1]
    # pages per grid step: 128 tokens, one lane-wide row of scores
    ppb = min(max(1, LANES // page), n_blocks)
    kernel = functools.partial(_decode_kernel, page=page, ppb=ppb,
                               window=window, cap=cap, scale=hd ** -0.5,
                               n_blocks=n_blocks)
    q_spec = pl.BlockSpec((None, K, G, hd), lambda b, j, pt, pos: (b, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    page_buf = pltpu.VMEM((2, ppb, K, page, hd), pool_k.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, pl.cdiv(n_blocks, ppb)),
        in_specs=[q_spec, hbm, hbm],
        out_specs=q_spec,
        scratch_shapes=[
            page_buf, page_buf,
            pltpu.SemaphoreType.DMA((2,)),     # one per buffer slot
            pltpu.SMEM((1,), jnp.int32),       # slot of the current step
            pltpu.VMEM((K, G, LANES), F32),    # running max m
            pltpu.VMEM((K, G, LANES), F32),    # running sum l
            pltpu.VMEM((K, G, hd), F32),       # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        # step j starts the fetch that a later step waits on: keep the grid
        # in order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(page_table, positions, q.reshape(B, K, G, hd), pool_k, pool_v)
    return out.reshape(B, H, hd)


def _paged_call(q, pool_k, k_scale, pool_v, v_scale, page_table, positions,
                *, name, window, cap, interpret):
    """Shared by every public entry point: decode over a bf16 pool takes
    _decode_call's walk, everything else the (B, K, n_blocks) one here.
    q (B, Sq, H, hd); pool_k/v (P, K, page, hd_store); k_scale/v_scale
    (P, K, page) fp32 for quantized pools, None for bf16 ones. Returns
    (B, Sq, H, hd).

    ``name`` becomes the kernel's HLO instruction name (the custom call's
    ``kernel_name``; without it the instruction takes the name of the
    enclosing jitted function), which is how a device trace finds it."""
    B, Sq, H, hd = q.shape
    if Sq == 1 and k_scale is None:
        return _decode_call(q[:, 0], pool_k, pool_v, page_table, positions,
                            name=name, window=window, cap=cap,
                            interpret=interpret)[:, None]
    _, K, page, hd_store = pool_k.shape
    bits = 16 if k_scale is None else ref.kv_bits_of(pool_k, hd)
    G = H // K
    n_blocks = page_table.shape[1]
    rows = Sq * G
    # (B, Sq, K, G, hd) -> (B, K, Sq*G, hd): row r = s*G + g of kv head k
    qr = jnp.moveaxis(q.reshape(B, Sq, K, G, hd), 1, 2).reshape(B, K, rows,
                                                                hd)

    kernel = functools.partial(_paged_kernel, page=page, Sq=Sq, G=G,
                               bits=bits, window=window, cap=cap,
                               scale=hd ** -0.5, n_blocks=n_blocks)
    kv_map = _kv_index_map(page, window, Sq)
    q_spec = pl.BlockSpec((None, None, rows, hd),
                          lambda b, k, i, pt, pos: (b, k, 0, 0))
    kv_spec = pl.BlockSpec((None, None, page, hd_store), kv_map)
    if bits == 16:
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = (qr, pool_k, pool_v)
    else:
        scale_spec = pl.BlockSpec(
            (None, K, page), lambda b, k, i, pt, pos: kv_map(b, k, i, pt,
                                                             pos)[:1] + (0, 0))
        in_specs = [q_spec, kv_spec, scale_spec, kv_spec, scale_spec]
        operands = (qr, pool_k, k_scale, pool_v, v_scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, n_blocks),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((rows, LANES), F32),    # running max m
            pltpu.VMEM((rows, LANES), F32),    # running sum l
            pltpu.VMEM((rows, hd), F32),       # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, rows, hd), q.dtype),
        interpret=interpret,
        name=name,
    )(page_table, positions, *operands)
    return jnp.moveaxis(out.reshape(B, K, Sq, G, hd), 2, 1).reshape(
        B, Sq, H, hd)


_STATIC = ("window", "cap", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def paged_attention_fwd(q, pool_k, pool_v, page_table, positions, *,
                        window=0, cap=0.0, interpret=False):
    """Paged-attention decode. q (B, H, hd); pool_k/v (P, K, page, hd);
    page_table (B, n_blocks) int32 (unused tails -> scratch page 0);
    positions (B,) int32 position of each query token. H = K*G.
    Returns (B, H, hd) in q.dtype."""
    return _paged_call(q[:, None], pool_k, None, pool_v, None, page_table,
                       positions, name="paged_attention_fwd", window=window,
                       cap=cap, interpret=interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=_STATIC)
def paged_attention_quant_fwd(q, pool_k, k_scale, pool_v, v_scale,
                              page_table, positions, *, window=0, cap=0.0,
                              interpret=False):
    """Fused-dequant paged-attention decode over a quantized pool.

    q (B, H, hd) fp; pool_k/v (P, K, page, hd_store) int8 with hd_store =
    hd (int8 KV) or hd//2 (int4 packed along head_dim); k_scale/v_scale
    (P, K, page) fp32 per-token per-head scales; page_table/positions as in
    paged_attention_fwd. Returns (B, H, hd) in q.dtype."""
    return _paged_call(q[:, None], pool_k, k_scale, pool_v, v_scale,
                       page_table, positions,
                       name="paged_attention_quant_fwd", window=window,
                       cap=cap, interpret=interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=_STATIC)
def paged_prefill_fwd(q, pool_k, pool_v, page_table, positions, *,
                      window=0, cap=0.0, interpret=False):
    """Chunked-prefill attention over the page pool (prefill-with-cache).

    q (B, Sq, H, hd) — one prompt chunk of queries per sequence, whose K/V
    have already been scattered into the pool; pool_k/v (P, K, page, hd);
    page_table (B, n_blocks) int32 (unused tails -> scratch page 0);
    positions (B,) int32 absolute position of each chunk's FIRST token.
    Query t of sequence b attends causally to kpos <= positions[b] + t —
    the resident prompt prefix plus the chunk itself. Returns
    (B, Sq, H, hd) in q.dtype."""
    return _paged_call(q, pool_k, None, pool_v, None, page_table, positions,
                       name="paged_prefill_fwd", window=window, cap=cap,
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def paged_prefill_quant_fwd(q, pool_k, k_scale, pool_v, v_scale,
                            page_table, positions, *, window=0, cap=0.0,
                            interpret=False):
    """Fused-dequant chunked-prefill attention over a quantized page pool
    (layouts as in paged_attention_quant_fwd, queries and positions as in
    paged_prefill_fwd). Returns (B, Sq, H, hd) in q.dtype."""
    return _paged_call(q, pool_k, k_scale, pool_v, v_scale, page_table,
                       positions, name="paged_prefill_quant_fwd",
                       window=window, cap=cap, interpret=interpret)
