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

Chunked prefill over a bf16 pool (``Sq > 1``): the grid is ``(B, K, query
tiles, ceil(n_blocks / ppb))`` with the same ppb, so one grid step is one
(sequence, kv head)'s query rows against 128 tokens of that head's pages.
A query tile is the whole chunk while its ``Sq*G`` rows fit ``ROWS``, else
the largest divisor of Sq that does: what the kernel holds in VMEM (the q
block and its fp32 m, l and acc scratch) follows the tile, not the chunk.
Tile t walks only up to the page of its own last query. Each live page's
``(page, hd)`` tile of the step's kv head is one DMA out of the HBM pools
into a double-buffered ``(2, ppb, page, hd)`` VMEM buffer, started one
live step ahead — within the tile, or the first live step of the next
(sequence, head, tile) while this one's last computes; a page outside the
tile's ``_block_range`` starts none, and a step with no live page computes
nothing. With one kv head per step a tile can hold the whole chunk, so a
call reads each page once; all K heads in a step would hold K times the
rows, cut the chunk into K times as many tiles and read every page once
per tile. ``q.k`` runs in the stored dtype with fp32 accumulation and the
scale applied to the fp32 ``(rows, 128)`` scores; ``p.v`` in fp32. The
running max m is kept in every lane and the running sum l as per-lane
partial sums, added up once when the tile's output is written, so a step
moves across lanes only for the row max. Rows are taken ``SUB_ROWS`` at a
time. Only a step that reaches past the tile's first query, or below its
last query's window, is masked.

Quantized pools, decode and chunked prefill: the grid is ``(B, K,
n_blocks)``, one (sequence, kv head)'s ``Sq*G`` query rows against one
``(page, hd)`` tile per step. The BlockSpec index maps resolve logical
block ``i`` to page ``page_table[b, i]`` and clamp skipped blocks onto an
already-resident page (no new copy is pipelined in) while ``pl.when``
skips their FLOPs; the block axis is innermost, so the (m, l, acc) scratch
carries along it and the output tile is written once on the final block.
Quantized pools keep this walk because the TPU compiler refuses a manual
DMA out of a pool whose minor dim is under 128 lanes: the ``(K, page)``
scale tiles, and int4 codes packed to hd/2.

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
ROWS = 4096  # most query rows (queries x G) one prefill grid step holds
SUB_ROWS = 1024  # query rows a prefill step's inner loop takes at a time


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


def _paged_kernel(pt_ref, pos_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page, Sq, G, bits, window, cap,
                  scale, n_blocks):
    # q_ref: (Sq*G, hd) — one (sequence, kv-head)'s queries, row r = s*G + g
    # holds query s; kv refs: one (page, hd_store) tile of this kv head;
    # scale refs: the page's whole (K, page) scale tile.
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
        # this kv head's per-token scales as a (1, page) row: scaling the
        # score columns equals dequantizing K before the dot
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


def _prefill_kernel(pt_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
                    sem, slot_ref, m_ref, l_ref, acc_ref, *, page, ppb, tq, G,
                    window, cap, scale, n_blocks):
    # Grid step (b, kh, t, j): query tile t (queries t*tq .. t*tq+tq-1 of
    # the chunk) of sequence b's kv head kh against its logical pages
    # j*ppb .. j*ppb+ppb-1. q_ref/o_ref: (tq*G, hd), row r = s*G + g. The
    # pools stay in HBM; each live page's (page, hd) tile of kv head kh is
    # copied into slot ``slot_ref[0]`` of the double-buffered (2, ppb, page,
    # hd) VMEM page buffers, one live step ahead of its use.
    B, K, n_qt, n_steps = (pl.num_programs(a) for a in range(4))
    T = ppb * page
    b, kh, t, j = (pl.program_id(a) for a in range(4))

    def live_blocks(bb, tt):
        lo, hi = _block_range(pos_ref[bb] + tt * tq, page, window, tq)
        hi = jnp.minimum(hi, n_blocks - 1)
        # a tile wholly past the table still walks one (masked) step
        return jnp.minimum(lo, hi), hi

    def for_live_pages(bb, kk, tt, jj, slot, act):
        """act(copy) for the K and V copy of every live page of step jj of
        (sequence bb, kv head kk, tile tt) into ``slot``; a page outside
        [lo, hi] starts none."""
        lo, hi = live_blocks(bb, tt)
        for i in range(ppb):
            blk = jj * ppb + i

            @pl.when((blk >= lo) & (blk <= hi))
            def _():
                pid = pt_ref[bb, blk]
                for src, dst in ((k_hbm, k_buf), (v_hbm, v_buf)):
                    act(pltpu.make_async_copy(src.at[pid, kk],
                                              dst.at[slot, i], sem.at[slot]))

    lo, hi = live_blocks(b, t)
    first, last = lo // ppb, hi // ppb     # this tile's live steps
    # the (sequence, kv head, tile) after this one in grid order
    t_wraps = t == n_qt - 1
    k_wraps = t_wraps & (kh == K - 1)
    nt = jnp.where(t_wraps, 0, t + 1)
    nk = jnp.where(t_wraps, jnp.where(k_wraps, 0, kh + 1), kh)
    nb = jnp.where(k_wraps, b + 1, b)

    @pl.when((b == 0) & (kh == 0) & (t == 0) & (j == 0))
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

    q0 = pos_ref[b] + t * tq               # the tile's first query position

    rows = q_ref.shape[0]
    sub = SUB_ROWS if rows % SUB_ROWS == 0 else rows

    def attend(slot, masked):
        def rows_from(r0):
            rs = pl.ds(r0, sub)
            q = q_ref[rs, :]                                    # (sub, hd)
            k = k_buf[slot].reshape(T, -1).astype(q.dtype)
            v = v_buf[slot].reshape(T, -1).astype(F32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=F32) * scale
            if cap:
                s = cap * jnp.tanh(s / cap)
            if masked:
                # row r holds query q0 + r // G: kpos <= q0 + r // G
                # exactly when r >= (kpos - q0) * G, and kpos > qpos -
                # window when r < (kpos - q0 + window) * G
                r = r0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                d = j * T - q0 + jax.lax.broadcasted_iota(jnp.int32,
                                                          s.shape, 1)
                valid = r >= d * G
                if window:
                    valid &= r < (d + window) * G
                s = jnp.where(valid, s, NEG)

            # m and l keep a value in every lane (l as per-lane partial
            # sums, added up once at the end), so only the row max crosses
            # lanes and nothing is broadcast back across them
            m_prev = m_ref[rs, :]                               # (sub, T)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            m_ref[rs, :] = m_new
            l_ref[rs, :] = l_ref[rs, :] * corr + p
            if corr.shape[1] != acc_ref.shape[1]:
                corr = corr[:, :1]
            acc_ref[rs, :] = acc_ref[rs, :] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32)

        if sub == rows:
            rows_from(0)
        else:
            @pl.loop(0, rows, step=sub)
            def _(r0):
                rows_from(pl.multiple_of(r0, sub))

    @pl.when((j >= first) & (j <= last))
    def _step():
        @pl.when((b == 0) & (kh == 0) & (t == 0) & (j == first))
        def _first_fetch():
            slot_ref[0] = 0
            for_live_pages(b, kh, t, j, 0, lambda c: c.start())

        slot = slot_ref[0]

        # start the next live step's pages, of this tile or the next
        @pl.when(j < last)
        def _next_step():
            for_live_pages(b, kh, t, j + 1, 1 - slot, lambda c: c.start())

        @pl.when((j == last) & (nb < B))
        def _next_tile():
            for_live_pages(nb, nk, nt, live_blocks(nb, nt)[0] // ppb,
                           1 - slot, lambda c: c.start())

        for_live_pages(b, kh, t, j, slot, lambda c: c.wait())
        # only a step that reaches past the tile's first query, or below
        # its last query's window, holds a key some row may not see
        edge = j * T + T - 1 > q0
        if window:
            edge |= j * T <= q0 + tq - 1 - window

        @pl.when(edge)
        def _masked():
            attend(slot, True)

        @pl.when(jnp.logical_not(edge))
        def _interior():
            attend(slot, False)

        slot_ref[0] = 1 - slot

    @pl.when(j == n_steps - 1)
    def _finalize():
        l = jnp.sum(l_ref[...], axis=-1, keepdims=True)
        out = acc_ref[...] / jnp.maximum(l, 1e-30)
        o_ref[...] = out.astype(o_ref.dtype)


def _query_tile(Sq, G):
    """Queries per grid step: the whole chunk while its ``Sq*G`` rows fit
    ``ROWS``, else the largest divisor of Sq, a multiple of 16, whose rows
    do (the VMEM the q block, its fp32 scratch and score tiles take grows
    with the rows, not with the chunk)."""
    if Sq * G <= ROWS:
        return Sq
    fits = [d for d in range(16, Sq, 16) if Sq % d == 0 and d * G <= ROWS]
    return max(fits, default=Sq)


def _prefill_call(q, pool_k, pool_v, page_table, positions, *, name, window,
                  cap, interpret):
    """The chunked-prefill pallas_call over an unquantized pool. q (B, Sq,
    H, hd); pool_k/v (P, K, page, hd). Returns (B, Sq, H, hd)."""
    B, Sq, H, hd = q.shape
    _, K, page, _ = pool_k.shape
    G = H // K
    n_blocks = page_table.shape[1]
    # pages per grid step: 128 tokens, lane-wide score tiles
    ppb = min(max(1, LANES // page), n_blocks)
    T = ppb * page
    tq = _query_tile(Sq, G)
    rows = tq * G
    # (B, Sq, K, G, hd) -> (B, K, Sq*G, hd): row r = s*G + g of kv head k
    qr = jnp.moveaxis(q.reshape(B, Sq, K, G, hd), 1, 2).reshape(
        B, K, Sq * G, hd)
    kernel = functools.partial(_prefill_kernel, page=page, ppb=ppb, tq=tq,
                               G=G, window=window, cap=cap, scale=hd ** -0.5,
                               n_blocks=n_blocks)
    q_spec = pl.BlockSpec((None, None, rows, hd),
                          lambda b, k, t, j, pt, pos: (b, k, t, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    page_buf = pltpu.VMEM((2, ppb, page, hd), pool_k.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, Sq // tq, pl.cdiv(n_blocks, ppb)),
        in_specs=[q_spec, hbm, hbm],
        out_specs=q_spec,
        scratch_shapes=[
            page_buf, page_buf,
            pltpu.SemaphoreType.DMA((2,)),     # one per buffer slot
            pltpu.SMEM((1,), jnp.int32),       # slot of the current step
            pltpu.VMEM((rows, T), F32),        # running max m, every lane
            pltpu.VMEM((rows, T), F32),        # running sums l, by lane
            pltpu.VMEM((rows, hd), F32),       # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, Sq * G, hd), q.dtype),
        # step j starts the fetch that a later step waits on: keep the grid
        # in order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4),
        interpret=interpret,
        name=name,
    )(page_table, positions, qr, pool_k, pool_v)
    return jnp.moveaxis(out.reshape(B, K, Sq, G, hd), 2, 1).reshape(
        B, Sq, H, hd)


def _paged_call(q, pool_k, k_scale, pool_v, v_scale, page_table, positions,
                *, name, window, cap, interpret):
    """Shared by every public entry point: over a bf16 pool decode takes
    _decode_call's walk and a chunk _prefill_call's; a quantized pool takes
    the (B, K, n_blocks) walk here.
    q (B, Sq, H, hd); pool_k/v (P, K, page, hd_store); k_scale/v_scale
    (P, K, page) fp32 for quantized pools, None for bf16 ones. Returns
    (B, Sq, H, hd).

    ``name`` becomes the kernel's HLO instruction name (the custom call's
    ``kernel_name``; without it the instruction takes the name of the
    enclosing jitted function), which is how a device trace finds it."""
    B, Sq, H, hd = q.shape
    if k_scale is None and Sq == 1:
        return _decode_call(q[:, 0], pool_k, pool_v, page_table, positions,
                            name=name, window=window, cap=cap,
                            interpret=interpret)[:, None]
    if k_scale is None:
        return _prefill_call(q, pool_k, pool_v, page_table, positions,
                             name=name, window=window, cap=cap,
                             interpret=interpret)
    _, K, page, hd_store = pool_k.shape
    bits = ref.kv_bits_of(pool_k, hd)
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
    scale_spec = pl.BlockSpec(
        (None, K, page), lambda b, k, i, pt, pos: kv_map(b, k, i, pt,
                                                         pos)[:1] + (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, n_blocks),
        in_specs=[q_spec, kv_spec, scale_spec, kv_spec, scale_spec],
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
    )(page_table, positions, qr, pool_k, k_scale, pool_v, v_scale)
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
