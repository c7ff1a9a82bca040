"""Chunked prefill: kernel parity vs the dense oracle, served tokens
against the dense forward across chunk and page sizes (conftest.
served_gaps), chunk-size invariance on quantized pools, mid-prefill
preemption round trips, and the no-dense-prompt-KV jaxpr guarantee."""
import numpy as np

import jax
import jax.numpy as jnp
import pytest
from conftest import assert_served

from repro.configs import tiny_config
from repro.kernels import ops, ref
from repro.kernels import paged_attention as pa
from repro.models.api import build_model
from repro.serving.engine import AdmissionPolicy, Engine, Request


def _policy(**kw):
    base = dict(hw_name="test", max_model_len=64, page_size=16,
                num_pages=10_000, max_batch=4, prefill_chunk=16,
                quant_bits=16, decode_slo_s=0.03, est_decode_s=0.0,
                est_prefill_s=0.0)
    base.update(kw)
    return AdmissionPolicy(**base)


def _req(rid, S, gen, *, vocab=512, seed=None):
    rng = np.random.default_rng(rid if seed is None else seed)
    return Request(rid=rid, prompt=rng.integers(2, vocab, S, dtype=np.int64)
                   .astype(np.int32), max_new=gen)


# ------------------------------------------------------ kernel parity ------
def _prefill_case(B, H, K, hd, page, n_blocks, Sq, *, num_pages=11, seed=0,
                  positions=None):
    """Random pool + ragged chunk-start positions: each sequence's chunk
    begins at a different resident-prefix length (``positions``, drawn
    when not given), pages shuffled, unused page-table tails on the
    poisoned scratch page 0."""
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool_k = jax.random.normal(ks[0], (num_pages, K, page, hd), jnp.float32)
    pool_v = jax.random.normal(ks[1], (num_pages, K, page, hd), jnp.float32)
    pool_k = pool_k.at[0].set(37.0)          # a masking bug reads these
    pool_v = pool_v.at[0].set(-53.0)
    q = jax.random.normal(ks[2], (B, Sq, H, hd), jnp.float32)
    if positions is None:
        positions = rng.integers(0, n_blocks * page - Sq, B).astype(np.int32)
        positions[0] = 0                      # empty-prefix edge case
    positions = np.asarray(positions, np.int32)
    pt = np.zeros((B, n_blocks), np.int32)
    for b in range(B):
        need = (positions[b] + Sq - 1) // page + 1
        pt[b, :need] = rng.choice(np.arange(1, num_pages), need,
                                  replace=False)
    return (q, pool_k, pool_v, jnp.asarray(pt),
            jnp.asarray(positions, jnp.int32))


@pytest.mark.parametrize("page,n_blocks", [(8, 6), (16, 4), (32, 2)])
@pytest.mark.parametrize("Sq", [1, 5, 16])
@pytest.mark.parametrize("window,cap", [(0, 0.0), (24, 0.0), (0, 30.0)])
@pytest.mark.parametrize("H,K", [(4, 2), (4, 1)])
def test_prefill_kernel_parity(page, n_blocks, Sq, window, cap, H, K):
    """Pallas chunked-prefill kernel (interpret) and the pure-JAX walk both
    match the dense gather+mask oracle across chunk sizes, page sizes,
    local windows, GQA shapes, ragged chunk starts, and scratch tails.
    Sq == 1 degenerates to the decode walk's semantics."""
    q, pk, pv, pt, pos = _prefill_case(3, H, K, 32, page, n_blocks, Sq)
    want = ref.paged_prefill_dense_ref(q, pk, pv, pt, pos,
                                       window=window, cap=cap)
    got_k = pa.paged_prefill_fwd(q, pk, pv, pt, pos, window=window,
                                 cap=cap, interpret=True)
    got_r = ref.paged_prefill_ref(q, pk, pv, pt, pos, window=window,
                                  cap=cap)
    assert float(jnp.max(jnp.abs(got_k - want))) < 1e-5
    assert float(jnp.max(jnp.abs(got_r - want))) < 1e-5


# One case per edge of the bf16 chunk walk (128 tokens, 128 // page pages,
# per grid step): 68 pages of 16 fill 8 steps and a ninth of 4; every case
# has a chunk at position 0 and one whose context ends on the table's last
# slot (1087 of 1088 tokens); the others end mid-step, and under a window
# their first live page sits mid-step. Chunks of more than 4096 rows
# (queries x G) span two query tiles; tiles of 2048 and 3072 rows take
# their rows 1024 at a time.
WALK_CASES = {
    # name: (H, K, page, n_blocks, Sq, positions, window, cap)
    "tail_step_G4": (8, 2, 16, 68, 512, [0, 576, 300], 0, 0.0),
    "tail_step_G6_softcap": (12, 2, 16, 68, 40, [0, 1048, 300], 0, 30.0),
    "window_mid_step_G4": (8, 2, 16, 68, 24, [0, 1064, 700], 200, 0.0),
    "window_mid_step_G6": (12, 2, 16, 68, 56, [0, 1032, 517], 300, 0.0),
    "page32_G4": (8, 2, 32, 34, 40, [0, 1048, 500], 0, 0.0),
    "page32_window_softcap": (8, 2, 32, 34, 40, [0, 1048, 590], 100, 30.0),
    "page64_G6": (12, 2, 64, 17, 40, [0, 1048, 620], 0, 0.0),
    "page64_window": (12, 2, 64, 17, 40, [0, 1048, 620], 150, 0.0),
    "two_query_tiles_G4_window": (4, 1, 16, 68, 1056, [0, 32], 300, 0.0),
    "two_query_tiles_G6_softcap": (6, 1, 16, 68, 1024, [0, 64], 0, 30.0),
}


@pytest.mark.parametrize("case", list(WALK_CASES))
def test_prefill_walk_parity(case):
    """The bf16-pool chunk walk (interpret) matches the dense oracle where
    a step is cut: a table that ends mid-step, contexts that end mid-step
    or on the last slot, windows whose first live page is mid-step, pages
    of 32 and 64, softcaps, chunks at position 0 and chunks over two
    query tiles."""
    H, K, page, n_blocks, Sq, positions, window, cap = WALK_CASES[case]
    q, pk, pv, pt, pos = _prefill_case(len(positions), H, K, 32, page,
                                       n_blocks, Sq, num_pages=n_blocks + 9,
                                       positions=positions)
    want = ref.paged_prefill_dense_ref(q, pk, pv, pt, pos,
                                       window=window, cap=cap)
    got = pa.paged_prefill_fwd(q, pk, pv, pt, pos, window=window, cap=cap,
                               interpret=True)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("page,Sq", [(8, 6), (16, 16)])
@pytest.mark.parametrize("window", [0, 24])
def test_prefill_kernel_parity_quant(bits, page, Sq, window):
    """The fused-dequant chunked-prefill walk (ref and Pallas interpret)
    matches the dense oracle over the dequantized pool exactly — the
    quantization error lives in the pool contents, not the walk."""
    q, pk, pv, pt, pos = _prefill_case(2, 4, 2, 32, page, 4, Sq, seed=5)
    qk, sk = ref.quantize_kv(pk, bits)
    qv, sv = ref.quantize_kv(pv, bits)
    want = ref.paged_prefill_dense_ref(
        q, ref.dequantize_kv(qk, sk, bits), ref.dequantize_kv(qv, sv, bits),
        pt, pos, window=window)
    got_r = ops.paged_attention_prefill_quant(q, qk, sk, qv, sv, pt, pos,
                                              window=window, mode="ref")
    got_k = ops.paged_attention_prefill_quant(q, qk, sk, qv, sv, pt, pos,
                                              window=window, mode="pallas")
    assert float(jnp.max(jnp.abs(got_r - want))) < 1e-5
    assert float(jnp.max(jnp.abs(got_k - want))) < 1e-5


def test_prefill_chunk_overruns_page_table():
    """Regression: a final chunk whose padding extends past the page-table
    width (Sq not dividing the model length) must not corrupt the REAL
    query rows — the ref walk used to stage the overrun blocks' all-masked
    scores at a clamped offset, clobbering the last real block."""
    page, n_blocks, Sq = 16, 6, 64          # chunk spans blocks 4..7 of 6
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    pk = jax.random.normal(ks[0], (9, 2, page, 32), jnp.float32)
    pv = jax.random.normal(ks[1], (9, 2, page, 32), jnp.float32)
    q = jax.random.normal(ks[2], (1, Sq, 4, 32), jnp.float32)
    pt = jnp.asarray(np.arange(1, n_blocks + 1, dtype=np.int32)[None])
    pos = jnp.asarray([64], jnp.int32)      # real rows: qpos 64..95
    want = ref.paged_prefill_dense_ref(q, pk, pv, pt, pos)
    got_r = ref.paged_prefill_ref(q, pk, pv, pt, pos)
    got_k = pa.paged_prefill_fwd(q, pk, pv, pt, pos, interpret=True)
    real = slice(0, n_blocks * page - 64)   # rows whose qpos < T
    assert float(jnp.max(jnp.abs(got_r[:, real] - want[:, real]))) < 1e-5
    assert float(jnp.max(jnp.abs(got_k[:, real] - want[:, real]))) < 1e-5


# ---------------------------------------------- engine greedy equivalence --
@pytest.fixture(scope="module")
def gemma_tiny():
    cfg = tiny_config("gemma2-2b")     # local/global mix + softcap + GQA
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.mark.parametrize("chunk", [4, 16, 32])
@pytest.mark.parametrize("page_size", [8, 16])
def test_chunked_matches_whole_prompt(gemma_tiny, chunk, page_size):
    """At every chunk size x page size the served tokens are those of the
    whole prompt run through one dense forward: each within GAP_TOL of its
    best logit (prompts span the local window and cross page and chunk
    boundaries)."""
    model, params = gemma_tiny
    reqs = [_req(0, 37, 8), _req(1, 44, 6), _req(2, 7, 5), _req(3, 16, 4)]
    engine = Engine(model, params,
                    _policy(prefill_chunk=chunk, page_size=page_size))
    outs = engine.run(reqs)
    assert engine.stats["prefill_chunks"] >= sum(
        -(-len(r.prompt) // chunk) for r in reqs)
    assert_served(model, params, reqs, outs)


def test_chunk_padding_past_model_len(gemma_tiny):
    """Regression: prompts whose final chunk pads beyond max_model_len
    (chunk does not divide the model length) are served sound tokens —
    overflow rows land on the scratch page, never on live pages or
    undefined scatter indices."""
    model, params = gemma_tiny
    reqs = [_req(0, 85, 11), _req(1, 90, 6)]    # prompts fill the table
    for chunk in (64, 32):
        pol = _policy(max_model_len=96, prefill_chunk=chunk, max_batch=2)
        outs = Engine(model, params, pol).run(reqs)
        assert_served(model, params, reqs, outs)


def test_chunked_matches_sequential_baseline(gemma_tiny):
    """The chunked engine serves a mixed trace the greedy tokens of each
    request alone: within GAP_TOL of an unbatched dense forward's best."""
    model, params = gemma_tiny
    engine = Engine(model, params, _policy(prefill_chunk=8))
    reqs = [_req(i, 5 + 9 * i, 6) for i in range(4)]
    assert_served(model, params, reqs, engine.run(reqs))


@pytest.mark.parametrize("kv_bits", [8, (4, 8)])
def test_chunked_quantized_pool_matches_single_chunk(gemma_tiny, kv_bits):
    """On quantized pools the chunk size must not change outputs either:
    many small chunks == one whole-prompt-sized chunk, bit-identically
    (quantize-on-write uses per-token scales, so chunking never re-scales
    resident tokens)."""
    model, params = gemma_tiny
    reqs = [_req(0, 37, 6), _req(1, 22, 5)]
    outs = {}
    for name, chunk in (("small", 8), ("whole", 64)):
        engine = Engine(model, params,
                        _policy(prefill_chunk=chunk, kv_bits=kv_bits))
        outs[name] = engine.run([_req(r.rid, len(r.prompt), r.max_new)
                                 for r in reqs])
    for r in reqs:
        assert np.array_equal(outs["small"][r.rid], outs["whole"][r.rid]), \
            r.rid


# ------------------------------------------------- mid-prefill preemption --
def test_mid_prefill_preemption_roundtrip(gemma_tiny):
    """A sequence preempted in the middle of its prompt chunks (pages freed,
    requeued) restarts at re-admission and is still served tokens within
    GAP_TOL of the dense forward's best."""
    model, params = gemma_tiny
    # page 2, 35 usable pages: seq 0 (9-prompt, 5 pages) decodes from tick
    # 3 and crosses a page boundary every other tick (growths at ticks 4
    # and 6); seq 1's 57-token prompt reserves 29 pages and chunks for 8
    # ticks at chunk 8, leaving ONE free page after admission — seq 0's
    # second growth exhausts the pool at tick 6, while seq 1 (younger)
    # still owes two chunks, so the preemption victim is chunk-pending.
    engine = Engine(model, params,
                    _policy(max_batch=2, num_pages=36, page_size=2,
                            prefill_chunk=8))
    preempted_mid_prefill = []
    orig = engine.scheduler.preempt

    def spy(seq):
        if not seq.prefill_done:
            preempted_mid_prefill.append(
                (seq.req.rid, seq.prefill_progress, len(seq.req.prompt)))
        orig(seq)

    engine.scheduler.preempt = spy
    reqs = [_req(0, 9, 44), _req(1, 57, 6)]
    outs = engine.run(reqs)
    assert preempted_mid_prefill, \
        "trace did not preempt a mid-prefill sequence; retune the pool"
    rid, progress, S = preempted_mid_prefill[0]
    assert 0 < progress < S       # genuinely mid-prompt, chunk-aligned
    assert progress % 8 == 0
    assert_served(model, params, reqs, outs)
    assert engine.kv.allocator.num_allocated == 0


def test_scheduler_gates_chunk_pending_sequences(gemma_tiny):
    """Chunk-pending sequences hold a batch slot but never enter the
    decode batch; they join it the tick their final chunk lands."""
    model, params = gemma_tiny
    engine = Engine(model, params, _policy(max_batch=2, prefill_chunk=8))
    engine.submit(_req(0, 4, 12))         # ready after one chunk
    engine.submit(_req(1, 33, 4))         # 5 chunks of 8
    for tick in range(5):
        engine.step()
        pending = engine.scheduler.prefill_pending()
        ready = engine.scheduler.decode_ready()
        if tick < 4:
            assert [s.req.rid for s in pending] == [1]
            assert [s.req.rid for s in ready] == [0]
            assert pending[0].prefill_progress == 8 * (tick + 1)
            assert not pending[0].generated    # no token before last chunk
        else:
            assert not pending                 # final chunk landed
    assert any(s.req.rid == 1 and s.generated
               for s in engine.scheduler.active.values())


# ------------------------------------------------------- jaxpr guarantee ---
def _iter_avals(jaxpr):
    from jax.extend.core import Jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for p in eqn.params.values():
            subs = p if isinstance(p, (list, tuple)) else [p]
            for s in subs:
                inner = getattr(s, "jaxpr", None)
                if isinstance(s, Jaxpr):
                    yield from _iter_avals(s)
                elif isinstance(inner, Jaxpr):
                    yield from _iter_avals(inner)


def test_chunked_prefill_never_builds_dense_prompt_kv(gemma_tiny):
    """The jitted chunk forward contains no chronological dense prompt KV
    intermediate — neither the flat (1, max_pages*page, K, hd) gather nor
    its pre-reshape (1, max_pages, K|page, page|K, hd) forms."""
    model, params = gemma_tiny
    pol = _policy()
    maxp, page = pol.pages_per_seq, pol.page_size
    K, hd = model.cfg.num_kv_heads, model.cfg.resolved_head_dim
    pool = model.init_pool(9, page)
    pt = jnp.zeros((1, maxp), jnp.int32)
    toks = jnp.zeros((1, pol.prefill_chunk), jnp.int32)
    pos = jnp.zeros((1,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: model.prefill_chunk_paged(*a))(params, pool, pt, toks,
                                                  pos)
    banned = {(1, maxp * page, K, hd), (1, maxp, K, page, hd),
              (1, maxp, page, K, hd)}
    dense = [a for a in _iter_avals(jaxpr.jaxpr)
             if getattr(a, "shape", None) in banned]
    assert not dense, dense
    # positive control: the dense oracle must trip the same scan
    q = jnp.zeros((1, pol.prefill_chunk, model.cfg.num_heads, hd),
                  jnp.bfloat16)
    pk = jax.tree.leaves(pool)[0][0]          # (P, K, page, hd)
    jx = jax.make_jaxpr(
        lambda *a: ref.paged_prefill_dense_ref(*a))(q, pk, pk, pt, pos)
    hits = [a for a in _iter_avals(jx.jaxpr)
            if getattr(a, "shape", None) in banned]
    assert hits, "aval scan lost its teeth"


# ----------------------------------------------------------- slow smoke ----
@pytest.mark.slow
def test_chunked_long_trace_smoke(gemma_tiny):
    """CI smoke: a 10-request trace with long prompts on a constrained pool
    — admission, chunking, growth, preemption (possibly mid-prefill), and
    backfill in one run, every output checked against the dense
    forward."""
    model, params = gemma_tiny
    engine = Engine(model, params,
                    _policy(max_batch=3, num_pages=9, prefill_chunk=8))
    rng = np.random.default_rng(11)
    reqs = []
    for i in range(10):
        S = int(rng.integers(4, 49))
        gen = int(rng.integers(4, 64 - S))
        reqs.append(Request(rid=i, prompt=rng.integers(
            2, model.cfg.vocab_size, S).astype(np.int32), max_new=gen))
    outs = engine.run(reqs)
    assert engine.stats["prefill_chunks"] > len(reqs)
    assert_served(model, params, reqs, outs)
    assert engine.kv.allocator.num_allocated == 0
