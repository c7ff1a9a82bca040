"""Continuous-batching engine: scheduler admission/eviction/backfill,
roofline admission policy, paged-pool bookkeeping, and served tokens
judged by their logit gap under the dense forward (conftest.served_gaps)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import GAP_TOL, assert_served, served_gaps, _dense_logits

from repro.configs import get_config, tiny_config
from repro.core.hardware_model import V5E_EDGE, V5E_POD
from repro.models.api import build_model
from repro.serving.engine import (AdmissionPolicy, Engine, PageAllocator,
                                  Request, Scheduler, derive_policy)


def _policy(**kw):
    base = dict(hw_name="test", max_model_len=64, page_size=16,
                num_pages=10_000, max_batch=4, prefill_chunk=16,
                quant_bits=16, decode_slo_s=0.03, est_decode_s=0.0,
                est_prefill_s=0.0)
    base.update(kw)
    return AdmissionPolicy(**base)


def _req(rid, S, gen, *, vocab=512, arrival=0.0, seed=None):
    rng = np.random.default_rng(rid if seed is None else seed)
    return Request(rid=rid, prompt=rng.integers(2, vocab, S, dtype=np.int64)
                   .astype(np.int32), max_new=gen, arrival=arrival)


def _sched(max_batch=2, num_pages=9, page_size=16, max_len=64):
    return Scheduler(PageAllocator(num_pages, page_size), max_batch, max_len)


# -------------------------------------------------------------- scheduler --
def test_admission_respects_max_batch():
    s = _sched(max_batch=2, num_pages=100)
    for i in range(4):
        s.submit(_req(i, 8, 8))
    admitted = s.admit()
    assert [a.req.rid for a in admitted] == [0, 1]   # FIFO order
    assert s.num_active == 2 and s.num_queued == 2
    assert s.admit() == []                            # slots full


def test_admission_reserves_prompt_pages_only():
    """Lazy admission: a (20-prompt, 20-gen) request reserves only the
    2 pages its prompt (+ first decode slot) needs, not the 3-page
    worst case — so 3 requests fit where upfront reservation admits 2."""
    s = _sched(max_batch=4, num_pages=9, page_size=16)
    for i in range(3):
        s.submit(_req(i, 20, 20))                     # 21 tokens -> 2 pages
    admitted = s.admit()
    assert len(admitted) == 3
    assert all(len(a.pages) == 2 for a in admitted)
    assert s.allocator.num_free == 2
    assert all(0 not in a.pages for a in admitted)    # scratch never leased


def test_admission_respects_page_budget():
    # 5 usable pages (page 0 is scratch); each request needs 2 up front,
    # and admission keeps a one-page growth watermark once anything is in
    # flight — so the 3rd request (needing 2 + 1 headroom > 1 free) waits.
    s = _sched(max_batch=4, num_pages=6, page_size=16)
    for i in range(3):
        s.submit(_req(i, 20, 20))
    admitted = s.admit()
    assert len(admitted) == 2
    assert s.allocator.num_free == 1


def test_admission_upfront_reserves_worst_case():
    """reserve_upfront=True restores the legacy policy: every page of
    prompt+max_new reserved at admission (3 pages each here)."""
    s = Scheduler(PageAllocator(9, 16), 4, 64, reserve_upfront=True)
    for i in range(3):
        s.submit(_req(i, 20, 20))                     # 40 tokens -> 3 pages
    admitted = s.admit()
    assert len(admitted) == 2
    assert all(len(a.pages) == 3 for a in admitted)
    assert s.allocator.num_free == 2


def test_eviction_frees_pages_and_backfills():
    s = _sched(max_batch=2, num_pages=6, page_size=16)
    for i in range(3):
        s.submit(_req(i, 20, 20))
    first = s.admit()
    assert len(first) == 2                            # slots full
    assert s.admit() == []
    s.release(first[0])
    assert s.allocator.num_free == 3
    backfilled = s.admit()
    assert [a.req.rid for a in backfilled] == [2]
    assert backfilled[0].slot == first[0].slot        # slot reused


def test_page_allocator_rejects_double_free():
    """Regression: a page freed twice used to enter the free list twice and
    could be handed to two sequences."""
    a = PageAllocator(6, 16)
    pages = a.alloc(3)
    a.free(pages[:1])
    with pytest.raises(ValueError, match="double free"):
        a.free(pages)                    # pages[0] already back in the pool
    with pytest.raises(ValueError, match="double free"):
        a.free([pages[1], pages[1]])     # duplicate within a single call
    # the guard kept state consistent: remaining pages still free cleanly
    a.free(pages[1:])
    assert a.num_free == 5 and a.num_allocated == 0
    with pytest.raises(ValueError):
        a.free([0])                      # scratch page is never leased


def test_growth_and_preemption_bookkeeping():
    """ensure_capacity grows page-by-page; exhaustion preempts the youngest,
    folding its generated tokens into a front-of-queue prompt extension."""
    s = _sched(max_batch=2, num_pages=5, page_size=16)
    s.submit(_req(0, 8, 40))
    s.submit(_req(1, 8, 40))
    a, b = s.admit()                     # 1 page each, 2 free
    for seq in (a, b):
        seq.generated.append(7)
        seq.pos = 8
    # walk a to position 47: needs 3 pages total, grabs the 2 free ones
    a.pos = 47
    assert s.ensure_capacity(a) and len(a.pages) == 3
    assert s.allocator.num_free == 0
    b.pos = 16                           # b crosses into block 1: no pages
    assert not s.ensure_capacity(b)
    # pages flow young -> old: the youngest is the victim, even when it is
    # the grower itself (b here, so b yields rather than stalling a)
    victim = s.youngest_active()
    assert victim is b
    s.preempt(victim)
    assert s.num_preempted == 1
    assert s.num_active == 1 and s.allocator.num_free == 1
    # b went back to the FIFO front with its generated token folded in
    req = s.queue[0]
    assert req.rid == 1 and len(req.prompt) == 9 and req.max_new == 39
    # with only a active, a itself is the youngest (the engine treats
    # "victim is grower and alone" as a pool-sizing error)
    assert s.youngest_active() is a


def test_admission_respects_arrival_times():
    s = _sched(max_batch=4, num_pages=100)
    s.submit(_req(0, 8, 8, arrival=0.0))
    s.submit(_req(1, 8, 8, arrival=5.0))
    assert [a.req.rid for a in s.admit(now=1.0)] == [0]
    assert [a.req.rid for a in s.admit(now=6.0)] == [1]


def test_submit_rejects_oversized_request():
    s = _sched(max_len=32)
    with pytest.raises(ValueError):
        s.submit(_req(0, 30, 10))


# ------------------------------------------------------- admission policy --
def test_admission_policy_haq_quant_on_edge():
    """8B params at bf16 (~16 GiB) can't fit the edge chip's HBM next to a
    4k sequence -> policy demands the HAQ int8 policy; the pod doesn't."""
    cfg = get_config("granite-3-8b")
    edge = derive_policy(cfg, V5E_EDGE, max_model_len=4096)
    pod = derive_policy(cfg, V5E_POD, max_model_len=4096)
    assert edge.quant_bits == 8
    assert pod.quant_bits == 16
    assert pod.max_batch > edge.max_batch
    assert pod.prefill_chunk >= edge.prefill_chunk
    assert edge.est_decode_s <= edge.decode_slo_s


def test_admission_policy_pages_fit_hbm():
    cfg = get_config("gemma2-2b")
    pol = derive_policy(cfg, V5E_EDGE, max_model_len=4096)
    from repro.serving.engine.admission import kv_bytes_per_token
    kv_bytes = (pol.num_pages - 1) * pol.page_size * kv_bytes_per_token(cfg)
    assert kv_bytes + cfg.param_count() * 2 * pol.quant_bits / 16 \
        <= V5E_EDGE.hbm_bytes
    # the pool must always hold >= 1 full-length sequence, else a legal
    # request could wait on page allocation forever
    assert pol.num_pages - 1 >= pol.pages_per_seq


# ----------------------------------------------------------------- engine --
@pytest.fixture(scope="module")
def gemma_tiny():
    cfg = tiny_config("gemma2-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def test_engine_matches_sequential_greedy(gemma_tiny):
    """Mixed-length continuous batching serves every request the greedy
    tokens of serving it alone: each within GAP_TOL of the best logit of
    an unbatched dense forward over that request."""
    model, params = gemma_tiny
    engine = Engine(model, params, _policy())
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(7):
        S = int(rng.integers(4, 44))     # spans the local window (32)
        gen = int(rng.integers(2, 16))
        reqs.append(Request(rid=i, prompt=rng.integers(
            2, model.cfg.vocab_size, S).astype(np.int32), max_new=gen))
    outs = engine.run(reqs)
    assert engine.stats["admitted"] == len(reqs)
    # batched: strictly fewer decode ticks than total decoded tokens
    assert engine.stats["decode_ticks"] < engine.stats["decode_tokens"]
    for r in reqs:
        assert outs[r.rid].shape == (len(r.prompt) + r.max_new,)
    assert_served(model, params, reqs, outs)


def test_served_gaps_has_teeth(gemma_tiny):
    """A served token swapped for the token the dense forward ranks at the
    vocabulary's median sits far above GAP_TOL: the oracle tells a wrong
    token from a bfloat16 near-tie."""
    model, params = gemma_tiny
    r = _req(0, 20, 8)
    served = Engine(model, params, _policy()).run([r])[0][20:]
    assert served_gaps(model, params, r.prompt, served).max() <= GAP_TOL
    V = model.cfg.vocab_size
    rows = _dense_logits(model, params,
                         np.concatenate([r.prompt, served[:-1]]))[19:]
    for i, row in enumerate(rows):
        wrong = served.copy()
        wrong[i] = np.argsort(row[:V])[V // 2]
        gaps = served_gaps(model, params, r.prompt, wrong)
        assert gaps[i] > 20 * GAP_TOL, (i, float(gaps[i]))


def test_engine_backfills_mid_flight(gemma_tiny):
    """With 2 slots and 3 requests, the short request finishes first and the
    queued one backfills while the long one is still decoding."""
    model, params = gemma_tiny
    engine = Engine(model, params, _policy(max_batch=2))
    reqs = [_req(0, 8, 2), _req(1, 8, 24), _req(2, 8, 2)]
    for r in reqs:
        engine.submit(r)
    finish_order = []
    while engine.scheduler.has_work():
        finish_order.extend(engine.step())
    assert finish_order[0] == 0
    assert finish_order.index(2) < finish_order.index(1)


def test_engine_eos_early_exit(gemma_tiny):
    model, params = gemma_tiny
    # find the greedy first token, then use it as eos: generation stops at 1
    r = _req(0, 8, 16)
    engine = Engine(model, params, _policy())
    first = engine.run([r])[0][len(r.prompt)]
    r2 = Request(rid=1, prompt=r.prompt, max_new=16, eos_id=int(first))
    out = Engine(model, params, _policy()).run([r2])[1]
    assert len(out) == len(r.prompt) + 1
    # pages were freed on eviction
    assert engine.kv.allocator.num_free == engine.kv.allocator.num_pages - 1


def test_engine_moe_routing_smoke():
    """MoE decode rides the same paged path (drop-free tiny capacity)."""
    cfg = tiny_config("granite-moe-3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(model, params, _policy(max_batch=2))
    reqs = [_req(i, 10, 4, vocab=cfg.vocab_size) for i in range(3)]
    assert_served(model, params, reqs, engine.run(reqs))


def test_engine_rejects_non_attention_families():
    cfg = tiny_config("mamba2-370m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError):
        Engine(model, params, _policy())


def test_engine_quantized_weights_path(gemma_tiny):
    """quant_bits < 16 swaps in HAQ-quantized weights + dequant dot; the
    engine still serves (outputs differ from bf16 — only shape-checked)."""
    model, params = gemma_tiny
    engine = Engine(model, params, _policy(quant_bits=8))
    r = _req(0, 12, 4)
    out = engine.run([r])[0]
    assert out.shape == (16,)


def test_engine_pallas_kernel_path(gemma_tiny):
    """The Pallas paged-attention kernel (interpret mode on CPU) serves the
    same trace the block-walk path does, every token within GAP_TOL of
    the dense forward's best. Kept tiny — interpret mode runs the kernel
    body per grid program in Python."""
    model, params = gemma_tiny
    engine = Engine(model, params, _policy(max_batch=2),
                    paged_kernel="pallas")
    reqs = [_req(0, 8, 4), _req(1, 11, 3)]
    assert_served(model, params, reqs, engine.run(reqs))


def test_engine_preemption_roundtrip_exact(gemma_tiny):
    """A pool too small for both sequences' full lifetimes forces at least
    one preemption (free pages + requeue as prompt-extension + re-prefill);
    every served token stays within GAP_TOL of the dense forward's best."""
    model, params = gemma_tiny
    # pages_per_seq=4 (64/16); 6 usable pages; both requests grow to 4
    # pages (12 + 44 = 56 tokens), so one must be preempted mid-flight.
    engine = Engine(model, params, _policy(max_batch=2, num_pages=7))
    reqs = [_req(0, 12, 44), _req(1, 12, 44)]
    outs = engine.run(reqs)
    assert engine.stats["preemptions"] >= 1
    assert engine.stats["grown_pages"] >= 3      # lazy growth really ran
    assert engine.scheduler.num_preempted == engine.stats["preemptions"]
    assert_served(model, params, reqs, outs)
    # all pages returned after drain
    assert engine.kv.allocator.num_allocated == 0


def test_engine_lazy_beats_upfront_admission(gemma_tiny):
    """With the same constrained pool, lazy allocation admits both requests
    at once where upfront reservation serializes them."""
    model, params = gemma_tiny
    reqs = [_req(0, 12, 44), _req(1, 12, 44)]
    ticks = {}
    for upfront in (True, False):
        engine = Engine(model, params, _policy(max_batch=2, num_pages=7),
                        reserve_upfront=upfront)
        outs = engine.run([_req(i, 12, 44) for i in range(2)])
        ticks[upfront] = engine.stats["decode_ticks"]
        assert_served(model, params, reqs, outs)
    # upfront: 4+4 pages never fit 6 -> strictly serial -> ~2x the ticks
    assert ticks[False] < ticks[True]


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


def test_paged_decode_never_builds_dense_kv(gemma_tiny):
    """Acceptance: the jitted decode step contains no chronological
    (B, max_pages*page, K, hd) dense KV intermediate — neither flat nor
    in its pre-reshape (B, max_pages, K|page, page|K, hd) forms."""
    model, params = gemma_tiny
    pol = _policy()
    B, maxp, page = pol.max_batch, pol.pages_per_seq, pol.page_size
    K, hd = model.cfg.num_kv_heads, model.cfg.resolved_head_dim
    pool = model.init_pool(9, page)
    pt = jnp.zeros((B, maxp), jnp.int32)
    tok = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: model.decode_step_paged(*a))(params, pool, pt, tok, pos)
    banned = {(B, maxp * page, K, hd), (B, maxp, K, page, hd),
              (B, maxp, page, K, hd)}
    dense = [a for a in _iter_avals(jaxpr.jaxpr)
             if getattr(a, "shape", None) in banned]
    assert not dense, dense
    # positive control: the same scan flags the dense-gather oracle
    from repro.kernels.ref import paged_attention_dense_ref
    q = jnp.zeros((B, model.cfg.num_heads, hd), jnp.bfloat16)
    pk = jax.tree.leaves(pool)[0][0]          # (P, K, page, hd)
    jx = jax.make_jaxpr(
        lambda *a: paged_attention_dense_ref(*a))(q, pk, pk, pt, pos)
    hits = [a for a in _iter_avals(jx.jaxpr)
            if getattr(a, "shape", None) in banned]
    assert hits, "aval scan lost its teeth"


def test_one_chunk_executable_for_every_prompt_length(gemma_tiny):
    """Every prompt length rides the one fixed-shape chunk program: after
    prompts of five lengths the chunk and decode closures each hold one
    compiled executable."""
    model, params = gemma_tiny
    engine = Engine(model, params, _policy(prefill_chunk=4))
    engine.run([_req(i, 4 * (i + 1) - i % 2, 2) for i in range(5)])
    assert engine.stats["prefill_chunks"] == sum(i + 1 for i in range(5))
    m = engine.telemetry.metrics
    assert m.gauge("jit.chunk.cache_size").value == 1.0
    assert m.gauge("jit.decode.cache_size").value == 1.0


@pytest.mark.slow
def test_engine_smoke_long_trace(gemma_tiny):
    """CI smoke: a 12-request trace with long tails on a constrained pool —
    exercises admission, growth, preemption, backfill, and eviction in one
    run and checks every output against the dense forward."""
    model, params = gemma_tiny
    engine = Engine(model, params, _policy(max_batch=3, num_pages=9))
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(12):
        S = int(rng.integers(4, 16))
        gen = int(rng.integers(4, 64 - S))
        reqs.append(Request(rid=i, prompt=rng.integers(
            2, model.cfg.vocab_size, S).astype(np.int32), max_new=gen))
    outs = engine.run(reqs)
    assert_served(model, params, reqs, outs)
    assert engine.kv.allocator.num_allocated == 0
