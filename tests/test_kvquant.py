"""HAQ-searched KV-cache quantization (serving/kvquant): storage-mapping
round trips, fused-dequant kernel parity, bit-policy search + gating,
KV-aware admission capacity, quantized engine drift bounds, window-trim
page freeing, and the no-dense-fp-KV jaxpr guarantee."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_served

from repro.configs import get_config, tiny_config
from repro.core import haq
from repro.core.hardware_model import V5E_EDGE
from repro.kernels import ops, ref
from repro.kernels import paged_attention as pa
from repro.models.api import build_model
from repro.models.transformer import normalize_kv_bits
from repro.serving import kvquant
from repro.serving.engine import (AdmissionPolicy, Engine, PageAllocator,
                                  Request, Scheduler, derive_policy)
from repro.serving.engine.admission import kv_bytes_per_token

# Documented greedy-drift tolerances for the FIXED untrained tiny subject
# and traces below (deterministic on CPU; measured ~0.61 / ~1.07). An
# untrained model's KV carries full-scale noise, so these are loose upper
# bounds on the serving regime, not quality claims — trained-subject
# quality ordering is benchmarks/table6's job.
DRIFT_TOL = {8: 1.0, 4: 1.6}
# Preemption round-trip: tokens generated before a preemption are folded
# into the prompt verbatim, so only post-resume tokens may drift.
PREEMPT_MATCH_TOL = 0.9


def _policy(**kw):
    base = dict(hw_name="test", max_model_len=64, page_size=16,
                num_pages=10_000, max_batch=4, prefill_chunk=16,
                quant_bits=16, decode_slo_s=0.03, est_decode_s=0.0,
                est_prefill_s=0.0)
    base.update(kw)
    return AdmissionPolicy(**base)


def _req(rid, S, gen, *, vocab=512, seed=None):
    rng = np.random.default_rng(rid if seed is None else seed)
    return Request(rid=rid, prompt=rng.integers(2, vocab, S)
                   .astype(np.int32), max_new=gen)


@pytest.fixture(scope="module")
def gemma_tiny():
    cfg = tiny_config("gemma2-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


# ------------------------------------------------------- storage mapping --
def test_int4_pack_roundtrip_exact():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.integers(-7, 8, (3, 5, 2, 32)), jnp.int8)
    packed = ref.pack_int4_hd(q)
    assert packed.shape == (3, 5, 2, 16) and packed.dtype == jnp.int8
    assert jnp.array_equal(ref.unpack_int4_hd(packed), q)


@pytest.mark.parametrize("bits,hd", [(8, 32), (8, 16), (4, 32), (4, 16)])
@pytest.mark.parametrize("granularity", ["token", "page"])
def test_kv_roundtrip_bounded(bits, hd, granularity):
    x = jax.random.normal(jax.random.PRNGKey(bits + hd),
                          (3, 2, 8, hd), jnp.float32) * 2.0  # (P, K, page)
    q, scale = kvquant.quantize_kv(x, bits, granularity=granularity)
    deq = kvquant.dequantize_kv(q, scale, bits, granularity=granularity)
    bound = scale[..., None] if granularity == "token" \
        else scale[..., None, None]
    assert bool(jnp.all(jnp.abs(deq - x) <= bound * 0.5 + 1e-6))
    # int4 really halves storage; scale tile is per (head, slot) or (head,)
    assert q.shape[-1] == (hd if bits == 8 else hd // 2)
    assert scale.shape == ((3, 2, 8) if granularity == "token" else (3, 2))


def test_kv_roundtrip_property():
    """Hypothesis sweep of the uniform-quantizer bound |x - deq| <= scale/2
    across (bits, head_dim, scale granularity) — the invariant every
    consumer of the page layout (writers, kernel, ref walk) relies on."""
    pytest.importorskip("hypothesis",
                        reason="optional dep: property tests need hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=40, deadline=None)
    @given(bits=st.sampled_from([4, 8]),
           hd=st.sampled_from([2, 8, 16, 64]),
           gran=st.sampled_from(["token", "page"]),
           slots=st.integers(1, 9), heads=st.integers(1, 3),
           seed=st.integers(0, 50), amp=st.floats(1e-3, 100.0))
    def check(bits, hd, gran, slots, heads, seed, amp):
        x = jnp.asarray(np.random.default_rng(seed)
                        .standard_normal((heads, slots, hd)) * amp,
                        jnp.float32)
        q, scale = kvquant.quantize_kv(x, bits, granularity=gran)
        deq = kvquant.dequantize_kv(q, scale, bits, granularity=gran)
        bound = scale[..., None] if gran == "token" \
            else scale[..., None, None]
        assert bool(jnp.all(jnp.abs(deq - x) <= bound * 0.5
                            + 1e-6 * amp + 1e-9))
        # monotone: int8 reconstruction never worse than int4
        if bits == 4:
            q8, s8 = kvquant.quantize_kv(x, 8, granularity=gran)
            d8 = kvquant.dequantize_kv(q8, s8, 8, granularity=gran)
            assert float(jnp.max(jnp.abs(d8 - x))) <= \
                float(jnp.max(jnp.abs(deq - x))) + 1e-6 * amp

    check()


def test_kv_bits_inference_rejects_garbage():
    assert ref.kv_bits_of(jnp.zeros((2, 4, 1, 32), jnp.int8), 32) == 8
    assert ref.kv_bits_of(jnp.zeros((2, 4, 1, 16), jnp.int8), 32) == 4
    with pytest.raises(ValueError):
        ref.kv_bits_of(jnp.zeros((2, 4, 1, 8), jnp.int8), 32)


# -------------------------------------------------------- kernel parity ---
def _quant_case(B, H, K, hd, page, n_blocks, bits, *, num_pages=11, seed=0,
                positions=None):
    """Random quantized pool + ragged page tables; scratch page 0 codes AND
    scales poisoned so any leak past the mask explodes the error.
    ``positions`` (B of them) fixes the positions; a row at 0 is then an
    idle slot, its whole page table on scratch page 0."""
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    num_pages = max(num_pages, n_blocks + 1)
    pool_k = jax.random.normal(ks[0], (num_pages, K, page, hd), jnp.float32)
    pool_v = jax.random.normal(ks[1], (num_pages, K, page, hd), jnp.float32)
    q = jax.random.normal(ks[2], (B, H, hd), jnp.float32)
    kq, ksc = ref.quantize_kv(pool_k, bits)
    vq, vsc = ref.quantize_kv(pool_v, bits)
    kq = kq.at[0].set(55)
    vq = vq.at[0].set(-55)
    ksc = ksc.at[0].set(97.0)
    vsc = vsc.at[0].set(83.0)
    idle = positions is not None
    if positions is None:
        positions = rng.integers(0, n_blocks * page, B).astype(np.int32)
        positions[0] = 0
    positions = np.asarray(positions, np.int32)
    pt = np.zeros((B, n_blocks), np.int32)
    for b in range(B):
        if idle and positions[b] == 0:
            continue
        need = positions[b] // page + 1
        pt[b, :need] = rng.choice(np.arange(1, num_pages), need,
                                  replace=False)
    return (q, kq, ksc, vq, vsc, jnp.asarray(pt),
            jnp.asarray(positions, jnp.int32))


# H, K, window, cap, page, n_blocks, positions, bits (positions as in
# _quant_case; the last four cases are tests/test_kernels.py's walk edges)
QUANT_WALK_CASES = [
    pytest.param(H, K, window, cap, page, n_blocks, None, bits,
                 id=f"{H}-{K}-{window}-{cap}-{page}-{n_blocks}-{bits}")
    for H, K in [(4, 2), (2, 2), (4, 1)]
    for window, cap in [(0, 0.0), (24, 0.0), (0, 30.0)]
    for page, n_blocks in [(8, 6), (16, 4), (32, 2)]
    for bits in [8, 4]
] + [
    pytest.param(*case, bits, id=f"{name}-{bits}")
    for name, case in [
        ("G6-68pages", (48, 8, 0, 0.0, 16, 68, (0, 300, 1087))),
        ("G6-68pages-window-lo-mid-step",
         (48, 8, 200, 0.0, 16, 68, (1000, 0, 812))),
        ("page32-19pages", (4, 2, 0, 30.0, 32, 19, (0, 129, 607))),
        ("page64-5pages", (4, 1, 100, 0.0, 64, 5, (319, 200, 0)))]
    for bits in [8, 4]
]


@pytest.mark.parametrize("H,K,window,cap,page,n_blocks,positions,bits",
                         QUANT_WALK_CASES)
def test_paged_attention_quant_parity(H, K, window, cap, page, n_blocks,
                                      positions, bits):
    """Fused-dequant Pallas kernel (interpret) and the pure-JAX quant walk
    both match the dense oracle evaluated on the dequantized pool, across
    bitwidths, page sizes, local windows, GQA shapes, ragged positions,
    poisoned scratch pages/scales and idle slots."""
    q, kq, ksc, vq, vsc, pt, pos = _quant_case(3, H, K, 32, page, n_blocks,
                                               bits, positions=positions)
    kd = ref.dequantize_kv(kq, ksc, bits)
    vd = ref.dequantize_kv(vq, vsc, bits)
    want = ref.paged_attention_dense_ref(q, kd, vd, pt, pos,
                                         window=window, cap=cap)
    got_k = pa.paged_attention_quant_fwd(q, kq, ksc, vq, vsc, pt, pos,
                                         window=window, cap=cap,
                                         interpret=True)
    got_r = ref.paged_attention_quant_ref(q, kq, ksc, vq, vsc, pt, pos,
                                          window=window, cap=cap)
    assert float(jnp.max(jnp.abs(got_k - want))) < 1e-5
    assert float(jnp.max(jnp.abs(got_r - want))) < 1e-5


def test_quant_dispatch_modes():
    q, kq, ksc, vq, vsc, pt, pos = _quant_case(2, 4, 2, 32, 16, 3, 8)
    want = ref.paged_attention_quant_ref(q, kq, ksc, vq, vsc, pt, pos)
    got = ops.paged_attention_quant(q, kq, ksc, vq, vsc, pt, pos,
                                    mode="auto")
    assert float(jnp.max(jnp.abs(got - want))) < 1e-6
    with pytest.raises(ValueError):
        ops.paged_attention_quant(q, kq, ksc, vq, vsc, pt, pos,
                                  mode="dense")


# ------------------------------------------------- pool layout & policy ---
def test_normalize_kv_bits_forms():
    cfg = tiny_config("gemma2-2b")          # period 2: (local, global)
    assert normalize_kv_bits(cfg, None) is None
    assert normalize_kv_bits(cfg, 16) is None
    assert normalize_kv_bits(cfg, (16, 16)) is None
    assert normalize_kv_bits(cfg, 8) == (8, 8)
    assert normalize_kv_bits(cfg, (4,)) == (4, 4)
    assert normalize_kv_bits(cfg, {"sub0": 4}) == (4, 16)
    # a searched policy (kv_sub{j} site names) round-trips as-is
    assert normalize_kv_bits(cfg, {"kv_sub0": 4, "kv_sub1": 8}) == (4, 8)
    assert normalize_kv_bits(cfg, [4, 8]) == (4, 8)
    with pytest.raises(ValueError):
        normalize_kv_bits(cfg, 5)
    with pytest.raises(ValueError):
        normalize_kv_bits(cfg, (4, 8, 16))   # 3 does not cycle into 2
    with pytest.raises(ValueError):
        normalize_kv_bits(cfg, {"sub2": 4})  # beyond the period
    with pytest.raises(ValueError):
        normalize_kv_bits(cfg, {"Sub0": 4})  # typo must not drop quant


def test_pool_specs_quantized_layout(gemma_tiny):
    model, _ = gemma_tiny
    cfg = model.cfg
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    G = cfg.num_layers // 2
    specs = model.pool_specs(9, 16, kv_bits=(4, 8))
    s4, s8 = specs["sub0"]["k"], specs["sub1"]["k"]
    assert s4["q"].shape == (G, 9, K, 16, hd // 2)
    assert s8["q"].shape == (G, 9, K, 16, hd)
    assert s4["q"].dtype == jnp.int8
    assert s4["scale"].shape == (G, 9, K, 16)
    assert s4["scale"].dtype == jnp.float32
    # fp slots keep the bf16 layout; all-16 collapses to it entirely
    mixed = model.pool_specs(9, 16, kv_bits={"sub0": 8})
    assert mixed["sub1"]["k"].dtype == jnp.bfloat16
    assert model.pool_specs(9, 16, kv_bits=16) == model.pool_specs(9, 16)


def test_enumerate_kv_sites_and_gate():
    cfg = get_config("gemma2-2b")
    sites = haq.enumerate_kv_sites(cfg, batch=1, ctx=8192)
    assert [s.name for s in sites] == ["kv_sub0", "kv_sub1"]
    local, glob = sites
    assert local.local and not glob.local
    assert local.eff_ctx == cfg.window_size and glob.eff_ctx == 8192
    assert kvquant.allowed_kv_bits(local) == (4, 8, 16)
    assert kvquant.allowed_kv_bits(glob) == (8, 16)
    # int8 halves the latency-model KV traffic, roughly
    t16 = glob.latency(V5E_EDGE, 16)
    t8 = glob.latency(V5E_EDGE, 8)
    assert t8 < 0.7 * t16


def test_search_kv_policy_budget_and_gate():
    cfg = get_config("gemma2-2b")
    # deterministic back-off: tight budget drops local slots to int4 first,
    # global slots floor at int8 (the sensitivity gate)
    res = kvquant.search_kv_policy(cfg, V5E_EDGE, max_model_len=4096,
                                   episodes=0, budget_frac=0.4)
    assert res["policy"] == {"kv_sub0": 4, "kv_sub1": 8}
    assert res["resource"] <= res["budget"] * 1.001
    assert res["kv_bytes_per_token"] < res["kv_bytes_per_token_fp"]
    # RL search: feasible unless even the gated floor cannot fit
    res = kvquant.search_kv_policy(cfg, V5E_EDGE, max_model_len=4096,
                                   episodes=4, budget_frac=0.55, seed=0)
    floor = [min(kvquant.allowed_kv_bits(s)) for s in
             haq.enumerate_kv_sites(cfg, 1, 4096)]
    feasible = res["resource"] <= res["budget"] * 1.001
    at_floor = res["bits"] == tuple(floor)
    assert feasible or at_floor
    assert all(b >= 8 for b, s in zip(res["bits"],
                                      haq.enumerate_kv_sites(cfg, 1, 4096))
               if not s.local)


def test_admission_capacity_scales_with_kv_bits():
    """Acceptance: at equal HBM budget the int8-KV policy fits >= 1.5x the
    resident sequences (and ~2x the pages) of the fp pool; the HAQ-mixed
    policy more. Scale tiles are priced in, so the ratios are honest."""
    cfg = get_config("gemma2-2b")
    per16 = kv_bytes_per_token(cfg)
    per8 = kv_bytes_per_token(cfg, 8)
    per48 = kv_bytes_per_token(cfg, (4, 8))
    assert per16 / per8 >= 1.5 and per16 / per48 >= 2.0
    # a generous SLO keeps the batch memory-bound so capacity is visible
    fp = derive_policy(cfg, V5E_EDGE, max_model_len=4096, decode_slo_s=1.0)
    q8 = derive_policy(cfg, V5E_EDGE, max_model_len=4096, decode_slo_s=1.0,
                       kv_bits=8)
    mx = derive_policy(cfg, V5E_EDGE, max_model_len=4096, decode_slo_s=1.0,
                       kv_bits=(4, 8))
    assert q8.num_pages >= 1.5 * fp.num_pages
    assert q8.max_batch >= 1.5 * fp.max_batch
    assert mx.num_pages > q8.num_pages
    assert q8.kv_bits == (8,) and mx.kv_bits == (4, 8)
    # quantized pages are smaller, so the same HBM must never be exceeded
    kv_bytes = (q8.num_pages - 1) * q8.page_size * per8
    assert kv_bytes + cfg.param_count() * 2 * q8.quant_bits / 16 \
        <= V5E_EDGE.hbm_bytes


# ------------------------------------------------------- engine + drift ---
def _kv_trace(cfg, n=4):
    """The actual bench kv trace (same generator, same seed), so the drift
    tolerance asserted here covers what BENCH_engine.json publishes."""
    from benchmarks.bench_engine_throughput import (TRACE_SEEDS,
                                                    make_skewed_trace)
    return make_skewed_trace(cfg, n, seed=TRACE_SEEDS["kv"])


@pytest.mark.slow
def test_engine_int8_drift_bounded_on_bench_trace(gemma_tiny):
    """Acceptance: the int8-KV engine on the bench trace is token-identical
    to the fp pool until a drift-explained flip — teacher-forced max-abs
    logit drift is under the documented tolerance, and at each request's
    first divergence the fp top-2 margin is within 2x the measured drift
    (a larger flip would need a logit error above the bound)."""
    model, params = gemma_tiny
    reqs = _kv_trace(model.cfg)
    fp = Engine(model, params, _policy(max_model_len=128)).run(reqs)
    q8 = Engine(model, params,
                _policy(max_model_len=128, kv_bits=(8,))).run(reqs)
    worst = 0.0
    for r in reqs:
        rep = kvquant.greedy_drift(model, params, fp[r.rid],
                                   len(r.prompt), kv_bits=8)
        worst = max(worst, rep["max_abs"])
        a, b = fp[r.rid], q8[r.rid]
        S = len(r.prompt)
        div = np.nonzero(a[S:] != b[S:])[0]
        if len(div):
            gap = rep["margins"][div[0]]
            assert gap <= 2 * rep["max_abs"] + 1e-6, (r.rid, gap)
    assert worst <= DRIFT_TOL[8], worst


@pytest.mark.slow
def test_engine_quantized_preemption_roundtrip(gemma_tiny):
    """A quantized-pool run survives forced preemption + requeue: the
    non-preempted sequence is token-identical to the unpressured quantized
    run, pre-preemption tokens are preserved verbatim (prompt-extension),
    and overall per-token agreement stays above the stated tolerance
    (requantized KV after the resume re-prefill may drift)."""
    model, params = gemma_tiny
    reqs = [_req(0, 12, 44), _req(1, 12, 44)]
    pre = Engine(model, params,
                 _policy(max_batch=2, num_pages=7, kv_bits=(8,)))
    outs_pre = pre.run(reqs)
    assert pre.stats["preemptions"] >= 1
    assert pre.kv.allocator.num_allocated == 0
    no = Engine(model, params, _policy(max_batch=2, kv_bits=(8,)))
    outs_no = no.run(reqs)
    assert no.stats["preemptions"] == 0
    match = total = 0
    for r in reqs:
        S = len(r.prompt)
        a, b = outs_no[r.rid][S:], outs_pre[r.rid][S:]
        assert a.shape == b.shape == (44,)
        match += int(np.sum(a == b))
        total += len(a)
    assert match / total >= PREEMPT_MATCH_TOL, (match, total)


def test_engine_quantized_smoke_and_stats(gemma_tiny):
    """Fast tier-1 cover: a short int8 + HAQ-mixed engine run completes
    with clean bookkeeping and bounded drift on one stream."""
    model, params = gemma_tiny
    reqs = [_req(0, 8, 6), _req(1, 12, 5)]
    for kvb in ((8,), (4, 8)):
        eng = Engine(model, params, _policy(kv_bits=kvb))
        outs = eng.run(reqs)
        assert eng.kv_bits == normalize_kv_bits(model.cfg, kvb)
        assert eng.kv.allocator.num_allocated == 0
        for r in reqs:
            assert outs[r.rid].shape == (len(r.prompt) + r.max_new,)
        rep = kvquant.greedy_drift(model, params, outs[reqs[0].rid],
                                   len(reqs[0].prompt), kv_bits=kvb)
        assert rep["max_abs"] <= DRIFT_TOL[min(kvb)], (kvb, rep["max_abs"])


# ----------------------------------------------------------- window trim --
def test_scheduler_trim_window_releases_dead_blocks():
    s = Scheduler(PageAllocator(12, 16), 2, 160)
    s.submit(Request(rid=0, prompt=np.arange(8, dtype=np.int32),
                     max_new=100))
    (seq,) = s.admit()
    seq.pos = 8
    for _ in range(5):
        seq.pages.extend(s.allocator.alloc(1))
    assert len(seq.pages) == 6
    before = s.allocator.num_allocated
    seq.pos = 90                      # window 32: kpos <= 58 dead
    freed = s.trim_window(seq, 32)
    # lo = (90 - 32 + 1) // 16 = 3 blocks wholly behind the window
    assert freed == 3
    assert s.allocator.num_allocated == before - 3
    assert seq.pages[:3] == [0, 0, 0] and all(p for p in seq.pages[3:])
    assert s.trim_window(seq, 32) == 0            # idempotent
    s.release(seq)                                # zeros skipped on free
    assert s.allocator.num_allocated == 0


def test_engine_window_trim_occupancy_drops_outputs_exact():
    """All-local model: the engine releases pages behind the window while
    decoding — peak pool occupancy stays at the window footprint instead of
    the full sequence — and every served token stays within GAP_TOL of the
    dense forward's best (the walk never read those blocks)."""
    cfg = tiny_config("gemma2-2b").replace(attn_pattern=("local",))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = Engine(model, params, _policy(max_model_len=96, num_pages=100))
    r = _req(0, 8, 80)
    engine.submit(r)
    peak = 0
    while engine.scheduler.has_work():
        engine.step()
        peak = max(peak, engine.kv.allocator.num_allocated)
    # window 32 spans at most ceil((32 + 16)/16) + 1 = 4 live pages; the
    # untrimmed sequence would hold ceil(88/16) = 6
    assert peak <= 4
    assert engine.stats["trimmed_pages"] >= 2
    assert engine.kv.allocator.num_allocated == 0
    assert_served(model, params, [r], engine._outputs)


# ------------------------------------------------------------ jaxpr scan --
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


@pytest.mark.parametrize("kv_bits", [(8,), (4, 8)])
def test_quant_decode_never_builds_dense_fp_kv(gemma_tiny, kv_bits):
    """Acceptance: the quantized decode step materializes neither the
    chronological dense KV view nor a full-pool fp dequant — the only fp
    KV ever built is the per-block (B, K, page, hd) tile inside the walk."""
    model, params = gemma_tiny
    pol = _policy()
    B, maxp, page = pol.max_batch, pol.pages_per_seq, pol.page_size
    cfg = model.cfg
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    P, G = 9, cfg.num_layers // 2
    pool = model.init_pool(P, page, kv_bits=kv_bits)
    pt = jnp.zeros((B, maxp), jnp.int32)
    tok = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: model.decode_step_paged(*a))(params, pool, pt, tok, pos)
    banned = {(B, maxp * page, K, hd), (B, maxp, K, page, hd),
              (P, K, page, hd), (G, P, K, page, hd)}
    dense = [a for a in _iter_avals(jaxpr.jaxpr)
             if getattr(a, "shape", None) in banned
             and jnp.issubdtype(a.dtype, jnp.inexact)]
    assert not dense, dense
    # positive control: dequantizing the whole pool trips the same scan
    leaf = pool["sub1"]["k"] if len(kv_bits) > 1 else pool["sub0"]["k"]
    jx = jax.make_jaxpr(lambda q, s: kvquant.dequantize_kv(q, s, 8))(
        leaf["q"][0], leaf["scale"][0])
    hits = [a for a in _iter_avals(jx.jaxpr)
            if getattr(a, "shape", None) in banned
            and jnp.issubdtype(a.dtype, jnp.inexact)]
    assert hits, "aval scan lost its teeth"
