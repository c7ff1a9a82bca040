"""SPMD serving engine (serving/engine/sharded.py): token-exactness vs the
1-device engine across pools/chunking/preemption, per-device pool layout,
mesh-aware admission sizing, and the no-dense-KV jaxpr contract.

Multi-device cases need forced host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``, the CI
``multi-device`` job) and skip elsewhere; the admission/roofline cases are
pure host math and run everywhere."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tiny_config
from repro.core.hardware_model import V5E_EDGE, allreduce_cost
from repro.launch.mesh import make_serving_mesh
from repro.models.api import build_model
from repro.serving.engine import (AdmissionPolicy, Engine, Request,
                                  derive_policy)
from repro.serving.engine.admission import step_latency

NDEV = jax.device_count()
needs2 = pytest.mark.skipif(
    NDEV < 2, reason="needs >=2 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
needs4 = pytest.mark.skipif(
    NDEV < 4, reason="needs >=4 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _policy(**kw):
    base = dict(hw_name="test", max_model_len=64, page_size=16,
                num_pages=10_000, max_batch=4, prefill_chunk=16,
                quant_bits=16, decode_slo_s=0.03, est_decode_s=0.0,
                est_prefill_s=0.0)
    base.update(kw)
    return AdmissionPolicy(**base)


def _reqs(cfg, n=6, seed=0, gen_hi=16):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        S = int(rng.integers(4, 44))        # spans the local window (32)
        gen = int(rng.integers(2, gen_hi))
        out.append(Request(rid=i, prompt=rng.integers(
            2, cfg.vocab_size, S).astype(np.int32), max_new=gen))
    return out


@pytest.fixture(scope="module")
def gemma_tiny():
    cfg = tiny_config("gemma2-2b")          # GQA (H=4, K=2), local+global
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _assert_identical(model, params, pol, reqs, mesh, **engine_kw):
    base = Engine(model, params, pol, **engine_kw)
    want = base.run(reqs)
    sharded = Engine(model, params, pol, mesh=mesh, **engine_kw)
    got = sharded.run(reqs)
    for r in reqs:
        assert np.array_equal(want[r.rid], got[r.rid]), r.rid
    return base, sharded


# ------------------------------------------------------- token exactness --
@needs2
@pytest.mark.parametrize("kv_bits", [None, (8,), (4, 8)],
                         ids=["fp", "int8", "haq-mixed"])
def test_sharded_matches_unsharded(gemma_tiny, kv_bits):
    """Greedy outputs on a model=2 mesh are bit-identical to the 1-device
    engine for the fp, int8, and HAQ-mixed (int4 local / int8 global)
    pools — chunked prefill included (prompts up to 43 vs chunk 16)."""
    model, params = gemma_tiny
    pol = _policy(kv_bits=kv_bits)
    _assert_identical(model, params, pol, _reqs(model.cfg),
                      make_serving_mesh(model=2))


@needs4
def test_sharded_data_axis(gemma_tiny):
    """The data axis is at-rest param FSDP: outputs unchanged on a
    model=2 x data=2 mesh."""
    model, params = gemma_tiny
    _assert_identical(model, params, _policy(), _reqs(model.cfg),
                      make_serving_mesh(model=2, data=2))


@needs2
def test_sharded_preemption_roundtrip_exact(gemma_tiny):
    """Forced preemption (pool smaller than two full lifetimes) replays
    identically on the sharded engine: same preemption count, same
    tokens, all pages returned on both."""
    model, params = gemma_tiny
    pol = _policy(max_batch=2, num_pages=7)
    reqs = [Request(rid=i, prompt=np.full(12, 7 + i, np.int32), max_new=44)
            for i in range(2)]
    base, sharded = _assert_identical(model, params, pol, reqs,
                                      make_serving_mesh(model=2))
    assert base.stats["preemptions"] >= 1
    assert sharded.stats["preemptions"] == base.stats["preemptions"]
    assert sharded.kv.allocator.num_allocated == 0


@needs2
def test_sharded_moe_smoke():
    """MoE decode under a mesh (expert weights gathered at use)."""
    cfg = tiny_config("granite-moe-3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    _assert_identical(model, params, _policy(max_batch=2),
                      _reqs(cfg, n=3, seed=1), make_serving_mesh(model=2))


# ------------------------------------------------------- device layout ----
@needs2
def test_pool_is_sharded_on_kv_heads(gemma_tiny):
    """Acceptance: every pool leaf (codes AND quant scale tiles) stores a
    1/N kv-head slice per device — per-device pool bytes really drop Nx."""
    model, params = gemma_tiny
    for kv_bits in (None, (8,)):
        pol = _policy(kv_bits=kv_bits)
        eng = Engine(model, params, pol, mesh=make_serving_mesh(model=2))
        K = model.cfg.num_kv_heads
        for leaf in jax.tree.leaves(eng.kv.pool):
            local = leaf.sharding.shard_shape(leaf.shape)
            assert local[2] == K // 2, (leaf.shape, local)
        # replicated decode inputs, sharded params at rest: param bytes per
        # device strictly below the full footprint
        full = sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(eng.params))
        local = sum(int(np.prod(x.sharding.shard_shape(x.shape)))
                    * x.dtype.itemsize for x in jax.tree.leaves(eng.params))
        assert local < full


@needs2
def test_sharded_decode_never_builds_dense_kv(gemma_tiny):
    """The sharded decode jaxpr never materializes a chronological dense KV
    view — neither at the full kv-head count nor at the local slice."""
    from test_engine import _iter_avals

    model, params = gemma_tiny
    pol = _policy()
    mesh = make_serving_mesh(model=2)
    eng = Engine(model, params, pol, mesh=mesh)
    B, maxp, page = pol.max_batch, pol.pages_per_seq, pol.page_size
    K, hd = model.cfg.num_kv_heads, model.cfg.resolved_head_dim
    pt = jnp.zeros((B, maxp), jnp.int32)
    tok = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: eng._decode(*a))(eng.params, eng.kv.pool, pt, tok, pos)
    banned = set()
    for k in (K, K // 2):
        banned |= {(B, maxp * page, k, hd), (B, maxp, k, page, hd),
                   (B, maxp, page, k, hd)}
    dense = [a for a in _iter_avals(jaxpr.jaxpr)
             if getattr(a, "shape", None) in banned]
    assert not dense, dense


def test_mesh_validation_errors(gemma_tiny):
    """K=2 does not divide model=4; the engine refuses instead of silently
    regrouping GQA (page slots must stay whole for bit-exactness)."""
    from repro.serving.engine.sharded import validate_mesh

    model, params = gemma_tiny

    class FakeMesh:
        def __init__(self, **axes):
            self.shape = axes

    with pytest.raises(ValueError, match="kv heads"):
        validate_mesh(model.cfg, FakeMesh(data=1, model=4))
    with pytest.raises(ValueError, match="data/model"):
        validate_mesh(model.cfg, FakeMesh(rows=2))
    validate_mesh(model.cfg, FakeMesh(data=4, model=2))   # fine


# ------------------------------------------- mesh-aware admission sizing --
def test_policy_pool_capacity_scales_with_model_axis():
    """Acceptance: pool capacity per device scales >= 1.9x from 1 -> 2
    model shards (per-device page bytes halve; weights also spread)."""
    cfg = tiny_config("gemma2-2b")
    base = derive_policy(cfg, V5E_EDGE, max_model_len=64)
    two = derive_policy(cfg, V5E_EDGE, max_model_len=64, mesh_model=2)
    assert two.num_pages >= 1.9 * base.num_pages
    assert two.mesh_model == 2 and two.mesh_data == 1
    # expected-footprint resident-sequence capacity rises with it
    assert two.max_batch >= base.max_batch
    # data axis alone replicates the pool: capacity moves only via the
    # (spread) weight share, never ~2x
    dp = derive_policy(cfg, V5E_EDGE, max_model_len=64, mesh_data=2)
    assert dp.num_pages < 1.5 * base.num_pages
    # defaults reproduce the single-device policy exactly
    one = derive_policy(cfg, V5E_EDGE, max_model_len=64,
                        mesh_model=1, mesh_data=1)
    assert one == base


def test_step_latency_mesh_model_prices_collectives():
    """The mesh-aware roofline is faithful to the gather-at-use design:
    only output-dim-sharded work splits N ways, so with free ICI the tick
    shrinks but never to t1/N; real ICI only ever adds (activation
    all-reduces + weight all-gathers), and the whole-on-every-device part
    keeps t2 above perfect scaling."""
    import dataclasses as dc

    cfg = tiny_config("gemma2-2b")
    t1 = step_latency(cfg, 8, 1, 64, V5E_EDGE)
    t2 = step_latency(cfg, 8, 1, 64, V5E_EDGE, mesh_model=2)
    free_ici = dc.replace(V5E_EDGE, ici_bw=1e18)
    t2_free = step_latency(cfg, 8, 1, 64, free_ici, mesh_model=2)
    assert t1 / 2.0 < t2_free < t1          # split helps, whole part stays
    assert t2 >= t2_free                    # collectives only ever add
    ar = float(allreduce_cost(8, cfg.d_model, 2).latency(V5E_EDGE))
    assert ar > 0.0
    assert t2 >= t2_free + 2 * cfg.num_layers * ar - 1e-12


def test_sharded_engine_rejects_weight_quant(gemma_tiny):
    """HAQ weight dicts have no logical specs yet: the mesh + quant_bits<16
    combination must refuse loudly (kv_bits is the sharded memory lever)."""
    model, params = gemma_tiny
    with pytest.raises(NotImplementedError, match="weight quant"):
        Engine(model, params, _policy(quant_bits=8),
               mesh=make_serving_mesh(model=1))
