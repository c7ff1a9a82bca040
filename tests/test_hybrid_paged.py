"""Serving a model with Mamba-2 layers beside attention (granite-4.0-h) on
the paged path: what the engine refuses, how admission prices recurrent
state, and that the new configuration fields leave the dense models'
paged programs exactly as they were. Served logits against the float32
reference are checked in bench/tests/test_bench_hybrid.py."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, tiny_config
from repro.core.hardware_model import HARDWARES
from repro.models.api import build_model
from repro.serving.engine import AdmissionPolicy, Engine, Request, \
    derive_policy
from repro.serving.engine.admission import kv_bytes_per_token, \
    state_bytes_per_seq


def _policy(**kw):
    base = dict(hw_name="test", max_model_len=64, page_size=16,
                num_pages=200, max_batch=2, prefill_chunk=16, quant_bits=16,
                decode_slo_s=0.03, est_decode_s=0.0, est_prefill_s=0.0)
    base.update(kw)
    return AdmissionPolicy(**base)


@pytest.fixture(scope="module")
def hybrid_tiny():
    cfg = tiny_config("granite-4.0-h-small")
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _stage4():
    base = get_config("granite-4.0-h-small")
    return base.replace(num_layers=10,
                        moe=dataclasses.replace(base.moe, num_held=9))


def test_engine_serves_hybrid_chunked(hybrid_tiny):
    """Prompts longer than a chunk, more requests than batch slots: every
    request is served its tokens, and the state pool holds a row per
    batch slot and a scratch row."""
    model, params = hybrid_tiny
    engine = Engine(model, params, _policy())
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(2, 512, n).astype(np.int32),
                    max_new=5) for i, n in enumerate([7, 23, 40])]
    outs = engine.run(reqs)
    for r in reqs:
        assert len(outs[r.rid]) == len(r.prompt) + 5
    state = engine.kv.pool["sub0"]["state"]
    assert state.shape[1] == 3 and state.dtype == jnp.float32
    assert "k" in engine.kv.pool["sub1"]


@pytest.mark.parametrize("kw", [dict(mesh="model=1"),
                                dict(policy=dict(kv_bits=(8,)))],
                         ids=["mesh", "kv-bits"])
def test_engine_refuses_hybrid_off_the_chunked_path(hybrid_tiny, kw):
    """The state rows are served on one device beside a bf16 K/V pool:
    a mesh (even of one device) or a quantized pool is refused."""
    from repro.launch.mesh import make_serving_mesh
    model, params = hybrid_tiny
    kw = dict(kw)
    pol = _policy(**kw.pop("policy", {}))
    if "mesh" in kw:
        kw["mesh"] = make_serving_mesh(model=1, data=1)
    with pytest.raises(NotImplementedError):
        Engine(model, params, pol, **kw)


def test_admission_prices_recurrent_state():
    """K/V bytes per token count the attention layers only; every batch
    slot's state is reserved before the pages; the decode-SLO search
    prices the state each tick reads and writes."""
    cfg = _stage4()
    assert kv_bytes_per_token(cfg) == 2 * 8 * 128 * 2          # one layer
    assert state_bytes_per_seq(cfg) == 9 * (
        8192 * 128 * 4 + 3 * (8192 + 256) * 2)
    model = build_model(cfg)
    hw = HARDWARES["v5e-1chip"]
    kw = dict(max_model_len=4096, param_bytes=model.param_bytes(),
              max_batch_cap=128, hbm_util=0.8, page_size=16)
    pol = derive_policy(cfg, hw, **kw)
    assert pol.max_batch == 128
    free = hw.hbm_bytes * 0.8 - model.param_bytes() \
        - 129 * state_bytes_per_seq(cfg)
    assert pol.num_pages == int(free // (16 * 4096)) + 1
    # the state alone makes a decode tick at 128 rows dearer than at 64
    from repro.serving.engine.admission import step_latency
    assert step_latency(cfg, 128, 1, 4096, hw) > \
        1.5 * step_latency(cfg, 64, 1, 4096, hw)


def test_param_count_of_the_cut():
    """The analytic count (norm scales aside) matches the parameter tree
    of the cut: 10 layers, 9 of 72 experts held."""
    cfg = _stage4()
    model = build_model(cfg)
    norms = 10 * 2 * cfg.d_model + cfg.d_model + 9 * cfg.d_inner
    assert cfg.param_count() == model.param_count() - norms
    assert 2.40e9 < cfg.param_count() < 2.43e9


# Digests of the lowered StableHLO of the dense benchmark models' paged
# decode and chunk programs (tiny widths, the block walk; jax 0.9.0). The
# fields that model other families (mixers, NoPE, multipliers, held and
# shared experts) keep their neutral defaults in these models and leave
# these programs exactly as they are; a change meant to alter the dense
# programs records new digests.
DENSE_PROGRAMS = {"granite-3-8b": ("08661a9c69371754", "4e566d714d9e4e36"),
                  "nemotron-4-15b": ("5d7a028b3982047b", "874479b29b91a68d")}


@pytest.mark.parametrize("arch", sorted(DENSE_PROGRAMS))
def test_dense_paged_programs_unchanged(arch):
    model = build_model(tiny_config(arch))
    P, pool = model.abstract_params(), model.pool_specs(9, 16)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)

    def digest(fn, *args):
        text = jax.jit(fn).lower(P, pool, *args).as_text()
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    dec = digest(lambda p, pool, pt, t, pos: model.decode_step_paged(
        p, pool, pt, t, pos, kernel="ref"), i32(2, 4), i32(2, 1), i32(2))
    chunk = digest(lambda p, pool, pt, t, pos: model.prefill_chunk_paged(
        p, pool, pt, t, pos, kernel="ref"), i32(1, 4), i32(1, 32), i32(1))
    assert (dec, chunk) == DENSE_PROGRAMS[arch]
