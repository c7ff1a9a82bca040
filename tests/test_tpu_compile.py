"""Compile the serving path for a described TPU v5e — no chip attached.

The TPU compiler is installed even where no TPU is: it compiles for a chip
that is described by ``jax.experimental.topologies`` and refuses what the
chip would refuse (misaligned Pallas blocks, too much VMEM, programs that
do not fit HBM), which interpret-mode kernel tests never check. Nothing runs
here, so these tests say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library, and the test
workers all import this file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.hardware_model import V5E_EDGE
from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.models.api import build_model
from repro.serving.autotune.space import PAGE_SIZES
from repro.serving.engine import derive_policy

CFG = get_config("gemma2-2b")
K, H, HD = CFG.num_kv_heads, CFG.num_heads, CFG.resolved_head_dim
NUM_PAGES, N_BLOCKS, BATCH, CHUNK = 512, 64, 8, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("page", PAGE_SIZES)
@pytest.mark.parametrize("bits", [16, 8, 4])
@pytest.mark.parametrize("Sq", [1, CHUNK], ids=["decode", "prefill"])
def test_paged_kernels_compile(one_chip, page, bits, Sq):
    """All four paged-attention kernels (decode / chunked prefill over
    bf16 and quantized pools) at gemma2-2b widths, local-window and
    softcapped as its layers are, at every page size the autotuner may
    pick."""
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    B = BATCH if Sq == 1 else 1
    q = sd((B, H, HD) if Sq == 1 else (B, Sq, H, HD), jnp.bfloat16)
    pt, pos = sd((B, N_BLOCKS), jnp.int32), sd((B,), jnp.int32)
    kw = dict(window=CFG.window_size, cap=CFG.attn_softcap)
    if bits == 16:
        kv = sd((NUM_PAGES, K, page, HD), jnp.bfloat16)
        fn = pa.paged_attention_fwd if Sq == 1 else pa.paged_prefill_fwd
        c = _compile(lambda *a: fn(*a, **kw), q, kv, kv, pt, pos)
    else:
        kv = sd((NUM_PAGES, K, page, HD if bits == 8 else HD // 2),
                jnp.int8)
        sc = sd((NUM_PAGES, K, page), jnp.float32)
        fn = pa.paged_attention_quant_fwd if Sq == 1 \
            else pa.paged_prefill_quant_fwd
        c = _compile(lambda *a: fn(*a, **kw), q, kv, sc, kv, sc, pt, pos)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("name", ["paged_attention_fwd",
                                  "paged_attention_quant_fwd",
                                  "paged_prefill_fwd",
                                  "paged_prefill_quant_fwd"])
def test_paged_kernel_hlo_names(one_chip, name):
    """Each kernel's custom call keeps its own HLO instruction name inside
    a jitted step of another name: the device trace finds the kernel by
    it (``pallas_call(name=...)`` sets it; without it the instruction
    would take the enclosing function's name)."""
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    page, Sq = 16, (1 if "attention" in name else CHUNK)
    B = BATCH if Sq == 1 else 1
    q = sd((B, H, HD) if Sq == 1 else (B, Sq, H, HD), jnp.bfloat16)
    pt, pos = sd((B, N_BLOCKS), jnp.int32), sd((B,), jnp.int32)
    fn = getattr(pa, name)
    if "quant" in name:
        kv = sd((NUM_PAGES, K, page, HD), jnp.int8)
        sc = sd((NUM_PAGES, K, page), jnp.float32)
        args = (q, kv, sc, kv, sc, pt, pos)
    else:
        kv = sd((NUM_PAGES, K, page, HD), jnp.bfloat16)
        args = (q, kv, kv, pt, pos)

    def serving_step(*a):
        return fn(*a) * 2

    text = _compile(serving_step, *args).as_text()
    calls = [ln.strip() for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    assert calls[0].startswith(f"%{name}.") or \
        calls[0].startswith(f"%{name} "), calls[0][:120]


@pytest.fixture(scope="module")
def full_width(one_chip):
    """gemma2-2b's abstract parameters and page pool on one described
    chip, sized as the admission policy sizes them for a v5e at 8
    in-flight sequences."""
    model = build_model(CFG)
    pol = derive_policy(CFG, V5E_EDGE, max_model_len=1088,
                        param_bytes=model.param_bytes(), max_batch_cap=BATCH)
    pol = dataclasses.replace(pol, prefill_chunk=CHUNK)
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip)
    params = jax.tree.map(place, model.abstract_params())
    pool = jax.tree.map(place, model.pool_specs(
        pol.max_batch * pol.pages_per_seq + 1, pol.page_size))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    return model, pol, params, pool, i32


@pytest.mark.parametrize("step", ["decode", "chunk_prefill"])
def test_full_width_serving_step_compiles_and_fits(full_width, monkeypatch,
                                                   step):
    """The engine's jitted decode and chunk-prefill steps at full width
    compile for one v5e with the Pallas kernel in them (the backend here is
    the CPU, so the kernel dispatch is steered to the TPU lowering), and
    the program's arguments, outputs and temporaries fit its HBM."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    model, pol, params, pool, i32 = full_width
    B, maxp = pol.max_batch, pol.pages_per_seq
    if step == "decode":
        args = (params, pool, i32(B, maxp), i32(B, 1), i32(B))
        fn = model.decode_step_paged
    else:
        args = (params, pool, i32(1, maxp), i32(1, pol.prefill_chunk),
                i32(1))
        fn = model.prefill_chunk_paged
    c = jax.jit(lambda *a: fn(*a), donate_argnums=(1,)).lower(
        *args).compile()
    assert "tpu_custom_call" in c.as_text()
    m = c.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert used <= V5E_EDGE.hbm_bytes, used
