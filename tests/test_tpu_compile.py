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
import re

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


_INST = re.compile(r"\s*(ROOT )?%(\S+) = (.*?) ([a-z][a-z-]*)\((.*)$")
_ARRAY = re.compile(r"\[([\d,]*)\]\{([\d,]*)")
_POOL_ITSELF = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
                "scatter"}
_STAGING = {"copy-start", "copy-done", "slice-start", "custom-call"}


def _pool_copies(text, leaves):
    """The instructions of a compiled program whose value has the shape of
    one of the pool ``leaves`` — stacked over the layer groups, one
    group's, or flattened over them — other than the pool itself
    (parameters, tuples, bitcasts, the layer loop) and the scatters that
    write it in place. Each is a copy or a slice of a whole pool leaf.

    A pool small enough for on-chip memory (space ``S(1)``) may be staged
    there by the compiler, moved in and out in the row-major layout the
    kernels read; those moves are not counted. A move into any other
    layout (a relayout) is."""
    dims = set()
    for leaf in leaves:
        d = leaf.shape
        dims |= {d, d[1:], (d[0] * d[1],) + d[2:]}
    dims = {",".join(map(str, d)) for d in dims}
    insts, roots, comp = {}, {}, None
    for line in text.splitlines():
        if line.startswith(("%", "ENTRY")):
            comp = line.split()[line.startswith("ENTRY")].lstrip("%")
            continue
        m = _INST.match(line)
        if m:
            root, name, shape, op, rest = m.groups()
            insts[name] = (shape, op, rest)
            if root:
                roots[comp] = op

    def own_layout(shape):
        return all(lay == ",".join(map(str, reversed(range(d.count(",") + 1))))
                   for d, lay in _ARRAY.findall(shape) if d in dims)

    found = []
    for name, (shape, op, rest) in insts.items():
        if not any(d in dims for d, _ in _ARRAY.findall(shape)) \
                or op in _POOL_ITSELF:
            continue
        called = re.search(r"calls=%([\w.\-]+)", rest)
        if op == "fusion" and called and roots.get(called.group(1)) == \
                "scatter":
            continue
        operands = [insts.get(o, ("",))[0] for o in
                    re.findall(r"%([\w.\-]+)", rest.split("), ")[0])]
        staging = op in _STAGING and (op != "custom-call"
                                      or "ConcatBitcast" in rest)
        if staging and "S(1)" in shape + "".join(operands) \
                and all(map(own_layout, [shape] + operands)):
            continue
        found.append(f"{op} {name} {shape[:48]}")
    return found


def _assert_in_place(compiled, pool):
    """Neither a copy nor a slice of a K/V pool leaf in the program, less
    than one K/V leaf of temporaries, and the whole pool aliased from the
    donated argument to the output."""
    kv = [pool[sub][x] for sub in pool if "k" in pool[sub] for x in "kv"]
    copies = _pool_copies(compiled.as_text(), kv)
    assert not copies, copies
    m = compiled.memory_analysis()
    leaf_bytes = kv[0].size * kv[0].dtype.itemsize // kv[0].shape[0]
    assert m.temp_size_in_bytes < leaf_bytes, (m.temp_size_in_bytes,
                                               leaf_bytes)
    pool_bytes = sum(leaf.size * leaf.dtype.itemsize
                     for leaf in jax.tree.leaves(pool))
    assert m.alias_size_in_bytes >= pool_bytes, (m.alias_size_in_bytes,
                                                 pool_bytes)


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


@pytest.mark.parametrize("B,H,num_pages", [(42, 48, 2965), (18, 32, 2655)],
                         ids=["nemotron-4-15b", "granite-3-8b"])
def test_decode_kernel_compiles_at_cell_shapes(one_chip, B, H, num_pages):
    """The bf16 decode kernel at the benchmark cells' decode shapes: 8 kv
    heads of 128, pages of 16, 256 page-table slots per sequence."""
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    kv = sd((num_pages, 8, 16, 128), jnp.bfloat16)
    c = _compile(pa.paged_attention_fwd, sd((B, H, 128), jnp.bfloat16), kv,
                 kv, sd((B, 256), jnp.int32), sd((B,), jnp.int32))
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


def test_ssm_decode_kernel_compiles_with_its_name(one_chip):
    """The Mamba-2 decode state update at granite-4.0-h-small widths (128
    heads of 64, d_state 128) over 128 live rows and a scratch slot, in a
    jitted step of another name: it compiles, keeps its pinned HLO name
    for the device trace, and updates the state pool in place."""
    from repro.kernels import ssm_decode as sd
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    B, H, P, N = 128, 128, 64, 128
    args = (f32(B + 1, H, P, N),
            jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip),
            f32(B, H, P), f32(B, H), f32(H), f32(B, 1, N), f32(B, 1, N),
            f32(H))

    def serving_step(state, *a):
        y, state = sd.ssm_decode_fwd(state, *a)
        return y * 2, state

    c = jax.jit(serving_step, donate_argnums=(0,)).lower(*args).compile()
    calls = [ln.strip() for ln in c.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    assert calls[0].startswith("%ssm_decode_fwd"), calls[0][:120]
    assert c.memory_analysis().alias_size_in_bytes >= 4 * (B + 1) * H * P * N


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
    the CPU, so the kernel dispatch is steered to the TPU lowering), the
    program's arguments, outputs and temporaries fit its HBM, and the page
    pool is written in place."""
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
    _assert_in_place(c, pool)


@pytest.mark.parametrize("step", ["decode", "chunk_prefill"])
def test_full_width_hybrid_step_compiles_and_fits(one_chip, monkeypatch,
                                                  step):
    """granite-4.0-h-small's benchmark cut (one period of 10 layers, 9 of
    72 experts held) at 128 in-flight sequences: the engine's decode step
    (the paged-attention and SSM-decode kernels in it) and its 512-token
    chunk step compile for one v5e and fit its HBM beside the state rows
    of every batch slot and the page pool, which they write in place."""
    from repro.serving.engine.admission import state_bytes_per_seq
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    base = get_config("granite-4.0-h-small")
    cfg = base.replace(num_layers=10,
                       moe=dataclasses.replace(base.moe, num_held=9))
    model = build_model(cfg)
    B, maxp = 128, 4096 // 16
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip)
    params = jax.tree.map(place, model.abstract_params())
    pool = jax.tree.map(place, model.pool_specs(B * maxp + 1, 16,
                                                state_slots=B + 1))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    if step == "decode":
        fn = lambda p, pool, pt, t, pos, rows: model.decode_step_paged(
            p, pool, pt, t, pos, rows=rows)
        args = (params, pool, i32(B, maxp), i32(B, 1), i32(B), i32(B))
        kernels = {"paged_attention_fwd", "ssm_decode_fwd"}
    else:
        fn = lambda p, pool, pt, t, pos, rows, n: model.prefill_chunk_paged(
            p, pool, pt, t, pos, rows=rows, lengths=n)
        args = (params, pool, i32(1, maxp), i32(1, 512), i32(1), i32(1),
                i32(1))
        kernels = {"paged_prefill_fwd"}
    c = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text = c.as_text()
    found = {ln.strip()[1:].split(" ")[0].rsplit(".", 1)[0]
             for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln}
    assert found == kernels, found
    m = c.memory_analysis()
    assert m.alias_size_in_bytes >= (B + 1) * state_bytes_per_seq(cfg)
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert used <= V5E_EDGE.hbm_bytes, used
    _assert_in_place(c, pool)


@pytest.mark.parametrize("step", ["decode", "chunk_prefill"])
def test_dense_cell_step_writes_pool_in_place(one_chip, monkeypatch, step):
    """nemotron-4-15b.stage4's widths (42 sequences, 2965 pages of 16, 256
    page-table slots, 512-token chunks), two layers so that the scan over
    layer groups carries the pool: the decode and chunk steps neither copy
    nor slice a pool leaf and hold less than one leaf of temporaries."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    model = build_model(get_config("nemotron-4-15b").replace(num_layers=2))
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip)
    params = jax.tree.map(place, model.abstract_params())
    pool = jax.tree.map(place, model.pool_specs(2965, 16))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    if step == "decode":
        fn = model.decode_step_paged
        args = (params, pool, i32(42, 256), i32(42, 1), i32(42))
    else:
        fn = model.prefill_chunk_paged
        args = (params, pool, i32(1, 256), i32(1, 512), i32(1))
    c = jax.jit(lambda *a: fn(*a), donate_argnums=(1,)).lower(
        *args).compile()
    assert "tpu_custom_call" in c.as_text()
    _assert_in_place(c, pool)
