"""Jit'd public wrappers around the Pallas kernels.

Handle layout (rank-3 activations, padding to block multiples), backend
dispatch (compiled on TPU; interpret=True on the CPU so tests execute the
kernel body), and the weight-quantization caching used by the serving path.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import paged_attention as pa
from repro.kernels import quant_matmul as qmm
from repro.kernels import ref

F32 = jnp.float32


def _interpret() -> bool:
    """Pallas kernels compile for the TPU and run in interpret mode on the
    CPU (tests only); any other backend has no lowering for them."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise NotImplementedError(
            f"Pallas TPU kernels cannot run on backend {backend!r}")
    return backend == "cpu"


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def quant_matmul(x: jax.Array, w: jax.Array, *, w_bits: int = 8,
                 a_bits: int = 16, bm: int = 128, bn: int = 128,
                 bk: int = 256) -> jax.Array:
    """Drop-in einsum('...d,df->...f') replacement with on-the-fly weight
    quantization — the HAQ `dot` hook's kernel path. For a real deployment
    the weights are quantized once via `prepare_quantized` below."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[-1]
    x2 = x.reshape(-1, K)
    x2, pm = _pad_to(x2, bm if x2.shape[0] >= bm else 8, 0)
    bm_eff = min(bm, x2.shape[0])
    interp = _interpret()
    if w_bits <= 4:
        packed, scale = ref.quantize_w4_packed(w)
        out = qmm.quant_matmul_w4a16(x2, packed, scale, bm=bm_eff, bn=bn,
                                     bk=bk, interpret=interp)
    elif a_bits <= 8:
        wq, ws = ref.quantize_w8(w)
        xq, xs = ref.quantize_a8(x2)
        out = qmm.quant_matmul_w8a8(xq, xs, wq, ws, bm=bm_eff, bn=bn,
                                    bk=bk, out_dtype=x.dtype,
                                    interpret=interp)
    else:
        wq, ws = ref.quantize_w8(w)
        out = qmm.quant_matmul_w8a16(x2, wq, ws, bm=bm_eff, bn=bn, bk=bk,
                                     interpret=interp)
    if pm:
        out = out[:-pm]
    return out.reshape(*lead, N)


def prepare_quantized(w: jax.Array, w_bits: int) -> Dict[str, jax.Array]:
    """One-time weight quantization for serving (stored int side tables)."""
    if w_bits <= 4:
        packed, scale = ref.quantize_w4_packed(w)
        return {"q": packed, "scale": scale, "bits": jnp.asarray(4)}
    q, scale = ref.quantize_w8(w)
    return {"q": q, "scale": scale, "bits": jnp.asarray(8)}


def quant_matmul_prepared(x: jax.Array, qw: Dict[str, jax.Array],
                          *, a_bits: int = 16) -> jax.Array:
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    x2, pm = _pad_to(x2, 8, 0)
    interp = _interpret()
    bm = min(128, x2.shape[0])
    if int(qw["bits"]) <= 4:
        out = qmm.quant_matmul_w4a16(x2, qw["q"], qw["scale"], bm=bm,
                                     interpret=interp)
    elif a_bits <= 8:
        xq, xs = ref.quantize_a8(x2)
        out = qmm.quant_matmul_w8a8(xq, xs, qw["q"], qw["scale"],
                                    bm=bm, out_dtype=x.dtype,
                                    interpret=interp)
    else:
        out = qmm.quant_matmul_w8a16(x2, qw["q"], qw["scale"], bm=bm,
                                     interpret=interp)
    if pm:
        out = out[:-pm]
    return out.reshape(*lead, -1)


def flash_attention(q, k, v, *, causal=True, window=0, cap=0.0,
                    bq=256, bkv=256) -> jax.Array:
    """Pallas flash attention forward (serving path)."""
    return fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                  cap=cap, bq=bq, bkv=bkv,
                                  interpret=_interpret())


def _paged_mode(mode: str) -> str:
    """Resolve the paged-attention dispatch once for all four entry points:
    "auto" lowers to the Pallas page-walk kernel on TPU and the pure-JAX
    block walk on the CPU. The choice is backend-global and shape-free, so
    the same dispatch works inside shard_map-partitioned programs — the
    sharded engine (serving/engine/sharded.py) traces these walks per shard
    with a local kv-head slice of the pool."""
    if mode == "auto":
        return "ref" if _interpret() else "pallas"
    if mode not in ("ref", "pallas"):
        raise ValueError(f"unknown paged-attention mode {mode!r}")
    return mode


def paged_attention(q, pool_k, pool_v, page_table, positions, *,
                    window=0, cap=0.0, mode: str = "auto") -> jax.Array:
    """Paged-attention decode: q (B,H,hd) against the page pool.

    mode: "auto" -> Pallas kernel on TPU, pure-JAX block walk on the CPU;
    "pallas" forces the kernel (interpret mode on the CPU — slow, tests
    only); "ref" forces the block walk. Both walk pages and never
    materialize the dense chronological KV view."""
    if _paged_mode(mode) == "ref":
        return ref.paged_attention_ref(q, pool_k, pool_v, page_table,
                                       positions, window=window, cap=cap)
    return pa.paged_attention_fwd(q, pool_k, pool_v, page_table, positions,
                                  window=window, cap=cap,
                                  interpret=_interpret())


def paged_attention_quant(q, pool_k, k_scale, pool_v, v_scale, page_table,
                          positions, *, window=0, cap=0.0,
                          mode: str = "auto") -> jax.Array:
    """Paged-attention decode over a quantized KV page pool.

    pool_k/v are int8 (int4 packed along head_dim — bitwidth is inferred
    from the stored minor-dim size) with (P, K, page) fp32 scales. Same
    dispatch contract as paged_attention; every path dequantizes block-by-
    block inside the walk and never materializes a dense fp KV view."""
    if _paged_mode(mode) == "ref":
        return ref.paged_attention_quant_ref(
            q, pool_k, k_scale, pool_v, v_scale, page_table, positions,
            window=window, cap=cap)
    return pa.paged_attention_quant_fwd(
        q, pool_k, k_scale, pool_v, v_scale, page_table, positions,
        window=window, cap=cap, interpret=_interpret())


def paged_attention_prefill(q, pool_k, pool_v, page_table, positions, *,
                            window=0, cap=0.0, mode: str = "auto"):
    """Chunked-prefill attention: q (B, Sq, H, hd) — one prompt chunk per
    sequence whose K/V are already resident in the pool — against the page
    pool, causal at each query's absolute position (``positions`` holds the
    chunk-start offsets). Same dispatch contract as paged_attention; both
    paths walk pages and never materialize the dense prompt KV view."""
    if _paged_mode(mode) == "ref":
        return ref.paged_prefill_ref(q, pool_k, pool_v, page_table,
                                     positions, window=window, cap=cap)
    return pa.paged_prefill_fwd(q, pool_k, pool_v, page_table, positions,
                                window=window, cap=cap,
                                interpret=_interpret())


def paged_attention_prefill_quant(q, pool_k, k_scale, pool_v, v_scale,
                                  page_table, positions, *, window=0,
                                  cap=0.0, mode: str = "auto"):
    """Chunked-prefill attention over a quantized KV page pool (the chunk's
    K/V are already quantized on write); dequantization happens block-by-
    block inside the walk on every path."""
    if _paged_mode(mode) == "ref":
        return ref.paged_prefill_quant_ref(
            q, pool_k, k_scale, pool_v, v_scale, page_table, positions,
            window=window, cap=cap)
    return pa.paged_prefill_quant_fwd(
        q, pool_k, k_scale, pool_v, v_scale, page_table, positions,
        window=window, cap=cap, interpret=_interpret())
