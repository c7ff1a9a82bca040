"""Pallas kernels vs kernels/ref.py oracles: shape/dtype/block sweeps in
interpret mode (assignment requirement (c))."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels import quant_matmul as qmm


def _xw(M, K, N, dtype, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (M, K), jnp.float32).astype(dtype)
    w = (jax.random.normal(kw, (K, N), jnp.float32) * 0.1)
    return x, w


@pytest.mark.parametrize("M,K,N", [(32, 256, 128), (64, 512, 256),
                                   (128, 256, 512), (8, 512, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_w8a16_shapes_dtypes(M, K, N, dtype):
    x, w = _xw(M, K, N, dtype)
    wq, ws = ref.quantize_w8(w)
    got = qmm.quant_matmul_w8a16(x, wq, ws, bm=min(32, M), bn=128, bk=128,
                                 interpret=True)
    want = ref.quant_matmul_w8a16(x, wq, ws)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    assert got.dtype == x.dtype
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < tol * max(1.0, float(jnp.max(jnp.abs(want)))), err


@pytest.mark.parametrize("bm,bn,bk", [(16, 64, 64), (32, 128, 128),
                                      (64, 128, 256)])
def test_w8a16_block_sweep(bm, bn, bk):
    x, w = _xw(64, 512, 256, jnp.float32)
    wq, ws = ref.quantize_w8(w)
    got = qmm.quant_matmul_w8a16(x, wq, ws, bm=bm, bn=bn, bk=bk,
                                 interpret=True)
    want = ref.quant_matmul_w8a16(x, wq, ws)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


@pytest.mark.parametrize("M,K,N", [(32, 256, 128), (64, 512, 256)])
def test_w4a16(M, K, N):
    x, w = _xw(M, K, N, jnp.float32)
    packed, scale = ref.quantize_w4_packed(w)
    got = qmm.quant_matmul_w4a16(x, packed, scale, bm=min(32, M), bn=128,
                                 bk=128, interpret=True)
    want = ref.quant_matmul_w4a16(x, packed, scale)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    # int4 packing really halves the weight bytes
    assert packed.size == w.size // 2 and packed.dtype == jnp.int8


def test_w4_unpack_roundtrip():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 0.3
    packed, scale = ref.quantize_w4_packed(w)
    unpacked = ref.unpack_w4(packed)
    assert int(jnp.max(unpacked)) <= 7 and int(jnp.min(unpacked)) >= -7
    rel = float(jnp.linalg.norm(unpacked * scale[None, :] - w)
                / jnp.linalg.norm(w))
    assert rel < 0.15, rel  # int4 per-channel ~ 11% error on gaussian


@pytest.mark.parametrize("M,K,N", [(32, 256, 128), (64, 512, 256)])
def test_w8a8(M, K, N):
    x, w = _xw(M, K, N, jnp.float32)
    wq, ws = ref.quantize_w8(w)
    xq, xs = ref.quantize_a8(x)
    got = qmm.quant_matmul_w8a8(xq, xs, wq, ws, bm=min(32, M), bn=128,
                                bk=128, out_dtype=jnp.float32,
                                interpret=True)
    want = ref.quant_matmul_w8a8(xq, xs, wq, ws, out_dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


@pytest.mark.parametrize("causal,window,cap", [
    (True, 0, 0.0), (True, 128, 0.0), (False, 0, 0.0), (True, 0, 30.0)])
@pytest.mark.parametrize("H,K", [(4, 2), (2, 2), (4, 1)])
def test_flash_kernel(causal, window, cap, H, K):
    B, S, hd = 2, 256, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              cap=cap, bq=64, bkv=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   cap=cap)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


# ------------------------------------------------------- paged attention ---
def _paged_case(B, H, K, hd, page, n_blocks, *, num_pages=11, seed=0,
                dtype=jnp.float32):
    """Random pool + ragged page tables: each sequence at a different
    position, allocated pages shuffled, unused tails left on scratch page
    0 (whose contents are poisoned to catch any leak past the mask)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool_k = jax.random.normal(ks[0], (num_pages, K, page, hd),
                               jnp.float32).astype(dtype)
    pool_v = jax.random.normal(ks[1], (num_pages, K, page, hd),
                               jnp.float32).astype(dtype)
    # poison the scratch page: a masking bug shows up as a huge error
    pool_k = pool_k.at[0].set(37.0)
    pool_v = pool_v.at[0].set(-53.0)
    q = jax.random.normal(ks[2], (B, H, hd), jnp.float32).astype(dtype)
    positions = rng.integers(0, n_blocks * page, B).astype(jnp.int32)
    positions[0] = 0                          # scratch-tail-only edge case
    pt = np.zeros((B, n_blocks), np.int32)
    for b in range(B):
        need = positions[b] // page + 1
        pt[b, :need] = rng.choice(np.arange(1, num_pages), need,
                                  replace=False)
    return (q, pool_k, pool_v, jnp.asarray(pt),
            jnp.asarray(positions, jnp.int32))


@pytest.mark.parametrize("page,n_blocks", [(8, 6), (16, 4), (32, 2)])
@pytest.mark.parametrize("window,cap", [(0, 0.0), (24, 0.0), (0, 30.0)])
@pytest.mark.parametrize("H,K", [(4, 2), (2, 2), (4, 1)])
def test_paged_attention_kernel_parity(page, n_blocks, window, cap, H, K):
    """Pallas page-walk kernel (interpret) and pure-JAX block walk both
    match the dense gather+mask oracle across page sizes, local windows,
    GQA shapes, ragged positions, and scratch-page tails."""
    q, pk, pv, pt, pos = _paged_case(3, H, K, 32, page, n_blocks)
    want = ref.paged_attention_dense_ref(q, pk, pv, pt, pos,
                                         window=window, cap=cap)
    from repro.kernels import paged_attention as pa
    got_k = pa.paged_attention_fwd(q, pk, pv, pt, pos, window=window,
                                   cap=cap, interpret=True)
    got_r = ref.paged_attention_ref(q, pk, pv, pt, pos, window=window,
                                    cap=cap)
    assert float(jnp.max(jnp.abs(got_k - want))) < 1e-5
    assert float(jnp.max(jnp.abs(got_r - want))) < 1e-5


def test_paged_attention_bf16_and_dispatch():
    """ops.paged_attention: bf16 pools round-trip in q.dtype; mode="auto"
    resolves to the block walk off-TPU; unknown modes are rejected."""
    q, pk, pv, pt, pos = _paged_case(2, 4, 2, 32, 16, 3,
                                     dtype=jnp.bfloat16)
    want = ref.paged_attention_dense_ref(q, pk, pv, pt, pos)
    got = ops.paged_attention(q, pk, pv, pt, pos, mode="auto")
    assert got.dtype == jnp.bfloat16
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < 5e-2, err
    with pytest.raises(ValueError):
        ops.paged_attention(q, pk, pv, pt, pos, mode="dense")


def test_paged_attention_window_trim_matches_full_walk():
    """Window-trimmed walks (lo > 0) drop only blocks wholly outside the
    window: a local layer whose window spans everything equals the
    untrimmed causal walk."""
    q, pk, pv, pt, pos = _paged_case(3, 4, 2, 32, 16, 4, seed=3)
    full = ref.paged_attention_ref(q, pk, pv, pt, pos, window=0)
    wide = ref.paged_attention_ref(q, pk, pv, pt, pos,
                                   window=16 * 4)    # covers every block
    assert float(jnp.max(jnp.abs(full - wide))) < 1e-6


def test_quant_dot_hook_end_to_end():
    """The HAQ dot hook with use_kernel routes through the Pallas kernel and
    stays close to the bf16 baseline at W8A16."""
    from repro.core.quantization import make_quant_dot
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 256)) * 0.05
    dot_k = make_quant_dot({"site": (8, 16)}, use_kernel=True)
    dot_f = make_quant_dot({"site": (8, 16)}, use_kernel=False)
    got = dot_k(x, w, "site")
    want = dot_f(x, w, "site")
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 1e-3, rel
