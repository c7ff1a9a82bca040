"""Where the paged decode and chunk calls write each new token's K/V: every
live pool slot against a NumPy oracle, for bf16 and quantized pools, and
every page the call does not write left byte for byte as it was. Page 0
is the scratch page, which idle rows and a final chunk's overflow write
and nothing reads unmasked; it is not compared."""
import numpy as np

import jax
import jax.numpy as jnp
import pytest

from repro.configs import tiny_config
from repro.kernels import ref as kref
from repro.models import attention as attn
from repro.models.api import build_model

CFG = tiny_config("gemma2-2b")
PAGE, NUM_PAGES, N_BLOCKS = 8, 24, 6
K, HD = CFG.num_kv_heads, CFG.resolved_head_dim

# Decode rows: mid-page, at a page start, idle (the scratch page), and a
# local layer's row whose leading blocks were trimmed to the scratch page.
DECODE_PT = [[3, 7, 11, 0, 0, 0], [5, 9, 0, 0, 0, 0], [0] * 6,
             [0, 0, 14, 2, 19, 0]]
DECODE_POS = [13, 16, 0, 27]
# Chunk rows of 12 tokens: one from mid-page (blocks 1-3), one from a
# trimmed prefix whose last tokens run past the table's width.
CHUNK_PT = [[3, 7, 11, 4, 0, 0], [0, 0, 0, 0, 0, 13]]
CHUNK_POS = [13, 40]
SQ = 12


def _pool(rng, bits):
    shape = (NUM_PAGES, K, PAGE)
    if bits == 16:
        return jnp.asarray(rng.standard_normal(shape + (HD,)), jnp.bfloat16)
    hs = HD if bits == 8 else HD // 2
    return {"q": jnp.asarray(rng.integers(-100, 100, shape + (hs,)),
                             jnp.int8),
            "scale": jnp.asarray(rng.random(shape), jnp.float32)}


def _oracle(pool, new, pt, pos, bits):
    """The pool with row b's token t at page pt[b, (pos+t) // PAGE], slot
    (pos+t) % PAGE; tokens bound for the scratch page are left out."""
    if bits == 16:
        leaves, vals = {"": np.array(pool)}, {"": np.asarray(new)}
    else:
        qv, sc = jax.jit(kref.quantize_kv, static_argnums=1)(new, bits)
        leaves = {"q": np.array(pool["q"]), "scale": np.array(pool["scale"])}
        vals = {"q": np.asarray(qv), "scale": np.asarray(sc)}
    for b in range(len(pos)):
        for t in range(new.shape[1]):
            a = pos[b] + t
            pid = pt[b][a // PAGE] if a // PAGE < N_BLOCKS else 0
            for key in leaves:
                if pid:
                    leaves[key][pid, :, a % PAGE] = vals[key][b, t]
    return leaves


def _leaves(pool, bits):
    if bits == 16:
        return {"": np.asarray(pool)}
    return {k: np.asarray(v) for k, v in pool.items()}


@pytest.mark.parametrize("bits", [16, 8, 4])
@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_written_slots_match_oracle(step, bits):
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a[0], params["blocks"]["sub0"])["attn"]
    rng = np.random.default_rng(bits)
    pool_k, pool_v = _pool(rng, bits), _pool(rng, bits)
    if step == "decode":
        pt, pos, sq = DECODE_PT, DECODE_POS, 1
        call = attn.attention_decode_paged
    else:
        pt, pos, sq = CHUNK_PT, CHUNK_POS, SQ
        call = attn.attention_prefill_paged
    x = jnp.asarray(rng.standard_normal((len(pos), sq, CFG.d_model)),
                    CFG.dtype)
    abs_pos = np.asarray(pos)[:, None] + np.arange(sq)
    _, k_new, v_new = attn.project(p, x, CFG, jnp.asarray(abs_pos))
    _, got_k, got_v = jax.jit(
        lambda pk, pv: call(p, x, pk, pv, jnp.asarray(pt, jnp.int32),
                            jnp.asarray(pos, jnp.int32), "local", CFG,
                            kernel="ref"))(pool_k, pool_v)
    written = {pid for b, row in enumerate(pt) for pid in row[
        pos[b] // PAGE:(pos[b] + sq - 1) // PAGE + 1]}
    for pool, new, got in ((pool_k, k_new, got_k), (pool_v, v_new, got_v)):
        want = _oracle(pool, new, pt, pos, bits)
        before = _leaves(pool, bits)
        for key, arr in _leaves(got, bits).items():
            assert arr.dtype == want[key].dtype
            np.testing.assert_array_equal(arr[1:], want[key][1:])
            for pid in set(range(1, NUM_PAGES)) - written:
                assert arr[pid].tobytes() == before[key][pid].tobytes()
    assert written - {0}
