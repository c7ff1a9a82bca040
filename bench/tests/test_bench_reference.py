"""The plain float32 reference against the engine's paged path (chunked
prefill, then paged decode, then the unembedding) at a tiny dense size on
the CPU, and the harness's comparison failing where the served path is
broken or computed in the control's lower precision."""
import numpy as np
import pytest

import jax.numpy as jnp

from bench import harness, weights as W
from bench.families import dense
from bench.reference import Reference
from bench.tests import tinyroot


def ref_logits(m, seed, tokens):
    """Full reference logits at every position of ``tokens``."""
    ref = Reference(dense, m, seed)
    h = ref.final_hidden([tokens])[0][:len(tokens)]
    sn, sh = ref.specs[("final_norm",)], ref.specs[("lm_head",)]
    fn = W.block_values(ref.keys[("final_norm",)][0], sn.shape, (0,),
                        sn.shape, sn.std).astype(jnp.float32)
    h = h * (1 / jnp.sqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-5)) \
        * (1 + fn)
    w = W.block_values(ref.keys[("lm_head",)][0], sh.shape, (0, 0),
                       sh.shape, sh.std).astype(jnp.bfloat16)
    return np.asarray(h @ w.astype(jnp.float32))[:, :m["vocab_size"]]


def engine_logits(engine, tokens, C=64):
    """Logits of the last two positions through the engine's programs:
    the prompt in C-token chunks (last row unembedded), then one paged
    decode step."""
    pol = engine.policy
    L = len(tokens) - 1
    pages = engine.kv.allocator.alloc(engine.kv.allocator.pages_for(L + 1))
    pt = np.zeros((pol.max_batch, pol.pages_per_seq), np.int32)
    pt[0, :len(pages)] = pages
    for start in range(0, L, C):
        part = tokens[start:min(start + C, L)]
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :len(part)] = part
        hidden, engine.kv.pool = engine._chunk_prefill(
            engine.params, engine.kv.pool, jnp.asarray(pt[:1]),
            jnp.asarray(chunk), jnp.asarray([start], jnp.int32))
    last = engine._unembed_row(engine.params, hidden,
                               jnp.asarray(L - 1 - start, jnp.int32))
    tok = np.zeros((pol.max_batch, 1), np.int32)
    tok[0, 0] = tokens[L]
    pos = np.full((pol.max_batch,), L, np.int32)
    step, engine.kv.pool = engine._decode(
        engine.params, engine.kv.pool, jnp.asarray(pt), jnp.asarray(tok),
        jnp.asarray(pos))
    return np.stack([np.asarray(last[0, 0]), np.asarray(step[0, 0])])


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make_root(tmp_path_factory.mktemp("tiny"))


def test_reference_matches_paged_engine_logits(root):
    from repro.core.hardware_model import HARDWARES
    cell = harness.load_cell("tiny.chat", root)
    devices = harness.check_devices(1, require_tpu=False)
    engine, _ = harness.build(cell, 11, devices, lambda s: None,
                              HARDWARES["v5e-1chip"])
    m = cell.config["model"]
    toks = np.random.default_rng(0).integers(2, m["vocab_size"], 200)
    got = engine_logits(engine, toks.astype(np.int32))
    want = ref_logits(m, 11, toks.astype(np.int32))[-2:]
    scale = np.max(np.abs(want))
    # bf16 weights and activations against float32: about 1% of the
    # logit scale at these widths
    assert np.max(np.abs(got - want)) < 0.03 * scale
    assert np.array_equal(np.argmax(got, -1), np.argmax(want, -1))


def test_sound_run_correct_and_control_fails(root):
    r = tinyroot.run(root, seconds=2.0, control=True)
    c = r["checks"]
    assert r["correct"], c
    assert c["max_logit_gap"]["value"] <= tinyroot.TINY_LIMIT
    assert c["control_max_logit_gap"]["value"] > tinyroot.TINY_LIMIT
    # the control's gaps in the served tokens' place: not correct
    assert not harness.passed(
        dict(c, max_logit_gap=c["control_max_logit_gap"]))


def test_altered_token_fails(root, monkeypatch):
    """A token altered where it is produced (the engine's sampler)."""
    import repro.serving.engine.engine as E
    orig = E.sample_token
    calls = [0]

    def wrong(row, temperature, key):
        calls[0] += 1
        tok = orig(row, temperature, key)
        return (tok + 1) % 512 if calls[0] % 7 == 0 else tok

    monkeypatch.setattr(E, "sample_token", wrong)
    r = tinyroot.run(root, seconds=2.0)
    assert not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] > tinyroot.TINY_LIMIT


def test_stale_pool_fails(root, monkeypatch):
    """A decode step that returns its state unchanged: the K/V it writes
    for each new token is dropped."""
    import jax
    import jax.numpy as jnp
    build = harness.build

    def stale_build(*a, **kw):
        engine, policy = build(*a, **kw)
        decode = engine._decode

        def stale(p, pool, *rest):
            keep = jax.tree.map(jnp.copy, pool)
            logits, _ = decode(p, pool, *rest)
            return logits, keep
        engine._decode = stale
        return engine, policy

    monkeypatch.setattr(harness, "build", stale_build)
    r = tinyroot.run(root, seconds=2.0)
    assert not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] > tinyroot.TINY_LIMIT
