import os

# Smoke tests and benches must see the single real CPU device (the dry-run
# sets its own 512-device flag in its own process).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


# The engine tests' one oracle. A served token is sound when its logit
# under a teacher-forced dense forward (batch 1, no pages, no pool, no
# chunks) lies at most GAP_TOL below that forward's best logit. Both sides
# run the served bfloat16 weights, so a sound token's gap is rounding
# noise: over every engine test that uses this oracle (tiny gemma2 and
# granite-moe on the CPU) the largest gap is 0.00093, a bfloat16 near-tie
# in test_engine_matches_sequential_greedy, and all but two tests read 0.
# A wrong token sits far lower: the vocabulary's median-ranked token lies
# 0.57-0.84 below the best in test_served_gaps_has_teeth.
GAP_TOL = 0.01

_FORWARD_JITS = {}


def _dense_logits(model, params, tokens):
    """(S, V) float32 logits of one unbatched forward over ``tokens``."""
    fn, _ = _FORWARD_JITS.get(id(model), (None, None))
    if fn is None:
        fn = jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0])
        # the model rides along so its id is not recycled
        _FORWARD_JITS[id(model)] = (fn, model)
    return np.asarray(fn(params, jnp.asarray(tokens[None]))[0], np.float32)


def served_gaps(model, params, prompt, served):
    """For each served token, the teacher-forced forward's best logit at
    its position minus the logit of the token served. ``served`` is the
    engine's continuation of ``prompt`` (the output past the prompt)."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    toks = np.concatenate([prompt, served[:-1]])
    rows = _dense_logits(model, params, toks)[len(prompt) - 1:]
    return rows.max(-1) - rows[np.arange(len(served)), served]


def assert_served(model, params, reqs, outs):
    """Every request was served ``max_new`` in-vocabulary tokens (fewer
    only when it stopped at its eos) and every served token is within
    GAP_TOL of the dense forward's best. Returns the largest gap."""
    worst = 0.0
    for r in reqs:
        S = len(r.prompt)
        got = np.asarray(outs[r.rid])
        assert np.array_equal(got[:S], r.prompt), r.rid
        served = got[S:]
        if len(served) < r.max_new:
            assert r.eos_id is not None and served[-1] == r.eos_id, r.rid
        assert len(served) <= r.max_new, r.rid
        assert np.all((served >= 0) & (served < model.cfg.vocab_size)), r.rid
        gaps = served_gaps(model, params, r.prompt, served)
        assert gaps.max() <= GAP_TOL, (r.rid, int(gaps.argmax()),
                                       float(gaps.max()))
        worst = max(worst, float(gaps.max()))
    return worst


def tiny_batch(cfg, B=2, S=32, seed=1):
    key = jax.random.PRNGKey(seed)
    if cfg.is_encdec:
        Sd = max(S // cfg.dec_ratio, 2)
        return {
            "frames": jax.random.normal(key, (B, S, cfg.d_model),
                                        jnp.bfloat16),
            "tokens": jnp.ones((B, Sd), jnp.int32),
            "labels": jnp.ones((B, Sd), jnp.int32),
        }
    if cfg.frontend == "vision_stub":
        Sp = int(S * cfg.patch_frac)
        return {
            "patches": jax.random.normal(key, (B, Sp, cfg.d_model),
                                         jnp.bfloat16),
            "tokens": jnp.ones((B, S - Sp), jnp.int32),
            "labels": jnp.ones((B, S - Sp), jnp.int32),
        }
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    return {"tokens": toks, "labels": toks}
