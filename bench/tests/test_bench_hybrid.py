"""The hybrid MoE family (bench/families/hybrid_moe.py) against the served
program: a tiny period of three Mamba-2 layers and one attention layer
without position encoding, 8 experts of which this chip holds 4, top-2,
a shared expert and Granite's multipliers, served through ``Engine`` on
its chunked paged path. Every served step's logits are compared with the
float32 reference, across prompt-chunk boundaries, partial final chunks,
reused batch slots and a preempted, recomputed sequence."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import reference as R
from bench import weights as W
from bench.tests import tinyroot

FAMILY = harness.load_family("hybrid_moe")
HYBRID = dict(
    family="hybrid_moe", num_layers=8, d_ff=0, tie_embeddings=True,
    layer_types=["mamba", "mamba", "mamba", "attention"],
    position_embedding="nope", embedding_multiplier=12.0,
    attention_multiplier=0.1, residual_multiplier=0.22, logits_scaling=16.0,
    moe={"num_experts": 8, "experts_per_token": 2, "d_ff_expert": 32,
         "d_ff_shared": 48, "num_held": 4, "first_held": 2},
    ssm={"d_state": 16, "expand": 2, "num_heads": 8, "head_dim": 16,
         "n_groups": 1, "conv_width": 4, "chunk": 16})
CHUNK = 32          # prompt chunk: SSD chunks of 16 inside it
SEED = 11


def _model(**over):
    from repro.models.api import build_model
    conf = tinyroot.tiny_config(**dict(HYBRID, **over))
    m = conf["model"]
    model = build_model(harness.model_config(conf))
    specs = FAMILY.leaf_specs(m)
    W.check_layout(specs, model.abstract_params())
    params = W.make_params_fn(specs)(W.leaf_keys(specs, SEED))
    return m, model, specs, params


def _policy(num_pages=10_000, max_batch=3):
    from repro.serving.engine import AdmissionPolicy
    return AdmissionPolicy(
        hw_name="test", max_model_len=160, page_size=16,
        num_pages=num_pages, max_batch=max_batch, prefill_chunk=CHUNK,
        quant_bits=16, decode_slo_s=0.03, est_decode_s=0.0,
        est_prefill_s=0.0)


def _served_rows(engine):
    """Record (rid, position, logits row) of every token the engine
    samples: the first token after a sequence's last prompt chunk, and
    every decode row of a live sequence."""
    rec = []
    first, decode = engine._first_token, engine._decode

    def first_token(seq, row):
        rec.append((seq.req.rid, len(seq.req.prompt) - 1, np.asarray(row)))
        first(seq, row)

    def decode_tick(p, pool, pt, tok, pos, *state):
        logits, pool = decode(p, pool, pt, tok, pos, *state)
        rows, pos = np.asarray(logits[:, 0]), np.asarray(pos)
        for slot, seq in engine.scheduler.active.items():
            if seq.prefill_done:
                rec.append((seq.req.rid, int(pos[slot]), rows[slot]))
        return logits, pool

    engine._first_token, engine._decode = first_token, decode_tick
    return rec


def _reference_logits(m, specs, seqs):
    """Full float32 reference logits (divided by logits_scaling, as the
    program's) of each token sequence."""
    ref = R.Reference(FAMILY, m, SEED)
    hs = ref.final_hidden(seqs)

    def leaf(path):
        s = specs[path]
        return R.as_served(s, W.block_values(ref.keys[path][0], s.shape,
                                             (0,) * len(s.shape), s.shape,
                                             s.std))

    fn, emb = leaf(("final_norm",)), leaf(("embed",))
    V = m["vocab_size"]
    return [np.asarray(R.mm("td,vd->tv", R.rms(h, fn, m["norm_eps"]),
                            emb[:V]) / m["logits_scaling"])
            for h in hs]


def _compare(m, specs, outs, rec):
    """Largest |served - reference| logit over every recorded row, and
    the spread of the reference logits (their std, mean over the rows)."""
    rids = sorted(outs)
    ref = dict(zip(rids, _reference_logits(
        m, specs, [outs[r][:-1] for r in rids])))
    V = m["vocab_size"]
    worst = max(float(np.max(np.abs(row[:V] - ref[rid][pos])))
                for rid, pos, row in rec)
    spread = float(np.mean([np.std(ref[rid][pos]) for rid, pos, _ in rec]))
    return worst, spread


def _requests(lengths, gen=10):
    from repro.serving.engine import Request
    rng = np.random.default_rng(5)
    return [Request(rid=i, prompt=rng.integers(2, 512, n).astype(np.int32),
                    max_new=gen) for i, n in enumerate(lengths)]


def _float32(params):
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


# Prompts of 20-95 tokens in 32-token chunks: one chunk, several, partial
# final chunks and one exact multiple; five requests over three batch
# slots, so slots are reused after a finish.
LENGTHS = [20, 45, 64, 95, 33]


def test_served_logits_match_reference():
    """The program in float32 (the weights as served, bf16 values held in
    float32; the pool in float32 too) against the reference: every served
    row within 0.1% of the logits' spread. Float32 leaves differences of
    summation order, about 0.002% of it (CPU), and no routing choice
    flips; a state restarted at a chunk boundary, a slot shared by two
    sequences, or a padded row that advances the state moves rows by about
    the spread."""
    from repro.serving.engine import Engine
    m, model, specs, params = _model(dtype="float32")
    engine = Engine(model, _float32(params), _policy())
    rec = _served_rows(engine)
    outs = engine.run(_requests(LENGTHS))
    assert engine.stats["prefill_chunks"] >= 10
    worst, spread = _compare(m, specs, outs, rec)
    assert len(rec) == 10 * len(LENGTHS)
    assert worst <= 1e-3 * spread, (worst, spread)


def test_preempted_sequence_recomputed_exactly():
    """A pool too small for three growing sequences preempts the youngest;
    its recomputed prompt (prompt + tokens served so far) starts again
    from zero state, and every row served, before and after, matches the
    reference as tightly as without preemption."""
    from repro.serving.engine import Engine
    m, model, specs, params = _model(dtype="float32")
    engine = Engine(model, _float32(params), _policy(num_pages=13))
    rec = _served_rows(engine)
    outs = engine.run(_requests([40, 50, 60], gen=40))
    assert engine.stats["preemptions"] >= 1
    worst, spread = _compare(m, specs, outs, rec)
    assert worst <= 1e-3 * spread, (worst, spread)


def test_served_bf16_close_to_reference():
    """The configuration as served (bfloat16), with every expert chosen
    (top-8 of 8, so that no routing choice turns on a rounding): every
    served row's largest |served - reference| logit within 60% of the
    logits' spread. bf16 activations give up to 32% and 11% typically
    (CPU); a prompt chunk that restarts the state, or decode rows that
    share one slot, give 3 to 5 times the spread."""
    from repro.serving.engine import Engine
    moe = dict(HYBRID["moe"], experts_per_token=8)
    m, model, specs, params = _model(moe=moe)
    engine = Engine(model, params, _policy())
    rec = _served_rows(engine)
    outs = engine.run(_requests(LENGTHS))
    worst, spread = _compare(m, specs, outs, rec)
    assert worst <= 0.6 * spread, (worst, spread)


@pytest.mark.parametrize("chips", [2, 4])
def test_expert_shares_add_up_to_uncut_layer(chips):
    """Each chip holds 8 / chips experts and routes over all 8: the
    program's per-chip layers, with the shared expert counted once, add
    up to the uncut layer, the program's (to float32 sums) and the
    float32 reference's."""
    from repro.models import moe as moe_lib
    from repro.models.layers import ffn_apply
    m, model, specs, params = _model(
        dtype="float32", moe=dict(HYBRID["moe"], num_held=0, first_held=0))
    cfg = model.cfg
    p = jax.tree.map(lambda a: a[0].astype(jnp.float32),
                     params["blocks"]["sub0"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, m["d_model"]))
    x = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))   # a norm's output
    whole = moe_lib.moe_serve(p, x, cfg.moe)

    def held(first, n):
        return moe_lib.moe_serve(
            dict(p, **{k: p[k][first:first + n]
                       for k in ("w_in", "w_gate", "w_out")}),
            x, dataclasses.replace(cfg.moe, num_held=n, first_held=first))

    per = 8 // chips
    parts = [held(c * per, per) for c in range(chips)]
    shared = ffn_apply(p["shared"], x, cfg.activation)
    total = sum(parts) - (chips - 1) * shared
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)
    w = R.layer_weights(specs, ("blocks", "sub0"), R.layer_keys(
        W.leaf_keys(specs, SEED), ("blocks", "sub0"), 0))
    # the reference normalises x itself: a zero scale leaves x as it is
    want = FAMILY.experts(m, False, dict(w, ln2=jnp.zeros_like(w["ln2"])),
                          x[0])
    np.testing.assert_allclose(total[0], want, rtol=1e-3,
                               atol=1e-3 * float(jnp.max(jnp.abs(want))))


def test_hybrid_cell_files_only(tmp_path):
    """The family's configuration enters the harness as files only, runs
    to ``correct`` and reports the shared per-layer metrics through the
    family's counts (top-8 of 8 experts, so that no routing choice turns
    on a rounding)."""
    moe = dict(HYBRID["moe"], experts_per_token=8)
    root = tinyroot.make_root(tmp_path, **dict(HYBRID, moe=moe))
    conf = json.loads((root / "bench" / "configs" / "tiny.json").read_text())
    conf["serving"]["prefill_chunk"] = CHUNK
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(conf))
    r = tinyroot.run(root, trace=True)
    assert r["correct"], r["checks"]
    for name in ("decode.mfu", "prefill.mfu", "decode.step_ms"):
        assert name in r["metrics"], name
    assert "ssm.decode_roofline" not in r["metrics"]   # no TPU kernel here


def test_counts():
    """The family's counts at the published widths: the held share of the
    routed experts and the state the decode update moves."""
    conf = json.loads((harness.ROOT / "bench" / "configs" /
                       "granite-4.0-h-small.stage4.json").read_text())
    m = conf["model"]
    assert FAMILY.layer_matmul_params(m, "mamba") == int(
        4096 * (2 * 8192 + 2 * 128 + 128) + 8192 * 4096 + 4096 * 72
        + 10 * 9 / 72 * 3 * 4096 * 768 + 3 * 4096 * 1536)
    flops, nbytes = FAMILY.ssm_decode_work(m, 128)
    state = 128 * 64 * 128 * 4
    assert nbytes == 9 * 128 * (2 * state + (2 * 128 * 64 + 128 + 256) * 4)
    assert flops == 9 * 128 * (5 * 128 * 64 * 128 + 2 * 128 * 64)
    fa, ba = FAMILY.decode_attention_work(m, [100, 200])
    assert fa == 4 * 32 * 128 * 300 and ba == 2 * 8 * 128 * 300 * 2
