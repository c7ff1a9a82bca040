"""Chip smoke test: serve gemma2-2b at its published widths on a TPU through
the continuous-batching engine.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded engine on four chips

Run from the root of a checkout on a machine with a TPU. Weights are random,
made from ``--seed``. One process does everything and starts no child.

One chip: the admission policy is derived for the attached device's
hardware entry (capped at 8 in-flight sequences, 256-token prefill chunks),
six seeded requests of 64-1000 prompt tokens and 16 generated tokens each
are served through ``Engine.run``, and the engine's compiled decode and
chunk-prefill programs must contain the Pallas paged-attention kernel
(``tpu_custom_call``). Then one prompt goes through the engine's own
chunk-prefill and decode programs and their logits are compared with
``model.forward`` over the same tokens.

Four chips (``--chips 4``): the same requests through the sharded engine on a
``model=4`` mesh and through the one-device engine. The parameters and the
page pool must span all four devices, and one prompt's logits through the
sharded engine's programs must match the one-device engine's within the
same bound; where the greedy tokens of the two engines part is printed.

Any failed check exits non-zero. The last line of a passing run is one JSON
object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.hardware_model import hardware_for_device
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_serving_mesh
from repro.models.api import build_model
from repro.serving.engine import Engine, Request, derive_policy

ARCH = "gemma2-2b"
MAX_MODEL_LEN = 1088
MAX_BATCH = 8
PREFILL_CHUNK = 256            # the 1000-token prompt takes four chunks
PROMPT_LENS = (1000, 64, 333, 700, 128, 517)
GEN = 16
LOGITS_PROMPT = 300            # two prefill chunks, then one decode step
# The paged path (Pallas walk, f32 online softmax) and model.forward (dense
# masked softmax) round attention differently, and bf16 activations carry
# that through 26 layers: a few percent of the logit scale at most. A wrong
# page, mask or scale moves logits by a sizable fraction of that scale.
LOGITS_RTOL = 0.05             # of the reference's max |logit|


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def make_requests(cfg, seed: int):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(2, cfg.vocab_size, n)
                    .astype(np.int32), max_new=GEN)
            for i, n in enumerate(PROMPT_LENS)]


def check_outputs(cfg, reqs, outs) -> int:
    """Every request finished with its prompt intact and GEN in-vocabulary
    tokens. Returns the number of tokens generated."""
    for r in reqs:
        out = outs[r.rid]
        check(len(out) == len(r.prompt) + GEN, f"request {r.rid} length")
        check(np.array_equal(out[:len(r.prompt)], r.prompt),
              f"request {r.rid} prompt changed")
        gen = out[len(r.prompt):]
        check(bool(np.all((gen >= 0) & (gen < cfg.vocab_size))),
              f"request {r.rid} generated out-of-vocabulary tokens {gen}")
    return GEN * len(reqs)


def kernel_in(jitted, args) -> tuple:
    """Lower and compile ``jitted`` for ``args`` ahead of time. Returns
    (lowering seconds, compile seconds — what a warm persistent cache
    saves — and whether the compiled program holds a Pallas TPU kernel)."""
    t = time.monotonic()
    lowered = jitted.lower(*args)
    t_lower = time.monotonic() - t
    t = time.monotonic()
    text = lowered.compile().as_text()
    return t_lower, time.monotonic() - t, "tpu_custom_call" in text


def paged_logits(engine, tokens):
    """Logits for the last two positions of ``tokens`` through the engine's
    compiled programs: the prompt ``tokens[:-1]`` in prefill chunks (the
    last row unembedded), then one paged decode step for ``tokens[-1]``."""
    pol = engine.policy
    L = len(tokens) - 1
    C = pol.prefill_chunk
    pages = engine.kv.allocator.alloc(engine.kv.allocator.pages_for(L + 1))
    check(pages is not None, "no free pages for the logits check")
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
    engine.kv.allocator.free(pages)
    return np.stack([np.asarray(last[0, 0]), np.asarray(step[0, 0])])


def logits_tokens(cfg, seed: int):
    return np.random.default_rng(seed + 1).integers(
        2, cfg.vocab_size, LOGITS_PROMPT + 1).astype(np.int32)


def compare_logits(got, want, what: str) -> None:
    dev = float(np.max(np.abs(got - want)))
    tol = LOGITS_RTOL * float(np.max(np.abs(want)))
    print(f"logits: {what} over {LOGITS_PROMPT + 1} tokens: max |diff| "
          f"{dev:.6f}, tolerance {tol:.6f} ({LOGITS_RTOL} x reference max "
          f"|logit|)", flush=True)
    check(dev <= tol, f"{what}: logits deviate by {dev} > {tol}")


def one_chip(model, params, policy, reqs, seed: int) -> None:
    cfg = model.cfg
    engine = Engine(model, params, policy)
    B, maxp = policy.max_batch, policy.pages_per_seq
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    for name, jitted, args in (
            ("decode", engine._decode, (engine.params, engine.kv.pool,
                                        i32(B, maxp), i32(B, 1), i32(B))),
            ("chunk prefill", engine._chunk_prefill, (
                engine.params, engine.kv.pool, i32(1, maxp),
                i32(1, policy.prefill_chunk), i32(1)))):
        lower_s, compile_s, kernel = kernel_in(jitted, args)
        print(f"{name}: lower {lower_s:.2f}s, compile {compile_s:.2f}s, "
              f"tpu_custom_call {kernel}", flush=True)
        check(kernel, f"the paged-attention Pallas kernel is missing from "
              f"the compiled {name} program")

    t = time.monotonic()
    outs = engine.run(reqs)
    dt = time.monotonic() - t
    n_tok = check_outputs(cfg, reqs, outs)
    chunks = sum(-(-len(r.prompt) // policy.prefill_chunk) for r in reqs)
    check(engine.stats["prefill_chunks"] == chunks,
          f"{engine.stats['prefill_chunks']} prefill chunks, "
          f"expected {chunks}")
    print(f"served {len(reqs)} requests: {n_tok} generated tokens, "
          f"{engine.stats['prefill_chunks']} prefill chunks, "
          f"{engine.stats['decode_ticks']} decode ticks, "
          f"{engine.stats['preemptions']} preemptions, {dt:.2f}s wall "
          f"(compiles included)", flush=True)

    tokens = logits_tokens(cfg, seed)
    want = jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0])(
        params, jnp.asarray(tokens[None]))
    compare_logits(paged_logits(engine, tokens),
                   np.asarray(want[0, -2:], np.float32),
                   "chunk prefill + paged decode vs model.forward")


def four_chips(model, params, policy, reqs, seed: int) -> None:
    """The sharded engine on a model=4 mesh against the one-device engine,
    both under the same one-chip policy, on the same requests. The two
    engines compile different programs (sliced matmuls, gathers), whose
    bf16 rounding differs, so greedy tokens may part at a near-tie; the
    decisive comparison is the logits of one prompt through each engine's
    compiled programs."""
    cfg = model.cfg
    tokens = logits_tokens(cfg, seed)
    one = Engine(model, params, policy)
    t = time.monotonic()
    want = one.run(reqs)
    print(f"one device: served {len(reqs)} requests in "
          f"{time.monotonic() - t:.2f}s (compiles included)", flush=True)
    check_outputs(cfg, reqs, want)
    want_logits = paged_logits(one, tokens)
    del one

    mesh = make_serving_mesh(model=4)
    engine = Engine(model, params, policy, mesh=mesh)
    spans = {len(x.sharding.device_set)
             for x in jax.tree.leaves((engine.params, engine.kv.pool))}
    print(f"sharded: params and pool span {sorted(spans)} devices",
          flush=True)
    check(spans == {4}, f"sharded arrays span {sorted(spans)} devices, "
          f"not 4")
    t = time.monotonic()
    got = engine.run(reqs)
    print(f"sharded model=4: served {len(reqs)} requests in "
          f"{time.monotonic() - t:.2f}s (compiles included)", flush=True)
    check_outputs(cfg, reqs, got)
    for r in reqs:
        g, w = got[r.rid][len(r.prompt):], want[r.rid][len(r.prompt):]
        diff = np.nonzero(g != w)[0]
        print(f"request {r.rid}: " + ("token-identical" if not len(diff)
              else f"first differing generated token {int(diff[0])}"),
              flush=True)
    compare_logits(paged_logits(engine, tokens), want_logits,
                   "sharded model=4 vs one-device engine")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu", f"no TPU: JAX found platform "
          f"{dev.platform!r} ({dev.device_kind}); this script runs only on "
          f"a TPU")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, found "
          f"{len(devices)}")
    hw = hardware_for_device(dev)

    cfg = get_config(ARCH)
    model = build_model(cfg)
    print(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.num_heads} heads / {cfg.num_kv_heads} kv heads x "
          f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}; {model.param_count()} parameters, "
          f"{model.param_bytes()} bytes", flush=True)
    t = time.monotonic()
    # the TPU's hardware bit generator: threefry over 2.6B parameters takes
    # over a minute to compile, rbg a few seconds
    params = jax.block_until_ready(
        jax.jit(model.init)(jax.random.key(args.seed, impl="rbg")))
    print(f"init from seed {args.seed}: {time.monotonic() - t:.2f}s",
          flush=True)
    policy = derive_policy(cfg, hw, max_model_len=MAX_MODEL_LEN,
                           param_bytes=model.param_bytes(),
                           max_batch_cap=MAX_BATCH)
    policy = dataclasses.replace(policy, prefill_chunk=PREFILL_CHUNK)
    print(f"policy[{hw.name}] for {dev.device_kind}: "
          f"max_batch={policy.max_batch} page_size={policy.page_size} "
          f"prefill_chunk={policy.prefill_chunk} "
          f"pages_per_seq={policy.pages_per_seq}", flush=True)
    reqs = make_requests(cfg, args.seed)

    if args.chips == 4:
        four_chips(model, params, policy, reqs, args.seed)
    else:
        one_chip(model, params, policy, reqs, args.seed)
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} on "
          f"{dev.device_kind} (device 0)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
