"""Serving launcher — a thin CLI over the continuous-batching engine
(serving/engine), with the sequential batched generate kept as the
reference baseline for equivalence tests and throughput comparisons.

``python -m repro.launch.serve --arch gemma2-2b --tiny --requests 8``
``python -m repro.launch.serve --arch gemma2-2b --tiny --sequential``
``python -m repro.launch.serve --arch gemma2-2b --tiny --kv-bits 8``
``python -m repro.launch.serve --arch gemma2-2b --tiny --kv-policy haq``
``XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  python -m repro.launch.serve --arch gemma2-2b --tiny --mesh model=2,data=4``
``python -m repro.launch.serve --arch gemma2-2b --tiny \\
  --autotune 64 --autotune-out SERVING_gemma2.json``
``python -m repro.launch.serve --arch gemma2-2b --tiny \\
  --serving-config SERVING_gemma2.json``
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, tiny_config
from repro.core.hardware_model import HARDWARES, hardware_for_device
from repro.core.quantization import make_quant_dot
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import build_model
from repro.models.transformer import to_page_layout
from repro.serving.engine import Engine, Request, derive_policy
from repro.serving.engine.pool import quiet_donation

# decode closures are cached per (cfg, dot) so repeated generate() calls —
# one per request in the sequential baseline — reuse one jitted function
# instead of retracing every call. Values hold the dot hook alive so id()
# keys can't be recycled.
_DECODE_JIT: Dict[Tuple, Tuple] = {}


def _decode_fn(model, dot, kernel="auto"):
    paged = model.cfg.family in ("dense", "moe", "vlm") \
        and not model.cfg.is_encdec
    key = (model.cfg, None if dot is None else id(dot), paged, kernel)
    ent = _DECODE_JIT.get(key)
    if ent is None:
        if paged:
            fn = jax.jit(lambda p, pool, pt, t, pos: model.decode_step_paged(
                p, pool, pt, t, pos, dot=dot, kernel=kernel),
                donate_argnums=(1,))
        else:
            fn = jax.jit(lambda p, c, t, pos: model.decode_step(p, c, t, pos,
                                                                dot=dot))
        ent = (fn, dot)
        _DECODE_JIT[key] = ent
    return ent[0], paged


def _identity_paged_pool(cache, B: int, max_len: int, page: int):
    """Scatter a full-layout prefill cache into a fresh identity-mapped page
    pool: sequence b's logical block i lives at physical page 1 + b*ppseq
    + i (page 0 stays the scratch page, as in the engine)."""
    ppseq = -(-max_len // page)
    span = ppseq * page
    pt = np.arange(B * ppseq, dtype=np.int32).reshape(B, ppseq) + 1

    def to_pages(c):                     # (G, B, S, K, hd) full layout
        pad = [(0, 0)] * c.ndim
        pad[2] = (0, span - c.shape[2])
        c = jnp.pad(c, pad)
        c = to_page_layout(c.reshape(c.shape[0], B * span, *c.shape[3:]),
                           page)
        pool = jnp.zeros((c.shape[0], B * ppseq + 1) + c.shape[2:], c.dtype)
        return pool.at[:, 1:].set(c)

    return jax.tree.map(to_pages, cache), jnp.asarray(pt)


def generate(model, params, prompt_tokens, gen_len: int, *, temperature=0.0,
             dot=None, key=None, page_size: int = 16, kernel: str = "auto"):
    """prompt (B, S) -> (B, S+gen_len).

    Sequential baseline: one fixed batch, no admission — the engine's
    continuous batching supersedes this for traffic; kept as the exactness
    reference. Decode runs the same paged-attention walk as the engine over
    an identity page table (block i of sequence b at page 1 + b*ppseq + i,
    ``page_size`` matching the default admission policy), so the reduction
    order — and therefore every greedy token — is bit-comparable with the
    engine regardless of batch composition, growth, or preemption. The
    paged walk itself is validated against the dense oracle in
    tests/test_kernels.py; dense ring-buffer decode stays covered by
    tests/test_decode_equivalence.py.

    Families the engine does not serve (ssm / hybrid / encdec) fall back to
    the dense-cache ``decode_step`` path."""
    B, S = prompt_tokens.shape
    max_len = S + gen_len
    decode, paged = _decode_fn(model, dot, kernel)

    logits, cache = model.prefill(params, {"tokens": prompt_tokens}, dot=dot,
                                  cache_layout="full")
    if paged:
        pool, pt = _identity_paged_pool(cache, B, max_len, page_size)
    else:
        cache = _grow_cache(model, cache, S, max_len)

    out = [prompt_tokens]
    tok = _sample(logits, temperature, key)
    for i in range(gen_len):
        out.append(tok)
        if i == gen_len - 1:
            break
        if paged:
            positions = jnp.full((B,), S + i, jnp.int32)
            with quiet_donation():
                logits, pool = decode(params, pool, pt, tok, positions)
        else:
            logits, cache = decode(params, cache, tok,
                                   jnp.asarray(S + i, jnp.int32))
        if key is not None:
            key = jax.random.fold_in(key, i)
        tok = _sample(logits, temperature, key)
    return jnp.concatenate(out, axis=1)


def _grow_cache(model, cache, cur: int, max_len: int):
    """Pad dense KV caches from prefill length to max_len (the non-paged
    family fallback)."""
    def grow(path, a):
        ks = jax.tree_util.keystr(path)
        if a.ndim == 5 and "mamba" not in ks and a.shape[2] == cur:
            pad = [(0, 0)] * 5
            pad[2] = (0, max_len - cur)
            return jnp.pad(a, pad)
        return a
    return jax.tree_util.tree_map_with_path(grow, cache)


def _sample(logits, temperature, key):
    logits = logits[:, -1]
    if temperature <= 0.0 or key is None:
        return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature)[:, None] \
        .astype(jnp.int32)


def _parse_mesh(spec: str) -> Dict[str, int]:
    """'model=2' / 'model=2,data=4' -> axis sizes (missing axes = 1)."""
    sizes = {"model": 1, "data": 1}
    for part in filter(None, spec.split(",")):
        name, _, val = part.partition("=")
        name = name.strip()
        if name not in sizes or not val.strip().isdigit():
            raise ValueError(
                f"bad --mesh entry {part!r}; expected model=N[,data=M]")
        sizes[name] = int(val)
    return sizes


def _make_requests(args, cfg):
    rng = np.random.default_rng(0)
    reqs = []
    lo = min(4, args.prompt_len)
    for i in range(args.requests):
        S = int(rng.integers(lo, args.prompt_len + 1))
        prompt = rng.integers(2, cfg.vocab_size, S).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, max_new=args.gen))
    return reqs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--hw", default="", choices=sorted(HARDWARES),
                    help="hardware target the admission roofline prices "
                         "(default: the attached device, looked up by "
                         "its device kind)")
    ap.add_argument("--requests", type=int, default=8,
                    help="engine mode: number of requests in the trace")
    ap.add_argument("--batch", type=int, default=4,
                    help="sequential mode: fixed batch size")
    ap.add_argument("--max-batch", type=int, default=0,
                    help="override the policy's max in-flight batch")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV pool page size in tokens (both modes)")
    ap.add_argument("--paged-kernel", default="auto",
                    choices=("auto", "pallas", "ref"),
                    help="paged-attention path: Pallas page-walk kernel, "
                         "pure-JAX block walk, or auto (Pallas on TPU)")
    ap.add_argument("--reserve-upfront", action="store_true",
                    help="legacy admission: reserve every page of "
                         "prompt+max_new at admission instead of growing "
                         "lazily with preemption")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="engine mode: override the policy's roofline-"
                         "derived prompt chunk (tokens per prefill tick; "
                         "0 keeps the derived value)")
    ap.add_argument("--no-chunked-prefill", action="store_true",
                    help="engine mode: prefill whole prompts into padding "
                         "buckets in one forward (the pre-chunking "
                         "behaviour — one long prompt stalls every "
                         "resident decode for its full prefill latency)")
    ap.add_argument("--expected-occupancy", type=float, default=None,
                    help="fraction of max_model_len the admission policy "
                         "assumes a typical sequence occupies (default "
                         "0.5, or 1.0 with --reserve-upfront: worst-case "
                         "reservation can never fill slots an expected-"
                         "footprint batch was sized for)")
    ap.add_argument("--sequential", action="store_true",
                    help="legacy fixed-batch loop instead of the engine")
    ap.add_argument("--quant-policy", default="",
                    help="json file: {site: [w_bits, a_bits]} "
                         "(sequential mode only)")
    ap.add_argument("--kv-bits", type=int, default=16,
                    choices=(4, 8, 16),
                    help="engine mode: stored KV-cache bits for the paged "
                         "pool, uniform across layers (16 = bf16 exact "
                         "baseline; 8/4 = serving/kvquant int pages with "
                         "per-token per-head scales, dequant fused into "
                         "the paged-attention walk)")
    ap.add_argument("--mesh", default="",
                    help="engine mode: SPMD serving over a device mesh, "
                         "e.g. 'model=2' or 'model=2,data=4' — the paged "
                         "pool shards kv_heads over the model axis, params "
                         "spread at rest over the whole mesh, outputs stay "
                         "token-identical to the 1-device engine (off-TPU "
                         "set XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N first)")
    ap.add_argument("--trace-out", default="",
                    help="engine mode: write the telemetry tick trace + "
                         "request spans as Chrome trace-event JSON to this "
                         "path (open in Perfetto / chrome://tracing) and "
                         "print the telemetry summary")
    ap.add_argument("--serving-config", default="",
                    help="engine mode: load a searched per-hardware "
                         "serving config JSON (serving/autotune, written "
                         "by --autotune-out or the bench's "
                         "--autotune-config-out) instead of hand-picking "
                         "knobs; owns page size, prefill chunk, occupancy, "
                         "KV policy, mesh split, and the batch cap")
    ap.add_argument("--autotune", type=int, default=0, metavar="BUDGET",
                    help="engine mode: autotune the serving config before "
                         "serving — calibrate the admission roofline on a "
                         "warmup run, search the config space "
                         "(DDPG + evolution, serving/autotune) with this "
                         "many objective evaluations, validate the top "
                         "candidates on the real engine, and serve the "
                         "trace with the measured winner (0 = off)")
    ap.add_argument("--autotune-out", default="",
                    help="with --autotune: write the searched serving "
                         "config JSON here for --serving-config to load "
                         "back ('' disables)")
    ap.add_argument("--kv-policy", default="",
                    help="engine mode: per-layer KV bit policy — 'haq' "
                         "runs the HAQ search over KV sites "
                         "(serving/kvquant/policy.py: roofline feedback, "
                         "sensitivity-gated int4), or a json file mapping "
                         "sub-layer slots to bits, e.g. "
                         "'{\"sub0\": 4, \"sub1\": 8}'. Overrides "
                         "--kv-bits")
    args = ap.parse_args()
    enable_compile_cache()
    if args.prompt_len < 1:
        ap.error("--prompt-len must be >= 1")
    if args.quant_policy and not args.sequential:
        ap.error("--quant-policy applies to --sequential mode only; the "
                 "engine derives its quantization from the admission policy")
    if args.sequential and (args.kv_policy or args.kv_bits != 16):
        ap.error("--kv-bits/--kv-policy apply to engine mode only; the "
                 "sequential baseline is the fp exactness reference")
    if args.sequential and args.mesh:
        ap.error("--mesh applies to engine mode only; the sequential "
                 "baseline is the single-device exactness reference")
    if args.sequential and args.trace_out:
        ap.error("--trace-out applies to engine mode only; the sequential "
                 "baseline has no telemetry recorder")
    if args.sequential and (args.autotune or args.serving_config):
        ap.error("--autotune/--serving-config apply to engine mode only; "
                 "the sequential baseline has no admission policy to tune")
    if args.autotune and args.serving_config:
        ap.error("--serving-config loads a finished search; drop it or "
                 "drop --autotune")
    if args.autotune_out and not args.autotune:
        ap.error("--autotune-out only makes sense with --autotune")
    if (args.autotune or args.serving_config) and (
            args.kv_policy or args.kv_bits != 16 or args.mesh):
        ap.error("--kv-bits/--kv-policy/--mesh are knobs the serving "
                 "config owns; drop them when using "
                 "--autotune/--serving-config")

    cfg = tiny_config(args.arch) if args.tiny else get_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    if args.sequential:
        dot = None
        if args.quant_policy:
            policy = {k: tuple(v) for k, v in
                      json.load(open(args.quant_policy)).items()}
            dot = make_quant_dot(policy)
            print(f"serving with quantization policy over "
                  f"{len(policy)} sites")
        prompt = jnp.asarray(
            np.random.default_rng(0).integers(
                2, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)
        t0 = time.time()
        out = generate(model, params, prompt, args.gen,
                       temperature=args.temperature, dot=dot,
                       page_size=args.page_size, kernel=args.paged_kernel,
                       key=jax.random.PRNGKey(1)
                       if args.temperature > 0 else None)
        dt = time.time() - t0
        print(f"{cfg.name}: generated {args.gen} tokens x batch "
              f"{args.batch} in {dt:.2f}s "
              f"({args.gen * args.batch / dt:.1f} tok/s)")
        print("sample:",
              np.asarray(out[0, args.prompt_len:args.prompt_len + 16]))
        return

    hw = HARDWARES[args.hw] if args.hw \
        else hardware_for_device(jax.devices()[0])
    max_len = args.prompt_len + args.gen
    occupancy = args.expected_occupancy
    if occupancy is None:
        occupancy = 1.0 if args.reserve_upfront else 0.5

    reqs = _make_requests(args, cfg)

    if args.serving_config or args.autotune:
        # the serving config owns every knob the flags below would set;
        # the incompatible-flag combinations already errored above
        from repro.serving.autotune import (ConfigSpace,
                                            autotune_serving_config,
                                            load_serving_config,
                                            save_serving_config)
        space = ConfigSpace(cfg, hw, max_model_len=max_len,
                            max_devices=jax.device_count(),
                            max_batch_cap=args.max_batch or 8,
                            param_bytes=model.param_bytes())
        if args.serving_config:
            sc, record = load_serving_config(args.serving_config)
            if (record.get("arch"), record.get("hw"),
                    record.get("max_model_len")) != \
                    (cfg.name, hw.name, max_len):
                print(f"serving-config: note — searched for "
                      f"{record.get('arch')}@{record.get('max_model_len')} "
                      f"on {record.get('hw')}, serving "
                      f"{cfg.name}@{max_len} on {hw.name}")
            print(f"serving-config[{record.get('hw')}]: {sc.as_dict()}")
        else:
            t0 = time.time()
            tune = autotune_serving_config(model, params, space, reqs,
                                           budget=args.autotune, seed=0)
            sc = tune.winner.scored.config
            corr = tune.rank_correlation
            print(f"autotune[{hw.name}]: {tune.search.evaluated} "
                  f"candidates ({tune.search.admissible} admissible) in "
                  f"{time.time() - t0:.1f}s -> "
                  f"{tune.winner.decode_tok_s:.1f} decode tok/s vs "
                  f"default {tune.default.decode_tok_s:.1f} "
                  f"({tune.searched_vs_default:.2f}x), rank corr "
                  + ("n/a" if corr is None else f"{corr:.2f}"))
            print(f"autotune[{hw.name}]: winner {sc.as_dict()}")
            if args.autotune_out:
                save_serving_config(args.autotune_out, tune.record(space))
                print(f"autotune: wrote {args.autotune_out} "
                      f"(load with --serving-config)")
        bad = space.violations(sc)
        if bad:
            ap.error(f"serving config not admissible for {cfg.name}@"
                     f"{max_len} on {hw.name}: {'; '.join(bad)}")
        policy = space.to_policy(sc)
        mesh = None
        if sc.mesh_model > 1:
            from repro.launch.mesh import make_serving_mesh
            mesh = make_serving_mesh(model=sc.mesh_model, data=1)
    else:
        kv_bits = None if args.kv_bits == 16 else args.kv_bits
        if args.kv_policy == "haq":
            from repro.serving.kvquant import search_kv_policy
            res = search_kv_policy(cfg, hw, max_model_len=max_len,
                                   episodes=8)
            kv_bits = res["bits"]
            print(f"kvquant[haq]: {res['policy']} "
                  f"({res['kv_bytes_per_token_fp']}->"
                  f"{res['kv_bytes_per_token']} B/token)")
        elif args.kv_policy:
            from repro.models.transformer import normalize_kv_bits
            kv_bits = normalize_kv_bits(
                cfg, json.load(open(args.kv_policy)))

        mesh = None
        mesh_sizes = {"model": 1, "data": 1}
        if args.mesh:
            from repro.launch.mesh import make_serving_mesh
            try:
                mesh_sizes = _parse_mesh(args.mesh)
                mesh = make_serving_mesh(**mesh_sizes)
            except ValueError as e:
                ap.error(str(e))

        policy = derive_policy(cfg, hw, max_model_len=max_len,
                               page_size=args.page_size,
                               expected_occupancy=occupancy,
                               param_bytes=model.param_bytes(),
                               kv_bits=kv_bits,
                               mesh_model=mesh_sizes["model"],
                               mesh_data=mesh_sizes["data"])
        if args.max_batch or args.prefill_chunk:
            import dataclasses
            over = {}
            if args.max_batch:
                over["max_batch"] = args.max_batch
            if args.prefill_chunk:
                over["prefill_chunk"] = args.prefill_chunk
            policy = dataclasses.replace(policy, **over)
    print(f"admission[{hw.name}]: max_batch={policy.max_batch} "
          f"prefill_chunk={policy.prefill_chunk} "
          f"chunked={not args.no_chunked_prefill} "
          f"quant={policy.quant_bits}b "
          f"kv={policy.kv_bits or 'bf16'} pages={policy.num_pages} "
          f"page_size={policy.page_size} "
          f"mesh=model:{policy.mesh_model},data:{policy.mesh_data} "
          f"(est decode {policy.est_decode_s * 1e3:.2f}ms/step)")
    engine = Engine(model, params, policy, temperature=args.temperature,
                    paged_kernel=args.paged_kernel,
                    reserve_upfront=args.reserve_upfront,
                    chunked_prefill=not args.no_chunked_prefill,
                    mesh=mesh)
    t0 = time.time()
    outs = engine.run(reqs)
    dt = time.time() - t0
    gen_total = engine.stats["decode_tokens"] + engine.stats["prefills"]
    print(f"{cfg.name}: served {len(reqs)} requests, {gen_total} tokens in "
          f"{dt:.2f}s ({gen_total / dt:.1f} tok/s, "
          f"{engine.stats['decode_ticks']} decode ticks, "
          f"{engine.stats['prefill_chunks']} prefill chunks, "
          f"{engine.stats['preemptions']} preemptions, "
          f"{engine.stats['grown_pages']} pages grown)")
    first = outs[0]
    print("sample:", first[len(reqs[0].prompt):len(reqs[0].prompt) + 16])
    if args.trace_out:
        from repro.serving.telemetry import summarize, write_chrome_trace
        write_chrome_trace(engine.telemetry, args.trace_out)
        print(f"telemetry: wrote Chrome trace to {args.trace_out} "
              f"(open in https://ui.perfetto.dev)")
        print(summarize(engine.telemetry))


if __name__ == "__main__":
    main()
