"""Serving launcher — a thin CLI over the continuous-batching engine
(serving/engine): builds a model, derives or loads the admission policy,
serves a seeded request trace and prints the throughput and counters.

``python -m repro.launch.serve --arch gemma2-2b --tiny --requests 8``
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
from typing import Dict

import jax
import numpy as np

from repro.configs import get_config, tiny_config
from repro.core.hardware_model import HARDWARES, hardware_for_device
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import build_model
from repro.serving.engine import Engine, Request, derive_policy


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
                    help="number of requests in the trace")
    ap.add_argument("--max-batch", type=int, default=0,
                    help="override the policy's max in-flight batch")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV pool page size in tokens")
    ap.add_argument("--paged-kernel", default="auto",
                    choices=("auto", "pallas", "ref"),
                    help="paged-attention path: Pallas page-walk kernel, "
                         "pure-JAX block walk, or auto (Pallas on TPU)")
    ap.add_argument("--reserve-upfront", action="store_true",
                    help="legacy admission: reserve every page of "
                         "prompt+max_new at admission instead of growing "
                         "lazily with preemption")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="override the policy's roofline-"
                         "derived prompt chunk (tokens per prefill tick; "
                         "0 keeps the derived value)")
    ap.add_argument("--expected-occupancy", type=float, default=None,
                    help="fraction of max_model_len the admission policy "
                         "assumes a typical sequence occupies (default "
                         "0.5, or 1.0 with --reserve-upfront: worst-case "
                         "reservation can never fill slots an expected-"
                         "footprint batch was sized for)")
    ap.add_argument("--kv-bits", type=int, default=16,
                    choices=(4, 8, 16),
                    help="stored KV-cache bits for the paged pool, "
                         "uniform across layers (16 = bf16; 8/4 = "
                         "serving/kvquant int pages with per-token "
                         "per-head scales, dequant fused into the "
                         "paged-attention walk)")
    ap.add_argument("--mesh", default="",
                    help="SPMD serving over a device mesh, "
                         "e.g. 'model=2' or 'model=2,data=4' — the paged "
                         "pool shards kv_heads over the model axis, params "
                         "spread at rest over the whole mesh (off-TPU "
                         "set XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N first)")
    ap.add_argument("--trace-out", default="",
                    help="write the telemetry tick trace + "
                         "request spans as Chrome trace-event JSON to this "
                         "path (open in Perfetto / chrome://tracing) and "
                         "print the telemetry summary")
    ap.add_argument("--serving-config", default="",
                    help="load a searched per-hardware "
                         "serving config JSON (serving/autotune, written "
                         "by --autotune-out or the bench's "
                         "--autotune-config-out) instead of hand-picking "
                         "knobs; owns page size, prefill chunk, occupancy, "
                         "KV policy, mesh split, and the batch cap")
    ap.add_argument("--autotune", type=int, default=0, metavar="BUDGET",
                    help="autotune the serving config before "
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
                    help="per-layer KV bit policy — 'haq' "
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
          f"quant={policy.quant_bits}b "
          f"kv={policy.kv_bits or 'bf16'} pages={policy.num_pages} "
          f"page_size={policy.page_size} "
          f"mesh=model:{policy.mesh_model},data:{policy.mesh_data} "
          f"(est decode {policy.est_decode_s * 1e3:.2f}ms/step)")
    engine = Engine(model, params, policy, temperature=args.temperature,
                    paged_kernel=args.paged_kernel,
                    reserve_upfront=args.reserve_upfront, mesh=mesh)
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
