"""Engine throughput — continuous batching on a mixed trace, lazy page
allocation + preemption vs upfront reservation, fp vs quantized KV-cache
pools at equal HBM budget, and prefill stalls under long prompts.

Results are also written to ``BENCH_engine.json`` (see ``--out``) so the
perf trajectory stays machine-readable across PRs; every trace RNG is
seeded explicitly (TRACE_SEEDS).

Traces on the tiny CPU config:

  * **mixed** (16 requests, Poisson arrivals, Poisson-ish length mix):
    served through the continuous-batching engine in real time; records
    the engine's tokens/s.

  * **skewed** (long-``max_new`` tail on a page pool sized for the
    *expected*, not worst-case, footprint): served twice through the
    engine — once with the legacy upfront reservation
    (``ceil((prompt+max_new)/page)`` pages claimed at admission, which
    gates admission on pages most requests never touch) and once with
    lazy growth + youngest-first preemption. The derived column reports
    each mode's aggregate decode tokens/s; lazy wins because short
    requests slot into pages the long tail had only *nominally* reserved.

  * **kv-quant** (the skewed shape on a page pool capped by a fixed HBM
    *byte* budget): served through the engine with the fp pool, the int8
    pool, and the HAQ-searched mixed policy (serving/kvquant; local-window
    slots int4, global slots int8). All three pools get the same KV byte
    budget, so the quantized pools hold ~2x / ~2.3x the pages — fewer
    preemptions, more resident sequences, higher aggregate decode tok/s.
    The fp pool is the exactness baseline; quantized modes additionally
    report teacher-forced max-abs logit drift (kvquant.greedy_drift) and
    the greedy token-match fraction against fp.

  * **sharded** (the mixed shape served twice: 1-device vs an SPMD mesh —
    model=2 plus whatever data axis the forced host devices allow): greedy
    outputs are asserted token-identical (the sharded engine's acceptance
    bar), decode tok/s is recorded for both (host-device collectives make
    the sharded number a correctness trace, not a speedup, off-TPU), and
    the roofline capacity story is captured from ``derive_policy``:
    pool pages and resident sequences per device at 1 vs 2 model shards
    (the >=1.9x floor the CI gate enforces). Skipped (with a note) when
    fewer than 2 devices are visible — the multi-device CI job forces 8.

  * **longprompt** (a few short residents decoding for the whole run while
    long prompts keep a prefill in flight): served through the engine at a
    fixed chunk. Reports decode tok/s, per-decode-tick stall p50/p99 (the
    seconds a tick's already-ready sequences waited on prefill work,
    ``Engine.stall_log``), and TTFT p50/p99.

The long-prompt engine additionally contributes a ``telemetry``
section: measured per-decode-tick stall p50/p99 from the telemetry
record, per-kind tick counts, and the roofline predicted-vs-measured
calibration (`serving/telemetry/calibrate.py`) — the scale factors and
relative error that say how far `core/hardware_model`'s roofline is
from this host. ``--trace-out`` dumps the same engine's full tick trace
and request spans as Chrome trace-event JSON (Perfetto-loadable); the
CI engine-smoke job uploads it as a workflow artifact.

Engines are warmed on the exact trace shapes and re-timed on the same
instance, so jit compiles are excluded. Outputs are asserted identical
between the two admission modes.

Run: ``PYTHONPATH=src python -m benchmarks.bench_engine_throughput``.
CI: the engine-smoke job reruns the default (baseline-size) traces and
diffs the fresh JSON against the committed one via
``scripts/check_bench_regression.py``; the kv-quant job smokes
``--kv-requests 4`` separately.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from benchmarks.common import row
from repro.configs import tiny_config
from repro.core.hardware_model import V5E_EDGE
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import build_model
from repro.serving.engine import Engine, Request, derive_policy
from repro.serving.engine.admission import kv_bytes_per_token
from repro.serving.kvquant import greedy_drift, search_kv_policy
from repro.serving.telemetry import calibrate, write_chrome_trace

ARCH = "gemma2-2b"
MAX_BATCH = 8          # CPU-host cap on the policy's in-flight batch
PROMPT_MEAN = 24       # Poisson means for the mixed-trace length mix
GEN_MEAN = 24
ARRIVAL_RATE = 200.0   # req/s — a heavy-traffic burst

SKEW_MAX_LEN = 128     # skewed trace: model len, 8 pages of 16 per seq
SKEW_NUM_PAGES = 17    # 16 usable — two worst-case sequences' worth

LONG_MAX_LEN = 1024    # long-prompt trace: model len
LONG_PROMPT_LEN = 960  # the prompt whose prefill stalls resident decodes
LONG_CHUNK = 64        # fixed chunk so the stall bound is reproducible
LONG_RESIDENTS = 3     # short requests decoding for the whole run
LONG_RESIDENT_GEN = 224

# explicit trace seeds: the JSON trajectory is only comparable across PRs
# if every trace is reproducible
TRACE_SEEDS = {"mixed": 0, "skewed": 1, "kv": 2, "long": 3, "autotune": 4}

AUTOTUNE_BUDGET = 48   # default search budget (objective evaluations)
AUTOTUNE_TOPK = 3      # searched candidates re-measured on the real trace


def make_trace(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / ARRIVAL_RATE, n)
    arrivals = np.cumsum(gaps)
    reqs = []
    for i in range(n):
        S = int(np.clip(rng.poisson(PROMPT_MEAN), 4, 48))
        gen = int(np.clip(rng.poisson(GEN_MEAN), 4, 48))
        prompt = rng.integers(2, cfg.vocab_size, S).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, max_new=gen,
                            arrival=float(arrivals[i])))
    return reqs


def make_skewed_trace(cfg, n, seed=1):
    """Short prompts; every other request asks for a long generation. Under
    upfront reservation the long tail's worst-case pages throttle
    admission; lazily they are claimed only as decode reaches them."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        S = int(rng.integers(4, 13))
        if i % 2:
            gen = int(rng.integers(64, SKEW_MAX_LEN - S - 8))
        else:
            gen = int(rng.integers(8, 17))
        prompt = rng.integers(2, cfg.vocab_size, S).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, max_new=gen))
    return reqs


def build_engine(model, params, *, max_model_len=96, reserve_upfront=False,
                 num_pages=None, max_batch=MAX_BATCH, prefill_chunk=None):
    policy = derive_policy(model.cfg, V5E_EDGE,
                           max_model_len=max_model_len,
                           param_bytes=model.param_bytes())
    policy = dataclasses.replace(
        policy, max_batch=max_batch,
        **({"num_pages": num_pages} if num_pages else {}),
        **({"prefill_chunk": prefill_chunk} if prefill_chunk else {}))
    return Engine(model, params, policy, reserve_upfront=reserve_upfront)


def timed_run(engine, reqs, *, realtime):
    """Warm on the exact trace, then re-time the same engine instance."""
    engine.run(reqs, realtime=realtime)
    engine.reset_stats()
    t0 = time.monotonic()
    outs = engine.run(reqs, realtime=realtime)
    return outs, time.monotonic() - t0, engine.stats


def bench_mixed(model, params, cfg, n):
    reqs = make_trace(cfg, n, seed=TRACE_SEEDS["mixed"])
    total_gen = sum(r.max_new for r in reqs)
    engine = build_engine(model, params)
    _, eng_dt, stats = timed_run(engine, reqs, realtime=True)
    eng_tps = total_gen / eng_dt
    row("engine/continuous-batching", eng_dt / total_gen * 1e6,
        f"tok_s={eng_tps:.1f};ticks={stats['decode_ticks']}")
    print(f"# continuous batching: {eng_tps:.1f} tok/s", flush=True)
    return {"n": n, "engine_tok_s": eng_tps}


def bench_skewed(model, params, cfg, n):
    reqs = make_skewed_trace(cfg, n, seed=TRACE_SEEDS["skewed"])
    results = {}
    for mode, upfront in (("upfront", True), ("lazy", False)):
        engine = build_engine(model, params, max_model_len=SKEW_MAX_LEN,
                              num_pages=SKEW_NUM_PAGES,
                              reserve_upfront=upfront)
        outs, dt, stats = timed_run(engine, reqs, realtime=False)
        tps = stats["decode_tokens"] / dt
        results[mode] = (outs, tps)
        row(f"engine/skewed-{mode}", dt / max(stats["decode_tokens"], 1)
            * 1e6,
            f"decode_tok_s={tps:.1f};ticks={stats['decode_ticks']};"
            f"preempt={stats['preemptions']};grown={stats['grown_pages']}")
    for r in reqs:
        assert np.array_equal(results["upfront"][0][r.rid],
                              results["lazy"][0][r.rid]), (
            f"lazy/preempting engine diverged from upfront reservation "
            f"for request {r.rid}")
    gain = results["lazy"][1] / results["upfront"][1]
    # the >1x target applies at the default trace size — tiny CI smokes
    # (few requests) don't pressure the pool, so the flag is informational
    row("engine/skewed-lazy-vs-upfront", gain,
        f"speedup={gain:.2f}x;n={n};target>1x@n>=12;"
        f"pass={gain > 1.0 or n < 12}")
    print(f"# lazy paging: {results['lazy'][1]:.1f} decode tok/s vs "
          f"upfront {results['upfront'][1]:.1f} -> {gain:.2f}x "
          f"(outputs identical)", flush=True)
    return {"n": n, "upfront_decode_tok_s": results["upfront"][1],
            "lazy_decode_tok_s": results["lazy"][1], "gain": gain}


def make_long_trace(cfg, n, seed=3):
    """A few short prompts that decode for the whole run (the decode-SLO
    population) plus ``n`` long-prompt short-generation requests that keep
    a prefill in flight almost continuously. Chunked prefill bounds the
    per-tick stall of the residents at one chunk."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(LONG_RESIDENTS):
        prompt = rng.integers(2, cfg.vocab_size, 8).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, max_new=LONG_RESIDENT_GEN))
    for i in range(n):
        prompt = rng.integers(2, cfg.vocab_size,
                              LONG_PROMPT_LEN).astype(np.int32)
        reqs.append(Request(rid=LONG_RESIDENTS + i, prompt=prompt,
                            max_new=16))
    return reqs


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else 0.0


def telemetry_section(engine, n):
    """The ``telemetry`` block of BENCH_engine.json, read off the
    long-prompt engine (the trace with both tick kinds in flight):
    measured stall percentiles from the telemetry record and the roofline
    predicted-vs-measured calibration per tick kind — the scale factors
    and relative error `telemetry.calibrate` fits for hardware_model."""
    tel = engine.telemetry
    m = tel.metrics
    report = calibrate(tel.ticks)
    stall_ms = [s * 1e3 for s in tel.stall_log_view()]
    sec = {
        "n": n,
        "ticks": {k: c.value for k, c in sorted(m.counters.items())
                  if k.startswith("ticks.")},
        "stall_p50_ms": _pct(stall_ms, 50),
        "stall_p99_ms": _pct(stall_ms, 99),
        "pool_min_free": m.gauge("pool.min_free").value,
        "roofline_scale": report.scale_factors(),
        "roofline_rel_err": report.rel_err_by_kind(),
    }
    scale = sec["roofline_scale"]
    rel = sec["roofline_rel_err"]
    row("engine/telemetry-calibration",
        sum(v for v in rel.values() if v is not None),
        ";".join(f"{k}:scale="
                 + ("-" if scale[k] is None else f"{scale[k]:.2f}")
                 + ",relerr="
                 + ("-" if rel[k] is None else f"{rel[k]:.2f}")
                 for k in sorted(scale)))
    print(f"# telemetry: {len(tel.ticks)} tick events, stall p99 "
          f"{sec['stall_p99_ms']:.1f}ms; roofline scale "
          + ", ".join(f"{k}={scale[k]:.2f}" for k in sorted(scale)
                      if scale[k] is not None), flush=True)
    return sec


def bench_longprompt(model, params, cfg, n):
    """Chunked prefill on the long-prompt trace: decode tok/s,
    per-decode-tick stall p50/p99 (engine.stall_log), and TTFT."""
    reqs = make_long_trace(cfg, n, seed=TRACE_SEEDS["long"])
    out = {"n": n, "prompt_len": LONG_PROMPT_LEN, "chunk": LONG_CHUNK}
    engine = build_engine(model, params, max_model_len=LONG_MAX_LEN,
                          max_batch=LONG_RESIDENTS + 1,
                          prefill_chunk=LONG_CHUNK)
    _, dt, stats = timed_run(engine, reqs, realtime=False)
    stall_ms = [s * 1e3 for s in engine.stall_log]
    ttft_ms = [t * 1e3 for t in engine.first_token_s.values()]
    tps = stats["decode_tokens"] / dt
    rec = {"decode_tok_s": tps,
           "decode_ticks": stats["decode_ticks"],
           "prefill_chunks": stats["prefill_chunks"],
           "stall_p50_ms": _pct(stall_ms, 50),
           "stall_p99_ms": _pct(stall_ms, 99),
           "stall_max_ms": max(stall_ms) if stall_ms else 0.0,
           "ttft_p50_ms": _pct(ttft_ms, 50),
           "ttft_p99_ms": _pct(ttft_ms, 99)}
    out["chunked"] = rec
    row("engine/longprompt-chunked",
        dt / max(stats["decode_tokens"], 1) * 1e6,
        f"decode_tok_s={tps:.1f};stall_p99_ms={rec['stall_p99_ms']:.1f};"
        f"ttft_p50_ms={rec['ttft_p50_ms']:.0f};"
        f"chunks={stats['prefill_chunks']}")
    return out, engine


def _equal_budget_pages(cfg, kv_bits, page_size=16):
    """Pages a fixed KV byte budget holds at a given bit policy — the fp
    pool's SKEW_NUM_PAGES worth of bytes, re-sliced at quantized width."""
    budget = (SKEW_NUM_PAGES - 1) * page_size * kv_bytes_per_token(cfg)
    return int(budget // (page_size * kv_bytes_per_token(cfg, kv_bits))) + 1


def bench_kv(model, params, cfg, n):
    """fp vs int8 vs HAQ-mixed KV pools at equal HBM byte budget."""
    reqs = make_skewed_trace(cfg, n, seed=TRACE_SEEDS["kv"])
    haq = search_kv_policy(cfg, V5E_EDGE, max_model_len=SKEW_MAX_LEN,
                           episodes=0, budget_frac=0.4)
    modes = {"fp16": None, "int8": 8, "haq": haq["bits"]}
    out = {"haq_policy": haq["policy"], "n": n}
    fp_outs = None
    fp_replay = None     # one fp teacher-forced replay shared by all modes
    for name, bits in modes.items():
        pages = _equal_budget_pages(cfg, bits)
        policy = derive_policy(cfg, V5E_EDGE, max_model_len=SKEW_MAX_LEN,
                               param_bytes=model.param_bytes(),
                               kv_bits=bits)
        policy = dataclasses.replace(policy, max_batch=MAX_BATCH,
                                     num_pages=pages)
        engine = Engine(model, params, policy)
        outs, dt, stats = timed_run(engine, reqs, realtime=False)
        tps = stats["decode_tokens"] / dt
        rec = {"kv_bits": bits if bits is None or isinstance(bits, int)
               else list(bits),
               "num_pages": pages, "decode_tok_s": tps,
               "preemptions": stats["preemptions"],
               "decode_ticks": stats["decode_ticks"]}
        if fp_outs is None:
            fp_outs = outs
        else:
            match = total = 0
            for r in reqs:
                S = len(r.prompt)
                a, b = fp_outs[r.rid][S:], outs[r.rid][S:]
                match += int(np.sum(a == b))
                total += len(a)
            drift = greedy_drift(model, params, fp_outs[reqs[0].rid],
                                 len(reqs[0].prompt), kv_bits=bits,
                                 fp_logits=fp_replay)
            fp_replay = drift["fp_logits"]
            rec["token_match"] = match / max(total, 1)
            rec["logit_drift_max_abs"] = drift["max_abs"]
        out[name] = rec
        row(f"engine/kv-{name}",
            dt / max(stats["decode_tokens"], 1) * 1e6,
            f"decode_tok_s={tps:.1f};pages={pages};"
            f"preempt={stats['preemptions']};"
            + (f"match={rec.get('token_match', 1.0):.2f};"
               f"drift={rec.get('logit_drift_max_abs', 0.0):.3f}"
               if name != "fp16" else "baseline=fp16"))
    for name in ("int8", "haq"):
        gain = out[name]["decode_tok_s"] / out["fp16"]["decode_tok_s"]
        out[name]["gain_vs_fp"] = gain
        print(f"# kv-{name}: {out[name]['decode_tok_s']:.1f} decode tok/s "
              f"({gain:.2f}x fp) at {out[name]['num_pages']} vs "
              f"{out['fp16']['num_pages']} pages, drift "
              f"{out[name]['logit_drift_max_abs']:.3f}, token match "
              f"{out[name]['token_match']:.2f}", flush=True)
    return out


def bench_autotune(model, params, cfg, n, *, budget, topk, config_out):
    """The serving-stack autotuner on a mixed-shape trace: calibrate the
    roofline on the hand-picked default config's warmup run, search the
    engine config space on the scale-corrected roofline (DDPG +
    evolutionary, serving/autotune), re-measure the top-k candidates on
    the real engine, and ship the best *measured* config. Records
    searched vs default decode tok/s (the CI-gated floor: the winner may
    never measure below 0.95x the default — the default itself is in the
    validation set, so the search can only ever tie or win), TTFT p50
    for both, candidate counts, and the Spearman predicted-vs-measured
    rank correlation of the calibrated objective."""
    from repro.serving.autotune import (ConfigSpace, autotune_serving_config,
                                        save_serving_config)

    reqs = make_trace(cfg, n, seed=TRACE_SEEDS["autotune"])
    space = ConfigSpace(cfg, V5E_EDGE, max_model_len=96,
                        max_devices=jax.device_count(),
                        max_batch_cap=MAX_BATCH,
                        param_bytes=model.param_bytes())
    tune = autotune_serving_config(model, params, space, reqs,
                                   budget=budget, top_k=topk, seed=0)
    ratio = tune.searched_vs_default
    sec = {
        "n": n, "budget": budget, "top_k": topk,
        "method": tune.search.method, "seed": tune.search.seed,
        "candidates": tune.search.evaluated,
        "admissible": tune.search.admissible,
        "validated": len(tune.validated),
        "default": {
            "config": tune.default.scored.config.as_dict(),
            "decode_tok_s": tune.default.decode_tok_s,
            "ttft_p50_ms": tune.default.ttft_p50_s * 1e3,
        },
        "searched": {
            "config": tune.winner.scored.config.as_dict(),
            "decode_tok_s": tune.winner.decode_tok_s,
            "predicted_decode_tok_s":
                tune.winner.scored.pred_decode_tok_s,
            "ttft_p50_ms": tune.winner.ttft_p50_s * 1e3,
        },
        "searched_vs_default": ratio,
        "rank_correlation": tune.rank_correlation,
        "calibration_scale": dict(tune.scales.by_kind),
    }
    if config_out:
        save_serving_config(config_out, tune.record(space))
        print(f"# wrote searched serving config {config_out}", flush=True)
    corr = tune.rank_correlation
    row("engine/autotune-searched", ratio,
        f"searched_tok_s={tune.winner.decode_tok_s:.1f};"
        f"default_tok_s={tune.default.decode_tok_s:.1f};"
        f"ratio={ratio:.2f}x;candidates={tune.search.evaluated};"
        f"corr=" + ("-" if corr is None else f"{corr:.2f}")
        + f";target>=0.95x;pass={ratio >= 0.95}")
    print(f"# autotune: searched {tune.winner.decode_tok_s:.1f} decode "
          f"tok/s vs default {tune.default.decode_tok_s:.1f} "
          f"({ratio:.2f}x) over {tune.search.evaluated} candidates "
          f"({tune.search.admissible} admissible, "
          f"{len(tune.validated)} measured); rank corr "
          + ("n/a" if corr is None else f"{corr:.2f}")
          + f"; winner {tune.winner.scored.config.as_dict()}", flush=True)
    return sec


def bench_sharded(model, params, cfg, n):
    """1-device vs SPMD mesh on the mixed trace shape (same policy, same
    trace, outputs asserted identical) + mesh-aware admission capacity."""
    from repro.launch.mesh import make_serving_mesh

    ndev = jax.device_count()
    if ndev < 2:
        print("# sharded: skipped (1 visible device; set XLA_FLAGS="
              "--xla_force_host_platform_device_count=8)", flush=True)
        return None
    tp = 2                                   # tiny gemma2 has K=2
    dp = max(min(ndev // tp, 4), 1)
    mesh = make_serving_mesh(model=tp, data=dp)
    reqs = make_trace(cfg, n, seed=TRACE_SEEDS["mixed"])
    results = {}
    out = {"n": n, "model_shards": tp, "data_shards": dp, "devices": ndev}
    for mode, m in (("one_dev", None), ("sharded", mesh)):
        policy = derive_policy(cfg, V5E_EDGE, max_model_len=96,
                               param_bytes=model.param_bytes())
        policy = dataclasses.replace(policy, max_batch=MAX_BATCH)
        engine = Engine(model, params, policy, mesh=m)
        outs, dt, stats = timed_run(engine, reqs, realtime=False)
        tps = stats["decode_tokens"] / dt
        results[mode] = outs
        out[mode] = {"decode_tok_s": tps,
                     "decode_ticks": stats["decode_ticks"]}
        row(f"engine/sharded-{mode}",
            dt / max(stats["decode_tokens"], 1) * 1e6,
            f"decode_tok_s={tps:.1f};ticks={stats['decode_ticks']}")
    identical = all(np.array_equal(results["one_dev"][r.rid],
                                   results["sharded"][r.rid]) for r in reqs)
    # recorded, not asserted: the CI gate (check_bench_regression.py
    # sharded floors) owns the failure so a divergence still produces the
    # JSON + comparison table instead of dying before --out is written
    out["outputs_identical"] = identical
    if not identical:
        print("# sharded: WARNING — outputs diverged from the 1-device "
              "engine (the bench gate will fail on this)", flush=True)

    # roofline capacity: per-device pool pages + resident sequences at
    # 1 vs 2 model shards in the same per-device HBM (the CI-gated floor)
    p1 = derive_policy(cfg, V5E_EDGE, max_model_len=96,
                       param_bytes=model.param_bytes())
    p2 = derive_policy(cfg, V5E_EDGE, max_model_len=96,
                       param_bytes=model.param_bytes(), mesh_model=2)
    out["capacity"] = {
        "pages_1shard": p1.num_pages, "pages_2shard": p2.num_pages,
        "pages_scaling_2x": p2.num_pages / p1.num_pages,
        "resident_1shard": p1.max_batch, "resident_2shard": p2.max_batch,
    }
    row("engine/sharded-capacity", out["capacity"]["pages_scaling_2x"],
        f"pages={p1.num_pages}->{p2.num_pages};"
        f"resident={p1.max_batch}->{p2.max_batch};target>=1.9x;"
        f"pass={out['capacity']['pages_scaling_2x'] >= 1.9}")
    print(f"# sharded: outputs identical on model={tp},data={dp}; "
          f"{out['sharded']['decode_tok_s']:.1f} vs "
          f"{out['one_dev']['decode_tok_s']:.1f} decode tok/s (host-device "
          f"mesh); pool pages {p1.num_pages}->{p2.num_pages} per device at "
          f"2 model shards "
          f"({out['capacity']['pages_scaling_2x']:.2f}x)", flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=16,
                    help="mixed-trace size (0 skips the section)")
    ap.add_argument("--skewed-requests", type=int, default=12,
                    help="skewed-trace size (0 skips the section)")
    ap.add_argument("--kv-requests", type=int, default=12,
                    help="kv-quant trace size (0 skips the section)")
    ap.add_argument("--long-requests", type=int, default=6,
                    help="long-prompt trace: number of long prompts "
                         "(0 skips the section)")
    ap.add_argument("--sharded-requests", type=int, default=6,
                    help="sharded trace size (0 skips; auto-skips with a "
                         "note when <2 devices are visible)")
    ap.add_argument("--autotune-requests", type=int, default=8,
                    help="autotune trace size (0 skips the section)")
    ap.add_argument("--autotune-budget", type=int, default=AUTOTUNE_BUDGET,
                    help="autotune search budget in objective evaluations")
    ap.add_argument("--autotune-topk", type=int, default=AUTOTUNE_TOPK,
                    help="searched candidates re-measured on the engine")
    ap.add_argument("--autotune-config-out", default="",
                    help="write the searched per-hardware serving config "
                         "JSON here ('' disables; load it back with "
                         "launch/serve.py --serving-config)")
    ap.add_argument("--out", default="BENCH_engine.json",
                    help="machine-readable results file ('' disables)")
    ap.add_argument("--trace-out", default="",
                    help="write the long-prompt engine's telemetry "
                         "as Chrome trace-event JSON to this path (open in "
                         "Perfetto; '' disables)")
    # parse_known_args: benchmarks/run.py invokes main() with its own tag
    # arguments still on sys.argv
    args, _ = ap.parse_known_args()
    enable_compile_cache()

    cfg = tiny_config(ARCH)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    results = {
        "schema": 1,
        "config": {"arch": ARCH, "tiny": True, "max_batch": MAX_BATCH,
                   "page_size": 16, "skew_max_len": SKEW_MAX_LEN,
                   "skew_num_pages": SKEW_NUM_PAGES,
                   "long_max_len": LONG_MAX_LEN,
                   "long_prompt_len": LONG_PROMPT_LEN,
                   "long_chunk": LONG_CHUNK,
                   "trace_seeds": TRACE_SEEDS},
    }
    if args.requests:
        results["mixed"] = bench_mixed(model, params, cfg, args.requests)
    if args.skewed_requests:
        results["skewed"] = bench_skewed(model, params, cfg,
                                         args.skewed_requests)
    if args.kv_requests:
        results["kv"] = bench_kv(model, params, cfg, args.kv_requests)
    if args.long_requests:
        longprompt, long_engine = bench_longprompt(model, params, cfg,
                                                   args.long_requests)
        results["longprompt"] = longprompt
        results["telemetry"] = telemetry_section(long_engine,
                                                 args.long_requests)
        if args.trace_out:
            write_chrome_trace(long_engine.telemetry, args.trace_out)
            print(f"# wrote Chrome trace {args.trace_out} "
                  f"(open in https://ui.perfetto.dev)", flush=True)
    if args.sharded_requests:
        sharded = bench_sharded(model, params, cfg, args.sharded_requests)
        if sharded is not None:
            results["sharded"] = sharded
    if args.autotune_requests:
        results["autotune"] = bench_autotune(
            model, params, cfg, args.autotune_requests,
            budget=args.autotune_budget, topk=args.autotune_topk,
            config_out=args.autotune_config_out)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2, sort_keys=True)
        print(f"# wrote {args.out}", flush=True)


if __name__ == "__main__":
    main()
