"""The benchmark harness: one cell, one seed, one process.

Everything about a cell is data found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic, ``bench/configs/<config>.json`` holds the
model and serving settings, ``bench/traffic/<traffic>.json`` the mix,
``bench/metrics/<metric>.py`` the reader of each per-layer metric, and
``bench/families/<family>.py``, by the configuration's ``model.family``,
what depends on the model's structure: the parameter layout, the
reference's blocks and the counts the readers use.

A run: check the device; make the weights on the device from the seed in
one jitted call; derive the admission policy for the attached device with
the configuration's knobs; build the engine; warm its programs up with one
request; run the closed loop in through ``Engine.submit`` and
``Engine.step`` until it is steady (set-up ends here), then drive it for
``--seconds``; read the device's peak memory; free
the program's state; compare a seeded sample of the finished requests with
the float32 reference (bench/reference.py with the family's blocks);
print the result line.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
import typing
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
SAMPLE_SERVED = 384        # served tokens the correctness sample aims at
SAMPLE_MAX_REQUESTS = 12
SAMPLE_MAX_TOKENS = 24576  # prompt + served tokens run through the reference
TRACE_AT = 0.3             # share of the window before the profiler starts
TRACE_SECONDS = 4.0        # at least this long traced, at step boundaries


def fail(msg: str) -> None:
    raise SystemExit(f"bench: {msg}")


# ------------------------------------------------------------------ cells --
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    mix_name: str
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    family: ModuleType       # bench/families/<model.family>.py


def _applies(metric: dict, cell: str, e2e_names) -> bool:
    """A metric with ``workloads`` is reported in those cells; one
    without, in every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        fail(f"no workload {name!r} in BENCHMARK.json "
             f"(known: {sorted(cells)})")
    c = cells[name]
    conf = {x["name"]: x for x in bench["configs"]}[c["config"]]
    config = json.loads((root / conf["file"]).read_text())
    from bench.traffic import load_mix
    mix = load_mix(c["traffic"], root / "bench" / "traffic")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, ())]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return Cell(name, c["chips"], c["config"], config, c["traffic"], mix,
                e2e, per, load_family(config["model"]["family"], root))


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        fail(f"no peaks for device kind {kind!r} in bench/peaks.json "
             f"(known: {sorted(table)})")
    return table[kind]


def _load(path: Path, prefix: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        fail(f"no reader {path} for per-layer metric {name}")
    return _load(path, "bench_metric_", name).read


def load_family(name: str, root: Path = ROOT) -> ModuleType:
    """The module of one model family: ``leaf_specs``, ``forward`` (the
    reference's blocks) and the readers' counts."""
    rel = Path("bench") / "families" / f"{name}.py"
    if not (root / rel).is_file():
        fail(f"no module {rel} for the model family {name!r}")
    return _load(root / rel, "bench_family_", name)


# ----------------------------------------------------------------- device --
def check_devices(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        fail(f"no TPU: JAX found platform {devs[0].platform!r} "
             f"({devs[0].device_kind}); this benchmark measures a TPU")
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def enable_cache() -> str:
    """JAX's persistent compilation cache: JAX_COMPILATION_CACHE_DIR when
    set, else the fixed directory .jax_cache/ at the checkout's root."""
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


# ------------------------------------------------------------------ model --
def _nested_class(tp):
    """The dataclass that a field's type (``Optional[MoEConfig]``) names,
    or None."""
    return next((a for a in (tp, *typing.get_args(tp))
                 if dataclasses.is_dataclass(a)), None)


def _typed(cls, d: dict, where: str, base=None):
    """``cls`` (over ``base`` where given) with the fields of ``d``: a
    list becomes a tuple, a nested dict the dataclass its field holds,
    over the nested object that ``base`` already has there, so fields the
    file leaves out keep the base's values, not the class defaults."""
    hints = typing.get_type_hints(cls)
    kw = {}
    for k, v in d.items():
        if k not in hints:
            fail(f"configuration key {where}.{k} is not a field of the "
                 f"program's {cls.__name__}")
        if isinstance(v, dict):
            sub = _nested_class(hints[k])
            if sub is None:
                fail(f"configuration key {where}.{k} holds a dict; the "
                     f"program's {cls.__name__}.{k} takes no dataclass")
            v = _typed(sub, v, f"{where}.{k}",
                       getattr(base, k, None) if base is not None else None)
        kw[k] = tuple(v) if isinstance(v, list) else v
    try:
        return dataclasses.replace(base, **kw) if base is not None \
            else cls(**kw)
    except TypeError as e:
        fail(f"configuration {where}: {e}")


def _check_taken(obj, d: dict, where: str) -> None:
    for k, v in d.items():
        got = getattr(obj, k)
        if isinstance(v, dict):
            _check_taken(got, v, f"{where}.{k}")
        elif (list(got) if isinstance(got, tuple) else got) != v:
            fail(f"configuration {where}.{k}={v!r} not taken by the "
                 f"program ({got!r})")


def model_config(config: dict):
    """The program's ModelConfig for the file's model dict (nested
    ``moe`` and ``ssm`` dicts become the program's MoEConfig and
    SSMConfig), checked field by field against the file."""
    from repro.configs import get_config
    base = get_config(config["arch"])
    cfg = _typed(type(base), config["model"], "model", base)
    _check_taken(cfg, config["model"], "model")
    return cfg


def build(cell: Cell, seed: int, devices, log, hw=None):
    """(engine, policy): weights made on the device from the seed, the
    admission policy for the attached device (``hw`` overrides it, for
    tests off the chip), the engine built on them."""
    import jax
    from repro.core.hardware_model import hardware_for_device
    from repro.models.api import build_model
    from repro.serving.engine import Engine, derive_policy
    from bench import weights as W

    conf = cell.config
    cfg = model_config(conf)
    model = build_model(cfg)
    specs = cell.family.leaf_specs(conf["model"])
    W.check_layout(specs, model.abstract_params())
    srv = conf["serving"]
    if cell.chips > 1:
        fail("the harness builds one-chip engines only")
    t = time.monotonic()
    make = W.make_params_fn(specs)
    keys = W.leaf_keys(specs, seed)
    with jax.default_device(devices[0]):
        params = jax.block_until_ready(make(keys))
    log(f"weights: {model.param_count()} parameters, {model.param_bytes()} "
        f"bytes, made on the device in {time.monotonic() - t:.2f}s")
    hw = hw or hardware_for_device(devices[0])
    policy = derive_policy(
        cfg, hw, max_model_len=srv["max_model_len"],
        param_bytes=model.param_bytes(),
        max_batch_cap=srv.get("max_batch_cap", 1024),
        hbm_util=srv.get("hbm_util", 0.9),
        decode_slo_s=srv.get("decode_slo_s", 0.030),
        page_size=srv.get("page_size", 16),
        expected_occupancy=srv.get("expected_occupancy", 0.5))
    if "prefill_chunk" in srv:
        policy = dataclasses.replace(policy, prefill_chunk=srv["prefill_chunk"])
    log(f"policy[{hw.name}]: max_batch={policy.max_batch} "
        f"prefill_chunk={policy.prefill_chunk} num_pages={policy.num_pages} "
        f"page_size={policy.page_size} quant_bits={policy.quant_bits}")
    if policy.quant_bits != 16:
        fail(f"the policy quantizes weights to {policy.quant_bits} bits; "
             f"the configuration states bfloat16")
    engine = Engine(model, params, policy)
    return engine, policy


def warm_up(engine, vocab: int) -> None:
    """One request through every program the window runs: a prompt chunk,
    the unembedding of its last row, and decode ticks."""
    from repro.serving.engine import Request
    engine.submit(Request(rid=-1, prompt=np.arange(2, 19, dtype=np.int32)
                          % vocab, max_new=3))
    while engine.scheduler.has_work():
        engine.step()
    engine.reset_stats()


# ------------------------------------------------------------------- loop --
def served_count(seq, prompt_len: int) -> int:
    """Tokens served so far to an active sequence (a preempted request
    carries its earlier tokens in its extended prompt)."""
    return len(seq.req.prompt) - prompt_len + len(seq.generated)


def run_window(engine, traffic, clients: int, seconds: float,
               trace_dir: Optional[str] = None):
    """Drive the closed loop: first until half as many requests as there
    are clients have finished (the first requests are cut to what would
    remain of them, bench/traffic.py), so the window opens on a loop in
    its steady state; then for ``seconds``. Returns the Window and the
    indices of the window's steps that ran under the profiler."""
    import jax
    from repro.serving.engine import Request
    from bench.stats import Window

    w = Window()
    next_k = [0] * clients
    profiled: List[int] = []
    tracing = False
    rid = [0]

    def send(c: int) -> None:
        with jax.profiler.TraceAnnotation("bench.client"):
            prompt, max_new = traffic.request(c, next_k[c])
            r = rid[0]
            rid[0] += 1
            w.origin[r] = (c, next_k[c])
            next_k[c] += 1
            w.sent[r] = time.monotonic()
            w.prompt_len[r] = len(prompt)
            w.max_new[r] = max_new
            engine.submit(Request(rid=r, prompt=prompt, max_new=max_new))

    for c in range(clients):
        send(c)
    turned = 0
    while turned < (clients + 1) // 2:
        with jax.profiler.TraceAnnotation("bench.step"):
            done = engine.step()
        w.pre_steps += 1
        turned += len(done)
        for r in done:
            engine._outputs.pop(r)
            send(w.origin[r][0])
    w.pre_done = turned
    for seq in engine.scheduler.active.values():
        r = seq.req.rid
        w.before[r] = served_count(seq, w.prompt_len[r])
    for req in engine.scheduler.queue:      # preempted: tokens in the prompt
        w.before[req.rid] = len(req.prompt) - w.prompt_len[req.rid]
    w.t_open = time.monotonic()
    t_end = w.t_open + seconds
    t_trace = w.t_open + TRACE_AT * seconds
    while True:
        now = time.monotonic()
        if now >= t_end and not tracing:
            break
        if trace_dir and not tracing and not profiled and now >= t_trace:
            jax.profiler.start_trace(trace_dir)
            tracing, t_traced = True, now
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.step"):
            done = engine.step()
        t1 = time.monotonic()
        w.steps.append((t0, t1))
        if tracing:
            profiled.append(len(w.steps) - 1)
        for seq in engine.scheduler.active.values():
            r = seq.req.rid
            n = served_count(seq, w.prompt_len[r])
            if n:
                w.stamp(r, n, t1)
        for r in done:
            out = engine._outputs.pop(r)
            w.finished[r] = out[w.prompt_len[r]:]
            w.stamp(r, len(out) - w.prompt_len[r], t1)
        for r in done:
            send(w.origin[r][0])
        if tracing and t1 - t_traced >= min(TRACE_SECONDS, seconds / 3):
            jax.profiler.stop_trace()
            tracing = False
    w.t_close = time.monotonic()
    return w, profiled


# ------------------------------------------------------------ tick record --
@dataclasses.dataclass
class Tick:
    kind: str
    step: int              # index into Window.steps
    measured_s: float
    contexts: List[int]    # decode: keys each sequence attends over
    span: tuple            # chunk: (start, end) real prompt rows


def ticks_of(engine, w) -> List[Tick]:
    """The engine's tick events of the window with the work each did:
    per decode tick the context of every sequence in it, per chunk tick
    the real rows it advanced. Ticks before the open are read for the
    contexts and left out."""
    tel = engine.telemetry
    firsts = {r: sorted(e.t for e in s.events if e.kind == "first_token")
              for r, s in tel.spans.items()}
    chunks = {r: [(e.attrs["start"], e.attrs["end"]) for e in s.events
                  if e.kind == "chunk"] for r, s in tel.spans.items()}
    decoded: Dict[int, int] = {}
    chunk_i: Dict[int, int] = {}
    out = []
    for ev in tel.ticks:
        step = ev.step - 1 - w.pre_steps
        if ev.kind == "decode":
            ctx = []
            for r in ev.rids:
                served = decoded.get(r, 0) + sum(
                    t < ev.t_start for t in firsts.get(r, ()))
                decoded[r] = decoded.get(r, 0) + 1
                ctx.append(w.prompt_len[r] + served)
            out.append(Tick("decode", step, ev.measured_s, ctx, ()))
        elif ev.kind == "chunk":
            r = ev.rids[0]
            i = chunk_i.get(r, 0)
            chunk_i[r] = i + 1
            out.append(Tick("chunk", step, ev.measured_s, [], chunks[r][i]))
        else:
            out.append(Tick(ev.kind, step, ev.measured_s, [], ()))
    return [t for t in out if t.step >= 0]


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader sees."""
    model: dict
    family: ModuleType       # the model family's counts
    peaks: dict
    chips: int
    window: object
    ticks: List[Tick]
    profiled: set            # indices of the steps run under the profiler
    trace: object            # xplane.Trace or None

    def unprofiled(self, kind: str) -> List[Tick]:
        return [t for t in self.ticks
                if t.kind == kind and t.step not in self.profiled]

    def profiled_ticks(self, kind: str) -> List[Tick]:
        return [t for t in self.ticks
                if t.kind == kind and t.step in self.profiled]


# ------------------------------------------------------------ correctness --
def sample(window, seed: int) -> List[int]:
    """A sample of the finished requests drawn from the seed, with the
    one that was served most tokens in it."""
    fin = window.finished
    if not fin:
        return []
    order = sorted(fin)
    longest = max(order, key=lambda r: (len(fin[r]), -r))
    rest = [r for r in order if r != longest]
    rng = np.random.default_rng([seed % 2**63, seed >> 63, 3])
    rng.shuffle(rest)
    pick, served, toks = [longest], len(fin[longest]), 0
    toks = window.prompt_len[longest] + len(fin[longest])
    for r in rest:
        if served >= SAMPLE_SERVED or len(pick) >= SAMPLE_MAX_REQUESTS:
            break
        n = window.prompt_len[r] + len(fin[r])
        if toks + n > SAMPLE_MAX_TOKENS:
            continue
        pick.append(r)
        served += len(fin[r])
        toks += n
    return pick


def check(cell: Cell, seed: int, window, traffic, log,
          control: bool = False) -> Dict[str, dict]:
    """Numbers compared, each with its limit. ``control`` adds the fp8
    control's reading on the same sample (bench/control.py; the
    benchmark's own runs never compute it)."""
    from bench.reference import served_gaps
    m = cell.config["model"]
    limit = cell.config["check"]["max_logit_gap"]
    wrong = 0
    for r, served in window.finished.items():
        if len(served) != window.max_new[r] or np.any(served < 0) \
                or np.any(served >= m["vocab_size"]):
            wrong += 1
    rids = sample(window, seed)
    reqs = []
    for r in rids:
        prompt, _ = traffic.request(*window.origin[r])
        reqs.append((prompt, window.finished[r].astype(np.int32)))
    t = time.monotonic()
    gaps, ctl = (served_gaps(cell.family, m, seed, reqs, control=control)
                 if reqs else (np.asarray([np.inf]), None))
    n_served = sum(len(s) for _, s in reqs)
    log(f"reference: {len(reqs)} requests, {n_served} served tokens, "
        f"{sum(len(p) for p, _ in reqs)} prompt tokens, "
        f"{time.monotonic() - t:.2f}s")
    out = {"max_logit_gap": {"value": float(np.max(gaps)), "limit": limit},
           "wrong_length_requests": {"value": wrong, "limit": 0},
           "served_tokens_compared": {"value": n_served,
                                      "limit": min(SAMPLE_SERVED, 64)}}
    if ctl is not None:
        out["control_max_logit_gap"] = {"value": float(np.max(ctl)),
                                        "limit": limit}
    return out


def passed(checks: Dict[str, dict]) -> bool:
    c = checks
    gap = c["max_logit_gap"]
    return (gap["limit"] is not None and gap["value"] <= gap["limit"]
            and c["wrong_length_requests"]["value"] == 0
            and c["served_tokens_compared"]["value"]
            >= c["served_tokens_compared"]["limit"]
            and c.get("compiles_in_window", {"value": 0})["value"] == 0)


# ------------------------------------------------------------------- main --
_COMPILES: Optional[List[float]] = None


def _compile_times() -> List[float]:
    """Times at which JAX compiled (or loaded from its cache) a program,
    from one listener registered once per process."""
    global _COMPILES
    if _COMPILES is None:
        import jax
        _COMPILES = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda ev, dur, **kw: _COMPILES.append(time.monotonic())
            if ev == "/jax/core/compile/backend_compile_duration" else None)
    return _COMPILES


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def device_info(devices, chips: int) -> dict:
    peak = 0
    for d in devices[:chips]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def free(engine) -> None:
    """Delete the program's device state before the reference runs."""
    import jax
    for x in jax.tree.leaves((engine.params, engine.kv.pool)):
        x.delete()


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, *, root: Path = ROOT, require_tpu: bool = True,
        hw=None, trace_dir: Optional[str] = None, cache: bool = True,
        control: bool = False) -> dict:
    """One run of one cell. Returns the result line's object."""
    import jax
    from bench.traffic import ClosedLoopTraffic
    from bench import stats, xplane

    cell = load_cell(workload, root)
    devices = check_devices(cell.chips, require_tpu)
    peaks = load_peaks(devices[0].device_kind) if require_tpu else \
        json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]
    cache = enable_cache() if cache else "off"
    compiles = _compile_times()
    compiles.clear()
    log(f"{cell.name}: {cell.config_name} x {cell.mix_name} on "
        f"{len(devices)} {devices[0].device_kind}; compile cache {cache}")
    engine, policy = build(cell, seed, devices, log, hw)
    m = cell.config["model"]
    warm_up(engine, m["vocab_size"])
    clients = cell.mix["clients"]
    clients = policy.max_batch if clients == "max_batch" else int(clients)
    traffic = ClosedLoopTraffic(cell.mix, clients, m["vocab_size"], seed)
    t_loop = time.monotonic()
    log(f"engine warm {t_loop - t_start:.2f}s; {clients} clients, "
        f"window {seconds}s")

    tdir = None
    if trace:
        tdir = trace_dir or str(root / "chiprun_out" / "bench_trace"
                                / f"{workload}-{seed}")
        shutil.rmtree(tdir, ignore_errors=True)
    w, profiled = run_window(engine, traffic, clients, seconds, tdir)
    setup_s = w.t_open - t_start
    log(f"set-up {setup_s:.2f}s, of which the loop's run-in "
        f"{w.t_open - t_loop:.2f}s ({w.pre_steps} steps, {w.pre_done} "
        f"requests finished, {len(w.before)} in flight at the open)")
    in_window = sum(w.t_open <= t <= w.t_close for t in compiles)
    dev = device_info(devices, cell.chips)
    ticks = ticks_of(engine, w)
    e2e = stats.end_to_end(w)
    log(f"window {w.seconds:.2f}s: {len(w.steps)} steps, "
        f"{len(w.sent) - len(w.before) - w.pre_done} requests sent, "
        f"{len(stats.ttft_s(w))} first tokens, {len(w.finished)} finished, {stats.tokens(w)} "
        f"tokens, {engine.stats['preemptions']} preemptions, "
        f"{in_window} compiles inside the window")
    free(engine)
    del engine

    result = {"correct": False, "attempted": len(w.sent) - w.pre_done,
              "failed": 0,
              "metrics": {}, "device": dev}
    if trace:
        tr = None
        path = xplane.newest(tdir) if tdir else None
        if path:
            tr = xplane.reduce(path)
        ctx = Context(m, cell.family, peaks, cell.chips, w, ticks,
                      set(profiled), tr)
        for spec in cell.per_layer:
            v = load_reader(spec["name"], root)(ctx)
            if isinstance(v, tuple):
                v, note = v
                log(f"{spec['name']}: {note}")
            if v is not None:
                result["metrics"][spec["name"]] = {"value": v,
                                                   "unit": spec["unit"]}
        if tr is not None:
            dev["busy_s"] = tr.busy_s
            dev["window_s"] = tr.window_s
            result["breakdown"] = {"device_ops": tr.device_ops(),
                                   "idle_gaps": tr.idle_gaps()}
    else:
        vals = dict(e2e, setup_s=setup_s)
        for spec in cell.end_to_end:
            v = vals.get(spec["name"])
            if v is not None:
                result["metrics"][spec["name"]] = {"value": v,
                                                   "unit": spec["unit"]}
    checks = check(cell, seed, w, traffic, log, control)
    checks["compiles_in_window"] = {"value": in_window, "limit": 0}
    result["failed"] = checks["wrong_length_requests"]["value"]
    result["correct"] = passed(checks)
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    return result


def main(argv=None, t_start: Optional[float] = None) -> None:
    import argparse
    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    if a.seed < 0:
        fail("--seed must be a whole number >= 0")
    result = run(a.workload, a.seed, a.seconds, bool(a.trace), t_start)
    print(json.dumps(result), flush=True)
