"""The serving engine: ties model, paged pool, and scheduler into a host
loop of interleaved prefill and decode ticks.

One ``step()``:
  1. admission — backfill free batch slots from the FIFO queue (page-
     and slot-gated, see scheduler.py). Admitted sequences owe their
     prompt to the pool; nothing runs yet;
  2. chunked prefill — every mid-prefill sequence advances by at most ONE
     ``policy.prefill_chunk``-token chunk (prefill-with-cache forward:
     the chunk's K/V are written into the sequence's pages and its
     attention walks the pool — resident prefix + chunk). The final chunk
     unembeds the last real prompt row and samples the first token; until
     then the sequence stays out of the decode batch, so one long prompt
     costs many bounded ticks instead of one decode-stalling bucket;
  3. growth — every decode-ready sequence whose position crosses a page
     boundary grows by one page; on pool exhaustion the youngest active
     sequence is preempted (freed + requeued as a prompt-extension; a
     mid-prefill victim simply restarts its prompt at re-admission) to
     make room, oldest-first so the head of the line always drains;
  4. decode tick — one batched ``decode_step_paged`` over the surviving
     prefill-complete slots (idle slots ride along against the scratch
     page and are ignored). The decode path walks pages with the Pallas
     paged-attention kernel (pure-JAX block walk off-TPU) — no dense
     chronological KV view is ever materialized;
  5. eviction — finished sequences free their pages/slot immediately, so
     the next step's admission backfills mid-flight.

The decode closure is jitted ONCE per engine (fixed shapes: the policy's
max_batch and page-table width), and so is the chunk-prefill closure
(fixed (1, chunk) tokens against the full-width page table, pool donated),
so every prompt length runs the same two executables. When the policy's
memory roofline demanded it, weights are HAQ-quantized (serving/quant.py)
and the dequantizing ``dot`` is threaded through both paths.
``policy.kv_bits`` additionally selects the HAQ KV-quantized pool
(serving/kvquant): pages stored int8/int4 with per-token per-head scales,
quantize-on-write in both writers (the chunk forward and the decode
scatter), fused dequant inside the paged-attention walk. On
all-local-attention models, pages wholly behind the sliding window are
released back to the allocator each tick (scheduler.trim_window).

Observability (serving/telemetry): every jitted dispatch — prompt chunk
or batched decode — emits a typed ``TickEvent``
into the engine's ``Telemetry`` recorder, carrying the *measured* wall
clock (fenced: the engine blocks on the dispatch's outputs before the
timer stops, so async jit dispatch is never billed as compute) next to
the ``admission.step_latency`` roofline *prediction* for the same
dispatch shape; request lifecycles (enqueue/admit/chunk/first_token/
preempt/requeue/finish/release) are recorded as per-rid spans, half by
the scheduler and half by this loop. ``stall_log`` (measured per-decode-
tick prefill stall seconds — the quantity ``prefill_stall_factor``
budgets, with the roofline's predicted stall recorded alongside in
``telemetry.stalls``) and ``first_token_s`` (per-request TTFT) survive
as thin views over that record; both feed the long-prompt section of
benchmarks/bench_engine_throughput.py, and ``telemetry.calibrate``
turns the tick trace into per-kind roofline scale factors. Each phase of
``step()`` (admission, prompt chunk, decode growth / input prep /
dispatch / fence / sampling, finish, gauges) runs inside a named
``telemetry.span`` — the tree is in serving/engine/__init__.py — so a
profiler trace puts every device idle gap down to a host phase.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.transformer import (PAGED_FAMILIES, has_state,
                                      normalize_kv_bits, sublayer_kinds)
from repro.serving.engine.admission import AdmissionPolicy, \
    RooflinePredictor
from repro.serving.engine.pool import PagedKVPool, quiet_donation
from repro.serving.engine.scheduler import ActiveSeq, Request, Scheduler
from repro.serving.telemetry import Telemetry, TickEvent
from repro.serving import quant as squant


def sample_token(logits_row, temperature: float, key) -> int:
    """One token from a (V,) f32 logits row (host array on the greedy path;
    np.argmax ties break first-max)."""
    if temperature <= 0.0 or key is None:
        return int(np.argmax(logits_row))
    return int(jax.random.categorical(key, jnp.asarray(logits_row)
                                      / temperature))


class Engine:
    def __init__(self, model, params, policy: AdmissionPolicy, *,
                 temperature: float = 0.0, seed: int = 0, dot=None,
                 paged_kernel: str = "auto", reserve_upfront: bool = False,
                 mesh=None, telemetry: Optional[Telemetry] = None,
                 roofline_scales=None):
        cfg = model.cfg
        if cfg.is_encdec or cfg.family not in PAGED_FAMILIES \
                or cfg.family == "vlm" or cfg.frontend != "none":
            raise NotImplementedError(
                f"engine serves decoder-only attention-cache LMs; "
                f"{cfg.name} (family={cfg.family!r}, "
                f"frontend={cfg.frontend!r}) is an open item (ROADMAP)")
        # Mamba layers keep per-sequence state in slot rows beside the
        # pages (batch slot s in row s, idle decode rows in the scratch row
        # max_batch)
        self._stateful = has_state(cfg)
        if self._stateful and (mesh is not None or policy.kv_bits is not None):
            raise NotImplementedError(
                f"{cfg.name} keeps recurrent state: served on one device "
                f"with a bf16 K/V pool only")
        self.model = model
        self.policy = policy
        self.temperature = temperature
        self._key = jax.random.PRNGKey(seed) if temperature > 0 else None
        # telemetry recorder (serving/telemetry): tick trace, sequence
        # spans, metrics. The default instance records in memory with a
        # no-op sink — cheap enough to leave on; pass your own Telemetry
        # (custom sink / clock) to stream or capture events.
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        # roofline predictions per dispatch shape, memoized (telemetry
        # pairs them with measured wall clock on every tick event). Pass
        # ``roofline_scales`` (a telemetry.ScaleLookup fitted on THIS host
        # by telemetry.calibrate) to emit host-corrected predictions —
        # what the autotuner's validation engines do, so their traces
        # report calibrated rel_err instead of the raw roofline's.
        self._predict = RooflinePredictor(cfg, policy,
                                          scales=roofline_scales)

        if mesh is not None and policy.quant_bits < 16:
            raise NotImplementedError(
                "sharded engine with HAQ weight quantization: quantized "
                "weight dicts have no logical specs yet (ROADMAP); use "
                "kv_bits for sharded memory savings")
        if policy.quant_bits < 16:
            params = squant.quantize_params(
                params, default_bits=policy.quant_bits)
            assert dot is None, "quant policy supplies its own dot hook"
            dot = squant.dequant_dot
        self.params = params

        # Allocate only the pages max_batch concurrent sequences can use,
        # capped by what the target's HBM holds (policy.num_pages) and
        # floored at one full-length sequence plus scratch — the growth
        # loop's guarantee that a lone sequence can always reach
        # max_model_len without preempting itself.
        needed = policy.max_batch * policy.pages_per_seq + 1
        num_pages = max(min(policy.num_pages, needed),
                        policy.pages_per_seq + 1)
        self.kv_bits = normalize_kv_bits(cfg, policy.kv_bits)
        # SPMD serving (serving/engine/sharded.py): params and the paged
        # pool sharded over the mesh, decode/chunk jits shard_map'd, the
        # host-side scheduler/page-table state untouched.
        self.mesh = mesh
        spmd = None
        if mesh is not None:
            from repro.serving.engine import sharded
            spmd = sharded.SpmdEngine(model, mesh, kv_bits=self.kv_bits,
                                      kernel=paged_kernel, dot=dot)
            params = self.params = spmd.shard_params(params)
        self.kv = PagedKVPool(
            model, num_pages, policy.page_size, kv_bits=self.kv_bits,
            spmd=spmd, state_slots=policy.max_batch + 1 if self._stateful
            else 0)
        self.scheduler = Scheduler(self.kv.allocator, policy.max_batch,
                                   policy.max_model_len,
                                   reserve_upfront=reserve_upfront,
                                   telemetry=self.telemetry)
        # mesh tags stamped on every tick event (engine/sharded.py)
        self._tags = spmd.event_tags() if spmd is not None else {}
        # Window-trim page freeing (ROADMAP): pages are shared across
        # layers, so blocks behind the sliding window can only be released
        # when EVERY layer is local — one global layer pins the history.
        # Off under reserve_upfront (the legacy worst-case baseline keeps
        # its reservations untouched).
        kinds = sublayer_kinds(cfg)
        self._trim_window = cfg.window_size if (
            not reserve_upfront and kinds
            and all(k["attn"] == "local" for k in kinds)) else None

        # jit once: fixed (max_batch, pages_per_seq) shapes for decode and
        # (1, prefill_chunk) for a prompt chunk. The pool is donated so
        # ticks update it in place instead of double-buffering it. Under a
        # mesh every closure is the shard_map'd twin with the identical
        # signature, so the host loop never branches.
        if spmd is None and self._stateful:
            # the state slot of each row, and the chunk's real tokens
            self._decode = jax.jit(
                lambda p, pool, pt, tok, pos, rows: model.decode_step_paged(
                    p, pool, pt, tok, pos, rows=rows, dot=dot,
                    kernel=paged_kernel),
                donate_argnums=(1,))
            self._chunk_prefill = jax.jit(
                lambda p, pool, pt, toks, pos, rows, n:
                model.prefill_chunk_paged(p, pool, pt, toks, pos, rows=rows,
                                          lengths=n, dot=dot,
                                          kernel=paged_kernel),
                donate_argnums=(1,))
        elif spmd is None:
            self._decode = jax.jit(
                lambda p, pool, pt, tok, pos: model.decode_step_paged(
                    p, pool, pt, tok, pos, dot=dot, kernel=paged_kernel),
                donate_argnums=(1,))
            self._chunk_prefill = jax.jit(
                lambda p, pool, pt, toks, pos: model.prefill_chunk_paged(
                    p, pool, pt, toks, pos, dot=dot, kernel=paged_kernel),
                donate_argnums=(1,))
        if spmd is None:
            self._unembed_row = jax.jit(
                lambda p, h, idx: model.unembed(
                    p, jnp.take_along_axis(h, idx.reshape(1, 1, 1), axis=1),
                    dot=dot))
        else:
            self._decode = spmd.jit_decode()
            self._chunk_prefill = spmd.jit_prefill_chunk()
            self._unembed_row = spmd.jit_unembed_row()
        self.stats = {"decode_ticks": 0, "decode_tokens": 0,
                      "prefills": 0, "prefill_chunks": 0, "admitted": 0,
                      "preemptions": 0, "grown_pages": 0,
                      "trimmed_pages": 0}
        self._outputs: Dict[int, np.ndarray] = {}
        # per-step telemetry bookkeeping: step index, admissions this
        # step, and the marks tick events difference page/preemption
        # counters against (each event reports deltas since the previous
        # event, so admission-time allocations land on the step's first
        # tick and growth/preempt frees on the decode tick that caused
        # them).
        self._step_idx = 0
        self._step_admitted = 0
        self._alloc_mark = self._free_mark = 0
        self._trim_mark = self._preempt_mark = 0

    # --------------------------------------------------- telemetry views --
    @property
    def stall_log(self) -> List[float]:
        """Measured per-decode-tick prefill stall seconds — the exact
        pre-telemetry list, as a view over ``telemetry.stalls`` (each
        record also carries the roofline's *predicted* stall for the
        same chunks; this view is measurement only)."""
        return self.telemetry.stall_log_view()

    @property
    def first_token_s(self) -> Dict[int, float]:
        """rid -> time-to-first-token seconds (trace clock), as a view
        over the telemetry spans; a preempted request keeps the
        timestamp of the first token it was actually served."""
        return self.telemetry.first_token_view()

    # ------------------------------------------------------------- intake --
    def submit(self, req: Request) -> None:
        self.scheduler.submit(req)

    def reset_stats(self) -> None:
        """Zero the counters, telemetry, and held outputs (benchmarks
        re-time a warmed engine instance so jit compiles stay out of the
        clock). Allocator lifetime counters are not zeroed (pool state
        persists) — the delta marks re-anchor on them instead, and the
        free-page low-water mark restarts at the current free count."""
        for k in self.stats:
            self.stats[k] = 0
        self.scheduler.num_preempted = 0
        self._outputs.clear()
        self.telemetry.reset()
        self._step_idx = 0
        self._step_admitted = 0
        alloc = self.kv.allocator
        self._alloc_mark = alloc.total_allocated
        self._free_mark = alloc.total_freed
        self._trim_mark = self._preempt_mark = 0
        alloc.min_free = alloc.num_free

    # --------------------------------------------------------------- step --
    def step(self, now: float = float("inf")) -> List[int]:
        """One scheduler tick: admit, run at most ONE prompt chunk per
        mid-prefill sequence, then one batched decode over the
        prefill-complete sequences. Returns the rids that finished during
        this step. Finished sequences are released the moment they finish
        — before the decode tick's growth phase — so their pages backfill
        growth instead of tempting the preemption picker."""
        with self.telemetry.span("engine.step"):
            self.telemetry.start_clock()
            self._step_idx += 1
            self._step_admitted = 0
            out: List[int] = []
            ready_before = len(self.scheduler.decode_ready())
            stall_pred = 0.0
            t_prefill = time.monotonic()
            with self.telemetry.span("engine.admit"):
                for seq in self.scheduler.admit(now):
                    self.stats["admitted"] += 1
                    self._step_admitted += 1
            for seq in self.scheduler.prefill_pending():
                stall_pred += self._run_prefill_chunk(seq)
                if seq.prefill_done and seq.is_done():
                    out.append(self._finish(seq))
            t_prefill = time.monotonic() - t_prefill
            live = self.scheduler.decode_ready()
            if live:
                finished: List[ActiveSeq] = []
                ticks_before = self.stats["decode_ticks"]
                self._decode_tick(live, finished)
                if self.stats["decode_ticks"] > ticks_before \
                        and ready_before:
                    # per-decode-tick stall: seconds this tick's already-
                    # ready sequences waited on prefill work (0.0 when none
                    # ran) — the quantity prefill_stall_factor budgets per
                    # tick, recorded next to the roofline's prediction for
                    # the same prefill work so calibration sees both sides.
                    self.telemetry.stall(t_prefill, stall_pred)
                for seq in finished:
                    out.append(self._finish(seq))
            self._update_gauges()
        return out

    # ---------------------------------------------------- telemetry emit --
    def _tick_deltas(self) -> Dict[str, int]:
        """Page/preemption deltas since the previous tick event (marks
        advance here, so each event owns exactly its own deltas)."""
        a = self.kv.allocator
        trimmed = self.stats["trimmed_pages"]
        preempted = self.scheduler.num_preempted
        d = {"pages_allocated": a.total_allocated - self._alloc_mark,
             "pages_freed": a.total_freed - self._free_mark,
             "pages_trimmed": trimmed - self._trim_mark,
             "preempted": preempted - self._preempt_mark}
        self._alloc_mark = a.total_allocated
        self._free_mark = a.total_freed
        self._trim_mark = trimmed
        self._preempt_mark = preempted
        return d

    def _emit_tick(self, kind: str, t_start: float, measured_s: float,
                   predicted_s: float, *, batch: int, padded_batch: int,
                   q_len: int, tokens: int, rids) -> None:
        a = self.kv.allocator
        self.telemetry.tick(TickEvent(
            kind=kind, step=self._step_idx, t_start=t_start,
            measured_s=measured_s, predicted_s=predicted_s, batch=batch,
            padded_batch=padded_batch, q_len=q_len, tokens=tokens,
            rids=tuple(rids), admitted=self._step_admitted,
            queue_depth=self.scheduler.num_queued, pool_free=a.num_free,
            pool_allocated=a.num_allocated, tags=self._tags,
            **self._tick_deltas()))

    def _update_gauges(self) -> None:
        """Per-step gauges that aren't per-tick deltas: pool occupancy
        (token-granular — allocated pages may be mostly empty while
        sequences are young; fragmentation is 1 - occupancy) and the
        jitted closures' cache sizes (steady-state serving must not
        retrace). O(active sequences)."""
        with self.telemetry.span("engine.gauges"):
            m = self.telemetry.metrics
            a = self.kv.allocator
            page = a.page_size
            used = 0
            for seq in self.scheduler.active.values():
                live_pages = len(seq.pages) - seq.trimmed
                used += max(min(seq.pos - seq.trimmed * page,
                                live_pages * page), 0)
            cap = a.num_allocated * page
            m.gauge("pool.occupancy").set(used / cap if cap else 0.0)
            m.gauge("pool.min_free").set(a.min_free)
            # the once-jitted closures: retrace count straight from jax (a
            # steady-state engine holds these at 1)
            for name, fn in (("decode", self._decode),
                             ("chunk", self._chunk_prefill)):
                size = getattr(fn, "_cache_size", lambda: -1)()
                m.gauge(f"jit.{name}.cache_size").set(size)

    def _finish(self, seq: ActiveSeq) -> int:
        with self.telemetry.span("engine.finish"):
            self.telemetry.seq_event(seq.req.rid, "finish",
                                     generated=len(seq.generated))
            self.scheduler.release(seq)
            self._outputs[seq.req.rid] = np.concatenate(
                [np.asarray(seq.req.prompt, np.int32),
                 np.asarray(seq.generated, np.int32)])
        return seq.req.rid

    def _first_token(self, seq: ActiveSeq, logits_row) -> None:
        """Sample the prompt's first generated token (prefill just
        finished) and stamp the request's time-to-first-token."""
        tok = sample_token(logits_row, self.temperature,
                           self._step_key(seq))
        seq.generated.append(tok)
        seq.pos = len(seq.req.prompt)
        self.stats["prefills"] += 1
        # a preempted sequence re-prefills its prompt-extension later and
        # emits another first_token edge, but TTFT views take the FIRST
        # edge — the request's first token was already served.
        self.telemetry.seq_event(seq.req.rid, "first_token", token=tok)

    def _run_prefill_chunk(self, seq: ActiveSeq) -> float:
        """One prompt chunk through the prefill-with-cache forward: the
        chunk's K/V land in the sequence's pages and its attention walks
        the pool (resident prefix + chunk). The final chunk unembeds the
        last real prompt row and samples the first generated token; until
        then the sequence stays out of the decode batch. Returns the
        roofline's predicted seconds for the chunk (the step's stall
        budget accumulates these)."""
        span = self.telemetry.span
        with span("engine.chunk"):
            with span("engine.chunk.prepare"):
                prompt = np.asarray(seq.req.prompt, np.int32)
                S = len(prompt)
                C = self.policy.prefill_chunk
                start = seq.prefill_progress
                end = min(start + C, S)
                toks = np.zeros((1, C), np.int32)
                toks[0, :end - start] = prompt[start:end]
                maxp = self.policy.pages_per_seq
                pt = np.zeros((1, maxp), np.int32)
                pt[0, :len(seq.pages)] = seq.pages
                state = (jnp.asarray([seq.slot], jnp.int32),
                         jnp.asarray([end - start], jnp.int32)
                         ) if self._stateful else ()
            t_start = time.monotonic()
            with span("engine.chunk.dispatch"), quiet_donation():
                hidden, self.kv.pool = self._chunk_prefill(
                    self.params, self.kv.pool, jnp.asarray(pt),
                    jnp.asarray(toks), jnp.asarray([start], jnp.int32),
                    *state)
            # sync before the step's stall timer stops: dispatch is async,
            # and an unblocked intermediate chunk would bill its compute to
            # the decode tick instead of the stall it actually causes.
            with span("engine.chunk.fence"):
                jax.block_until_ready(hidden)
            pred = self._predict("chunk", 1, C)
            self._emit_tick("chunk", t_start, time.monotonic() - t_start,
                            pred, batch=1, padded_batch=1, q_len=C,
                            tokens=end - start, rids=(seq.req.rid,))
            self.telemetry.seq_event(seq.req.rid, "chunk", start=start,
                                     end=end)
            seq.prefill_progress = end
            seq.pos = end
            self.stats["prefill_chunks"] += 1
            if end == S:
                with span("engine.chunk.first_token"):
                    logits = self._unembed_row(
                        self.params, hidden,
                        jnp.asarray(S - 1 - start, jnp.int32))
                    self._first_token(seq, np.asarray(logits[0, 0]))
        return pred

    def _is_live(self, seq: ActiveSeq) -> bool:
        return self.scheduler.active.get(seq.slot) is seq

    def _decode_tick(self, live: List[ActiveSeq],
                     finished: List[ActiveSeq]) -> None:
        span = self.telemetry.span
        with span("engine.decode"):
            with span("engine.decode.grow"):
                ready = self._grow(live)
            if not ready:
                return

            B = self.policy.max_batch
            with span("engine.decode.prepare"):
                maxp = self.policy.pages_per_seq
                tokens = np.zeros((B, 1), np.int32)
                # idle slots ride along against the scratch page; they
                # carry the minimum live position (not 0) so the block
                # walk's batch-wide window-trim bound stays tight for
                # local-attention layers.
                positions = np.full((B,), min(s.pos for s in ready),
                                    np.int32)
                pt = np.zeros((B, maxp), np.int32)       # 0 -> scratch page
                rows = np.full((B,), B, np.int32)        # B -> scratch row
                for seq in ready:
                    tokens[seq.slot, 0] = seq.last_token
                    positions[seq.slot] = seq.pos
                    pt[seq.slot, :len(seq.pages)] = seq.pages
                    rows[seq.slot] = seq.slot
                state = (jnp.asarray(rows),) if self._stateful else ()
            t_start = time.monotonic()
            with span("engine.decode.dispatch"), quiet_donation():
                logits, self.kv.pool = self._decode(
                    self.params, self.kv.pool, jnp.asarray(pt),
                    jnp.asarray(tokens), jnp.asarray(positions), *state)
            # fence before the host transfer so the tick's measured
            # duration is dispatch + compute, not whenever the async
            # stream drains
            with span("engine.decode.fence"):
                jax.block_until_ready(logits)
            measured = time.monotonic() - t_start
            self.stats["decode_ticks"] += 1
            # prediction priced at the PADDED jit batch — idle slots ride
            # along in the fixed-shape dispatch, so B is what actually runs
            self._emit_tick("decode", t_start, measured,
                            self._predict("decode", B, 1), batch=len(ready),
                            padded_batch=B, q_len=1, tokens=len(ready),
                            rids=(s.req.rid for s in ready))
            with span("engine.decode.sample"):
                rows = np.asarray(logits[:, 0])  # one host transfer per tick
                for seq in ready:
                    tok = sample_token(rows[seq.slot], self.temperature,
                                       self._step_key(seq))
                    seq.generated.append(tok)
                    seq.pos += 1
                    self.stats["decode_tokens"] += 1
                    if seq.is_done():
                        finished.append(seq)

    def _grow(self, live: List[ActiveSeq]) -> List[ActiveSeq]:
        """Growth phase, oldest first: crossing a page boundary claims a
        new page; exhaustion preempts the youngest active sequence — the
        grower itself, if it is the youngest, so pages only ever flow from
        younger to older and the FIFO head keeps draining. Returns the
        sequences still live, oldest first; victims ride the queue back in
        on a later step."""
        live = sorted(live, key=lambda s: s.birth)
        for seq in live:
            if not self._is_live(seq):
                continue                    # preempted earlier this tick
            if self._trim_window:
                # release blocks wholly behind the sliding window before
                # asking for growth — trimmed pages backfill the pool the
                # same tick they die, shrinking the preemption pressure.
                self.stats["trimmed_pages"] += self.scheduler.trim_window(
                    seq, self._trim_window)
            before = len(seq.pages)
            while not self.scheduler.ensure_capacity(seq):
                victim = self.scheduler.youngest_active()
                if victim is seq and self.scheduler.num_active == 1:
                    raise RuntimeError(
                        "page pool smaller than one max-length sequence")
                self.scheduler.preempt(victim)
                if victim is seq:
                    break                   # yielded to older sequences
            if self._is_live(seq):
                self.stats["grown_pages"] += len(seq.pages) - before
        self.stats["preemptions"] = self.scheduler.num_preempted
        return [s for s in live if self._is_live(s)]

    def _step_key(self, seq: ActiveSeq):
        if self._key is None:
            return None
        k = jax.random.fold_in(self._key, seq.req.rid)
        return jax.random.fold_in(k, len(seq.generated))

    # ---------------------------------------------------------------- run --
    def run(self, requests: List[Request], *,
            realtime: bool = False) -> Dict[int, np.ndarray]:
        """Serve a trace to completion. With ``realtime=True`` requests are
        admitted no earlier than their ``arrival`` offset (wall clock);
        otherwise arrivals are ignored (burst)."""
        for r in requests:
            self.submit(r)
        t0 = time.monotonic()
        while self.scheduler.has_work():
            now = (time.monotonic() - t0) if realtime else float("inf")
            if not self.step(now) and not self.scheduler.active:
                time.sleep(1e-4)             # waiting on future arrivals
        return {r.rid: self._outputs[r.rid] for r in requests}
