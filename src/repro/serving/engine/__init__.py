"""Continuous-batching serving engine with a paged KV-cache pool.

This is the deployed counterpart of the paper's hardware-in-the-loop search:
the same roofline simulator (`core/hardware_model.py`) that scores NAS/HAQ
candidates at *search* time sizes the runtime at *serve* time — KV pool
capacity from the target's HBM, max in-flight batch from the decode-latency
roofline, the prompt chunk from the prefill roofline, and a HAQ bit
policy (via `serving/quant.py`) when the memory roofline demands it.

Page-table layout
-----------------
The KV cache is a pool of fixed-size **pages** preallocated once per layer::

    pool["sub{j}"]["k"|"v"] : (n_groups, num_pages, K, page_size, hd) bf16

``num_pages`` and ``page_size`` are shared by every layer: a single logical
page allocation covers all layers, so the allocator hands out one list of
physical page ids per request and the per-layer pools index it identically
(vLLM's layout, transposed into the repo's scan-stacked group convention).
Within a page the kv heads are major: a page's (K, page_size, hd) tile is
contiguous, which the TPU decode kernel copies whole, one DMA per page,
and one head's (page_size, hd) tile within it is the block the prefill
kernel tiles (models/transformer.py::pool_specs is the one definition of
the layout).

Pages are the unit of **memory and compute**. Allocation is dynamic: a
request is admitted with only the pages its prompt (plus the first decode
slot) needs, then grows page-by-page as decode crosses block boundaries.
On pool exhaustion the youngest active sequence is preempted — its pages
are freed and it is requeued at the FIFO front with its generated tokens
folded into the prompt, so its next admission re-prefills the extension
(recompute). Freed pages are recycled
without clearing: a new owner only ever reads slots at ``j <= pos`` that it
has itself written (prompt chunks, then decode writes in position order),
so stale KV from a previous owner stays behind the mask. The legacy
worst-case policy — ``ceil((prompt + max_new) / page_size)`` pages reserved
at admission, no preemption — remains available as ``reserve_upfront``.

Recurrent state beside the pages
--------------------------------
A model with Mamba-2 layers (granite-4.0-h: ``layer_types``) keeps no pages
for them. Each Mamba sub-slot of the pool holds ``max_batch + 1`` rows of
per-sequence state instead — the fp32 SSM state and the conv tail — and a
sequence's state lives in its batch slot's row; idle decode rows update
the last, scratch row. A sequence's first prompt chunk, at position 0,
starts from zero state, so a reused slot and a preempted, recomputed
sequence start clean; only a chunk's real tokens advance the state. The
state is served on one device, with a bf16 K/V pool;
admission reserves every slot's state before it sizes the pages.

Chunked-prefill lifecycle
-------------------------
A sequence's prompt enters the pool in ``policy.prefill_chunk``-token
chunks, one per engine tick (``ActiveSeq.prefill_progress`` tracks the
resident prefix). Each chunk runs the prefill-with-cache forward
(``Model.prefill_chunk_paged``): its roped K/V are scattered into the
sequence's pages — quantize-on-write on quantized pools — and its
attention walks the page table itself, reading the resident prefix plus
the chunk (causal within the chunk; kernels/paged_attention.py's
``paged_prefill_fwd`` on TPU, the pure-JAX walk elsewhere — the dense
chronological prompt KV view is never materialized, asserted on the
jaxpr). Chunk states per sequence:

    queued -> chunk-pending (admitted; 0 < prefill_progress < prompt,
              holds a batch slot, excluded from the decode batch)
           -> decode-ready (final chunk landed: the last real prompt row
              is unembedded, the first token sampled)
           -> finished / preempted (a mid-prefill victim is requeued at
              its chunk boundary and simply restarts the prompt at
              re-admission)

``prefill_stall_factor`` is therefore a **per-tick** stall budget: the
admission policy sizes ``prefill_chunk`` as the largest chunk whose
prefill-with-cache latency (priced at worst-case resident context) stays
within ``prefill_stall_factor * decode_slo_s``, so a long prompt costs
more ticks — never a longer stall of resident decodes. The chunk
program is the only way a prompt enters the pool.

The scheduler packs active sequences into a fixed-width batch; a decode
tick calls ``Model.decode_step_paged`` with:

    page_table : (B, max_pages) int32 — physical page of logical block i;
                 unused tails (and idle batch slots) point at the scratch
                 page 0, which is never allocated to a request
    positions  : (B,) int32 — per-sequence absolute position, so every slot
                 can be at a different decode depth (continuous batching)

Token ``pos`` of sequence ``b`` lives at page ``page_table[b, pos // page]``
slot ``pos % page``. Attention walks the page table block-by-block — the
Pallas paged-attention kernel (kernels/paged_attention.py) on TPU, its
pure-JAX block-walk twin (kernels/ref.py) elsewhere — with local-window
layers trimming the walk to their window; the dense chronological
(B, max_pages*page_size, K, hd) KV view is never materialized. RoPE is
applied at cache-write time with absolute positions. The walk itself is
validated against the dense oracle in tests/test_kernels.py. The engine
tests judge every served token against one teacher-forced dense
``Model.forward`` at batch 1 (no pages, no chunks): across batching,
chunk sizes, growth and preemption, each served token's logit there lies
within a stated gap of the best (``served_gaps`` in tests/conftest.py).
bfloat16 near-ties mean the engine's greedy tokens need not equal that
forward's argmax exactly. The float32 reference of the chip benchmark
(bench/reference.py) judges the numerics at published widths.

KV-cache quantization (serving/kvquant): ``AdmissionPolicy.kv_bits``
selects a HAQ-searched per-sub-layer bit policy for the pool itself —
pages stored int8/int4 (packed along head_dim) with per-page-slot per-head
fp32 scale tiles, quantize-on-write in both writers (the chunk and decode
programs), and dequantization fused into the paged-attention block walk.
``kv_bytes_per_token`` and page sizing are bit-policy-aware, so the same
HBM budget holds 2-4x the pages and admission fits correspondingly more
resident sequences; quantized drift against the fp pool is bounded and
measured (kvquant.drift).

On models whose every attention layer is local (sliding-window), pages
wholly behind the window are released back to the allocator as decode
advances (``Scheduler.trim_window``; freed slots ride along in the page
table as scratch-page placeholders the walk never reads).

SPMD serving (``Engine(mesh=...)``, serving/engine/sharded.py)
--------------------------------------------------------------
The engine runs over a ("data", "model") device mesh with every jitted
tick (decode, chunk prefill, the first token's unembedding) shard_map'd.
Per-device layout:

    sharded over ``model`` (size N):
        pool["sub{j}"]["k"|"v"]      (G, num_pages, K/N, page, hd)
        quant pools: both the int codes and the fp32 scale tiles split
        the same way — per-device page bytes really drop Nx, which is how
        ``derive_policy(mesh_model=N)`` finds ~Nx the pool capacity (and
        resident sequences) in the same per-device HBM
        wq/wk/wv (heads dims), FFN up/gate (d_ff dim): used as local
        slices — these matmuls are output-dim-sharded, so each device
        computes an identical slice of the identical computation
    sharded at rest, all-gathered at use (FSDP-style):
        every other param (embed table, attn out-proj, FFN down-proj,
        MoE experts, norms) — a contraction-sharded matmul would need a
        partial-sum all-reduce, which is not bit-stable, so the inputs
        are gathered (pure data movement) and the contraction runs whole
    replicated (host-owned, never sharded):
        page table, positions, tokens, logits — and ALL scheduler state:
        admission, growth, preemption, window-trim, and chunk accounting
        run on the host exactly as on one device; one logical page id
        covers every shard's kv-head slice of that page

The ``data`` axis is at-rest param FSDP only (batch-sharding the decode
tick is the async-host-loop follow-on). kv_heads must divide the model
axis, so page slots stay whole and the online softmax keeps its 1-device
reduction order. tests/test_sharded_engine.py checks that greedy tokens
on forced host devices equal the 1-device engine's across fp/int8/
HAQ-mixed pools, GQA, windows and forced preemption (the multi-device CI
job, with the sharded floors of scripts/check_bench_regression.py). On
the chip nothing holds the tokens equal: ``chip_smoke.py --chips 4``
bounds the sharded logits against the 1-device engine's and prints token
identity per request.

Observability (serving/telemetry)
---------------------------------
Every engine owns a `Telemetry` recorder (in-memory and jax-free — it
costs a few dataclass appends per tick, and greedy outputs are
untouched). Two event streams, and phase spans:

**Tick events** — one per jitted dispatch, ``kind`` in {``chunk``,
``decode``}::

    TickEvent(kind, step, t_start, measured_s, predicted_s,
              batch, padded_batch, q_len, tokens, rids, admitted,
              preempted, pages_allocated/freed/trimmed,
              queue_depth, pool_free, pool_allocated, tags)

``measured_s`` is fenced wall clock (the engine blocks on the dispatch's
outputs before stopping the timer, so async jit dispatch is never billed
as compute); ``predicted_s`` is the ``admission.step_latency`` roofline
for the same shape, priced at the *padded* jit batch. Page counters are
deltas since the previous tick event. Under a mesh, ``tags`` carries the
shard layout (``mesh_model``/``mesh_data``/``mesh_devices``).

**Sequence spans** — per-rid lifecycle edges, scheduler-owned on the
queue side and engine-owned on the compute side::

    enqueue -> admit -> chunk* -> first_token
            -> (preempt -> requeue -> admit -> ...)* -> finish -> release

Spans yield real TTFT / queue-wait / stall; ``Engine.stall_log`` and
``Engine.first_token_s`` survive as thin views over them (a preempted
request keeps its first served token's TTFT).

**Phase spans** — the host loop opens a named span around each of its
phases through ``telemetry.span(name)``, by default a
``jax.profiler.TraceAnnotation``: under a profiler session it lands in
the same trace as the device ops, on their clock; with none it is a
near no-op. The tree, with the benchmark metric that reads it::

    engine.step                   Engine.step, whole     engine.schedule_ms
      engine.admit                scheduler.admit and the admission loop
      engine.chunk                one per prompt chunk
        engine.chunk.prepare      host token / page-table arrays
        engine.chunk.dispatch     input transfer + the jit call returning
        engine.chunk.fence        block_until_ready(hidden)
        engine.chunk.first_token  final chunk: unembed row, pull, sample
      engine.decode               the decode tick
        engine.decode.grow        trim / growth / preemption loop
        engine.decode.prepare     tokens, positions, page table
        engine.decode.dispatch    input transfer + the jit call returning
        engine.decode.fence       block_until_ready(logits)
        engine.decode.sample      logits to the host + a token per row
                                  (engine.sample_ms,
                                  device.idle_sample_share)
      engine.finish               one per finished request
      engine.gauges               the per-step gauges

``engine.schedule_ms`` is a step's self time: ``engine.step`` less its
``*.dispatch``, ``*.fence``, ``engine.decode.sample`` and
``engine.chunk.first_token`` spans (bench/engine_spans.py). No span is
opened per batch row, so a step opens as many spans at any
``max_batch``.

The metrics registry (``engine.telemetry.metrics``) rolls the streams
into counters/gauges/histograms: ``ticks.*``, ``tokens.*``,
``pool.free`` (min = low-water mark), ``pool.occupancy`` (fragmentation
is 1 - occupancy), ``pool.min_free``, ``preemptions``,
``jit.decode.cache_size`` / ``jit.chunk.cache_size`` (steady-state
serving must not retrace), ``tick.*.measured_s`` and
``stall.measured_s`` histograms.

Exports: ``telemetry.write_chrome_trace(engine.telemetry, path)`` emits
Chrome trace-event JSON — open it at https://ui.perfetto.dev (or
chrome://tracing): tick slices by kind on the engine track, pool/queue
counter tracks, one async span per request. ``--trace-out`` on
launch/serve.py and benchmarks/bench_engine_throughput.py does this
from the CLI (the CI engine-smoke job uploads the bench's trace as an
artifact). ``telemetry.summarize`` prints a text rollup, and
``telemetry.calibrate(engine.telemetry.ticks)`` fits measured vs
predicted per (kind, batch, q_len) — the per-kind scale factors
`core/hardware_model`'s roofline needs to match this host, feeding the
ROADMAP's serving-stack autotuner.

Autotuning (serving/autotune)
-----------------------------
The knobs above — page size, prefill chunk, expected occupancy, KV-bit
policy, mesh split, batch cap — form a typed config space
(`autotune.ConfigSpace`), and the serving-stack autotuner searches it the
way the paper searches bit policies:

1. **calibrate** — serve a short warmup trace with the hand-picked
   default; ``telemetry.calibrate(...).scale_lookup()`` fits per-(kind,
   batch, q_len) scale factors between the roofline's ``predicted_s``
   and the fenced ``measured_s`` on THIS host.
2. **search** — DDPG (`core/rl/ddpg.py`, the AMC/HAQ agent) plus a
   seeded evolutionary baseline walk the space, scored by the
   scale-corrected ``admission.step_latency`` (`autotune.Objective`;
   thousands of candidates per second, deterministic per seed). Kinds
   with no calibration fall back to the raw roofline with a logged
   warning — never silent zeros or a made-up 1.0.
3. **validate** — the top-k candidates are re-measured on the real
   engine next to the default; the *measured* best wins (ties ship the
   default), with the Spearman predicted-vs-measured rank correlation
   reported.
4. **emit** — the winner serializes as a per-hardware JSON config;
   ``launch/serve.py --autotune N --autotune-out f.json`` writes it,
   ``--serving-config f.json`` loads it back, and
   ``Engine(roofline_scales=...)`` threads the calibration into the
   telemetry predictions of the tuned engine.

Re-fit on a new host by simply re-running ``--autotune`` there: the
warmup trace is the calibration. CI's autotune-smoke lane runs a
32-candidate search on the 4-request trace and gates that the searched
config's measured decode tok/s never falls below 0.95x the default
(scripts/check_bench_regression.py, ``autotune`` floors); nightly runs
the full budget.

Modules: `pool` (page allocator + device pool), `scheduler` (FIFO admission / growth /
preemption / eviction / window-trim / prefill-progress bookkeeping),
`admission` (roofline-derived policy, expected-footprint batch sizing,
KV-bit-aware page sizing, per-tick chunk sizing, mesh-aware per-shard
sizing), `engine` (the host loop tying them to the model), `sharded`
(the SPMD machinery above); the KV quantization subsystem itself lives
in `serving/kvquant`.
"""
from repro.serving.engine.admission import AdmissionPolicy, derive_policy
from repro.serving.engine.engine import Engine
from repro.serving.engine.pool import PageAllocator, PagedKVPool
from repro.serving.engine.scheduler import Request, Scheduler

__all__ = ["AdmissionPolicy", "derive_policy", "Engine", "PageAllocator",
           "PagedKVPool", "Request", "Scheduler"]
