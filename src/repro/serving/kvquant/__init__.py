"""HAQ-searched mixed-precision KV-cache quantization for the serving
engine's paged pool.

PR 1 put HAQ bits on the *weights* when the memory roofline demanded it; at
long contexts the decode roofline is dominated by KV-cache bytes, not
weight bytes. This subsystem turns the same searched-bit machinery loose on
the pool itself: pages are stored int8 or int4 per sub-layer slot, sized
into admission (2-4x more pages in the same HBM), and dequantized *inside*
the paged-attention block walk — never as a materialized fp KV view.

Quantized page layout
---------------------
The fp pool stores, per sub-layer slot ``sub{j}`` (see serving/engine)::

    pool["sub{j}"]["k"|"v"] : (n_groups, num_pages, K, page_size, hd) bf16

A slot quantized to ``bits`` ∈ {8, 4} stores instead::

    pool["sub{j}"]["k"|"v"] = {
        "q":     (n_groups, num_pages, K, page_size, hd_store) int8,
        "scale": (n_groups, num_pages, K, page_size)            fp32,
    }

with ``hd_store = hd`` for int8 and ``hd // 2`` for int4 — int4 packs two
codes per byte along head_dim (element ``i`` in the low nibble, ``i +
hd/2`` in the high; kernels/ref.py::pack_int4_hd). The stored bitwidth is
encoded by the shape itself (``kv_bits_of``), so it stays static under jit
and no side-channel bits tag rides the pytree.

Scale placement
---------------
Scales are symmetric per page *slot* (token) and per kv head: each physical
page carries its own ``(K, page_size)`` fp32 scale tile next to its codes.
Per-token granularity is what makes quantize-on-write exact bookkeeping:
prefill scatters whole quantized pages, decode writes one ``(K, hd)`` token
into ``page_table[b, pos // page]`` slot ``pos % page`` — and neither ever
re-scales a resident token (a per-page scale would have to grow as new
tokens land, forcing a lossy requantize of the whole page on every write).
The coarser per-page granularity is kept in ``quantize_kv`` for the
error-bound study in tests/test_kvquant.py. Scale overhead is
``8 * K`` bytes per token per layer (k and v), priced into
``admission.kv_bytes_per_token`` so page sizing stays honest.

At attention time the scale tiles ride the same scalar-prefetched
page-table walk as their pages (kernels/paged_attention.py::
paged_attention_quant_fwd on TPU, kernels/ref.py::paged_attention_quant_ref
as the pure-JAX twin): dequant happens inside the online-softmax block
loop — the kernel folds each token's scale into its score and probability
columns, so the only fp tile is one (page, hd) block in VMEM.

Bit policy
----------
``policy.search_kv_policy`` runs the paper's HAQ loop over KV sites
(core/haq.py::enumerate_kv_sites — one per sub-layer slot, matching the
pool pytree): DDPG proposes per-site bits, latency/HBM feedback comes from
the hardware roofline (hardware_model.attention_cost with ``kv_bits``,
admission.step_latency for the whole tick), the paper's sequential back-off
enforces the budget, and an attention-sensitivity proxy gates which sites
may drop to int4 — sliding-window (local) layers first, since their bounded
effective context bounds the quantization-noise accumulation. The searched
policy is a per-sub-layer tuple that threads through
``AdmissionPolicy.kv_bits`` -> ``Engine`` -> ``Model.init_pool``.

The fp pool remains the exactness baseline; int8 greedy drift against it is
bounded and asserted in tests/test_kvquant.py, and
benchmarks/bench_engine_throughput.py headlines fp vs int8 vs HAQ-mixed
decode throughput at equal HBM budget (BENCH_engine.json).
"""
from repro.serving.kvquant.drift import greedy_drift, teacher_forced_logits
from repro.serving.kvquant.quantize import (dequantize_kv, kv_bits_of,
                                            normalize_kv_bits, pack_int4_hd,
                                            quantize_kv, quantize_pool,
                                            unpack_int4_hd)
from repro.serving.kvquant.policy import (kv_sensitivity, search_kv_policy,
                                          allowed_kv_bits)

__all__ = ["quantize_kv", "dequantize_kv", "kv_bits_of", "pack_int4_hd",
           "unpack_int4_hd", "quantize_pool", "normalize_kv_bits",
           "search_kv_policy", "kv_sensitivity", "allowed_kv_bits",
           "greedy_drift", "teacher_forced_logits"]
