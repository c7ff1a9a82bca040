"""Analytic TPU hardware simulator — the paper's "hardware in the loop".

HAQ (§4) queries a hardware simulator for latency/energy feedback instead of
proxies (FLOPs); ProxylessNAS (§2) builds a per-op latency lookup table. The
container has no TPU, so this module plays the simulator role for both: a
roofline-based per-op cost model for TPU v5e-class chips, calibrated against
``compiled.cost_analysis()`` from the dry-run (see EXPERIMENTS.md §Roofline).

Three hardware targets mirror the paper's HW1/HW2/HW3 specialization story
(Table 5): a single edge chip (memory-bound decode), a pod slice
(compute-bound prefill/train), and a multi-pod slice (collective-bound).

All latencies are returned in seconds, energies in joules. Functions are
jnp-friendly: bits may be traced arrays, so HAQ's RL loop and the NAS latency
loss are differentiable end-to-end where they need to be.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str
    chips: int
    peak_flops_bf16: float = 197e12   # per chip
    peak_flops_int8: float = 394e12   # v5e int8 MXU path
    hbm_bw: float = 819e9             # bytes/s per chip
    ici_bw: float = 50e9              # bytes/s per link
    hbm_bytes: float = 16 * 2**30
    vmem_bytes: float = 128 * 2**20
    # energy constants (public-literature scale values)
    pj_per_flop: float = 0.25         # bf16 MAC ~0.2-0.3 pJ on 5nm-class
    pj_per_hbm_byte: float = 120.0
    pj_per_ici_byte: float = 40.0
    mxu_dim: int = 128                # systolic array tile

    def peak_flops(self, w_bits) -> jax.Array:
        """Matmul peak vs weight precision: int8 path doubles throughput;
        sub-8-bit weights on TPU still use the int8 MXU (no extra compute
        speedup, only memory savings) — unlike BitFusion's bit-serial PEs.
        This asymmetry is exactly why TPU quantization policies differ from
        the paper's FPGA policies (DESIGN.md §2)."""
        w_bits = jnp.asarray(w_bits, jnp.float32)
        return jnp.where(w_bits <= 8, self.peak_flops_int8,
                         self.peak_flops_bf16)


V5E_EDGE = Hardware("v5e-1chip", chips=1)
V5E_POD = Hardware("v5e-pod256", chips=256)
V5E_2POD = Hardware("v5e-2pod512", chips=512,
                    ici_bw=25e9)  # pod axis traverses slower links

HARDWARES: Dict[str, Hardware] = {h.name: h for h in
                                  (V5E_EDGE, V5E_POD, V5E_2POD)}

# The one place a physical device is priced: ``device_kind`` as JAX reports
# it -> the per-chip HARDWARES entry. Peaks for TPU v5e from the Google Cloud
# "TPU v5e" documentation (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
DEVICE_KINDS: Dict[str, str] = {"TPU v5 lite": V5E_EDGE.name}


def hardware_for_device(device) -> Hardware:
    """The HARDWARES entry for a JAX device. A kind missing from
    DEVICE_KINDS is an error: pricing an unchecked device with another
    chip's peaks would make every roofline number wrong."""
    kind = device.device_kind
    if kind not in DEVICE_KINDS:
        raise ValueError(
            f"no hardware model for device kind {kind!r} "
            f"(platform {device.platform}); known kinds: "
            f"{sorted(DEVICE_KINDS)}")
    return HARDWARES[DEVICE_KINDS[kind]]


def mxu_pad(dim, tile: int = 128):
    """Effective dim after MXU tile padding — why the NAS searcher learns to
    pick 128-aligned widths (the paper's 7x7-conv-on-GPU moment, on TPU)."""
    dim = jnp.asarray(dim, jnp.float32)
    return jnp.ceil(dim / tile) * tile


@dataclasses.dataclass(frozen=True)
class OpCost:
    """Roofline terms for one op at one precision setting."""
    flops: jax.Array
    weight_bytes: jax.Array
    act_bytes: jax.Array
    coll_bytes: jax.Array = 0.0

    def latency(self, hw: Hardware, w_bits=16, a_bits=16) -> jax.Array:
        w_bits = jnp.asarray(w_bits, jnp.float32)
        a_bits = jnp.asarray(a_bits, jnp.float32)
        t_comp = self.flops / (hw.peak_flops(w_bits) * hw.chips)
        bytes_total = (self.weight_bytes * w_bits / 16.0
                       + self.act_bytes * a_bits / 16.0)
        t_mem = bytes_total / (hw.hbm_bw * hw.chips)
        t_coll = self.coll_bytes / (hw.ici_bw * hw.chips)
        return jnp.maximum(jnp.maximum(t_comp, t_mem), t_coll)

    def energy(self, hw: Hardware, w_bits=16, a_bits=16) -> jax.Array:
        w_bits = jnp.asarray(w_bits, jnp.float32)
        a_bits = jnp.asarray(a_bits, jnp.float32)
        # MAC energy scales ~linearly with operand width on MXU-class units
        e_flop = self.flops * hw.pj_per_flop * 1e-12 * \
            jnp.minimum(w_bits, a_bits) / 16.0
        e_mem = (self.weight_bytes * w_bits / 16.0
                 + self.act_bytes * a_bits / 16.0) * hw.pj_per_hbm_byte * 1e-12
        e_coll = self.coll_bytes * hw.pj_per_ici_byte * 1e-12
        return e_flop + e_mem + e_coll

    def intensity(self, w_bits=16, a_bits=16) -> jax.Array:
        """Operational intensity (FLOPs per HBM byte) — Fig. 4's x-axis."""
        b = (self.weight_bytes * jnp.asarray(w_bits, jnp.float32) / 16.0
             + self.act_bytes * jnp.asarray(a_bits, jnp.float32) / 16.0)
        return self.flops / jnp.maximum(b, 1.0)


# ------------------------------------------------------------- op costs ----
def linear_cost(tokens: int, d_in: int, d_out: int, *, tp: int = 1,
                pad: bool = True) -> OpCost:
    """Dense matmul (tokens, d_in) x (d_in, d_out), TP-sharded on d_out."""
    di = mxu_pad(d_in) if pad else jnp.asarray(float(d_in))
    do = mxu_pad(d_out) if pad else jnp.asarray(float(d_out))
    flops = 2.0 * tokens * di * do
    return OpCost(
        flops=flops,
        weight_bytes=di * do * 2.0,
        act_bytes=2.0 * tokens * (di + do),
        coll_bytes=2.0 * tokens * do / max(tp, 1),  # partial-sum reduce
    )


def attention_cost(batch: int, q_len: int, kv_len: int, n_heads: int,
                   n_kv: int, head_dim: int, *, window: int = 0,
                   decode: bool = False, kv_bits: int = 16) -> OpCost:
    """``kv_bits`` scales the KV-cache read traffic (the decode memory-
    roofline term) for a HAQ-quantized page pool: int8 halves it, int4
    quarters it, plus the fp32 per-token per-head scale tiles the pool
    stores alongside the codes (serving/kvquant). Compute is unchanged —
    dequant rides the block walk on the VPU."""
    eff_kv = min(window, kv_len) if window else kv_len
    flops = 4.0 * batch * q_len * eff_kv * n_heads * head_dim
    kv_bytes = 2.0 * batch * eff_kv * n_kv * head_dim * 2.0 * (kv_bits / 16.0)
    if kv_bits < 16:
        kv_bytes += 2.0 * batch * eff_kv * n_kv * 4.0   # scale tiles
    act = 2.0 * batch * q_len * n_heads * head_dim * 2.0
    return OpCost(flops=jnp.asarray(flops),
                  weight_bytes=jnp.asarray(0.0),
                  act_bytes=jnp.asarray(kv_bytes + act))


def allreduce_cost(tokens: int, d_model: int, shards: int) -> OpCost:
    """Ring all-reduce of a (tokens, d_model) bf16 activation across a
    tensor-parallel group: every rank moves ~2*(N-1)/N of the buffer over
    ICI. This is the per-layer activation-collective term of
    ``step_latency(mesh_model=N)`` for the sharded serving engine (its
    gather-based exact TP moves the same activation volume as the
    canonical Megatron pair) — the price of splitting the per-shard HBM
    roofline N ways (paper Fig. 4's bandwidth axis traded against the
    interconnect)."""
    n = max(int(shards), 1)
    coll = 2.0 * tokens * d_model * 2.0 * (n - 1) / n
    return OpCost(flops=jnp.asarray(0.0),
                  weight_bytes=jnp.asarray(0.0),
                  act_bytes=jnp.asarray(0.0),
                  coll_bytes=jnp.asarray(coll))


def gather_cost(nbytes, shards: int) -> OpCost:
    """Ring all-gather of ``nbytes`` of sharded-at-rest state onto every
    rank ((N-1)/N of the buffer crosses ICI per rank): how the SPMD
    serving engine pays for its FSDP-style gather-at-use weights (attn
    out-projection, FFN down-projection, MoE expert bank, embed table) —
    the contraction-sharded matmuls it deliberately refuses to psum-split
    for bit-exactness (serving/engine/sharded.py)."""
    n = max(int(shards), 1)
    return OpCost(flops=jnp.asarray(0.0),
                  weight_bytes=jnp.asarray(0.0),
                  act_bytes=jnp.asarray(0.0),
                  coll_bytes=jnp.asarray(float(nbytes) * (n - 1) / n))


def ssd_cost(batch: int, seq: int, d_inner: int, d_state: int,
             chunk: int) -> OpCost:
    """Mamba2 SSD: intra-chunk quadratic + state updates."""
    heads = max(d_inner // 64, 1)
    intra = 2.0 * batch * seq * chunk * heads * 64
    state = 4.0 * batch * seq * d_inner * d_state
    return OpCost(flops=jnp.asarray(intra + state),
                  weight_bytes=jnp.asarray(0.0),
                  act_bytes=jnp.asarray(2.0 * batch * seq * d_inner * 2.0))


def moe_cost(tokens: int, d_model: int, d_ff: int, n_experts: int,
             top_k: int, *, ep: int = 1) -> OpCost:
    """Top-k expert FFN + all-to-all dispatch."""
    active = linear_cost(tokens * top_k, d_model, d_ff)
    a2a = 2.0 * tokens * top_k * d_model * 2.0  # dispatch + combine
    return OpCost(
        flops=active.flops * 3.0,                       # in/gate/out
        weight_bytes=mxu_pad(d_model) * mxu_pad(d_ff) * 3.0 * n_experts * 2.0,
        act_bytes=active.act_bytes * 3.0,
        coll_bytes=jnp.asarray(a2a),
    )
