"""The hybrid MoE family (granite-4.0-h): layers in periods whose mixers
are Mamba-2 or attention at fixed places (``layer_types``), each followed
by a mixture of experts beside a shared expert; Granite's four scalar
multipliers. Its parameter layout, its blocks in the float32 reference,
and the operations and bytes its served work needs.

Block, as published (granitemoehybrid)::

    x <- embedding_multiplier * embed(token)
    per layer:
      x <- x + residual_multiplier * Mixer(RMSNorm(x))
      x <- x + residual_multiplier * (MoE_held(h) + Shared(h)),
           h = RMSNorm(x)
    logits = RMSNorm(x) . embed^T / logits_scaling

RMSNorm multiplies by (1 + scale). Mixer is either

* attention: causal grouped-query attention (query head h reads kv head
  h // (H / K)) with no position encoding and scores scaled by
  ``attention_multiplier``; or
* Mamba-2: in_proj to [z | x | B | C | dt]; a causal depthwise conv of
  width W with bias over [x | B | C], then SiLU; dt <- softplus(dt +
  dt_bias), A = -exp(a_log); per head h the state S (P x N) runs the
  recurrence S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T and y_t = S_t C_t +
  D x_t, computed here token by token (a ``lax.scan``), never by chunks;
  then RMSNorm(y * silu(z)) and out_proj.

The MoE router scores all ``num_experts`` experts in float32, keeps the
top k and normalises their gates over those k. This chip holds
``num_held`` experts (``first_held`` onwards) and adds what they give:
the reference is given the same share, and what the other chips' experts
would add is left out alike. The shared SwiGLU expert is added for every
token.

The reference's head (bench/reference.py) does not divide by
``logits_scaling``: gaps are read on logits times ``logits_scaling``,
which is argmax-equivalent, and the configuration's ``max_logit_gap`` is
in those units.

Counts are taken from the configuration's shapes at its stated precision
(bfloat16 weights and K/V, float32 recurrent state), never from what an
implementation moves. A multiply-add counts as two operations. Of the
routed experts a token needs the expected held share, k * num_held /
num_experts experts (uniform routing), stated as such.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp

from bench import reference as R
from bench import weights as W
from bench.costs import KV_BYTES
from bench.reference import F32, mm, q, rms

Q_BLOCK = 512          # query rows per attention block
STATE_BYTES = 4        # float32 recurrent state


def _blk(j: int) -> W.Path:
    return ("blocks", f"sub{j}")


def _dims(m: dict):
    s = m["ssm"]
    d = m["d_model"]
    di = s["expand"] * d
    H = s.get("num_heads") or di // s["head_dim"]
    G, N = s["n_groups"], s["d_state"]
    return d, di, H, s["head_dim"], G, N, s["conv_width"]


def _period(m: dict) -> int:
    return len(m["layer_types"])


def _layers(m: dict, kind: str) -> int:
    P = _period(m)
    return sum(m["layer_types"][i % P] == kind
               for i in range(m["num_layers"]))


# ----------------------------------------------------------------- layout --
def leaf_specs(m: dict) -> Dict[W.Path, W.Leaf]:
    """Parameter layout from the configuration's model dict: per sub-slot
    j of the period, its leaves stacked over the periods."""
    d, H, K = m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or d // H
    _, di, Hs, Ps, G, N, Wc = _dims(m)
    e = m["moe"]
    E, f, fs = e.get("num_held") or e["num_experts"], e["d_ff_expert"], \
        e["d_ff_shared"]
    P = _period(m)
    L = m["num_layers"] // P
    Vp = -(-m["vocab_size"] // 256) * 256
    bf, f32 = "bfloat16", "float32"
    d_conv = di + 2 * G * N
    specs = {
        # The tied table is also the unembedding. Scaled like a head, its
        # row times embedding_multiplier would outweigh what the layers add
        # to the residual, and every served token would copy its input:
        # at this std the input row's own logit sits about one spread of
        # the logits above the rest, and rounding can move the top token.
        ("embed",): W.Leaf((Vp, d), bf,
                           1 / (m["embedding_multiplier"] * math.sqrt(d)),
                           False),
        ("final_norm",): W.Leaf((d,), f32, W.NORM_STD, False),
    }
    mamba = {
        ("mamba", "in_proj"): ((d, 2 * di + 2 * G * N + Hs), bf,
                               1 / math.sqrt(d)),
        ("mamba", "conv_w"): ((Wc, d_conv), bf, 1 / math.sqrt(Wc)),
        ("mamba", "conv_b"): ((d_conv,), bf, 0.1),
        # A = -exp(a_log) from -32 to -0.03: decays from one token to
        # hundreds, as a trained model's heads have
        ("mamba", "a_log"): ((Hs,), f32, 2.0),
        ("mamba", "dt_bias"): ((Hs,), f32, 1.0),
        ("mamba", "d_skip"): ((Hs,), f32, 1.0),
        ("mamba", "norm"): ((di,), f32, W.NORM_STD),
        ("mamba", "out_proj"): ((di, d), bf, 1 / math.sqrt(di)),
    }
    attn = {
        ("attn", "wq"): ((d, H, hd), bf, 1 / math.sqrt(d)),
        ("attn", "wk"): ((d, K, hd), bf, 1 / math.sqrt(d)),
        ("attn", "wv"): ((d, K, hd), bf, 1 / math.sqrt(d)),
        ("attn", "wo"): ((H, hd, d), bf, 1 / math.sqrt(H * hd)),
    }
    ffn = {
        ("ln1",): ((d,), f32, W.NORM_STD),
        ("ln2",): ((d,), f32, W.NORM_STD),
        ("moe", "router"): ((d, e["num_experts"]), f32, 1 / math.sqrt(d)),
        ("moe", "w_in"): ((E, d, f), bf, 1 / math.sqrt(d)),
        ("moe", "w_gate"): ((E, d, f), bf, 1 / math.sqrt(d)),
        ("moe", "w_out"): ((E, f, d), bf, 1 / math.sqrt(f)),
        ("moe", "shared", "w_in"): ((d, fs), bf, 1 / math.sqrt(d)),
        ("moe", "shared", "w_gate"): ((d, fs), bf, 1 / math.sqrt(d)),
        ("moe", "shared", "w_out"): ((fs, d), bf, 1 / math.sqrt(fs)),
    }
    for j, kind in enumerate(m["layer_types"]):
        layer = {**ffn, **(mamba if kind == "mamba" else attn)}
        for p, (shape, dt, std) in layer.items():
            specs[_blk(j) + p] = W.Leaf((L,) + shape, dt, std, True)
    return specs


# -------------------------------------------------------------- reference --
def attention(m: dict, fp8: bool, w: dict, x):
    """Attn(RMSNorm(x)) over one sequence x (T, d): no position encoding,
    scores scaled by ``attention_multiplier``."""
    T = x.shape[0]
    H, K = m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or m["d_model"] // H
    G = H // K
    a = w["attn"]
    h = q(rms(x, w["ln1"], m["norm_eps"]), -1, fp8)
    qs = mm("td,dnh->tnh", h, q(a["wq"], 0, fp8))
    k = mm("td,dnh->tnh", h, q(a["wk"], 0, fp8))
    v = mm("td,dnh->tnh", h, q(a["wv"], 0, fp8))
    qs = q(qs, -1, fp8).reshape(T, K, G, hd)
    k, v = q(k, -1, fp8), q(v, 0, fp8)
    outs = []
    for s0 in range(0, T, Q_BLOCK):
        qb = qs[s0:s0 + Q_BLOCK]
        sc = mm("tkgh,skh->kgts", qb, k) * m["attention_multiplier"]
        causal = (jnp.arange(T)[None, :]
                  <= (s0 + jnp.arange(qb.shape[0]))[:, None])
        sc = jnp.where(causal, sc, -jnp.inf)
        p = q(jax.nn.softmax(sc, axis=-1), -1, fp8)
        outs.append(mm("kgts,skh->tkgh", p, v))
    o = q(jnp.concatenate(outs, 0).reshape(T, H * hd), -1, fp8)
    return mm("te,ed->td", o, q(a["wo"].reshape(H * hd, -1), 0, fp8))


def mamba(m: dict, fp8: bool, w: dict, x):
    """Mamba2(RMSNorm(x)) over one sequence x (T, d), the state run token
    by token."""
    T = x.shape[0]
    d, di, H, P, G, N, Wc = _dims(m)
    p = w["mamba"]
    h = q(rms(x, w["ln1"], m["norm_eps"]), -1, fp8)
    zxbcdt = mm("td,de->te", h, q(p["in_proj"], 0, fp8))
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * G * N], axis=-1)
    pad = jnp.pad(xbc, ((Wc - 1, 0), (0, 0)))
    conv = sum(pad[i:i + T] * p["conv_w"][i] for i in range(Wc))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs, Bm, Cm = jnp.split(xbc, [di, di + G * N], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                  # (T, H)
    A = -jnp.exp(p["a_log"])
    xs = xs.reshape(T, H, P)
    Bh = jnp.repeat(Bm.reshape(T, G, N), H // G, axis=1)     # (T, H, N)
    Ch = jnp.repeat(Cm.reshape(T, G, N), H // G, axis=1)

    def step(S, inp):
        x_t, dt_t, b_t, c_t = inp
        S = S * jnp.exp(dt_t * A)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return S, jnp.sum(S * c_t[:, None, :], -1)           # (H, P)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (xs, dt, Bh, Ch))
    y = (y + xs * p["d_skip"][None, :, None]).reshape(T, di)
    y = rms(y * jax.nn.silu(z), p["norm"], m["norm_eps"])
    return mm("te,ed->td", q(y, -1, fp8), q(p["out_proj"], 0, fp8))


def experts(m: dict, fp8: bool, w: dict, x):
    """MoE_held(h) + Shared(h), h = RMSNorm(x)."""
    e, c = w["moe"], m["moe"]
    k, first = c["experts_per_token"], c.get("first_held", 0)
    h = rms(x, w["ln2"], m["norm_eps"])
    probs = jax.nn.softmax(mm("td,de->te", h, e["router"]), -1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, -1, keepdims=True)
    held = first + jnp.arange(e["w_in"].shape[0])
    gate = jnp.sum(jnp.where(idx[:, :, None] == held, top[:, :, None], 0.0),
                   axis=1)                                   # (T, held)
    h = q(h, -1, fp8)
    up = mm("td,edf->etf", h, q(e["w_in"], 1, fp8))
    g = mm("td,edf->etf", h, q(e["w_gate"], 1, fp8))
    y = mm("etf,efd->etd", q(jax.nn.silu(g) * up, -1, fp8),
           q(e["w_out"], 1, fp8))
    s = e["shared"]
    su = mm("td,df->tf", h, q(s["w_in"], 0, fp8))
    sg = mm("td,df->tf", h, q(s["w_gate"], 0, fp8))
    shared = mm("tf,fd->td", q(jax.nn.silu(sg) * su, -1, fp8),
                q(s["w_out"], 0, fp8))
    return mm("te,etd->td", gate, y) + shared


def forward(m: dict, specs, keys, xs, precision: str):
    """Final-layer hidden states (before the final norm) of the embedded
    sequences ``xs``, one layer at a time, its weights made again from
    their keys (bench/reference.py)."""
    fp8 = precision == "fp8"
    rm = m["residual_multiplier"]
    P = _period(m)

    def block(mixer, w, x):
        x = x + rm * mixer(m, fp8, w, x)
        return x + rm * experts(m, fp8, w, x)

    makes = [jax.jit(functools.partial(R.layer_weights, specs, _blk(j)))
             for j in range(P)]
    blocks = [jax.jit(functools.partial(
        block, mamba if kind == "mamba" else attention))
        for kind in m["layer_types"]]
    xs = [x * m["embedding_multiplier"] for x in xs]
    for layer in range(m["num_layers"]):
        g, j = divmod(layer, P)
        w = makes[j](R.layer_keys(keys, _blk(j), g))
        xs = [blocks[j](w, x) for x in xs]
    return xs


# ----------------------------------------------------------------- counts --
def layer_matmul_params(m: dict, kind: str) -> int:
    """Weights one token multiplies in one layer: its mixer's projections,
    the router, the expected held share of the routed experts, and the
    shared expert."""
    d, di, H, P, G, N, _ = _dims(m)
    e = m["moe"]
    held = e.get("num_held") or e["num_experts"]
    if kind == "mamba":
        mixer = d * (2 * di + 2 * G * N + H) + di * d
    else:
        h, K = m["num_heads"], m["num_kv_heads"]
        hd = m.get("head_dim") or d // h
        mixer = 2 * d * h * hd + 2 * d * K * hd
    routed = e["experts_per_token"] * held / e["num_experts"]
    return int(mixer + d * e["num_experts"]
               + routed * 3 * d * e["d_ff_expert"] + 3 * d * e["d_ff_shared"])


def ssm_token_flops(m: dict) -> int:
    """One token's Mamba-2 work besides the projections, all Mamba
    layers: the conv (2 W per channel), the state update (decay, outer
    product and sum: 3 per state element), its readout (2 per element)
    and the D skip."""
    d, di, H, P, G, N, Wc = _dims(m)
    per = 2 * Wc * (di + 2 * G * N) + 5 * H * P * N + 2 * H * P
    return per * _layers(m, "mamba")


def attention_flops(m: dict, keys: int) -> int:
    """Scores and weighted sum of one query over ``keys`` keys, all
    attention layers."""
    H = m["num_heads"]
    hd = m.get("head_dim") or m["d_model"] // H
    return 4 * H * hd * keys * _layers(m, "attention")


def kv_bytes(m: dict, tokens: int) -> int:
    """K and V of ``tokens`` positions, all attention layers."""
    H = m["num_heads"]
    hd = m.get("head_dim") or m["d_model"] // H
    return 2 * m["num_kv_heads"] * hd * tokens * _layers(m, "attention") \
        * KV_BYTES


def _token_flops(m: dict) -> int:
    return sum(2 * layer_matmul_params(m, kind) * m["num_layers"] // len(
        m["layer_types"]) for kind in m["layer_types"]) + ssm_token_flops(m)


def decode_flops(m: dict, contexts: Iterable[int]) -> int:
    """Model operations of one decode token per sequence, each attending
    over its context (keys including itself), with the unembedding."""
    per_tok = _token_flops(m) + 2 * m["d_model"] * m["vocab_size"]
    return sum(per_tok + attention_flops(m, c) for c in contexts)


def chunk_flops(m: dict, start: int, end: int) -> int:
    """Model operations of the real prompt rows ``start .. end-1`` of one
    prefill chunk (no unembedding). Row p attends over keys 0..p."""
    n = end - start
    return _token_flops(m) * n + attention_flops(m, (start + 1 + end) * n // 2)


def decode_attention_work(m: dict, contexts: Iterable[int]) -> Tuple[int, int]:
    """(operations, bytes) the paged-attention kernel needs in one decode
    tick: each sequence reads its context's K/V once."""
    cs = list(contexts)
    return attention_flops(m, sum(cs)), kv_bytes(m, sum(cs))


def chunk_attention_work(m: dict, start: int, end: int) -> Tuple[int, int]:
    """(operations, bytes) the kernel needs for one chunk's real rows:
    the scores over keys 0..p for each row p, and the K/V of positions
    0..end-1 read once."""
    n = end - start
    return attention_flops(m, (start + 1 + end) * n // 2), kv_bytes(m, end)


def ssm_decode_work(m: dict, live: int) -> Tuple[int, int]:
    """(operations, bytes) the decode state update needs in one decode
    tick over all Mamba layers, for ``live`` sequences: each one's state
    read and written once, its x, dt, B, C read and its y written, and
    the update, readout and D skip computed."""
    d, di, H, P, G, N, _ = _dims(m)
    state = H * P * N * STATE_BYTES
    io = (2 * H * P + H + 2 * G * N) * 4
    flops = 5 * H * P * N + 2 * H * P
    n = _layers(m, "mamba") * live
    return flops * n, (2 * state + io) * n
