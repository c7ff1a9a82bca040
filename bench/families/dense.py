"""The dense decoder family: its parameter layout, its blocks in the
float32 reference, and the operations and bytes its served work needs.

Block: x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x)). RMSNorm multiplies by
(1 + scale). Attention is causal, grouped-query (query head h reads kv
head h // (H / K)), with rotary embedding over all head dims (the two
halves of a head rotated as a pair, base ``rope_theta``) and scale
hd^-1/2. FFN is SwiGLU (silu(x Wg) * (x Wi)) Wo or squared ReLU
relu(x Wi)^2 Wo. The embedding, the final norm and the unembedding are
every family's (bench/reference.py).

Counts are taken from the configuration's shapes at its stated precision
(bfloat16 weights and K/V), never from what an implementation happens to
move. A multiply-add counts as two operations.
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

BLK = ("blocks", "sub0")
Q_BLOCK = 512          # query rows per attention block


# ----------------------------------------------------------------- layout --
def leaf_specs(m: dict) -> Dict[W.Path, W.Leaf]:
    """Parameter layout of the dense decoder from the configuration's
    model dict (keys as in ``bench/configs/*.json``)."""
    d, H, K = m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or d // H
    F, L = m["d_ff"], m["num_layers"]
    Vp = -(-m["vocab_size"] // 256) * 256
    bf, f32 = "bfloat16", "float32"
    tied = m.get("tie_embeddings", False)
    # a tied table is also the unembedding: scaled like the head, so the
    # logits spread about as a trained model's do and rounding can move
    # the top token (at std 1 it never does, in bf16 or in fp8)
    specs = {
        ("embed",): W.Leaf((Vp, d), bf, 1 / math.sqrt(d) if tied else 1.0,
                           False),
        ("final_norm",): W.Leaf((d,), f32, W.NORM_STD, False),
    }
    if not tied:
        specs[("lm_head",)] = W.Leaf((d, Vp), bf, 1 / math.sqrt(d), False)
    layer = {
        ("ln1",): ((d,), f32, W.NORM_STD),
        ("ln2",): ((d,), f32, W.NORM_STD),
        ("attn", "wq"): ((d, H, hd), bf, 1 / math.sqrt(d)),
        ("attn", "wk"): ((d, K, hd), bf, 1 / math.sqrt(d)),
        ("attn", "wv"): ((d, K, hd), bf, 1 / math.sqrt(d)),
        ("attn", "wo"): ((H, hd, d), bf, 1 / math.sqrt(H * hd)),
        ("ffn", "w_in"): ((d, F), bf, 1 / math.sqrt(d)),
        ("ffn", "w_out"): ((F, d), bf, 1 / math.sqrt(F)),
    }
    if m["activation"] in ("swiglu", "geglu"):
        layer[("ffn", "w_gate")] = ((d, F), bf, 1 / math.sqrt(d))
    for p, (shape, dt, std) in layer.items():
        specs[BLK + p] = W.Leaf((L,) + shape, dt, std, True)
    return specs


# -------------------------------------------------------------- reference --
def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None, None] * inv            # (T, 1, hd/2)
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * c - b * s, a * s + b * c], -1)


def attention(m: dict, fp8: bool, w: dict, x):
    """x + Attn(RMSNorm(x)) over one sequence x (T, d); ``w`` holds one
    layer's ``ln1`` and ``attn`` leaves."""
    T = x.shape[0]
    H, K = m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or m["d_model"] // H
    G = H // K
    eps, theta = m["norm_eps"], m["rope_theta"]
    a = w["attn"]
    pos = jnp.arange(T)
    h = q(rms(x, w["ln1"], eps), -1, fp8)
    qs = _rope(mm("td,dnh->tnh", h, q(a["wq"], 0, fp8)), pos, theta)
    k = _rope(mm("td,dnh->tnh", h, q(a["wk"], 0, fp8)), pos, theta)
    v = mm("td,dnh->tnh", h, q(a["wv"], 0, fp8))
    qs = q(qs, -1, fp8).reshape(T, K, G, hd)
    k, v = q(k, -1, fp8), q(v, 0, fp8)
    outs = []
    for s0 in range(0, T, Q_BLOCK):
        qb = qs[s0:s0 + Q_BLOCK]
        sc = mm("tkgh,skh->kgts", qb, k) * (hd ** -0.5)
        causal = (jnp.arange(T)[None, :]
                  <= (s0 + jnp.arange(qb.shape[0]))[:, None])
        sc = jnp.where(causal, sc, -jnp.inf)
        p = q(jax.nn.softmax(sc, axis=-1), -1, fp8)
        outs.append(mm("kgts,skh->tkgh", p, v))
    o = q(jnp.concatenate(outs, 0).reshape(T, H * hd), -1, fp8)
    return x + mm("te,ed->td", o, q(a["wo"].reshape(H * hd, -1), 0, fp8))


def feed_forward(m: dict, fp8: bool, w: dict, x):
    """x + FFN(RMSNorm(x)); ``w`` holds one layer's ``ln2`` and ``ffn``
    leaves."""
    f = w["ffn"]
    h = q(rms(x, w["ln2"], m["norm_eps"]), -1, fp8)
    up = mm("td,df->tf", h, q(f["w_in"], 0, fp8))
    if "w_gate" in f:
        g = mm("td,df->tf", h, q(f["w_gate"], 0, fp8))
        a = jax.nn.silu(g) * up if m["activation"] == "swiglu" \
            else jax.nn.gelu(g, approximate=True) * up
    else:
        a = jnp.square(jax.nn.relu(up))
    return x + mm("tf,fd->td", q(a, -1, fp8), q(f["w_out"], 0, fp8))


def forward(m: dict, specs, keys, xs, precision: str):
    """Final-layer hidden states (before the final norm) of the embedded
    sequences ``xs``, one layer at a time, its weights made again from
    their keys (bench/reference.py)."""
    fp8 = precision == "fp8"
    make = jax.jit(functools.partial(R.layer_weights, specs, BLK))
    block = jax.jit(lambda w, x: feed_forward(m, fp8, w,
                                              attention(m, fp8, w, x)))
    for l in range(m["num_layers"]):
        w = make(R.layer_keys(keys, BLK, l))
        xs = [block(w, x) for x in xs]
    return xs


# ----------------------------------------------------------------- counts --
def _dims(m: dict):
    d, H, K = m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or d // H
    return d, H, K, hd, m["d_ff"], m["num_layers"], m["vocab_size"]


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies per layer (attention projections and
    the feed-forward)."""
    d, H, K, hd, F, _, _ = _dims(m)
    gated = m["activation"] in ("swiglu", "geglu")
    return d * H * hd + 2 * d * K * hd + H * hd * d + d * F * (3 if gated else 2)


def attention_flops(m: dict, keys: int) -> int:
    """Scores and weighted sum of one query over ``keys`` keys, all
    layers: 2 * H * hd for q.k and as much for p.v, per key."""
    d, H, K, hd, F, L, V = _dims(m)
    return 4 * H * hd * keys * L


def kv_bytes(m: dict, tokens: int) -> int:
    """K and V of ``tokens`` positions, all layers."""
    d, H, K, hd, F, L, V = _dims(m)
    return 2 * K * hd * tokens * L * KV_BYTES


def decode_flops(m: dict, contexts: Iterable[int]) -> int:
    """Model operations of one decode token per sequence, each attending
    over its context (keys including itself), with the unembedding."""
    d, H, K, hd, F, L, V = _dims(m)
    per_tok = 2 * layer_matmul_params(m) * L + 2 * d * V
    return sum(per_tok + attention_flops(m, c) for c in contexts)


def chunk_flops(m: dict, start: int, end: int) -> int:
    """Model operations of the real prompt rows ``start .. end-1`` of one
    prefill chunk (no unembedding: the chunk program returns hidden
    states). Row p attends over keys 0..p."""
    n = end - start
    keys = (start + 1 + end) * n // 2
    return 2 * layer_matmul_params(m) * m["num_layers"] * n \
        + attention_flops(m, keys)


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
