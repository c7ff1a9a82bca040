"""Mamba-2 SSD (state-space duality, arXiv:2405.21060) in chunked JAX form.

Forward uses the SSD chunked algorithm: quadratic attention-like compute
inside length-Q chunks, linear state recurrence across chunks (lax.scan).
Decode is the O(1) recurrent update. All state math in fp32.

Serving (``mamba_block_fwd`` with a cache and lengths, ``mamba_decode_rows``)
keeps each sequence's conv tail and state in a row of per-layer slot pools:
a prompt chunk continues from its row, padding rows past a chunk's real end
leave it as it was, and a decode tick updates each live row's slot once
(kernels/ssm_decode.py on the TPU).

Block structure (mamba_block_*):
  in_proj -> [z | xs | B | C | dt] -> causal depthwise conv(xs,B,C) -> SiLU
  -> SSD -> gated RMSNorm (y * silu(z)) -> out_proj
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models.layers import rms_norm
from repro.models.params import PDef

F32 = jnp.float32


def mamba_defs(cfg) -> dict:
    d, s = cfg.d_model, cfg.ssm
    di = cfg.d_inner
    H = cfg.ssm_heads
    G, N = s.n_groups, s.d_state
    d_conv = di + 2 * G * N
    return {
        "in_proj": PDef((d, 2 * di + 2 * G * N + H), ("embed", "ssm_inner"),
                        "scaled"),
        "conv_w": PDef((s.conv_width, d_conv), ("conv", "ssm_inner"),
                       "scaled", scale=0.5),
        "conv_b": PDef((d_conv,), ("ssm_inner",), "zeros"),
        "a_log": PDef((H,), ("null",), "zeros", dtype=jnp.float32),
        "dt_bias": PDef((H,), ("null",), "zeros", dtype=jnp.float32),
        "d_skip": PDef((H,), ("null",), "ones", dtype=jnp.float32),
        "norm": PDef((di,), ("ssm_inner",), "zeros", dtype=jnp.float32),
        "out_proj": PDef((di, d), ("ssm_inner", "embed"), "scaled"),
    }


def _split_proj(cfg, zxbcdt):
    di = cfg.d_inner
    G, N = cfg.ssm.n_groups, cfg.ssm.d_state
    z, xs, Bm, Cm, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + G * N, 2 * di + 2 * G * N], axis=-1)
    return z, xs, Bm, Cm, dt


def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                tail=None) -> jax.Array:
    """Depthwise causal conv. x (B,S,C), w (W,C); ``tail`` (B,W-1,C) holds
    the W-1 inputs before x (zeros where None)."""
    W = w.shape[0]
    if tail is None:
        xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    out = jnp.zeros_like(x, dtype=F32)
    for i in range(W):  # W is 4; unrolled taps beat a conv op on TPU VPU
        out = out + xp[:, i:i + x.shape[1]].astype(F32) * w[i].astype(F32)
    return (out + b.astype(F32)).astype(x.dtype)


def ssd_chunked(xh, dt, a_log, Bm, Cm, chunk: int, init=None):
    """SSD scan. xh (B,S,H,P), dt (B,S,H) fp32 post-softplus, Bm/Cm (B,S,G,N),
    starting from state ``init`` (B,H,P,N) (zeros where None). A row with
    dt = 0 leaves the state as it was.

    Returns (y (B,S,H,P), final_state (B,H,P,N)).
    """
    B, S, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = min(chunk, S)
    S_orig = S
    if S % Q:  # pad with dt=0/x=0 tokens: state-neutral (decay 1, contrib 0)
        pad = Q - S % Q
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S = S + pad
    nc = S // Q
    hg = H // G
    A = -jnp.exp(a_log.astype(F32))                       # (H,) negative

    xc = xh.reshape(B, nc, Q, H, P).astype(F32)
    dtc = dt.reshape(B, nc, Q, H)
    Bc = Bm.reshape(B, nc, Q, G, N).astype(F32)
    Cc = Cm.reshape(B, nc, Q, G, N).astype(F32)

    dA = dtc * A                                          # (B,nc,Q,H) <= 0
    cum = jnp.cumsum(dA, axis=2)                          # within-chunk
    # intra-chunk (masked "attention"): L[i,j] = exp(cum_i - cum_j), i >= j
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,Q,Q,H)
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.where(mask[None, None, :, :, None], jnp.exp(diff), 0.0)
    # scores_gij = C_i . B_j  per group -> expand to heads
    CB = jnp.einsum("bcign,bcjgn->bcijg", Cc, Bc)         # (B,nc,Q,Q,G)
    CB = jnp.repeat(CB, hg, axis=-1)                      # (B,nc,Q,Q,H)
    W = CB * L * dtc[:, :, None, :, :]                    # weight on x_j
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", W, xc)

    # chunk summary states: sum_j exp(cum_Q - cum_j) dt_j B_j x_j
    decay_tail = jnp.exp(cum[:, :, -1:, :] - cum)         # (B,nc,Q,H)
    Bh = jnp.repeat(Bc, hg, axis=3).reshape(B, nc, Q, H, N)
    states = jnp.einsum("bcqh,bcqhn,bcqhp->bchpn",
                        decay_tail * dtc, Bh, xc)         # (B,nc,H,P,N)
    chunk_decay = jnp.exp(cum[:, :, -1, :])               # (B,nc,H)

    def step(carry, inp):
        st, (s_c, dec) = carry, inp
        new = st * dec[:, :, None, None] + s_c
        return new, st                                    # emit state BEFORE chunk

    init = jnp.zeros((B, H, P, N), F32) if init is None \
        else init.astype(F32)
    xs_scan = (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0))
    final, prevs = jax.lax.scan(step, init, xs_scan)
    prev_states = jnp.moveaxis(prevs, 0, 1)               # (B,nc,H,P,N)

    # inter-chunk: y_i += C_i . (exp(cum_i) * prev_state)
    Ch = jnp.repeat(Cc, hg, axis=3).reshape(B, nc, Q, H, N)
    y_inter = jnp.einsum("bcqhn,bchpn,bcqh->bcqhp",
                         Ch, prev_states, jnp.exp(cum))
    y = (y_intra + y_inter).reshape(B, S, H, P)
    if S != S_orig:
        y = jax.lax.slice_in_dim(y, 0, S_orig, axis=1)
    return y, final


def mamba_block_fwd(p, x, cfg, *, dot=None, cache=None,
                    lengths=None) -> Tuple[jax.Array, dict]:
    """x (B,S,D) -> (y (B,S,D), cache {conv, state}).

    ``cache`` (as returned, B rows): continue from it instead of from zeros.
    ``lengths`` (B,) int32: rows at or past a sequence's length are padding;
    they leave the state as it was, and the returned conv tail is that of
    the sequence's last real rows."""
    B, S, D = x.shape
    s = cfg.ssm
    di, H, P = cfg.d_inner, cfg.ssm_heads, s.head_dim
    G, N = s.n_groups, s.d_state
    dot = dot or (lambda a, w, name: jnp.einsum(
        "bsd,de->bse", a, w))
    zxbcdt = dot(x, p["in_proj"], "ssm_in")
    z, xs, Bm, Cm, dt = _split_proj(cfg, zxbcdt)
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
    tail = None if cache is None else cache["conv"]
    conv_out = jax.nn.silu(causal_conv(conv_in, p["conv_w"], p["conv_b"],
                                       tail))
    xs, Bm, Cm = jnp.split(conv_out, [di, di + G * N], axis=-1)
    dtf = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])
    if lengths is not None:
        real = jnp.arange(S)[None, :] < lengths[:, None]
        dtf = jnp.where(real[..., None], dtf, 0.0)
    xh = xs.reshape(B, S, H, P)
    y, final = ssd_chunked(xh, dtf, p["a_log"], Bm.reshape(B, S, G, N),
                           Cm.reshape(B, S, G, N), s.chunk,
                           None if cache is None else cache["state"])
    y = y + xh.astype(F32) * p["d_skip"][None, None, :, None]
    y = y.reshape(B, S, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = dot(y, p["out_proj"], "ssm_out")
    # the W-1 conv inputs before the next token: x's row t sits at W-1+t
    W1 = s.conv_width - 1
    prev = jnp.zeros((B, W1, conv_in.shape[-1]), conv_in.dtype) \
        if tail is None else tail.astype(conv_in.dtype)
    full = jnp.concatenate([prev, conv_in], axis=1)
    if lengths is None:
        tail = full[:, S:]
    else:
        idx = lengths[:, None] + jnp.arange(W1)[None, :]
        tail = jnp.take_along_axis(full, idx[..., None], axis=1)
    cache = {"conv": tail, "state": final.astype(F32)}
    return out, cache


def mamba_block_decode(p, x, cache, cfg, *, dot=None):
    """One-token decode. x (B,1,D); cache {conv (B,W-1,C), state (B,H,P,N)}."""
    B = x.shape[0]
    s = cfg.ssm
    di, H, P = cfg.d_inner, cfg.ssm_heads, s.head_dim
    G, N = s.n_groups, s.d_state
    dot = dot or (lambda a, w, name: jnp.einsum(
        "bsd,de->bse", a, w))
    zxbcdt = dot(x, p["in_proj"], "ssm_in")
    z, xs, Bm, Cm, dt = _split_proj(cfg, zxbcdt)
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)      # (B,1,C)
    window = jnp.concatenate([cache["conv"], conv_in], axis=1)  # (B,W,C)
    conv_out = jnp.einsum("bwc,wc->bc", window.astype(F32),
                          p["conv_w"].astype(F32)) + p["conv_b"].astype(F32)
    conv_out = jax.nn.silu(conv_out)[:, None, :].astype(x.dtype)
    xs, Bm, Cm = jnp.split(conv_out, [di, di + G * N], axis=-1)
    dtf = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])  # (B,1,H)
    A = -jnp.exp(p["a_log"].astype(F32))
    dA = jnp.exp(dtf[:, 0, :] * A)                        # (B,H)
    xh = xs.reshape(B, H, P).astype(F32)
    Bh = jnp.repeat(Bm.reshape(B, G, N), H // G, axis=1)  # (B,H,N)
    Ch = jnp.repeat(Cm.reshape(B, G, N), H // G, axis=1)
    state = cache["state"] * dA[:, :, None, None] + \
        jnp.einsum("bh,bhn,bhp->bhpn", dtf[:, 0], Bh.astype(F32), xh)
    y = jnp.einsum("bhn,bhpn->bhp", Ch.astype(F32), state)
    y = y + xh * p["d_skip"][None, :, None]
    y = y.reshape(B, 1, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = dot(y, p["out_proj"], "ssm_out")
    new_cache = {"conv": window[:, 1:], "state": state}
    return out, new_cache


def mamba_cache_spec(cfg, batch: int):
    """ShapeDtypeStructs for one layer's decode cache."""
    s = cfg.ssm
    d_conv = cfg.d_inner + 2 * s.n_groups * s.d_state
    return {
        "conv": jax.ShapeDtypeStruct((batch, s.conv_width - 1, d_conv),
                                     cfg.dtype),
        "state": jax.ShapeDtypeStruct(
            (batch, cfg.ssm_heads, s.head_dim, s.d_state), jnp.float32),
    }


def mamba_decode_rows(p, x, conv_pool, state_pool, rows, cfg, *, dot=None,
                      kernel: str = "auto"):
    """One-token decode of B sequences whose conv tail and state live in
    row ``rows[b]`` of the slot pools: conv_pool (R, W-1, C) bf16,
    state_pool (R, H, P, N) f32. Each row's slot is read and written once;
    rows that point at one scratch slot leave every other slot as it was.
    ``kernel`` picks the state update (kernels/ops.py::ssm_decode).
    Returns (out (B,1,D), conv_pool, state_pool)."""
    B = x.shape[0]
    s = cfg.ssm
    di, H, P = cfg.d_inner, cfg.ssm_heads, s.head_dim
    G, N = s.n_groups, s.d_state
    dot = dot or (lambda a, w, name: jnp.einsum(
        "bsd,de->bse", a, w))
    zxbcdt = dot(x, p["in_proj"], "ssm_in")
    z, xs, Bm, Cm, dt = _split_proj(cfg, zxbcdt)
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)      # (B,1,C)
    window = jnp.concatenate([conv_pool[rows], conv_in], axis=1)
    conv_pool = conv_pool.at[rows].set(window[:, 1:], mode="promise_in_bounds")
    conv_out = jnp.einsum("bwc,wc->bc", window.astype(F32),
                          p["conv_w"].astype(F32)) + p["conv_b"].astype(F32)
    conv_out = jax.nn.silu(conv_out).astype(x.dtype)
    xs, Bm, Cm = jnp.split(conv_out, [di, di + G * N], axis=-1)
    dtf = jax.nn.softplus(dt[:, 0].astype(F32) + p["dt_bias"])   # (B,H)
    y, state_pool = kops.ssm_decode(
        state_pool, rows, xs.reshape(B, H, P).astype(F32), dtf, p["a_log"],
        Bm.reshape(B, G, N).astype(F32), Cm.reshape(B, G, N).astype(F32),
        p["d_skip"], mode=kernel)
    y = y.reshape(B, 1, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    return dot(y, p["out_proj"], "ssm_out"), conv_pool, state_pool
