"""Mixture-of-Experts FFN: top-k routing over every expert, the experts held
here, and an optional shared expert.

The router always scores all ``num_experts`` experts, takes the top k and
normalises their gates over those k. With expert parallelism a device holds
only ``moe.held`` of them (``first_held`` onwards): it computes the part of
the result its own experts give, and what the others would add is left to
the devices that hold them. A shared SwiGLU expert (``d_ff_shared``), when
the configuration has one, is added for every token.

Two dispatches of the routed tokens:

* ``moe_apply`` (training and whole-sequence forward): sort-based
  fixed-capacity dispatch (GShard). Routed pairs are sorted by expert id
  and scattered into an (E, C, D) buffer, experts run as one batched
  einsum (shardable over the "experts" logical axis), and outputs
  scatter-add back per token weighted by the gate. Capacity overflow drops
  tokens; the residual path keeps dropped tokens intact.
* ``moe_serve`` (the serving engine's paged decode and prompt chunks):
  every held expert runs on every token and is weighted by its gate, zero
  where the token did not choose it. Nothing is dropped, so what a request
  is served does not depend on the batch it is served in.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import ffn_apply, ffn_defs
from repro.models.params import PDef

F32 = jnp.float32


def moe_defs(d_model: int, moe, activation: str = "swiglu") -> dict:
    E, f = moe.held, moe.d_ff_expert
    defs = {
        "router": PDef((d_model, moe.num_experts), ("embed", "experts"),
                       "scaled", dtype=jnp.float32),
        "w_in": PDef((E, d_model, f), ("experts", "embed", "expert_ff"),
                     "scaled"),
        "w_gate": PDef((E, d_model, f), ("experts", "embed", "expert_ff"),
                       "scaled"),
        "w_out": PDef((E, f, d_model), ("experts", "expert_ff", "embed"),
                      "scaled"),
    }
    if moe.d_ff_shared:
        defs["shared"] = ffn_defs(d_model, moe.d_ff_shared, activation)
    return defs


def capacity(tokens: int, moe) -> int:
    c = math.ceil(tokens * moe.experts_per_token * moe.capacity_factor
                  / moe.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8 (VPU sublane)


def route(p, xf: jax.Array, moe):
    """(probs (T, E), gates (T, k), expert ids (T, k)) of tokens xf (T, D):
    softmax over every expert in float32, the top k, their gates
    normalised over those k. Pass the router's input in float32 where it
    is at hand: a top-k choice between near-equal scores turns on the
    rounding of its input, and a flipped choice moves the layer's output
    by a whole expert's share."""
    logits = jnp.einsum("td,de->te", xf.astype(F32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, moe.experts_per_token)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return probs, gates, idx


def _experts(p, buf, activation, dot):
    """The held experts on their (E, C, D) rows."""
    dot_e = dot or (lambda a, w, name: jnp.einsum(
        "ecd,edf->ecf", a, w))
    h = dot_e(buf, p["w_in"], "moe_in")
    g = dot_e(buf, p["w_gate"], "moe_gate")
    if activation == "swiglu":
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(g, approximate=True) * h
    dot_o = dot or (lambda a, w, name: jnp.einsum(
        "ecf,efd->ecd", a, w))
    return dot_o(h, p["w_out"], "moe_out")


def _with_shared(p, x, y, activation, dot):
    """y (float32) plus the shared expert on x, where there is one."""
    if "shared" not in p:
        return y
    return y + ffn_apply(p["shared"], x, activation, dot=dot).astype(F32)


def moe_apply(p, x: jax.Array, moe, activation: str = "swiglu",
              *, dot=None, ac=None, x_route=None
              ) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, D) -> (y (B, S, D) float32, aux_loss scalar); ``x_route``
    is x before its rounding, for the router (x where None). The experts'
    outputs are combined in float32, for the model's float32 residual
    stream. `ac` hints the dispatch-buffer sharding (see
    distributed.sharding.make_ac)."""
    B, S, D = x.shape
    T = B * S
    E, k = moe.held, moe.experts_per_token
    C = capacity(T, moe)
    xf = x.reshape(T, D)

    probs, gates, idx = route(p, xf if x_route is None
                              else x_route.reshape(T, D), moe)

    # load-balance aux loss (Switch): E * sum_e f_e * p_e
    n = moe.num_experts
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], n, dtype=F32), axis=0)
    aux = n * jnp.sum(me * ce)

    # pairs routed to experts held elsewhere sort last, into bucket E
    e_flat = idx.reshape(T * k) - moe.first_held
    held = (e_flat >= 0) & (e_flat < E)
    e_flat = jnp.where(held, e_flat, E)
    g_flat = gates.reshape(T * k)
    order = jnp.argsort(e_flat)                              # stable
    e_sorted = e_flat[order]
    tok_sorted = order // k
    counts = jnp.bincount(e_flat, length=E + 1)
    seg_start = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                 jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * k) - seg_start[e_sorted]
    keep = (pos < C) & (e_sorted < E)
    dest = jnp.where(keep, e_sorted * C + pos, E * C)        # OOB row drops

    x_sorted = xf[tok_sorted]
    buf = jnp.zeros((E * C + 1, D), x.dtype).at[dest].set(x_sorted)
    buf = buf[:-1].reshape(E, C, D)
    if ac is not None:
        buf = ac(buf, "moe_buf")

    out_buf = _experts(p, buf, activation, dot)
    if ac is not None:
        out_buf = ac(out_buf, "moe_buf")
    out_buf = out_buf.reshape(E * C, D)

    safe_dest = jnp.minimum(dest, E * C - 1)
    y_sorted = out_buf[safe_dest] * (keep & (dest < E * C))[:, None]
    contrib = y_sorted.astype(F32) * g_flat[order][:, None]
    y = jnp.zeros((T, D), F32).at[tok_sorted].add(contrib)
    y = _with_shared(p, xf, y, activation, dot)
    return y.reshape(B, S, D), aux


def moe_serve(p, x: jax.Array, moe, activation: str = "swiglu",
              *, dot=None, x_route=None) -> jax.Array:
    """x (B, S, D) -> y (B, S, D) float32: every held expert on every token,
    weighted by its gate (zero where the token did not choose it), plus
    the shared expert. No token is dropped, whatever the batch.
    ``x_route`` as in ``moe_apply``."""
    B, S, D = x.shape
    T = B * S
    E = moe.held
    xf = x.reshape(T, D)
    _, gates, idx = route(p, xf if x_route is None
                          else x_route.reshape(T, D), moe)
    ids = moe.first_held + jnp.arange(E)
    weight = jnp.sum(jnp.where(idx[:, :, None] == ids, gates[:, :, None],
                               0.0), axis=1)                 # (T, E) f32
    out = _experts(p, jnp.broadcast_to(xf, (E, T, D)), activation, dot)
    # in expert order, one rounded product and one add per expert, as
    # moe_apply's scatter-add sums a token's choices: both give the same
    # float32 sum (a zero weight adds an exact zero)
    y = jnp.zeros((T, D), F32)
    for e in range(E):
        y = y + weight[:, e:e + 1] * out[e].astype(F32)
    return _with_shared(p, xf, y, activation, dot).reshape(B, S, D)
