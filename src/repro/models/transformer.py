"""Generic decoder-only LM assembly for the dense / moe / vlm / ssm / hybrid /
hybrid_moe families. Layers are scanned in groups of ``period`` sub-layers,
where period is the LCM of the attention pattern (gemma2 local/global), the
MoE interleave (llama4 dense/MoE) and the mixer pattern (granite-4.0-h: a
Mamba-2 or an attention mixer per sub-layer) — each sub-layer slot has its
own stacked parameter pytree so `lax.scan` keeps HLO size and CPU compile
time bounded for the 88-layer/123B configs. The scan takes the stacked
parameters as its input; the paged serving programs keep the page pool,
flattened over the groups, in its carry, so that each layer writes its own
pages in place and no pool is sliced out or stacked again
(``_paged_layers``).

Public surface (used by models/api.py):
  param_defs(cfg)                     -> PDef pytree
  forward(params, batch, cfg, ...)    -> (logits, caches|None, aux)
  decode_step(params, cache, token, pos, cfg) -> (logits, new_cache)
  cache_specs(cfg, batch, seq_len)    -> ShapeDtypeStruct pytree
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (embed_defs, ffn_apply, ffn_defs, norm_def,
                                 rms_norm, softcap)
from repro.models.params import PDef, stacked

F32 = jnp.float32
Ac = Callable[[jax.Array, str], jax.Array]  # activation-sharding hook


def _identity_ac(x, kind):
    return x


# ------------------------------------------------------------- structure ----
def period_of(cfg) -> int:
    p = len(cfg.attn_pattern)
    if cfg.moe:
        p = math.lcm(p, cfg.moe.every)
    if cfg.layer_types:
        p = math.lcm(p, len(cfg.layer_types))
    return p


def sublayer_kinds(cfg):
    """Static description of each sub-layer slot within a period."""
    P = period_of(cfg)
    kinds = []
    for j in range(P):
        kinds.append({
            "attn": cfg.attn_pattern[j % len(cfg.attn_pattern)],
            "moe": cfg.is_moe_layer(j),
            "mixer": cfg.mixer(j),
        })
    return kinds


def has_state(cfg) -> bool:
    """Whether some sub-layer slot is a Mamba mixer: its paged serving
    keeps per-sequence recurrent state beside the K/V pages."""
    return any(cfg.mixer(j) == "mamba" for j in range(period_of(cfg)))


def hybrid_groups(cfg):
    """zamba2: sizes of mamba-layer groups between shared-attn applications."""
    k = cfg.shared_attn_every
    L = cfg.num_layers
    sizes = []
    while L > 0:
        sizes.append(min(k, L))
        L -= k
    return sizes


# ------------------------------------------------------------ param defs ----
def _dense_sublayer_defs(cfg, kind) -> dict:
    d = cfg.d_model
    defs: Dict[str, Any] = {"ln1": norm_def(d), "ln2": norm_def(d)}
    if kind.get("mixer") == "mamba":
        defs["mamba"] = ssm_lib.mamba_defs(cfg)
    else:
        defs["attn"] = attn.attn_defs(d, cfg.num_heads, cfg.num_kv_heads,
                                      cfg.resolved_head_dim)
    if kind["moe"]:
        defs["moe"] = moe_lib.moe_defs(d, cfg.moe, cfg.activation)
    else:
        defs["ffn"] = ffn_defs(d, cfg.d_ff, cfg.activation)
    if cfg.sandwich_norm:
        defs["ln1_post"] = norm_def(d)
        defs["ln2_post"] = norm_def(d)
    return defs


def param_defs(cfg) -> dict:
    d = cfg.d_model
    defs: Dict[str, Any] = {"embed": embed_defs(cfg.padded_vocab, d),
                            "final_norm": norm_def(d)}
    if not cfg.tie_embeddings:
        defs["lm_head"] = PDef((d, cfg.padded_vocab), ("embed", "vocab"),
                               "scaled")
    if cfg.frontend == "vision_stub":
        defs["frontend_proj"] = PDef((d, d), ("embed", "embed2"), "scaled")

    if cfg.family == "ssm":
        defs["mamba"] = stacked({"m": ssm_lib.mamba_defs(cfg)},
                                cfg.num_layers)["m"]
        defs["mamba_ln"] = stacked({"m": norm_def(d)}, cfg.num_layers)["m"]
    elif cfg.family == "hybrid":
        defs["mamba"] = stacked({"m": ssm_lib.mamba_defs(cfg)},
                                cfg.num_layers)["m"]
        defs["mamba_ln"] = stacked({"m": norm_def(d)}, cfg.num_layers)["m"]
        defs["shared"] = {
            "fuse_in": PDef((2 * d, d), ("embed2", "embed"), "scaled"),
            "fuse_out": PDef((d, d), ("embed2", "embed"), "scaled"),
            **_dense_sublayer_defs(cfg, {"attn": "global", "moe": False}),
        }
    else:
        P = period_of(cfg)
        kinds = sublayer_kinds(cfg)
        n_groups = cfg.num_layers // P
        assert cfg.num_layers % P == 0, (cfg.name, cfg.num_layers, P)
        defs["blocks"] = {
            f"sub{j}": stacked(_dense_sublayer_defs(cfg, kinds[j]), n_groups)
            for j in range(P)
        }
    return defs


# ----------------------------------------------------------------- blocks ----
def _residual(cfg, x, f):
    """x + residual_multiplier * f, rounded once (the plain sum where the
    multiplier is 1)."""
    if cfg.residual_multiplier == 1.0:
        return x + f
    return (x.astype(F32) + f.astype(F32) * cfg.residual_multiplier
            ).astype(x.dtype)


def _norm(x, scale, cfg):
    """RMSNorm of the residual x, in the compute dtype (a float32 residual
    stream feeds bf16 matmuls)."""
    return rms_norm(x, scale, cfg.norm_eps).astype(cfg.dtype)


def _route_in(p, x, cfg):
    """The feed-forward norm of x in float32 (not rounded to x's dtype),
    which the MoE router scores."""
    return rms_norm(x.astype(F32), p["ln2"], cfg.norm_eps)


def _dense_block_fwd(p, x, kind, cfg, positions, ac: Ac, dot=None,
                     want_cache=True, ring=True):
    h = _norm(x, p["ln1"], cfg)
    if "mamba" in p:
        a, cache = ssm_lib.mamba_block_fwd(p["mamba"], h, cfg, dot=dot)
    else:
        a, cache = attn.attention_fwd(p["attn"], h, kind["attn"], cfg,
                                      positions, dot=dot, ring=ring)
    if cfg.sandwich_norm:
        a = rms_norm(a, p["ln1_post"], cfg.norm_eps)
    x = ac(_residual(cfg, x, a), "resid")
    h = _norm(x, p["ln2"], cfg)
    if kind["moe"]:
        f, aux = moe_lib.moe_apply(p["moe"], h, cfg.moe, cfg.activation,
                                   dot=dot, ac=ac,
                                   x_route=_route_in(p, x, cfg))
    else:
        f, aux = ffn_apply(p["ffn"], h, cfg.activation, dot=dot), 0.0
    if cfg.sandwich_norm:
        f = rms_norm(f, p["ln2_post"], cfg.norm_eps)
    x = ac(_residual(cfg, x, f), "resid")
    if want_cache and ring and kind["attn"] == "local" and "attn" in p:
        W = cfg.window_size
        cache = {"k": _to_ring(cache["k"], W), "v": _to_ring(cache["v"], W)}
    return x, (cache if want_cache else None), aux


def _to_ring(k: jax.Array, W: int) -> jax.Array:
    S = k.shape[1]
    if S >= W:
        return attn._last_window_ring(k, W)
    pad = [(0, 0)] * k.ndim
    pad[1] = (0, W - S)
    return jnp.pad(k, pad)


def _dense_block_decode(p, x, cache, pos, kind, cfg, dot=None, ac=None):
    h = _norm(x, p["ln1"], cfg)
    if "mamba" in p:
        a, new_cache = ssm_lib.mamba_block_decode(p["mamba"], h, cache, cfg,
                                                  dot=dot)
    else:
        a, ck, cv = attn.attention_decode(p["attn"], h, cache["k"],
                                          cache["v"], pos, kind["attn"], cfg,
                                          dot=dot, ac=ac)
        new_cache = {"k": ck, "v": cv}
    if cfg.sandwich_norm:
        a = rms_norm(a, p["ln1_post"], cfg.norm_eps)
    x = _residual(cfg, x, a)
    h = _norm(x, p["ln2"], cfg)
    if kind["moe"]:
        f, _ = moe_lib.moe_apply(p["moe"], h, cfg.moe, cfg.activation,
                                 dot=dot, x_route=_route_in(p, x, cfg))
    else:
        f = ffn_apply(p["ffn"], h, cfg.activation, dot=dot)
    if cfg.sandwich_norm:
        f = rms_norm(f, p["ln2_post"], cfg.norm_eps)
    return _residual(cfg, x, f), new_cache


def _shared_block_fwd(p, x, emb, cfg, positions, ac, dot=None,
                      want_cache=True):
    u = jnp.concatenate([x, emb], axis=-1)
    u = jnp.einsum("bsd,de->bse", u, p["fuse_in"])
    u, cache, _ = _dense_block_fwd(
        p, u, {"attn": "global", "moe": False}, cfg, positions, ac, dot=dot,
        want_cache=want_cache)
    v = jnp.einsum("bsd,de->bse", u, p["fuse_out"])
    return ac(x + v, "resid"), cache


def _shared_block_decode(p, x, emb, cache, pos, cfg, dot=None, ac=None):
    u = jnp.concatenate([x, emb], axis=-1)
    u = jnp.einsum("bsd,de->bse", u, p["fuse_in"])
    u, cache = _dense_block_decode(p, u, cache, pos,
                                   {"attn": "global", "moe": False}, cfg,
                                   dot=dot, ac=ac)
    v = jnp.einsum("bsd,de->bse", u, p["fuse_out"])
    return x + v, cache


# ---------------------------------------------------------------- embed ----
def embed_tokens(params, tokens, cfg):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.moe:
        # top-k routing turns near-equal router scores into different
        # experts: carry the residual stream in float32, so that the
        # router sees its input without a rounding at every layer
        x = x.astype(F32)
    if cfg.embedding_multiplier != 1.0:
        x = (x.astype(F32) * cfg.embedding_multiplier).astype(x.dtype)
    return x


def _assemble_input(params, batch, cfg, ac: Ac):
    """Returns (x (B,S,D), loss_mask (B,S) or None)."""
    if cfg.frontend == "vision_stub":
        patches = batch["patches"].astype(jnp.bfloat16)
        pe = jnp.einsum("bsd,de->bse", patches, params["frontend_proj"])
        te = embed_tokens(params, batch["tokens"], cfg)
        x = jnp.concatenate([pe, te], axis=1)
        mask = jnp.concatenate(
            [jnp.zeros(pe.shape[:2], F32), jnp.ones(te.shape[:2], F32)],
            axis=1)
        return ac(x, "resid"), mask
    x = embed_tokens(params, batch["tokens"], cfg)
    return ac(x, "resid"), None


def unembed(params, x, cfg, *, dot=None):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    dot = dot or (lambda a, ww, name: jnp.einsum(
        "bsd,dv->bsv", a, ww, preferred_element_type=jnp.float32))
    logits = softcap(dot(x, w, "lm_head").astype(F32), cfg.logit_softcap)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.padded_vocab != cfg.vocab_size:  # mask vocab-padding columns
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, -1e9)
    return logits


# ------------------------------------------------------------ chunked CE ----
def chunked_ce(params, hidden, labels, cfg, *, dot=None, chunk: int = 256,
               loss_mask=None):
    """Next-token CE without materializing (B,S,V) logits: unembed + softmax
    run per seq-chunk inside a rematerialized scan, so peak live memory is
    (B, chunk, V) instead of (B, S, V) — the difference between fitting and
    not fitting 16GiB/chip for the 256k-vocab archs."""
    xs = hidden[:, :-1]
    ls = labels[:, 1:]
    B, n, D = xs.shape
    mask = jnp.ones((B, n), F32) if loss_mask is None \
        else loss_mask[:, 1:].astype(F32)
    chunk = min(chunk, n)
    pad = (-n) % chunk
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        ls = jnp.pad(ls, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nc = (n + pad) // chunk
    xs = jnp.moveaxis(xs.reshape(B, nc, chunk, D), 1, 0)
    ls = jnp.moveaxis(ls.reshape(B, nc, chunk), 1, 0)
    mask = jnp.moveaxis(mask.reshape(B, nc, chunk), 1, 0)

    @jax.checkpoint
    def body(carry, inp):
        xc, lc, mc = inp
        logits = unembed(params, xc, cfg, dot=dot)          # (B,chunk,V) f32
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        tot, cnt = carry
        return (tot + jnp.sum((logz - gold) * mc), cnt + jnp.sum(mc)), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros((), F32),
                                        jnp.zeros((), F32)), (xs, ls, mask))
    return tot / jnp.maximum(cnt, 1.0)


# --------------------------------------------------------------- forward ----
def forward(params, batch, cfg, *, want_cache: bool, remat: bool = False,
            ac: Ac = _identity_ac, dot=None, unembed_mode: str = "full",
            cache_layout: str = "ring"):
    """Full-sequence forward (training / prefill).

    unembed_mode: "full" -> logits (B,S,V); "last" -> logits (B,1,V) (prefill);
    "none" -> final hidden states (B,S,D) (training loss path).
    cache_layout: "ring" -> local-attention caches in ring layout (dense
    decode); "full" -> chronological full-length caches (paged engine).
    Returns (logits_or_hidden, caches or None, aux scalar, loss_mask).
    """
    ring = cache_layout == "ring"
    x, loss_mask = _assemble_input(params, batch, cfg, ac)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    aux_total = jnp.zeros((), F32)
    caches: Dict[str, Any] = {}

    if cfg.family in ("ssm", "hybrid"):
        emb0 = x

        def mamba_body(carry, xs):
            h = carry
            pm, ln = xs
            y, cache = ssm_lib.mamba_block_fwd(
                pm, rms_norm(h, ln, cfg.norm_eps), cfg, dot=dot)
            return ac(h + y, "resid"), (cache if want_cache else None)

        body = jax.checkpoint(mamba_body) if remat else mamba_body

        if cfg.family == "ssm":
            x, mcache = jax.lax.scan(body, x,
                                     (params["mamba"], params["mamba_ln"]))
            caches["mamba"] = mcache
        else:
            sizes = hybrid_groups(cfg)
            shared_caches, mamba_caches = [], []
            off = 0
            for g, size in enumerate(sizes):
                x, sc = _shared_block_fwd(params["shared"], x, emb0, cfg,
                                          positions, ac, dot=dot,
                                          want_cache=want_cache)
                shared_caches.append(sc)
                sl = jax.tree.map(
                    lambda a: jax.lax.slice_in_dim(a, off, off + size, axis=0),
                    (params["mamba"], params["mamba_ln"]))
                x, mc = jax.lax.scan(body, x, sl)
                mamba_caches.append(mc)
                off += size
            if want_cache:
                caches["shared"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *shared_caches)
                caches["mamba"] = jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, axis=0), *mamba_caches)
    else:
        P = period_of(cfg)
        kinds = sublayer_kinds(cfg)

        def group_body(carry, xs):
            h, aux = carry
            outs = {}
            for j in range(P):
                h, outs[f"sub{j}"], aux_j = _dense_block_fwd(
                    xs[f"sub{j}"], h, kinds[j], cfg, positions, ac, dot=dot,
                    want_cache=want_cache, ring=ring)
                aux = aux + aux_j
            return (h, aux), (outs if want_cache else None)

        body = jax.checkpoint(group_body) if remat else group_body
        (x, aux_total), gcaches = jax.lax.scan(
            body, (x, aux_total), params["blocks"])
        if want_cache:
            caches.update(gcaches)

    x = _norm(x, params["final_norm"], cfg)
    if unembed_mode == "none":
        return x, (caches if want_cache else None), aux_total, loss_mask
    if unembed_mode == "last":
        x = x[:, -1:]
    logits = unembed(params, x, cfg, dot=dot)
    return logits, (caches if want_cache else None), aux_total, loss_mask


# ----------------------------------------------------------------- decode ----
def decode_step(params, cache, token, pos, cfg, *, ac: Ac = _identity_ac,
                dot=None):
    """token (B,1) int32, pos scalar int32. Returns (logits (B,1,V), cache)."""
    x = embed_tokens(params, token, cfg)
    emb0 = x

    if cfg.family in ("ssm", "hybrid"):
        def mamba_body(h, xs):
            pm, ln, c = xs
            y, nc = ssm_lib.mamba_block_decode(
                pm, rms_norm(h, ln, cfg.norm_eps), c, cfg, dot=dot)
            return h + y, nc

        if cfg.family == "ssm":
            x, mcache = jax.lax.scan(
                mamba_body, x,
                (params["mamba"], params["mamba_ln"], cache["mamba"]))
            new_cache = {"mamba": mcache}
        else:
            sizes = hybrid_groups(cfg)
            new_shared, new_mamba = [], []
            off = 0
            for g, size in enumerate(sizes):
                sc = jax.tree.map(lambda a: a[g], cache["shared"])
                x, nsc = _shared_block_decode(params["shared"], x, emb0, sc,
                                              pos, cfg, dot=dot, ac=ac)
                new_shared.append(nsc)
                sl = jax.tree.map(
                    lambda a: jax.lax.slice_in_dim(a, off, off + size, axis=0),
                    (params["mamba"], params["mamba_ln"]))
                mc = jax.tree.map(
                    lambda a: jax.lax.slice_in_dim(a, off, off + size, axis=0),
                    cache["mamba"])
                x, nmc = jax.lax.scan(mamba_body, x, sl + (mc,))
                new_mamba.append(nmc)
                off += size
            new_cache = {
                "shared": jax.tree.map(lambda *xs: jnp.stack(xs), *new_shared),
                "mamba": jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                                      *new_mamba),
            }
    else:
        P = period_of(cfg)
        kinds = sublayer_kinds(cfg)

        def group_body(h, xs):
            blocks, caches_g = xs
            new_g = {}
            for j in range(P):
                h, new_g[f"sub{j}"] = _dense_block_decode(
                    blocks[f"sub{j}"], h, caches_g[f"sub{j}"], pos, kinds[j],
                    cfg, dot=dot, ac=ac)
            return h, new_g

        x, gcaches = jax.lax.scan(
            group_body, x, (params["blocks"],
                            {k: cache[k] for k in cache if k.startswith("sub")}))
        new_cache = gcaches

    x = _norm(x, params["final_norm"], cfg)
    logits = unembed(params, x, cfg, dot=dot)
    return logits, new_cache


# ------------------------------------------------------ paged programs ----
PAGED_FAMILIES = ("dense", "moe", "vlm", "hybrid_moe")


def _check_paged(cfg, what: str) -> None:
    if cfg.family not in PAGED_FAMILIES:
        raise NotImplementedError(
            f"{what} supports the families {PAGED_FAMILIES} only, "
            f"got {cfg.family!r}")


def _paged_layers(params, pool, x, cfg, attend, recur, dot):
    """Every layer of a paged program against its pool.

    Each sub-slot's pool leaves are flattened over the layer groups —
    group g's pages (or state rows) start at ``g`` times the leaf's second
    dim — and carried through the groups, so each layer writes its group's
    part of the pool in place: no pool is sliced out of the stack or
    stacked again. ``attend(p, h, kv, off, kind)`` and ``recur(p, h, st,
    off)`` run one layer's mixer on its sub-slot's flattened leaves, its
    group starting at ``off``. Returns (x, new_pool), the pool in
    ``pool_specs``' shape.

    The groups are scanned, except in models with Mamba sub-slots, which
    unroll their few groups: scanned, their chunk step compiles to other
    roundings (seen on the CPU) and their served tokens would move."""
    P = period_of(cfg)
    kinds = sublayer_kinds(cfg)
    flat = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), pool)

    def group(carry, xs):
        x, flat = carry
        blocks, g = xs
        flat = dict(flat)
        for j in range(P):
            name = f"sub{j}"
            p = blocks[name]
            off = g * jax.tree.leaves(pool[name])[0].shape[1]
            h = _norm(x, p["ln1"], cfg)
            if kinds[j]["mixer"] == "mamba":
                a, flat[name] = recur(p["mamba"], h, flat[name], off)
            else:
                a, flat[name] = attend(p["attn"], h, flat[name], off,
                                       kinds[j])
            if cfg.sandwich_norm:
                a = rms_norm(a, p["ln1_post"], cfg.norm_eps)
            x = _residual(cfg, x, a)
            h = _norm(x, p["ln2"], cfg)
            if kinds[j]["moe"]:
                f = moe_lib.moe_serve(p["moe"], h, cfg.moe, cfg.activation,
                                      dot=dot, x_route=_route_in(p, x, cfg))
            else:
                f = ffn_apply(p["ffn"], h, cfg.activation, dot=dot)
            if cfg.sandwich_norm:
                f = rms_norm(f, p["ln2_post"], cfg.norm_eps)
            x = _residual(cfg, x, f)
        return (x, flat), None

    n_groups = cfg.num_layers // P
    if has_state(cfg):
        for g in range(n_groups):
            (x, flat), _ = group((x, flat), (jax.tree.map(
                lambda a: a[g], params["blocks"]), g))
    else:
        (x, flat), _ = jax.lax.scan(
            group, (x, flat),
            (params["blocks"], jnp.arange(n_groups, dtype=jnp.int32)))
    return x, jax.tree.map(lambda a, o: a.reshape(o.shape), flat, pool)


def decode_step_paged(params, pool, page_table, token, positions, cfg, *,
                      rows=None, dot=None, kernel="auto"):
    """Batched slot-indexed decode against a paged KV pool.

    token (B,1) int32; positions (B,) int32 per-sequence absolute positions
    (continuous batching: every batch slot may be at a different depth);
    pool is the pytree from ``pool_specs`` and page_table (B, n_pages) maps
    each sequence's logical blocks to physical pages (shared across layers).
    ``kernel`` selects the paged-attention path (see attention_decode_paged)
    — every choice walks pages block-by-block; no layer materializes the
    dense chronological KV view, and local layers trim the walk to their
    window. Each token's K/V is written into the pool in place
    (``_paged_layers``, ``attention.write_pages``). With Mamba sub-slots,
    ``rows`` (B,) int32 names each batch row's state slot (idle rows: the
    scratch slot). Returns (logits (B,1,V), new_pool).
    """
    _check_paged(cfg, "paged decode")
    x = embed_tokens(params, token, cfg)

    def attend(p, h, kv, off, kind):
        a, k, v = attn.attention_decode_paged(
            p, h, kv["k"], kv["v"], page_table + off, positions,
            kind["attn"], cfg, dot=dot, kernel=kernel)
        return a, {"k": k, "v": v}

    def recur(p, h, st, off):
        a, conv, state = ssm_lib.mamba_decode_rows(
            p, h, st["conv"], st["state"], rows + off, cfg, dot=dot,
            kernel=kernel)
        return a, {"conv": conv, "state": state}

    x, new_pool = _paged_layers(params, pool, x, cfg, attend, recur, dot)
    x = _norm(x, params["final_norm"], cfg)
    return unembed(params, x, cfg, dot=dot), new_pool


def prefill_chunk_paged(params, pool, page_table, tokens, positions, cfg, *,
                        rows=None, lengths=None, dot=None, kernel="auto"):
    """One chunked-prefill step: run ``tokens`` (B, Sq) — a contiguous
    prompt chunk whose first token sits at absolute position
    ``positions[b]`` — through every layer, writing each layer's chunk K/V
    into the paged pool in place (the pages the chunk touches, see
    ``attention.write_pages``) and attending over the pool itself
    (resident prompt prefix + the chunk, causal within the chunk). The
    engine calls this once per tick per mid-prefill sequence, so one long
    prompt costs many small ticks instead of one decode-stalling bucket.

    Returns (hidden (B, Sq, D) final-norm hidden states, new_pool) — the
    caller unembeds only the rows it needs (the last real prompt position
    of the final chunk; intermediate chunks need no logits at all).

    With Mamba sub-slots, sequence b's conv tail and state live in slot
    ``rows[b]``; a chunk at position 0 starts from zeros, a later one from
    the slot, and only its first ``lengths[b]`` rows (the real prompt
    tokens) advance the state.
    """
    _check_paged(cfg, "paged prefill")
    x = embed_tokens(params, tokens, cfg)

    def attend(p, h, kv, off, kind):
        a, k, v = attn.attention_prefill_paged(
            p, h, kv["k"], kv["v"], page_table + off, positions,
            kind["attn"], cfg, dot=dot, kernel=kernel)
        return a, {"k": k, "v": v}

    def recur(p, h, st, off):
        r = rows + off
        fresh = positions == 0
        conv, state = st["conv"], st["state"]
        cache = {"conv": jnp.where(fresh[:, None, None], 0, conv[r]),
                 "state": jnp.where(fresh[:, None, None, None], 0.0,
                                    state[r])}
        a, new = ssm_lib.mamba_block_fwd(p, h, cfg, dot=dot, cache=cache,
                                         lengths=lengths)
        return a, {"conv": conv.at[r].set(new["conv"].astype(conv.dtype),
                                          mode="promise_in_bounds"),
                   "state": state.at[r].set(new["state"],
                                            mode="promise_in_bounds")}

    x, new_pool = _paged_layers(params, pool, x, cfg, attend, recur, dot)
    return _norm(x, params["final_norm"], cfg), new_pool


def normalize_kv_bits(cfg, kv_bits) -> Optional[Tuple[int, ...]]:
    """Canonicalize a KV bit spec to one entry per sub-layer slot.

    Accepts None (fp pool), an int (uniform), a dict keyed ``sub{j}`` or
    ``kv_sub{j}`` (the HAQ site names — a searched policy round-trips
    as-is; missing slots default to 16, unknown keys are rejected rather
    than silently dropping quantization), or a sequence cycled over the
    period like ``attn_pattern``. All-16 collapses to None so the fp pool
    layout (and its bit-exact serving path) stays the default
    representation."""
    if kv_bits is None:
        return None
    P = period_of(cfg)
    if isinstance(kv_bits, int):
        bits = (kv_bits,) * P
    elif isinstance(kv_bits, dict):
        by_slot = {}
        for key, v in kv_bits.items():
            slot = key[3:] if key.startswith("kv_sub") else key
            j = int(slot[3:]) if slot.startswith("sub") \
                and slot[3:].isdigit() else -1
            if not 0 <= j < P:
                raise ValueError(f"unknown KV policy key {key!r} "
                                 f"(period-{P} pool has sub0..sub{P - 1})")
            by_slot[j] = int(v)
        bits = tuple(by_slot.get(j, 16) for j in range(P))
    else:
        seq = tuple(int(b) for b in kv_bits)
        if not seq or P % len(seq):
            raise ValueError(f"kv_bits length {len(seq)} does not cycle "
                             f"into period {P}")
        bits = tuple(seq[j % len(seq)] for j in range(P))
    for b in bits:
        if b not in (4, 8, 16):
            raise ValueError(f"KV bits must be 4, 8 or 16, got {b}")
    if all(b == 16 for b in bits):
        return None
    if any(b == 4 for b in bits) and cfg.resolved_head_dim % 2:
        raise ValueError("int4 KV packs two codes per byte along head_dim; "
                         f"head_dim={cfg.resolved_head_dim} is odd")
    return bits


def pool_specs(cfg, num_pages: int, page_size: int, kv_bits=None,
               state_slots: int = 0):
    """Abstract paged-KV-pool pytree: per sub-layer slot, k/v pools of shape
    (n_groups, num_pages, K, page_size, hd) — kv-head-major within a page,
    so one kv head's (page_size, hd) tile is contiguous and is a block the
    TPU paged-attention kernel can tile (kernels/paged_attention.py). Page
    ids are shared across layers — one logical page allocation covers every
    layer's pool. Local (sliding-window) layers use the same full-length
    pages and are masked to the window at attention time; the engine frees
    pages behind the window when every layer is local
    (serving/engine/scheduler.py::trim_window).

    ``kv_bits`` (see normalize_kv_bits) selects the HAQ KV-quantized layout
    per sub-layer slot: 16 keeps the arrays in the configuration's dtype
    (bf16); 8/4 store
    ``{"q": int8 (n_groups, num_pages, K, page_size, hd_store),
       "scale": fp32 (n_groups, num_pages, K, page_size)}``
    with hd_store = hd for int8 and hd//2 for int4 (two codes per byte
    packed along head_dim). Scales are per page slot (token) and per kv
    head — each physical page carries its own (K, page_size) scale tile, so
    quantize-on-write never re-scales resident tokens (see
    serving/kvquant).

    Mamba sub-slots hold no pages: each keeps ``state_slots`` rows (the
    engine's batch slots and a scratch row) of recurrent state beside the
    pages, ``{"conv": (n_groups, state_slots, W-1, d_conv), "state":
    fp32 (n_groups, state_slots, H, P, N)}``."""
    _check_paged(cfg, "the paged pool")
    if has_state(cfg):
        if kv_bits is not None:
            raise NotImplementedError(
                "quantized K/V pages beside recurrent state are not built")
        if state_slots < 1:
            raise ValueError("a model with Mamba layers needs state_slots")
    hd = cfg.resolved_head_dim
    K = cfg.num_kv_heads
    P = period_of(cfg)
    n_groups = cfg.num_layers // P
    bits = normalize_kv_bits(cfg, kv_bits) or (16,) * P

    def kv_spec(b):
        if b == 16:
            return jax.ShapeDtypeStruct(
                (n_groups, num_pages, K, page_size, hd), cfg.dtype)
        hd_store = hd if b == 8 else hd // 2
        return {
            "q": jax.ShapeDtypeStruct(
                (n_groups, num_pages, K, page_size, hd_store), jnp.int8),
            "scale": jax.ShapeDtypeStruct(
                (n_groups, num_pages, K, page_size), jnp.float32),
        }

    def state_spec():
        one = ssm_lib.mamba_cache_spec(cfg, state_slots)
        return {k: jax.ShapeDtypeStruct((n_groups,) + v.shape, v.dtype)
                for k, v in one.items()}

    return {f"sub{j}": state_spec() if cfg.mixer(j) == "mamba"
            else {"k": kv_spec(bits[j]), "v": kv_spec(bits[j])}
            for j in range(P)}


def to_page_layout(c, page_size: int):
    """Full-layout cache (G, T, K, hd), T a multiple of ``page_size`` ->
    its pages in pool layout (G, T // page_size, K, page_size, hd): logical
    block i of the sequence becomes page i."""
    G, T, K, hd = c.shape
    c = c.reshape(G, T // page_size, page_size, K, hd)
    return jnp.swapaxes(c, 2, 3)


def pool_axes(cfg, kv_bits=None):
    """Logical-axis pytree matching ``pool_specs`` (for the SPMD serving
    engine). ``kv_heads`` is the only mesh-mapped axis: the page and
    page-slot dims stay unsharded because the paged-attention walk's online
    softmax must keep its single-device reduction order (bit-exact serving),
    and pages are the host allocator's unit — one logical page id covers
    every shard's kv-head slice of that page. The dense decode path's
    ``cache_seq`` fall-through (see distributed.sharding.CANDIDATES) does
    not apply here for the same reason."""
    kv = ("layer", None, "kv_heads", None, "head_dim")
    scale = ("layer", None, "kv_heads", None)
    return jax.tree.map(
        lambda s: kv if s.ndim == 5 else scale,
        pool_specs(cfg, 2, 2, kv_bits=kv_bits))


# ------------------------------------------------------------ cache specs ----
def cache_specs(cfg, batch: int, seq_len: int):
    """Abstract decode-cache pytree for dry-run lowering / allocation."""
    hd = cfg.resolved_head_dim
    K = cfg.num_kv_heads

    def kv(T, lead):
        return {
            "k": jax.ShapeDtypeStruct(lead + (batch, T, K, hd), jnp.bfloat16),
            "v": jax.ShapeDtypeStruct(lead + (batch, T, K, hd), jnp.bfloat16),
        }

    if cfg.family == "ssm":
        one = ssm_lib.mamba_cache_spec(cfg, batch)
        return {"mamba": jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((cfg.num_layers,) + s.shape,
                                           s.dtype), one)}
    if cfg.family == "hybrid":
        one = ssm_lib.mamba_cache_spec(cfg, batch)
        n_apps = len(hybrid_groups(cfg))
        return {
            "mamba": jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((cfg.num_layers,) + s.shape,
                                               s.dtype), one),
            "shared": kv(seq_len, (n_apps,)),
        }
    P = period_of(cfg)
    kinds = sublayer_kinds(cfg)
    n_groups = cfg.num_layers // P
    out = {}
    for j in range(P):
        if kinds[j]["mixer"] == "mamba":
            out[f"sub{j}"] = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((n_groups,) + s.shape,
                                               s.dtype),
                ssm_lib.mamba_cache_spec(cfg, batch))
            continue
        T = attn.cache_len_for(kinds[j]["attn"], cfg, seq_len)
        out[f"sub{j}"] = kv(T, (n_groups,))
    return out


def init_cache(cfg, batch: int, seq_len: int):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_specs(cfg, batch, seq_len))


def cache_axes(cfg):
    """Logical-axis pytree matching cache_specs (for sharding)."""
    kv_ax = {"k": ("layer", "batch", "cache_seq", "kv_heads", "head_dim"),
             "v": ("layer", "batch", "cache_seq", "kv_heads", "head_dim")}
    mamba_ax = {"conv": ("layer", "batch", "conv", "ssm_inner"),
                "state": ("layer", "batch", "ssm_heads", "head_dim",
                          "ssm_state")}
    if cfg.family == "ssm":
        return {"mamba": mamba_ax}
    if cfg.family == "hybrid":
        return {"mamba": mamba_ax, "shared": dict(kv_ax)}
    P = period_of(cfg)
    return {f"sub{j}": dict(mamba_ax) if cfg.mixer(j) == "mamba"
            else dict(kv_ax) for j in range(P)}
