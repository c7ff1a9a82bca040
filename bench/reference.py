"""Plain float32 reference of the dense decoder, and its lower-precision
control. Imports nothing of the program and takes nothing it made: the
weights are made again here from the seed (bench/weights.py), one layer at
a time, rounded to the bfloat16 the configuration serves and computed in
float32 at ``highest`` matmul precision.

Block: x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x)). RMSNorm multiplies by
(1 + scale). Attention is causal, grouped-query (query head h reads kv
head h // (H / K)), with rotary embedding over all head dims (the two
halves of a head rotated as a pair, base ``rope_theta``) and scale
hd^-1/2. FFN is SwiGLU (silu(x Wg) * (x Wi)) Wo or squared ReLU
relu(x Wi)^2 Wo. The head is RMSNorm then the unembedding (the embedding's transpose
where the configuration ties them).

``precision="fp8"`` is the control: the same forward with every matmul
operand (weights per output channel, activations per row, attention
probabilities per row) rounded to float8 e4m3 with an absmax scale.

``served_gaps`` runs a sample of served requests through (prompt and its
served tokens, teacher forced) in blocks that fit one chip, and returns
for each served token how far the reference's logit of the token chosen
falls below the reference's best logit at that position.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
BUCKET = 1024          # sequences are padded to a multiple of this
Q_BLOCK = 512          # query rows per attention block
ROW_BLOCK = 128        # rows per unembedding block
V_BLOCK = 16384        # vocabulary columns per unembedding block
FP8_MAX = 448.0


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _q(x, axis, fp8):
    return _fp8(x, axis) if fp8 else x


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HI, preferred_element_type=F32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None, None] * inv            # (T, 1, hd/2)
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * c - b * s, a * s + b * c], -1)


def _layer(m: dict, fp8: bool, w: Dict[str, jax.Array], x):
    """One block over one sequence x (T, d)."""
    T = x.shape[0]
    H, K = m["num_heads"], m["num_kv_heads"]
    hd = m.get("head_dim") or m["d_model"] // H
    G = H // K
    eps, theta = m["norm_eps"], m["rope_theta"]
    pos = jnp.arange(T)
    h = _q(_rms(x, w["ln1"], eps), -1, fp8)
    q = _rope(_mm("td,dnh->tnh", h, _q(w["wq"], 0, fp8)), pos, theta)
    k = _rope(_mm("td,dnh->tnh", h, _q(w["wk"], 0, fp8)), pos, theta)
    v = _mm("td,dnh->tnh", h, _q(w["wv"], 0, fp8))
    q = _q(q, -1, fp8).reshape(T, K, G, hd)
    k, v = _q(k, -1, fp8), _q(v, 0, fp8)
    outs = []
    for s0 in range(0, T, Q_BLOCK):
        qb = q[s0:s0 + Q_BLOCK]
        sc = _mm("tkgh,skh->kgts", qb, k) * (hd ** -0.5)
        causal = (jnp.arange(T)[None, :]
                  <= (s0 + jnp.arange(qb.shape[0]))[:, None])
        sc = jnp.where(causal, sc, -jnp.inf)
        p = _q(jax.nn.softmax(sc, axis=-1), -1, fp8)
        outs.append(_mm("kgts,skh->tkgh", p, v))
    o = _q(jnp.concatenate(outs, 0).reshape(T, H * hd), -1, fp8)
    x = x + _mm("te,ed->td", o, _q(w["wo"].reshape(H * hd, -1), 0, fp8))
    h = _q(_rms(x, w["ln2"], eps), -1, fp8)
    up = _mm("td,df->tf", h, _q(w["w_in"], 0, fp8))
    if "w_gate" in w:
        g = _mm("td,df->tf", h, _q(w["w_gate"], 0, fp8))
        a = jax.nn.silu(g) * up if m["activation"] == "swiglu" \
            else jax.nn.gelu(g, approximate=True) * up
    else:
        a = jnp.square(jax.nn.relu(up))
    return x + _mm("tf,fd->td", _q(a, -1, fp8), _q(w["w_out"], 0, fp8))


def _served(spec: W.Leaf, v):
    """The value the program serves: rounded to the leaf's dtype."""
    return v.astype(spec.dtype).astype(F32)


class Reference:
    """The reference forward of one configuration and seed."""

    def __init__(self, m: dict, seed: int, precision: str = "f32"):
        if precision not in ("f32", "fp8"):
            raise ValueError(precision)
        self.m, self.fp8 = m, precision == "fp8"
        self.specs = W.leaf_specs(m)
        self.keys = W.leaf_keys(self.specs, seed)
        self.blk = ("blocks", "sub0")
        self._layer_w = jax.jit(self._make_layer_weights)
        self._layer = jax.jit(lambda w, x: _layer(m, self.fp8, w, x))
        self._embed = jax.jit(self._embed_rows)
        self._head = jax.jit(self._head_rows)

    def _make_layer_weights(self, keys):
        out = {}
        for p, s in self.specs.items():
            if p[:2] != self.blk:
                continue
            one = s.shape[1:]
            v = W.block_values(keys[p], one, (0,) * len(one), one, s.std)
            out[p[-1]] = _served(s, v)
        return out

    def _embed_rows(self, key, toks):
        s = self.specs[("embed",)]
        return _served(s, W.row_values(key, s.shape, toks, s.std))

    def _head_rows(self, keys, x, served):
        """Per row of final hidden x (R, d): (best logit, its token, the
        logit of ``served``)."""
        m = self.m
        V = m["vocab_size"]
        sn = self.specs[("final_norm",)]
        fn = _served(sn, W.block_values(keys["final_norm"], sn.shape, (0,),
                                        sn.shape, sn.std))
        h = _q(_rms(x, fn, m["norm_eps"]), -1, self.fp8)
        tied = ("lm_head",) not in self.specs
        sh = self.specs[("embed",) if tied else ("lm_head",)]
        d = m["d_model"]
        n_blk = -(-V // V_BLOCK)

        def head_block(c0):
            """Columns c0 .. c0 + V_BLOCK of the unembedding (d, V_BLOCK):
            the embedding's rows, transposed, where the two are tied."""
            if tied:
                return W.block_values(keys["head"], sh.shape, (c0, 0),
                                      (V_BLOCK, d), sh.std).T
            return W.block_values(keys["head"], sh.shape, (0, c0),
                                  (d, V_BLOCK), sh.std)

        def body(carry, j):
            best, arg, got = carry
            c0 = j * V_BLOCK
            w = _served(sh, head_block(c0))
            lg = _mm("rd,dv->rv", h, _q(w, 0, self.fp8))
            col = c0 + jnp.arange(V_BLOCK)
            lg = jnp.where(col[None, :] < V, lg, -jnp.inf)
            b = jnp.max(lg, -1)
            a = c0 + jnp.argmax(lg, -1)
            hit = col[None, :] == served[:, None]
            got = got + jnp.sum(jnp.where(hit, lg, 0.0), -1)
            take = b > best
            return (jnp.where(take, b, best), jnp.where(take, a, arg),
                    got), None

        R = x.shape[0]
        init = (jnp.full((R,), -jnp.inf, F32), jnp.zeros((R,), jnp.int32),
                jnp.zeros((R,), F32))
        (best, arg, got), _ = jax.lax.scan(body, init, jnp.arange(n_blk))
        return best, arg, got

    def _head_keys(self):
        head = ("lm_head",) if ("lm_head",) in self.specs else ("embed",)
        return {"final_norm": self.keys[("final_norm",)][0],
                "head": self.keys[head][0]}

    def final_hidden(self, seqs: Sequence[np.ndarray]) -> List[jax.Array]:
        """Final-layer hidden states (before the final norm) of each token
        sequence, padded to a multiple of BUCKET."""
        ek = self.keys[("embed",)][0]
        xs = []
        for toks in seqs:
            T = -(-len(toks) // BUCKET) * BUCKET
            pad = np.zeros(T, np.int32)
            pad[:len(toks)] = toks
            xs.append(self._embed(ek, jnp.asarray(pad)))
        for l in range(self.m["num_layers"]):
            w = self._layer_w({p: k[l] for p, k in self.keys.items()
                               if p[:2] == self.blk})
            xs = [self._layer(w, x) for x in xs]
        return xs

    def head(self, rows: jax.Array, targets: np.ndarray):
        """(best logit, best token, logit of target) per row, in blocks."""
        R = rows.shape[0]
        Rp = -(-R // ROW_BLOCK) * ROW_BLOCK
        rows = jnp.pad(rows, ((0, Rp - R), (0, 0)))
        tg = np.zeros(Rp, np.int32)
        tg[:R] = targets
        out = [self._head(self._head_keys(), rows[i:i + ROW_BLOCK],
                          jnp.asarray(tg[i:i + ROW_BLOCK]))
               for i in range(0, Rp, ROW_BLOCK)]
        best, arg, got = (np.concatenate([np.asarray(o[j]) for o in out])[:R]
                          for j in range(3))
        return best, arg, got


def teacher_forced(requests: Sequence[Tuple[np.ndarray, np.ndarray]]):
    """For (prompt, served) pairs: the token sequences to run (prompt and
    every served token but the last) and, per request, the positions
    whose logits chose each served token."""
    seqs, picks = [], []
    for prompt, served in requests:
        seqs.append(np.concatenate([prompt, served[:-1]]).astype(np.int32))
        picks.append(np.arange(len(prompt) - 1, len(prompt) - 1 + len(served)))
    return seqs, picks


def served_gaps(m: dict, seed: int, requests, *, control: bool = False):
    """Per served token, the reference's best logit minus its logit of the
    token served. With ``control=True`` also, per position, the same gap
    of the token that the fp8 control puts first (same prompts and served
    tokens), read on the float32 reference's logits. Returns (served
    gaps, control gaps or None)."""
    seqs, picks = teacher_forced(requests)
    ref = Reference(m, seed)
    hs = ref.final_hidden(seqs)
    rows = jnp.concatenate([h[p] for h, p in zip(hs, picks)])
    del hs
    served = np.concatenate([s for _, s in requests]).astype(np.int32)
    best, _, got = ref.head(rows, served)
    if not control:
        return best - got, None
    ctl = Reference(m, seed, "fp8")
    hc = ctl.final_hidden(seqs)
    crow = jnp.concatenate([h[p] for h, p in zip(hc, picks)])
    del hc
    _, choice, _ = ctl.head(crow, served)
    best_c, _, got_c = ref.head(rows, choice)
    return best - got, best_c - got_c
