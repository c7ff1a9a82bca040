"""Plain float32 reference of a served decoder, and its lower-precision
control. Imports nothing of the program and takes nothing it made: the
weights are made again here from the seed (bench/weights.py), one layer at
a time, rounded to the bfloat16 the configuration serves and computed in
float32 at ``highest`` matmul precision.

What every model family shares is here: the embedding rows, the final
RMSNorm (multiplying by 1 + scale) and the blocked unembedding (the
embedding's transpose where the configuration ties them), teacher
forcing, the served tokens' gaps and the control. The blocks in between
are the family's: ``forward`` of bench/families/<family>.py, built from
the helpers below, which also gives the parameter layout (``leaf_specs``).

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
ROW_BLOCK = 128        # rows per unembedding block
V_BLOCK = 16384        # vocabulary columns per unembedding block
FP8_MAX = 448.0


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def q(x, axis, fp8: bool):
    """x, or with ``fp8`` x rounded to float8 e4m3 with one absmax scale
    per slice along ``axis``: every matmul operand goes through this."""
    return _fp8(x, axis) if fp8 else x


def mm(eq, a, b):
    """A float32 einsum at ``highest`` precision."""
    return jnp.einsum(eq, a, b, precision=HI, preferred_element_type=F32)


def rms(x, scale, eps):
    """RMSNorm multiplying by (1 + scale)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def as_served(spec: W.Leaf, v):
    """The value the program serves: rounded to the leaf's dtype."""
    return v.astype(spec.dtype).astype(F32)


def layer_keys(keys: Dict[W.Path, np.ndarray], prefix: W.Path, layer: int):
    """Layer ``layer``'s key of every stacked leaf under ``prefix``."""
    return {p: k[layer] for p, k in keys.items()
            if p[:len(prefix)] == prefix}


def layer_weights(specs: Dict[W.Path, W.Leaf], prefix: W.Path, keys):
    """One layer of every leaf whose ``layer_keys`` are given, as served
    (float32), nested by the rest of its path below ``prefix``."""
    out = {}
    for p, k in keys.items():
        s = specs[p]
        one = s.shape[1:]
        out[p[len(prefix):]] = as_served(
            s, W.block_values(k, one, (0,) * len(one), one, s.std))
    return W.nest(out)


class Reference:
    """The reference forward of one configuration and seed; ``family`` is
    the configuration's module of bench/families/."""

    def __init__(self, family, m: dict, seed: int, precision: str = "f32"):
        if precision not in ("f32", "fp8"):
            raise ValueError(precision)
        self.family, self.m, self.precision = family, m, precision
        self.fp8 = precision == "fp8"
        self.specs = family.leaf_specs(m)
        self.keys = W.leaf_keys(self.specs, seed)
        self._embed = jax.jit(self._embed_rows)
        self._head = jax.jit(self._head_rows)

    def _embed_rows(self, key, toks):
        s = self.specs[("embed",)]
        return as_served(s, W.row_values(key, s.shape, toks, s.std))

    def _head_rows(self, keys, x, served):
        """Per row of final hidden x (R, d): (best logit, its token, the
        logit of ``served``)."""
        m = self.m
        V = m["vocab_size"]
        sn = self.specs[("final_norm",)]
        fn = as_served(sn, W.block_values(keys["final_norm"], sn.shape,
                                           (0,), sn.shape, sn.std))
        h = q(rms(x, fn, m["norm_eps"]), -1, self.fp8)
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
            w = as_served(sh, head_block(c0))
            lg = mm("rd,dv->rv", h, q(w, 0, self.fp8))
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
        return self.family.forward(self.m, self.specs, self.keys, xs,
                                   self.precision)

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


def served_gaps(family, m: dict, seed: int, requests, *,
                control: bool = False):
    """Per served token, the reference's best logit minus its logit of the
    token served. With ``control=True`` also, per position, the same gap
    of the token that the fp8 control puts first (same prompts and served
    tokens), read on the float32 reference's logits. ``family`` is the
    configuration's module of bench/families/. Returns (served gaps,
    control gaps or None)."""
    seqs, picks = teacher_forced(requests)
    ref = Reference(family, m, seed)
    hs = ref.final_hidden(seqs)
    rows = jnp.concatenate([h[p] for h, p in zip(hs, picks)])
    del hs
    served = np.concatenate([s for _, s in requests]).astype(np.int32)
    best, _, got = ref.head(rows, served)
    if not control:
        return best - got, None
    ctl = Reference(family, m, seed, "fp8")
    hc = ctl.final_hidden(seqs)
    crow = jnp.concatenate([h[p] for h, p in zip(hc, picks)])
    del hc
    _, choice, _ = ctl.head(crow, served)
    best_c, _, got_c = ref.head(rows, choice)
    return best - got, best_c - got_c
