"""Seeded random weights, made from a counter-based hash.

Every element of every weight is a function of (seed, leaf name, layer,
element index) alone: a 32-bit murmur finalizer over the element's flat
index within its layer, keyed per (seed, leaf, layer). So the same value
comes out whether a leaf is made whole on one chip, sharded over four, or
one layer (or one block of rows or columns) at a time for the reference,
and on the CPU as on the TPU. Values are uniform with the leaf's standard
deviation, quantized to 16 bits before the one multiply by the scale, so
the float32 value is one correctly rounded product and the bfloat16 value
served is that product rounded once more.

A leaf table maps each parameter's path to its ``Leaf``: its shape as the
served program stores it (the layers stacked on a leading axis), its
dtype and the standard deviation of its values. Each model family gives
its own (``leaf_specs`` of bench/families/<family>.py); what is made from
a table is the same for every family. The harness checks the program's
own abstract parameter tree against the table before it makes anything.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

M32 = 0xFFFFFFFF
GOLDEN = 0x9E3779B1
NORM_STD = 0.1          # norm scales multiply by (1 + scale)


def fmix32(h: int) -> int:
    """murmur3's 32-bit finalizer on a Python int."""
    h &= M32
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & M32
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & M32
    h ^= h >> 16
    return h


def _fmix32_u32(x):
    """The same finalizer on a uint32 array (wrapping arithmetic)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


@dataclasses.dataclass(frozen=True)
class Leaf:
    shape: Tuple[int, ...]   # whole leaf, stacked layer axis first if any
    dtype: str               # "bfloat16" | "float32"
    std: float
    stacked: bool            # leading axis is the layer


Path = Tuple[str, ...]


def leaf_key(seed: int, path: Path, layer: int) -> int:
    """uint32 key of one (seed, leaf, layer); any non-negative seed."""
    h = fmix32(seed & M32 ^ 0x5BD1E995)
    h = fmix32(h ^ (seed >> 32) & M32)
    h = fmix32(h ^ ((seed >> 64) & M32))
    for ch in "/".join(path).encode():
        h = fmix32(h ^ ch)
    return fmix32((h + layer * GOLDEN) & M32)


def leaf_keys(specs: Dict[Path, Leaf], seed: int) -> Dict[Path, np.ndarray]:
    """Per leaf, a uint32 array of one key per layer (one for unstacked)."""
    return {p: np.asarray([leaf_key(seed, p, l) for l in
                           range(s.shape[0] if s.stacked else 1)], np.uint32)
            for p, s in specs.items()}


def block_values(key, full_shape, starts, sizes, std):
    """float32 values of the block ``[starts, starts + sizes)`` of one
    layer's leaf of shape ``full_shape`` (no layer axis), keyed by ``key``
    (a uint32 scalar). ``starts`` may be traced."""
    strides = np.cumprod((1,) + tuple(full_shape[::-1]))[:-1][::-1]
    idx = jnp.zeros(sizes, jnp.uint32)
    for ax, (st, stride) in enumerate(zip(starts, strides)):
        io = jax.lax.broadcasted_iota(jnp.uint32, sizes, ax)
        idx = idx + (io + jnp.asarray(st, jnp.uint32)) * jnp.uint32(stride)
    h = _fmix32_u32(idx * jnp.uint32(GOLDEN) + key)
    q = (h >> 16).astype(jnp.int32) - 32768          # uniform on [-2^15, 2^15)
    return q.astype(jnp.float32) * jnp.float32(std * math.sqrt(3.0) / 32768)


def row_values(key, full_shape, rows, std):
    """float32 rows ``rows`` (int array) of a 2-D leaf of one layer."""
    n_cols = full_shape[1]
    idx = (rows.astype(jnp.uint32)[:, None] * jnp.uint32(n_cols)
           + jax.lax.broadcasted_iota(jnp.uint32, (rows.shape[0], n_cols), 1))
    h = _fmix32_u32(idx * jnp.uint32(GOLDEN) + key)
    q = (h >> 16).astype(jnp.int32) - 32768
    return q.astype(jnp.float32) * jnp.float32(std * math.sqrt(3.0) / 32768)


def leaf_values(spec: Leaf, keys):
    """A whole leaf in its served dtype; ``keys`` is its (layers,) array."""
    if not spec.stacked:
        v = block_values(keys[0], spec.shape, (0,) * len(spec.shape),
                         spec.shape, spec.std)
        return v.astype(spec.dtype)
    one = spec.shape[1:]
    per_layer = jax.vmap(lambda k: block_values(
        k, one, (0,) * len(one), one, spec.std).astype(spec.dtype))
    return per_layer(keys)


def nest(flat: Dict[Path, object]) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = v
    return out


def flatten(tree) -> Dict[Path, object]:
    out = {}

    def walk(node, prefix):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, prefix + (k,))
        else:
            out[prefix] = node
    walk(tree, ())
    return out


def check_layout(specs: Dict[Path, Leaf], abstract) -> None:
    """Raise unless the program's abstract parameter tree is this table."""
    got = {p: (tuple(a.shape), str(a.dtype))
           for p, a in flatten(abstract).items()}
    want = {p: (s.shape, s.dtype) for p, s in specs.items()}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise SystemExit(f"bench: the program's parameter layout differs "
                         f"from the family's leaf_specs: {diff[:6]}")


def make_params_fn(specs: Dict[Path, Leaf]):
    """A jitted ``keys -> params`` (one call makes every leaf on the
    device, in its served dtype). Keys are arguments, so one compiled
    program serves every seed."""
    def make(keys):
        return nest({p: leaf_values(s, keys[p]) for p, s in specs.items()})
    return jax.jit(make)
