"""Paged KV-cache pool: host-side page allocator + device-side pool arrays.

The allocator is plain Python (a free list) — allocation decisions are
control flow, not compute, and stay off the device. The device pool is the
pytree from ``Model.pool_specs``; page 0 is reserved as scratch: idle batch
slots and unused page-table tails write/gather there, so scatters never need
masking inside the jitted decode step.
"""
from __future__ import annotations

import contextlib
import warnings
from collections import OrderedDict, deque
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@contextlib.contextmanager
def quiet_donation():
    """Silence JAX's unused-donation warning around the engine's own donated
    dispatches only: CPU ignores buffer donation, and process-wide filtering
    would hide genuine missed-donation regressions elsewhere."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


class PageAllocator:
    """Free-list allocator over ``num_pages`` physical pages (page 0 is the
    scratch page and is never handed out).

    Tracks the allocated set so a double-free is rejected instead of
    silently entering the free list twice — a page freed twice would be
    handed to two sequences, which corrupts both KV streams."""

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is scratch)")
        self.num_pages = num_pages
        self.page_size = page_size
        self._free: deque = deque(range(1, num_pages))
        self._allocated: set = set()
        # lifetime telemetry counters (serving/telemetry): tick events
        # report alloc/free *deltas* by differencing these, and min_free
        # is the free-page low-water mark — how close the pool came to
        # preemption pressure.
        self.total_allocated = 0
        self.total_freed = 0
        self.min_free = len(self._free)

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_allocated(self) -> int:
        return len(self._allocated)

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Reserve n pages, or None if the pool can't satisfy the request."""
        if n > len(self._free):
            return None
        pages = [self._free.popleft() for _ in range(n)]
        self._allocated.update(pages)
        self.total_allocated += n
        self.min_free = min(self.min_free, len(self._free))
        return pages

    def free(self, pages: Sequence[int]) -> None:
        seen = set()
        for p in pages:
            if not 1 <= p < self.num_pages:
                raise ValueError(f"freeing invalid page {p}")
            if p not in self._allocated or p in seen:
                raise ValueError(f"double free of page {p}")
            seen.add(p)
        self._allocated.difference_update(seen)
        self._free.extend(pages)
        self.total_freed += len(seen)


class JitLRU:
    """Bounded per-shape jit cache: each entry is its own ``jax.jit``
    instance keyed by a shape tuple, so evicting the entry really drops the
    compiled executable. Long-running engines see an open-ended set of
    bucket shapes (prefill buckets, prefill-span writers); without a cap the
    retrace caches grow without limit."""

    def __init__(self, cap: int = 8):
        self.cap = cap
        self._d: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._d)

    def get(self, key, make: Callable):
        fn = self._d.get(key)
        if fn is None:
            self.misses += 1
            fn = make()
            self._d[key] = fn
            while len(self._d) > self.cap:
                self._d.popitem(last=False)
        else:
            self.hits += 1
            self._d.move_to_end(key)
        return fn


class PagedKVPool:
    """Device pool arrays + the allocator that tracks their occupancy.

    ``kv_bits`` (already normalized — see transformer.normalize_kv_bits)
    selects the HAQ KV-quantized pool layout per sub-layer slot
    (serving/kvquant): quantized slots store int8/int4 codes plus
    per-page-slot per-head fp32 scale tiles, and the prefill writer
    quantizes on write with the same mapping the decode scatter uses."""

    WRITE_JIT_CAP = 8   # LRU cap on per-(n_pages, cache_len) writer jits

    def __init__(self, model, num_pages: int, page_size: int, *,
                 kv_bits=None, spmd=None):
        self.allocator = PageAllocator(num_pages, page_size)
        self.page_size = page_size
        self.kv_bits = kv_bits
        self.pool = model.init_pool(num_pages, page_size, kv_bits=kv_bits)
        # SPMD serving (engine/sharded.py): the pool lives sharded on
        # kv_heads over the mesh's model axis (every device holds a
        # 1/N-head slice of every page) and the span writer becomes its
        # shard_map twin — page ids stay host/replicated, the scatter is
        # shard-local.
        self._spmd = spmd
        if spmd is not None:
            self.pool = jax.device_put(self.pool, spmd.pool_shardings())
        self._write_jit = JitLRU(self.WRITE_JIT_CAP)

    @property
    def num_free(self) -> int:
        return self.allocator.num_free

    def write_prefill(self, cache, pages: Sequence[int], *,
                      start: int = 0) -> None:
        """Scatter one request's prefill cache (full layout, B=1, bucket-
        padded length) into its pages. Jitted per (n_pages, cache_len) shape
        with the pool donated, so the write is an in-place scatter rather
        than a full-pool copy per admission; the jits live in a small LRU so
        an open-ended mix of bucket/page-count shapes can't grow the retrace
        cache without bound. Bucket-padding garbage beyond the true prompt
        lands only inside the request's own pages and is masked (j <= pos)
        or overwritten by decode.

        ``start`` writes a per-chunk *span*: a cache holding tokens
        ``start..start+cache_len`` of the sequence lands at that offset
        within ``pages`` (chunk boundaries must be page-aligned for this
        writer; the chunked engine's own span writes happen inside the
        jitted prefill-with-cache forward, which scatters at arbitrary
        offsets — this host-side writer serves whole-prompt admission and
        chunk-granular replay/tests). Pages past the span's end are
        (re)padded, so spans must be written in chunk order.

        Quantized slots quantize on write: the bf16 prefill pages become
        int8/int4 codes + scale tiles in the same fused scatter (garbage
        slots quantize too, harmlessly — they stay behind the mask)."""
        from repro.kernels import ref as kref
        from repro.models.transformer import to_page_layout

        page = self.page_size
        if start % page:
            raise ValueError(
                f"span start {start} is not page-aligned (page={page})")
        pages = list(pages)[start // page:]
        n = len(pages)
        Sp = jax.tree.leaves(cache)[0].shape[2]
        span = n * page

        def make():
            def write(pool, cache, idx):
                def wr(pool_leaf, cache_leaf):
                    c = cache_leaf[:, 0]                # (G, Sp, K, hd)
                    if Sp >= span:
                        c = c[:, :span]
                    else:
                        c = jnp.pad(c, ((0, 0), (0, span - Sp))
                                    + ((0, 0),) * (c.ndim - 2))
                    c = to_page_layout(c, page)         # (G, n, K, page, hd)
                    if isinstance(pool_leaf, dict):     # quantized slot
                        bits = kref.kv_bits_of(pool_leaf["q"], c.shape[-1])
                        q, scale = kref.quantize_kv(c, bits)
                        return {"q": pool_leaf["q"].at[:, idx].set(q),
                                "scale": pool_leaf["scale"]
                                .at[:, idx].set(scale)}
                    return pool_leaf.at[:, idx].set(c)
                return jax.tree.map(
                    wr, pool, cache,
                    is_leaf=lambda x: isinstance(x, dict) and "q" in x)
            if self._spmd is not None:
                return self._spmd.jit_pool_writer(write, cache)
            return jax.jit(write, donate_argnums=(0,))

        fn = self._write_jit.get((n, Sp), make)
        with quiet_donation():
            self.pool = fn(self.pool, cache,
                           jnp.asarray(np.asarray(pages, np.int32)))
