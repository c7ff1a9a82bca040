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
from collections import deque
from typing import List, Optional, Sequence

import jax


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


class PagedKVPool:
    """Device pool arrays + the allocator that tracks their occupancy.

    For models with Mamba layers the pool also holds ``state_slots`` rows
    of recurrent state per Mamba layer (transformer.pool_specs); rows are
    owned by batch slots, not by the allocator.

    ``kv_bits`` (already normalized — see transformer.normalize_kv_bits)
    selects the HAQ KV-quantized pool layout per sub-layer slot
    (serving/kvquant): quantized slots store int8/int4 codes plus
    per-page-slot per-head fp32 scale tiles, written by the chunk and
    decode programs themselves (quantize-on-write)."""

    def __init__(self, model, num_pages: int, page_size: int, *,
                 kv_bits=None, spmd=None, state_slots: int = 0):
        self.allocator = PageAllocator(num_pages, page_size)
        self.page_size = page_size
        self.kv_bits = kv_bits
        self.pool = model.init_pool(num_pages, page_size, kv_bits=kv_bits,
                                    state_slots=state_slots)
        # SPMD serving (engine/sharded.py): the pool lives sharded on
        # kv_heads over the mesh's model axis (every device holds a
        # 1/N-head slice of every page); page ids stay host/replicated.
        if spmd is not None:
            self.pool = jax.device_put(self.pool, spmd.pool_shardings())

    @property
    def num_free(self) -> int:
        return self.allocator.num_free
