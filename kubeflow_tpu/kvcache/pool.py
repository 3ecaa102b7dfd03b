"""Device-resident KV block pool — the single owner of paged KV memory
(ISSUE 19 tentpole).

The slab engine sizes KV by worst case: `[slots, max_len]` rows, so one
2k-token straggler strands `max_len - 2k` tokens of HBM in every other
slot. The paged engine instead draws fixed-size blocks (`block_tokens`
tokens each — the SAME granule as the radix prefix trie, the gcd of the
prefill buckets) from this pool and stitches them into per-slot block
tables; concurrency is then bounded by *tokens actually resident*, not
by `slots x max_len`.

Split of responsibilities:

  - **This module** mints the device buffers (`make_block_pool_buffers`
    — the ONLY sanctioned construction site; scripts/check_dataplane.py
    lints that nothing outside `kvcache/` calls it) and owns the host
    allocator metadata: a free list, per-block reference counts, and
    the free-block watermark the admission valve keys on.
  - **The engine** (serving/paged.py) carries the returned buffers in
    its cache dict (they are donated through every compiled program and
    rebound on return — the pool never holds a device handle after
    construction, so donation stays sound) and asks the pool only for
    block *ids*.
  - **The radix trie** (kvcache/radix.py) stores block ids as payloads
    in paged mode: banking a prefix is a refcount increment, matching
    one is a table splice — zero-copy both ways.

Block 0 is the TRASH sentinel: it is never allocated, every empty table
entry points at it, and every junk write the slab engine aims at
masked-off rows (prefill right-pad, drained decode chunks of finished
slots, positions past a slot's reservation) lands there harmlessly.
Refcounts make sharing safe: a block referenced by a slot table AND by
the radix trie is freed only when the last reference drops.
"""

from __future__ import annotations

import threading
from typing import Any

import numpy as np


def make_block_pool_buffers(n_layers: int, n_blocks: int, block_tokens: int,
                            n_kv_heads: int, head_dim: int, dtype: Any,
                            kv_quantize: str | None = None) -> dict:
    """Mint the pool's device arrays: k/v `[L, N, bt, kv, hd]` (+ f32
    per-token scales, lane-major `[L, N, kv, bt]` as in llama.init_cache,
    when int8). kvcache-internal — everything else goes through
    `BlockPool.device_buffers()`."""
    import jax.numpy as jnp

    shape = (n_layers, n_blocks, block_tokens, n_kv_heads, head_dim)
    if kv_quantize == "int8":
        sshape = (n_layers, n_blocks, n_kv_heads, block_tokens)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_s": jnp.zeros(sshape, jnp.float32),
                "v_s": jnp.zeros(sshape, jnp.float32)}
    if kv_quantize is not None:
        raise ValueError(f"unknown kv_quantize {kv_quantize!r}")
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


class BlockPool:
    """Host-side allocator over a fixed population of device KV blocks.

    Thread-safe (the engine's submit path and scrape hooks race). All
    methods trade in integer block ids; the device payload those ids
    index lives in the engine's cache dict from `device_buffers()` on.
    """

    def __init__(self, n_layers: int, n_blocks: int, block_tokens: int,
                 n_kv_heads: int, head_dim: int, dtype: Any,
                 kv_quantize: str | None = None):
        if n_blocks < 2:
            raise ValueError("n_blocks must be >= 2 (block 0 is the "
                             "trash sentinel)")
        if block_tokens < 1:
            raise ValueError("block_tokens must be >= 1")
        self.n_layers = int(n_layers)
        self.n_blocks = int(n_blocks)
        self.block_tokens = int(block_tokens)
        self.n_kv_heads = int(n_kv_heads)
        self.head_dim = int(head_dim)
        self.dtype = dtype
        self.kv_quantize = kv_quantize
        self._lock = threading.Lock()
        # LIFO free list: recently-freed blocks are re-used first (their
        # junk contents are fully overwritten before any masked read)
        self._free: list[int] = list(range(self.n_blocks - 1, 0, -1))
        self._refs = np.zeros(self.n_blocks, np.int32)
        self._refs[0] = 1          # the sentinel is permanently held
        self._buffers_made = False
        self.allocs = 0
        self.frees = 0
        self.alloc_failures = 0

    # -- device side ---------------------------------------------------------

    def device_buffers(self) -> dict:
        """The pool's device arrays, minted exactly once. The caller
        (the paged engine's cache dict) owns them from here on — the
        pool keeps no handle, so donating them through compiled
        programs never aliases pool state."""
        with self._lock:
            if self._buffers_made:
                raise RuntimeError("BlockPool.device_buffers() is "
                                   "single-shot: the engine cache owns "
                                   "the arrays after construction")
            self._buffers_made = True
        return make_block_pool_buffers(
            self.n_layers, self.n_blocks, self.block_tokens,
            self.n_kv_heads, self.head_dim, self.dtype,
            kv_quantize=self.kv_quantize)

    # -- allocation ----------------------------------------------------------

    @property
    def capacity_blocks(self) -> int:
        """Allocatable blocks (the sentinel excluded)."""
        return self.n_blocks - 1

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def watermark_frac(self) -> float:
        """Free fraction of allocatable capacity — the admission
        signal: 1.0 = empty pool, 0.0 = fully committed."""
        cap = self.capacity_blocks
        with self._lock:
            return len(self._free) / cap if cap else 0.0

    def alloc(self, n: int) -> list[int] | None:
        """Take `n` blocks (each at refcount 1), or None — never a
        partial grab — when fewer than `n` are free. The caller runs
        the eviction valve and retries; partial grabs under pressure
        would deadlock two admissions each holding half."""
        if n < 0:
            raise ValueError("alloc count must be >= 0")
        with self._lock:
            if n > len(self._free):
                self.alloc_failures += 1
                return None
            ids = [self._free.pop() for _ in range(n)]
            for b in ids:
                self._refs[b] = 1
            self.allocs += n
            return ids

    def ref(self, ids) -> None:
        """Add one reference to each id (table splice of shared blocks,
        radix banking)."""
        with self._lock:
            for b in ids:
                if not 0 < b < self.n_blocks:
                    raise ValueError(f"block id {b} out of range")
                if self._refs[b] <= 0:
                    raise ValueError(f"ref of free block {b}")
                self._refs[b] += 1

    def deref(self, ids) -> int:
        """Drop one reference from each id; blocks reaching zero return
        to the free list. Returns how many were freed."""
        freed = 0
        with self._lock:
            for b in ids:
                if not 0 < b < self.n_blocks:
                    raise ValueError(f"block id {b} out of range")
                if self._refs[b] <= 0:
                    raise ValueError(f"deref of free block {b}")
                self._refs[b] -= 1
                if self._refs[b] == 0:
                    self._free.append(b)
                    freed += 1
            self.frees += freed
        return freed

    def refcount(self, block_id: int) -> int:
        with self._lock:
            return int(self._refs[block_id])

    # -- observability -------------------------------------------------------

    def stats(self) -> dict[str, Any]:
        cap = self.capacity_blocks
        with self._lock:
            free = len(self._free)
            return {
                "pool_blocks": cap,
                "block_tokens": self.block_tokens,
                "free_blocks": free,
                "used_blocks": cap - free,
                "watermark_frac": round(free / cap, 4) if cap else 0.0,
                "allocs": self.allocs,
                "frees": self.frees,
                "alloc_failures": self.alloc_failures,
            }

    def check_invariants(self) -> None:
        with self._lock:
            free = set(self._free)
            assert len(free) == len(self._free), "duplicate free ids"
            assert 0 not in free, "sentinel on the free list"
            assert self._refs[0] >= 1, "sentinel lost its permanent ref"
            for b in range(1, self.n_blocks):
                held = self._refs[b] > 0
                assert held != (b in free), (
                    f"block {b}: refs={self._refs[b]} free={b in free}")
