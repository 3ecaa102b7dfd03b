"""Paged KV + continuous batching (ISSUE 19 tentpole).

The slab engine allocates KV by worst case — `[n_slots, max_len]` rows
— so one long straggler strands `max_len - len` tokens of HBM in every
other slot, and concurrency is pinned at `n_slots` no matter how short
the live requests are. `PagedLLMEngine` replaces the slab with the
kvcache block pool (`kvcache/pool.py`): KV lives in fixed-size blocks
of `block_tokens` tokens (the SAME granule as the radix prefix trie —
the gcd of the prefill buckets), per-slot block TABLES stitch them into
logical rows, and admission funds each request by a block RESERVATION
against the pool's free-block watermark instead of by slot count.

What changes, layer by layer:

  - **Model** (models/llama.py `verify_inner`): with `"tbl"` in the
    cache dict, every write coordinate indirects through the table
    (position p of slot r lands at block `tbl[r, p//bt]`, offset
    `p % bt`) and `decode_attention` gathers the span through the same
    table — the XLA path via `jnp.take`, the flash kernel via a
    scalar-prefetched table on its kv-block grid axis
    (ops/flash_decode.py). One masking/softmax body for both layouts.
  - **Prefix cache**: radix payloads become pool block IDS. Banking a
    prefix is a refcount increment (`_bank_prefix_blocks` — zero copy,
    no extraction), a hit is a table SPLICE (`_splice_shared`), and
    trie eviction is the admission valve: under block pressure the
    engine evicts unpinned trie blocks and lets future hits recompute
    from whatever prefix survives — r12's disagg backpressure math
    generalized to block granularity.
  - **Admission**: `_admit_prefills` reserves
    `ceil(min(max_len, prompt+max_new) / bt)` blocks per action
    (all-or-nothing). Unfundable actions are HELD engine-side — their
    slots stay assigned, decode masks them out (`_mask_unfunded`), and
    they retry at the top of every step as blocks free up. Because a
    reservation covers every token the request can deliver, an
    admitted request always runs to completion — oversubscription can
    delay admission, never corrupt or starve a running stream.

Junk-write safety (the slab's `mode="drop"` story, rebuilt on tables):
block 0 is the pool's TRASH sentinel. Unallocated table entries are 0,
so prefill right-pad past a reservation, decode chunks of finished
slots (their rows are zeroed at release), and positions at/past
max_len all land in block 0 and are never read. Blocks of a finished
slot are deref'd only once NO dispatched-but-unfetched chunk remains
(`_flush_derefs`) — in-flight programs write through the table
snapshot they were dispatched with.

Byte parity with the slab engine (the bench floor): writes quantize
identically, the XLA gather twin feeds the identical einsum, and the
cont path never re-quantizes a dequantized prefix (the spliced blocks
already hold the bytes the slab path would recompute) — greedy AND
seeded sampling outputs match the slab engine byte-for-byte.

Selection: `kv_layout: slab|paged` via serving/llm_runtime.py,
default slab. Like LLMEngine, this class may only
be constructed inside supervisor factories (scripts/check_dataplane.py
lints the name).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.kvcache import BlockPool
from kubeflow_tpu.models import llama
from kubeflow_tpu.serving.llm import LLMEngine


class _PrefixEntry(tuple):
    """A materialized (k, v) prefix pair that ALSO carries the pool
    block ids backing it. Base-class consumers (`_stack_prefix`, the
    chunked chain's `ek, ev = pending` unpack) treat it as a plain
    2-tuple; the paged dispatch overrides read `.ids` for the
    zero-copy table splice."""

    def __new__(cls, kv, ids):
        self = super().__new__(cls, kv)
        self.ids = [int(b) for b in ids]
        return self


class PagedLLMEngine(LLMEngine):
    """LLMEngine over block-granular paged KV (see module docstring)."""

    kv_layout = "paged"
    _bank_uses_raw_extract = False   # banking is refcounting, not slicing
    _cont_writes_prefix = False      # spliced blocks already hold the bytes

    def __init__(self, params, cfg: llama.LlamaConfig, *,
                 pool_blocks: int | None = None, **kw):
        if kw.get("mesh") is not None:
            raise ValueError(
                "paged KV does not support mesh sharding yet: the pool's "
                "block axis has no GSPMD layout — use kv_layout=slab for "
                "tp/stage-sharded serving")
        n_slots = int(kw.get("n_slots", 4))
        max_len = int(kw.get("max_len", 512))
        buckets = tuple(sorted(kw.get("buckets", (64, 128, 256))))
        kw["buckets"] = buckets
        bt = math.gcd(*buckets)
        if max_len % bt:
            raise ValueError(
                f"paged KV needs block_tokens {bt} (gcd of buckets "
                f"{buckets}) to divide max_len {max_len}")
        self._bt = bt
        self._n_tbl = max_len // bt
        if pool_blocks is None:
            # default: the SAME HBM the slab would have spent — the A/B
            # then measures pure layout win, not extra memory
            pool_blocks = n_slots * self._n_tbl
        if pool_blocks < self._n_tbl:
            raise ValueError(
                f"pool_blocks {pool_blocks} cannot fund even one "
                f"max_len request ({self._n_tbl} blocks): admission "
                "would hold it forever")
        # +1: block 0 is the trash sentinel, never allocatable
        self._pool = BlockPool(cfg.n_layers, pool_blocks + 1, bt,
                               cfg.n_kv_heads, cfg.head_dim, cfg.dtype,
                               kv_quantize=kw.get("kv_quantize"))
        self._tbl_host = np.zeros((n_slots, self._n_tbl), np.int32)
        #: PrefillActions popped from the scheduler but not yet fundable
        #: (their slots stay assigned; retried every step)
        self._held: list = []
        #: block ids of finished slots, returned to the pool only when
        #: no dispatched-but-unfetched chunk remains (_flush_derefs)
        self._deferred_derefs: list[int] = []
        super().__init__(params, cfg, **kw)
        for s in self._span_menu():
            if s % bt:
                raise ValueError(
                    f"paged KV needs block_tokens {bt} to divide every "
                    f"attention span (got {s}); pick buckets whose gcd "
                    "divides 128 and max_len")
        if self.prefix_cache_enabled:
            # radix payloads are pool block ids from here on: eviction
            # derefs, stats read the pool's free-block watermark
            self.kvcache.attach_pool(self._pool)
            self.kvcache.evict_hook = self._on_radix_evict

    # -- cache layout --------------------------------------------------------

    def _alloc_cache(self):
        cache = self._pool.device_buffers()
        cache["tbl"] = self._put(self._tbl_host)
        cache["cnt"] = jnp.zeros((self.n_slots, self.cfg.vocab_size),
                                 jnp.int32)
        if self.spec:
            cache["hist"] = jnp.zeros((self.n_slots, self.max_len),
                                      jnp.int32)
        if self.adapters is not None:
            cache["aids"] = jnp.zeros((self.n_slots,), jnp.int32)
        return cache

    def _kv_block_tokens(self) -> int:
        return self._bt   # the kernel's KV block IS the pool's

    def _tbl_sync(self) -> None:
        """Re-upload the host table mirror. The table is tiny
        ([n_slots, max_len/bt] int32), so every mutation batch eagerly
        replaces the device copy — no dirty-tracking discipline to get
        wrong. The device never mutates tables (verify_inner passes
        them through), so the mirror is the single source of truth."""
        self.cache["tbl"] = self._put(self._tbl_host)

    # -- writes through the table --------------------------------------------

    def _cache_write(self, cache, slot, start: int, count: int, ks, vs):
        """Block-scatter write: rows [start, start+count) of `slot` land
        in the pool blocks its table names. start/count are STATIC block
        multiples (buckets and prefix lengths are; the tail chunk of a
        chunked chain writes its whole bucket). Table entries past the
        slot's reservation are 0 → the write lands in the trash block."""
        bt = self._bt
        if start % bt or count % bt:
            raise ValueError(
                f"paged cache write [{start}, {start + count}) must be "
                f"block-aligned (block_tokens={bt})")
        nb = count // bt
        blks = jax.lax.dynamic_slice(cache["tbl"],
                                     (slot, start // bt), (1, nb))[0]
        out = dict(cache)

        def scatter(buf, vals):
            v = vals.reshape(vals.shape[0], nb, bt, *vals.shape[2:])
            return buf.at[:, blks].set(v, mode="drop")

        def scatter_scales(buf, vals):   # [L, count, kv] -> [L, nb, kv, bt]
            v = vals.reshape(vals.shape[0], nb, bt, vals.shape[2])
            return buf.at[:, blks].set(jnp.swapaxes(v, 2, 3), mode="drop")

        if self.kv_quantize == "int8":
            kq, ksc = llama.quantize_kv(ks)
            vq, vsc = llama.quantize_kv(vs)
            out["k"] = scatter(cache["k"], kq)
            out["v"] = scatter(cache["v"], vq)
            # pool scales are lane-major: [L, N, kv, bt]
            out["k_s"] = scatter_scales(cache["k_s"], ksc)
            out["v_s"] = scatter_scales(cache["v_s"], vsc)
        else:
            out["k"] = scatter(cache["k"], ks.astype(cache["k"].dtype))
            out["v"] = scatter(cache["v"], vs.astype(cache["v"].dtype))
        return out

    # -- prefix extraction / materialization ---------------------------------

    def _gather_blocks(self, cache, blks, n_tokens: int):
        """Pool blocks → a slab-shaped [L, 1, n_tokens, ...] prefix (the
        store/continuation currency), dequantizing int8 at the edge."""
        def gather(name):
            g = jnp.take(cache[name], blks, axis=1)   # [L, nb, bt, ...]
            return g.reshape(g.shape[0], n_tokens, *g.shape[3:])[:, None]

        def gather_scales(name):   # [L, nb, kv, bt] -> [L, 1, n_tokens, kv]
            g = jnp.swapaxes(jnp.take(cache[name], blks, axis=1), 2, 3)
            return g.reshape(g.shape[0], n_tokens, g.shape[3])[:, None]

        k, v = gather("k"), gather("v")
        if self.kv_quantize == "int8":
            k = llama.dequantize_kv(k, gather_scales("k_s"), self.cfg.dtype)
            v = llama.dequantize_kv(v, gather_scales("v_s"), self.cfg.dtype)
        return k, v

    def _extract_prefix(self, cache, slot, *, p: int):
        """The slot's first `p` KV rows, gathered through its table (the
        chunked chain's boundary currency). p is a block multiple."""
        blks = jax.lax.dynamic_slice(cache["tbl"], (slot, 0),
                                     (1, p // self._bt))[0]
        return self._gather_blocks(cache, blks, p)

    def _extract_prefix_raw(self, cache, slot, *, p: int):
        raise RuntimeError(
            "paged engines bank block ids, not raw KV slices — "
            "_extract_prefix_raw has no paged caller by design")

    def _materialize_prefix(self, payloads: list):
        """Matched radix chain (block IDS in paged mode) → the
        continuation program's (k, v) prefix arrays, tagged with the
        ids so the dispatch can splice them into the slot table."""
        ids = [int(b) for b in payloads]
        blks = jnp.asarray(ids, jnp.int32)
        kv = self._gather_blocks(self.cache, blks, len(ids) * self._bt)
        return _PrefixEntry(kv, ids)

    # -- zero-copy banking / splicing ----------------------------------------

    def _bank_prefix_blocks(self, action) -> None:
        """Bank the slot's block-aligned prompt prefix into the radix
        trie as BLOCK IDS: each newly stored block costs one refcount
        increment — no extraction, no copy. The trie's ref keeps the
        block alive after the slot releases it."""
        prompt = self._prompts.get(action.req_id)
        if prompt is None:
            return
        bt = self._bt
        aligned = (len(prompt) // bt) * bt
        ns = self._req_aids.get(action.req_id, 0)
        if aligned <= 0:
            return
        if self.kvcache.cached_prefix_len(
                prompt, max_tokens=aligned, namespace=ns) >= aligned:
            return
        row = self._tbl_host[action.slot]
        pool = self._pool

        def payload(_i, s, e):
            bid = int(row[s // bt])
            pool.ref([bid])
            return bid

        self.kvcache.insert(prompt, payload, max_tokens=aligned,
                            tenant=self._req_tenant.get(action.req_id),
                            namespace=ns)

    def _on_radix_evict(self, payload) -> None:
        """Trie eviction drops the trie's reference; the block frees
        only when no slot table still names it."""
        if payload is not None:
            self._pool.deref([int(payload)])

    def _splice_shared(self, slot: int, ids: list[int]) -> None:
        """Point the slot's leading table entries at SHARED radix blocks
        (refcount++ each) instead of the exclusive blocks admission
        reserved. The displaced blocks were allocated this step and no
        dispatched program references them — they free immediately,
        giving back the reservation surplus a prefix hit creates."""
        row = self._tbl_host[slot]
        displaced = []
        for i, bid in enumerate(ids):
            if int(row[i]) == int(bid):
                continue
            self._pool.ref([int(bid)])
            if row[i]:
                displaced.append(int(row[i]))
            row[i] = bid
        if displaced:
            self._pool.deref(displaced)

    def _dispatch_prefill_cont_wave(self, p: int, t: int, pairs):
        self.phase_clock.enter("prefill_pack")
        nb = p // self._bt
        for a, entry in pairs:
            self._splice_shared(a.slot, entry.ids[:nb])
        self._tbl_sync()
        return super()._dispatch_prefill_cont_wave(p, t, pairs)

    def _dispatch_chunked_prefill(self, action) -> Any:
        """Chunked chain with a radix head start: splice the reusable
        prefix blocks into the slot table FIRST (the base method's own
        match — deterministic, nothing mutates the trie in between —
        then materializes the same chain and skips the prefix write)."""
        self.phase_clock.enter("prefill_pack")
        prompt = self._prompts[action.req_id]
        n = len(prompt)
        bt = self._bt
        if self.prefix_cache_enabled and n - 1 >= bt:
            m = self.kvcache.match(
                prompt, max_tokens=n - 1,
                namespace=self._req_aids.get(action.req_id, 0))
            done = m.tokens
            # mirror the base shrink: the spliced prefix must equal the
            # one the chain actually continues from
            while done > 0 and self._chunk_plan_from(n, done) is None:
                done -= bt
            if done > 0:
                self._splice_shared(
                    action.slot,
                    [int(b) for b in m.payloads[:done // bt]])
                self._tbl_sync()
            self.kvcache.release(m)
        return super()._dispatch_chunked_prefill(action)

    # -- admission: reservations, the eviction valve, held actions -----------

    def _need_blocks(self, action) -> int:
        """Blocks that fund the request END TO END: every position a
        delivered token can occupy is < prompt_len + max_new_tokens
        (clamped to max_len), so junk past the reservation — prefill
        right-pad, post-finish decode — hits unallocated entries
        (→ trash) and nothing real is ever lost."""
        plen = len(self._prompts.get(action.req_id, ()))
        if plen == 0:
            plen = action.prompt_len
        max_new = self._max_new.get(action.req_id, 1)
        return -(-min(self.max_len, plen + max_new) // self._bt)

    def _cached_prefix_match(self, action):
        """(match, block_ids) for the radix-cached prefix the DISPATCH
        will actually splice for this action — so funding can reserve
        only the uncached suffix. Mirrors the two dispatch paths'
        legality clamps exactly (nothing mutates the trie between
        admission and dispatch, the same determinism
        _dispatch_chunked_prefill already leans on): the chunked chain
        shrinks to a schedulable plan boundary, the continuation wave to
        a tail bucket that fits max_len. The returned match is PINNED —
        the caller keeps it pinned through the eviction valve (so the
        valve never eats the very prefix this admission is about to
        reuse) and releases it when funding resolves. Accounting probe
        only — the dispatch owns the hit/miss bookkeeping."""
        if not self.prefix_cache_enabled:
            return None, []
        prompt = self._prompts.get(action.req_id)
        bt = self._bt
        if prompt is None or len(prompt) - 1 < bt:
            return None, []
        n = len(prompt)
        m = self.kvcache.match(prompt, max_tokens=n - 1,
                               namespace=self._req_aids.get(
                                   action.req_id, 0))
        p = m.tokens
        if n > action.bucket_len:
            while p > 0 and self._chunk_plan_from(n, p) is None:
                p -= bt
        else:
            while p > 0:
                t = self._tail_bucket(n - p)
                if t is None:
                    p = 0
                    break
                if p + t <= self.max_len:
                    break
                p -= bt
        return m, [int(b) for b in m.payloads[:p // bt]]

    def _fund(self, action) -> bool:
        """All-or-nothing block reservation, with the radix eviction
        valve: under pressure, unpinned trie blocks are recomputable
        state (a future hit re-prefills from the surviving prefix), so
        they are evicted before an admission is held.

        A cached prefix funds itself: the leading table entries splice
        the shared radix blocks (refcount++, no copy) and only the
        uncached suffix draws fresh blocks. The match pin rides through
        the valve, so pressure evicts OTHER entries first. Held actions
        re-probe the cache on every retry — a prefix banked by requests
        that finished while this one waited shrinks the reservation it
        is waiting for."""
        need = self._need_blocks(action)
        m, cached = self._cached_prefix_match(action)
        alloc_need = need - len(cached)
        ids = self._pool.alloc(alloc_need)
        while ids is None and self.kvcache is not None:
            deficit = alloc_need - self._pool.free_blocks
            if self.kvcache.evict(max(1, deficit)) == 0:
                break   # nothing evictable left: hold
            ids = self._pool.alloc(alloc_need)
        if ids is None:
            if m is not None:
                self.kvcache.release(m)   # unpin; the retry re-probes
            return False
        if cached:
            # splice-at-fund: one pool ref per shared block transfers
            # ownership to the slot table (balanced by
            # _release_slot_blocks, exactly like _splice_shared's refs)
            self._pool.ref(cached)
        if m is not None:
            self.kvcache.release(m)
        row = self._tbl_host[action.slot]
        row[:] = 0
        row[:len(cached)] = cached
        row[len(cached):need] = ids
        return True

    def _admit_prefills(self, actions: list) -> list:
        self._flush_derefs()
        ready, held = [], []
        for a in self._held + list(actions):
            if self.scheduler.slot_request(a.slot) != a.req_id:
                continue   # cancelled while held
            (ready if self._fund(a) else held).append(a)
        self._held = held
        if ready:
            self._tbl_sync()
        return ready

    def _mask_unfunded(self, slot_req: list[int]) -> list[int]:
        if not self._held:
            return slot_req
        held = {a.slot for a in self._held}
        return [-1 if s in held else r for s, r in enumerate(slot_req)]

    def _step(self) -> bool:
        if self._held:
            # held retry first: finished chunks free blocks, so drain
            # the pipeline, then re-run admission before the scheduler
            # hands out anything new
            self._apply_cancellations()
            self._drain_pending()
            self.phase_clock.enter("sched")
            ready = self._admit_prefills([])
            if ready:
                self._run_prefill_actions(ready)
                return True
        return super()._step()

    # -- release / deferred frees --------------------------------------------

    def _release_slot_blocks(self, slot: int, sync: bool = True) -> None:
        """Zero the slot's table row (future junk writes → trash) and
        queue its blocks for deref. The deref itself waits for the
        pipeline to empty: dispatched-but-unfetched chunks write junk
        through the OLD device table into these very blocks."""
        row = self._tbl_host[slot]
        ids = [int(b) for b in row if b]
        if not ids:
            return
        row[:] = 0
        if sync:
            self._tbl_sync()
        self._deferred_derefs.extend(ids)
        self._flush_derefs()

    def _flush_derefs(self) -> None:
        if self._deferred_derefs and self._pending is None:
            self._pool.deref(self._deferred_derefs)
            self._deferred_derefs = []

    def _record_token(self, req_id: int, slot: int, token: int,
                      lp: float = 0.0, top=None,
                      first_token: bool = False) -> bool:
        freed = super()._record_token(req_id, slot, token, lp, top,
                                      first_token=first_token)
        if freed:
            self._release_slot_blocks(slot)
        return freed

    def _apply_cancellations(self) -> None:
        super()._apply_cancellations()
        changed = False
        for s in range(self.n_slots):
            if self.scheduler.slot_request(s) < 0 \
                    and self._tbl_host[s].any():
                self._release_slot_blocks(s, sync=False)
                changed = True
        if changed:
            self._tbl_sync()
        if self._held:
            self._held = [a for a in self._held
                          if self.scheduler.slot_request(a.slot)
                          == a.req_id]

    def _drain_pending(self) -> None:
        super()._drain_pending()
        self._flush_derefs()

    # -- observability / lifecycle -------------------------------------------

    def metrics(self) -> dict[str, Any]:
        out = super().metrics()
        out["kv_pool"] = self._pool.stats()
        out["held_prefills"] = len(self._held)
        return out

    def close(self) -> None:
        super().close()
        self._pool = None
