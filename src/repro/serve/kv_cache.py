"""Ref-counted, prefix-cached block-paged KV cache (vLLM-style).

The pool is one device allocation of ``num_blocks`` fixed-size pages per
layer; sequences own *lists of page ids* (host-side page tables) instead
of a dense ``max_len`` cache region. Page 0 is reserved as the **null
page**: page-table padding and masked-lane writes route there, so every
gather/scatter stays in bounds without host-side branching.

**Null-page invariant: page 0 is write-absorbing and is never read as
signal.** Writes that must go *somewhere* but mean nothing — the padded
tail of a final prefill chunk (``n_valid`` masking), idle decode lanes
(``sids=None`` rows feed ``token=0, pos=0`` through an all-null table),
null->null COW padding pairs — all scatter into page 0, so its contents
are arbitrary garbage at all times. That is safe because no read path
treats it as data: attention masks strictly by ``kv_len``, which for a
live sequence counts only tokens written through its *own* table
entries, and a null lane's output feeds only itself. Nothing may ever
zero-check or otherwise interpret page 0; correctness must be invariant
to arbitrary (finite) garbage pre-loaded into it — the regression test
``test_serve_engine.py::test_null_page_garbage_invariance`` pins
exactly that, for prefill and decode, on both attention backends.

On top of the PR-1 paging this adds the three mechanisms that let pages
be *shared* between sequences:

* **Ref-counted pages + content-hash index.** Every block-aligned token
  prefix of a finished prefill is chain-hashed and registered in
  ``_index`` (including the final *partial* block, hashed over exactly
  the prompt tokens it holds). A later request whose prompt walks the
  same chain attaches the cached pages (refcount++) and prefills only
  the tail through the existing ``q_start`` path.
* **Copy-on-write.** A write into a page with refcount > 1 first copies
  the page to a private one (``append_tokens`` returns the (src, dst)
  pairs; the engine replays them on device before the model step).
  Writes into refcount-1 pages go in place — including the recompute of
  the last prompt token of a fully-matched prompt, which rewrites
  identical content inside the hashed extent.
* **LRU eviction.** When a registered page's refcount drops to 0 it is
  *not* freed: it moves to an LRU evictable list and stays resident so
  future prompts can hit it. Allocation takes from the free list first
  and evicts LRU cached pages only under pressure (unregistering them).

Allocation itself is now **on demand**: there is no per-sequence
reservation call; ``append_tokens(seq_id, start, end)`` grows the page
table just enough to cover the token range about to be written and
reports failure (None) when the pool — free plus evictable — cannot,
which the scheduler turns into a preemption.

This is the memory half of SOLE's co-design argument carried to serving:
the paper stores Softmax intermediates in 4-bit codes because the memory
path, not the multiplier, bounds the unit; here the (optionally int8)
KV pool is the binding serving resource, so capacity is committed per
live token and identical prefixes are stored once.

Device state is functional: jitted steps take the pool dict and return
an updated one; only the free/evictable lists, refcounts, hash index and
page tables live host-side.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig

Array = jax.Array

# Logical axes of the page pools (see sharding/rules.py: "pages" is
# replicated by default; kv_heads shard over the model axis so each
# device holds its heads' slice of every page). Pages are head-major,
# (layers, pages, kv_heads, block_size, head_dim): one (page, head) is a
# contiguous (block_size, head_dim) tile, the block the paged kernel
# DMAs — a TPU block's last two dims must be the array's own or
# (8, 128)-aligned, so the head axis cannot sit between them.
PAGED_KV_AXES = {
    "k": ("layers", "pages", "kv_heads", None, "head_dim"),
    "v": ("layers", "pages", "kv_heads", None, "head_dim"),
}


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


class PagedKVCache:
    """Fixed pool of KV pages + host-side tables, refcounts and index."""

    def __init__(self, cfg: ArchConfig, *, num_blocks: int,
                 block_size: int = 16, max_seq_len: int = 512,
                 dtype=None, prefix_cache: bool = True,
                 kv_layers: Optional[int] = None):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (page 0 is the null page)")
        from repro.models.layers import kv_store_dtype
        self.cfg = cfg
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.max_blocks_per_seq = cdiv(max_seq_len, block_size)
        self.max_seq_len = max_seq_len
        self.prefix_cache = prefix_cache
        # kv_layers lets a family page only its attention layers (hybrid
        # blocks, encdec decoder layers); a pure-recurrent family passes
        # 0 and gets zero-byte pools with all host bookkeeping intact.
        self.kv_layers = cfg.n_layers if kv_layers is None else kv_layers
        dt = dtype or kv_store_dtype(cfg)
        shape = (self.kv_layers, num_blocks, cfg.n_kv_heads,
                 block_size, cfg.head_dim)
        self.pools: Dict[str, Array] = {"k": jnp.zeros(shape, dt),
                                        "v": jnp.zeros(shape, dt)}
        # LIFO free list; page 0 reserved as the null page.
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        # refcount-0 registered pages, LRU order (oldest first).
        self._evictable: "OrderedDict[int, int]" = OrderedDict()
        self._index: Dict[int, int] = {}        # chain hash -> page id
        self._registered: Dict[int, int] = {}   # page id -> chain hash
        # page id -> (parent page id, block token bytes): the content
        # proof a lookup verifies on every hash hit, so a 64-bit hash
        # collision degrades to a cache miss, never to foreign KV.
        self._entries: Dict[int, Tuple[Optional[int], bytes]] = {}
        self._ref: List[int] = [0] * num_blocks
        self._tables: Dict[int, List[int]] = {}
        # encdec cross-attention KV: written once at admission (encoder
        # pass), read-only for the sequence's whole life, never hashed
        # into the prefix index or COWed. Kept in a separate namespace so
        # self-attention growth/truncation never touches these rows.
        self._cross_tables: Dict[int, List[int]] = {}
        self.peak_blocks_in_use = 0
        self.evictions = 0
        self.cow_copies = 0
        self.prefix_hit_tokens = 0
        self.prefix_query_tokens = 0

    def shard(self, rules) -> None:
        """Lay the pools out per the active sharding rules (PAGED_KV_AXES:
        pages replicated, each page's kv_heads sliced over the model axis)."""
        self.pools = {
            name: jax.device_put(
                pool, rules.sharding(PAGED_KV_AXES[name], pool.shape))
            for name, pool in self.pools.items()
        }

    # -- accounting -----------------------------------------------------------

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def cached_blocks(self) -> int:
        """Resident refcount-0 pages, reclaimable under pressure."""
        return len(self._evictable)

    @property
    def blocks_in_use(self) -> int:
        """Pages referenced by at least one live sequence."""
        return (self.num_blocks - 1) - len(self._free) - len(self._evictable)

    def free_capacity(self) -> int:
        """Pages an allocation can draw on: free + evictable."""
        return len(self._free) + len(self._evictable)

    def utilization(self) -> float:
        return self.blocks_in_use / max(self.num_blocks - 1, 1)

    def blocks_for_tokens(self, num_tokens: int) -> int:
        return cdiv(num_tokens, self.block_size)

    def is_cached(self, page_id: int) -> bool:
        return page_id in self._evictable

    def prefix_hit_rate(self) -> float:
        return self.prefix_hit_tokens / max(self.prefix_query_tokens, 1)

    def reset_stats(self) -> None:
        self.evictions = 0
        self.cow_copies = 0
        self.prefix_hit_tokens = 0
        self.prefix_query_tokens = 0
        self.peak_blocks_in_use = self.blocks_in_use

    def check_refcounts(self) -> None:
        """Invariant sweep (tests): refcounts match the page tables and
        are never negative; free/evictable/table sets partition pages."""
        counts = [0] * self.num_blocks
        for table in self._tables.values():
            for pid in table:
                counts[pid] += 1
        for table in self._cross_tables.values():
            for pid in table:
                counts[pid] += 1
        assert self._ref == counts, (self._ref, counts)
        assert all(r >= 0 for r in self._ref)
        for pid in self._evictable:
            assert self._ref[pid] == 0 and pid in self._registered
        for pid in self._free:
            assert self._ref[pid] == 0 and pid not in self._registered
        resident = set(self._free) | set(self._evictable)
        for table in self._tables.values():
            assert resident.isdisjoint(table)
        for table in self._cross_tables.values():
            assert resident.isdisjoint(table)
            # cross pages are never registered/shared: refcount exactly 1
            for pid in table:
                assert self._ref[pid] == 1 and pid not in self._registered
        for h, pid in self._index.items():
            assert self._registered.get(pid) == h
            assert pid in self._entries
        assert set(self._entries) == set(self._registered)

    # -- content-hash prefix index --------------------------------------------

    def prefix_keys(self, prompt: np.ndarray) -> List[Tuple[int, bytes]]:
        """(chain hash, block token bytes) per block-aligned prefix, the
        final partial block keyed over exactly the prompt tokens it
        holds. Hash-chain identity plus per-hit byte verification; the
        scheduler caches this per sequence so re-admission attempts
        don't re-hash long prompts every engine step."""
        prompt = np.ascontiguousarray(np.asarray(prompt, np.int32))
        bs = self.block_size
        keys: List[Tuple[int, bytes]] = []
        h: Optional[int] = None
        for i in range(cdiv(len(prompt), bs)):
            seg = prompt[i * bs:min((i + 1) * bs, len(prompt))].tobytes()
            h = hash((h, seg))
            keys.append((h, seg))
        return keys

    def lookup_prefix(self, prompt: np.ndarray,
                      keys: Optional[List[Tuple[int, bytes]]] = None,
                      ) -> Tuple[List[int], int]:
        """Longest cached chain for this prompt: (page ids, token count).

        Every hash hit is verified against the registered page's
        ``(parent page, block bytes)`` entry — the parent link pins the
        whole prefix content inductively, so a hash collision is a miss,
        never a wrong match. The match is capped at ``len(prompt) - 1``
        so the final prompt position is always recomputed — its logits
        seed generation. A fully-matched final page is still returned
        (its earlier slots are valid); the recompute overwrites one
        slot, COW-protected if the page is shared.
        """
        plen = len(prompt)
        if not self.prefix_cache or plen <= 1:
            return [], 0
        pages: List[int] = []
        matched = 0
        prev: Optional[int] = None
        for i, (h, seg) in enumerate(keys or self.prefix_keys(prompt)):
            pid = self._index.get(h)
            if pid is None or self._entries.get(pid) != (prev, seg):
                break
            pages.append(pid)
            matched = min((i + 1) * self.block_size, plen)
            prev = pid
        if matched >= plen:
            matched = plen - 1
        if pages and matched <= (len(pages) - 1) * self.block_size:
            # capped below the last page's first slot: it contributes
            # nothing valid, keeping it would only pin it.
            pages.pop()
        return pages, matched

    def attach(self, seq_id: int, pages: Sequence[int], *,
               query_tokens: int = 0, hit_tokens: int = 0) -> None:
        """Create ``seq_id``'s table seeded with cached ``pages``
        (refcount++, pinned out of the evictable list)."""
        if seq_id in self._tables:
            raise ValueError(f"seq {seq_id} already has pages")
        for pid in pages:
            if self._ref[pid] == 0:
                self._evictable.pop(pid)
            self._ref[pid] += 1
        self._tables[seq_id] = list(pages)
        self.prefix_query_tokens += query_tokens
        self.prefix_hit_tokens += hit_tokens
        self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                      self.blocks_in_use)

    def register_prompt(self, seq_id: int, prompt: np.ndarray,
                        keys: Optional[List[Tuple[int, bytes]]] = None,
                        ) -> None:
        """Index ``seq_id``'s prompt pages by content so future prompts
        can share them. Called once the prompt is fully written; losers
        of a same-content race simply keep their pages private. The walk
        mirrors lookup verification: registration stops at the first
        level whose canonical entry is not byte-identical to this
        prompt, so a deeper page can never chain onto a colliding or
        diverged parent."""
        if not self.prefix_cache:
            return
        table = self._tables[seq_id]
        prev: Optional[int] = None
        for i, (h, seg) in enumerate(keys or self.prefix_keys(prompt)):
            pid = self._index.get(h)
            if pid is not None:
                if self._entries.get(pid) != (prev, seg):
                    break                  # collision: stop indexing deeper
                prev = pid
                continue
            mine = table[i]
            if self._registered.get(mine) is not None:
                break                      # already canonical elsewhere
            self._index[h] = mine
            self._registered[mine] = h
            self._entries[mine] = (prev, seg)
            prev = mine

    # -- allocation -----------------------------------------------------------

    def _acquire(self) -> int:
        """One fresh private page: free list first, else evict the LRU
        cached page (unregistering it from the index)."""
        if self._free:
            pid = self._free.pop()
        else:
            pid, h = self._evictable.popitem(last=False)
            del self._index[h]
            del self._registered[pid]
            del self._entries[pid]
            self.evictions += 1
        self._ref[pid] = 1
        return pid

    def append_tokens(self, seq_id: int, start: int,
                      end: int) -> Optional[List[Tuple[int, int]]]:
        """Make token positions ``[start, end)`` privately writable.

        Grows the table on demand to cover ``end`` tokens and
        copy-on-writes any shared page (refcount > 1) the write range
        touches. The range is arbitrary — one decode token, a prefill
        chunk, or a full decode horizon: the engine pre-extends a lane's
        table for all H tokens of a fused multi-token step in one call,
        so every page the in-jit scan will write exists (and is private)
        before dispatch. Returns the (src, dst) page copies the engine
        must replay on device before writing, or None (no state change)
        if the pool cannot cover the growth — the preemption signal.
        """
        table = self._tables[seq_id]
        bs = self.block_size
        need = cdiv(end, bs)
        if need > self.max_blocks_per_seq:
            raise ValueError(
                f"seq {seq_id} would span {need} pages "
                f"(max_blocks_per_seq {self.max_blocks_per_seq})")
        grow = max(0, need - len(table))
        cow = [i for i in range(start // bs, cdiv(end, bs))
               if i < len(table) and self._ref[table[i]] > 1]
        if grow + len(cow) > self.free_capacity():
            return None
        copies: List[Tuple[int, int]] = []
        for i in cow:
            old = table[i]
            new = self._acquire()
            copies.append((old, new))
            self._ref[old] -= 1
            table[i] = new
            self.cow_copies += 1
        for _ in range(grow):
            table.append(self._acquire())
        self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                      self.blocks_in_use)
        return copies

    def truncate(self, seq_id: int, num_tokens: int) -> int:
        """Shrink ``seq_id``'s table to cover ``num_tokens`` tokens,
        dropping the reference on every tail page.

        This is the reclaim path for **early exit**: a decode horizon
        pre-extends the table for all H tokens, so a lane that hits an
        eos/stop event at token k < H holds ``blocks(H) - blocks(k)``
        pages it will never use — post-truncation hands them back
        before the sequence is even reaped, so they fund the same
        step's admissions. Refcount-correct under COW/prefix sharing:
        each dropped page is dereferenced exactly like :meth:`release`
        does (shared pages just lose one ref; refcount-0 registered
        pages stay resident on the evictable LRU; private unregistered
        pages return to the free list). Returns the number of pages
        dropped from the table.
        """
        table = self._tables[seq_id]
        keep = self.blocks_for_tokens(num_tokens)
        dropped = 0
        while len(table) > keep:
            pid = table.pop()
            self._ref[pid] -= 1
            assert self._ref[pid] >= 0, f"negative refcount on page {pid}"
            if self._ref[pid] == 0:
                h = self._registered.get(pid)
                if h is not None:
                    self._evictable[pid] = h      # MRU end
                else:
                    self._free.append(pid)
            dropped += 1
        return dropped

    def release(self, seq_id: int) -> None:
        """Drop ``seq_id``'s references (finish or preemption). Pages
        reaching refcount 0 go back to the free list — unless they are
        registered in the prefix index, in which case they stay resident
        on the evictable LRU list for future prompts to hit. Pages are
        enqueued tail-first so pool pressure evicts chain *suffixes*
        before the prefixes they hang off — evicting block 0 first
        would orphan every deeper page of the chain as unmatchable
        resident dead weight."""
        for pid in reversed(self._tables.pop(seq_id)):
            self._ref[pid] -= 1
            assert self._ref[pid] >= 0, f"negative refcount on page {pid}"
            if self._ref[pid] == 0:
                h = self._registered.get(pid)
                if h is not None:
                    self._evictable[pid] = h      # MRU end
                else:
                    self._free.append(pid)
        # cross pages are private and unregistered: straight to free.
        for pid in self._cross_tables.pop(seq_id, []):
            self._ref[pid] -= 1
            assert self._ref[pid] == 0, f"shared cross page {pid}"
            self._free.append(pid)

    # -- encdec cross-attention pages -----------------------------------------

    def alloc_cross(self, seq_id: int, n_tokens: int) -> Optional[List[int]]:
        """Reserve private pages for ``n_tokens`` of encoder cross KV.

        The engine writes them exactly once (the admission-time encoder
        pass) and they stay read-only until :meth:`release`. Returns the
        page ids, or None (no state change) if the pool cannot cover
        them — the scheduler treats that like any other admission
        failure.
        """
        if seq_id in self._cross_tables:
            raise ValueError(f"seq {seq_id} already has cross pages")
        need = self.blocks_for_tokens(n_tokens)
        if need > self.free_capacity():
            return None
        pages = [self._acquire() for _ in range(need)]
        self._cross_tables[seq_id] = pages
        self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                      self.blocks_in_use)
        return pages

    def has_cross(self, seq_id: int) -> bool:
        return seq_id in self._cross_tables

    def cross_row(self, seq_id: int, width: Optional[int] = None
                  ) -> np.ndarray:
        """(width,) int32 cross-page table, null-page padded."""
        blocks = self._cross_tables[seq_id]
        row = np.zeros((width or len(blocks),), np.int32)
        row[:len(blocks)] = blocks
        return row

    def batch_cross(self, seq_ids: Sequence[Optional[int]],
                    width: int) -> np.ndarray:
        """(len(seq_ids), width) int32; None/crossless rows -> null."""
        out = np.zeros((len(seq_ids), width), np.int32)
        for i, sid in enumerate(seq_ids):
            if sid is not None and sid in self._cross_tables:
                out[i] = self.cross_row(sid, width)
        return out

    def table_row(self, seq_id: int) -> np.ndarray:
        """(max_blocks_per_seq,) int32 page table, null-page padded."""
        row = np.zeros((self.max_blocks_per_seq,), np.int32)
        blocks = self._tables[seq_id]
        row[:len(blocks)] = blocks
        return row

    def batch_tables(self, seq_ids: Sequence[Optional[int]]) -> np.ndarray:
        """(len(seq_ids), max_blocks_per_seq) int32; None rows -> null."""
        out = np.zeros((len(seq_ids), self.max_blocks_per_seq), np.int32)
        for i, sid in enumerate(seq_ids):
            if sid is not None:
                out[i] = self.table_row(sid)
        return out


# -- functional device-side ops (used inside jitted model steps) --------------


def write_tokens(pool: Array, kv: Array, block_ids: Array,
                 offsets: Array) -> Array:
    """Scatter token KV rows into one layer's page pool.

    pool: (N, KV, bs, hd); kv: (B, C, KV, hd); block_ids/offsets: (B, C)
    int32 page id / in-page slot per token (masked tokens aim at page 0).
    The two index arrays are split by the head slice, so the indexed
    view is (B, C, KV, hd) — the layout ``kv`` already has.
    """
    return pool.at[block_ids, :, offsets].set(kv.astype(pool.dtype))


def slots_for_positions(positions: Array, block_size: int,
                        tables: Array):
    """Map absolute positions (B, C) + tables (B, NB) -> (block_ids, offsets).

    Out-of-range positions (``>= NB * block_size``, or negative) route
    **explicitly to the null page 0** rather than being clamped into the
    last table entry: a live page sitting in a table's final row must
    never absorb an over-range write, regardless of what the caller put
    there. In-range positions of padded/inactive lanes still resolve
    through their (all-null) table rows as before.
    """
    nb = tables.shape[1]
    blk_idx = positions // block_size
    in_range = (blk_idx >= 0) & (blk_idx < nb)
    block_ids = jnp.take_along_axis(tables, jnp.clip(blk_idx, 0, nb - 1),
                                    axis=1)
    block_ids = jnp.where(in_range, block_ids, 0)
    offsets = positions % block_size
    return block_ids, offsets


def copy_pages(pools: Dict[str, Array], src: Array,
               dst: Array) -> Dict[str, Array]:
    """COW on device: duplicate pages ``src`` into ``dst`` across all
    layers of every pool (int32 id vectors — padding pairs point both
    ids at the null page 0; jitted by the engine)."""
    return {name: pool.at[:, dst].set(pool[:, src])
            for name, pool in pools.items()}


def gather_kv(pool: Array, table: Array) -> Array:
    """Reference path: gather one layer's pages to a contiguous cache.

    pool: (N, KV, bs, hd); table: (B, NB) -> (B, NB*bs, KV, hd). Used by
    the XLA fallback backend and by paged-vs-dense equivalence tests; the
    Pallas backend never materializes this.
    """
    n, kvh, bs, hd = pool.shape
    b, nb = table.shape
    pages = jnp.take(pool, table.reshape(-1), axis=0)   # (B*NB, KV, bs, hd)
    pages = pages.reshape(b, nb, kvh, bs, hd).transpose(0, 1, 3, 2, 4)
    return pages.reshape(b, nb * bs, kvh, hd)
