"""Paged KV cache: device page pool + host-side allocator with prefix reuse.

The pool is a pair of arrays ``[n_layers, num_pages * page_size, n_kv_heads,
head_dim]`` — fully static shapes so every engine step hits the same compiled
program. Logical→physical mapping lives in per-slot page tables (int32), and
the free list is host-side.

Prefix caching (automatic, vLLM-style): full pages are content-addressed by a
hash chain over their token ids. When a new request's prompt shares a
page-aligned prefix with pages still resident in HBM — the same system prompt
re-sent by every agent iteration — those pages are reused (refcounted,
copy-on-write-free: shared pages are never written, because decode only ever
writes the *last, unshared* page of a sequence) and prefill skips straight to
the first novel token. Pages whose last reference drops move to an LRU of
retired-but-resident pages and are only truly recycled under pool pressure.

Two interchangeable backends implement the allocator+index: pure Python here,
and the C++ one in :mod:`runbookai_tpu.native` (selected automatically when
the compiled library is available; ``RUNBOOKAI_NATIVE=0`` disables).

A model some of whose layers keep RECURRENT state (a fixed-size matrix a
sequence, not token rows: ``models/qwen3_next.py``) makes a page hit only
half a hit: the pages hold the attention layers' keys and values up to the
boundary, and the recurrent layers' state AT that boundary has to be at
hand too. :class:`StateSnapshots` is the host-side index of a small device
pool of such states, each tied to the chain hash of the page that ends at
its boundary; with one, a match is GRANTED only back to the deepest
boundary that has a snapshot (to nothing if none has).

A model some of whose layers attend to a WINDOW (the last ``window``
positions: ``models/afmoe.py``) has two GROUPS of layers with unlike
lifetimes. The full-attention group keeps a row for every position, in the
pages above. The window group gets a second pool with its own allocator,
its own prefix index and a second half of every page-table row
(:class:`WindowSpec`): a sequence holds there the pages its next dispatch
can read and no more, what falls behind the window is given back WHILE the
sequence lives (:meth:`KVCacheManager.extend`), and a page-hash hit is
granted only to a boundary whose window pages are still resident.

No reference counterpart (SURVEY.md §2.9 item 2 — green-field requirement).
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class PagePool:
    """Device arrays for the paged KV cache."""

    kv_k: jax.Array
    kv_v: jax.Array
    page_size: int
    num_pages: int

    @staticmethod
    def create(
        n_layers: int,
        num_pages: int,
        page_size: int,
        n_kv_heads: int,
        head_dim: int,
        dtype=jnp.bfloat16,
        sharding=None,
        v_side: Optional[tuple[int, int, int]] = None,
        window: Optional[tuple[int, int]] = None,
    ) -> "PagePool":
        """``v_side``: (layers, heads, values a head) of ``kv_v`` where it
        is not ``kv_k``'s (a latent pool: ``kv_k`` holds the latent of every
        attention sublayer, ``kv_v`` the rotated keys). Both sides keep
        ``num_pages * page_size`` token rows on axis 1. ``window``: (layers,
        pages) of a second group of layers with a pool of its own; each
        side is then ``{"full": ..., "window": ...}``, one tree through
        every step program."""
        if window is not None:
            full = PagePool.create(n_layers, num_pages, page_size, n_kv_heads,
                                   head_dim, dtype, sharding, v_side)
            win = PagePool.create(window[0], window[1], page_size, n_kv_heads,
                                  head_dim, dtype, sharding)
            return PagePool(kv_k={"full": full.kv_k, "window": win.kv_k},
                            kv_v={"full": full.kv_v, "window": win.kv_v},
                            page_size=page_size, num_pages=num_pages)
        tokens = num_pages * page_size
        k_shape = (n_layers, tokens, n_kv_heads, head_dim)
        v_shape = (k_shape if v_side is None
                   else (v_side[0], tokens, v_side[1], v_side[2]))
        # int8 pools carry one f32 absmax scale per (token, kv head) —
        # tuple leaves thread through jit/scan/donation as a pytree, so
        # no engine signature changes (ops/attention.py quantize_kv).
        quantized = jnp.dtype(dtype) == jnp.int8
        scale_sharding = None
        if sharding is not None and quantized:
            from jax.sharding import NamedSharding, PartitionSpec

            scale_sharding = NamedSharding(
                sharding.mesh, PartitionSpec(*sharding.spec[:3]))

        if sharding is not None:
            # Create directly sharded (kv-heads over the model axis): a
            # host-side zeros + device_put would materialize the full
            # pool on one device first — an OOM at exactly the scale TP
            # exists for. One jitted closure per shape, reused for K and
            # V, so each zeros program compiles once.
            zeros = jax.jit(lambda shape: jnp.zeros(shape, dtype=dtype),
                            static_argnums=0, out_shardings=sharding)
            zeros_s = (jax.jit(lambda shape: jnp.zeros(shape, jnp.float32),
                               static_argnums=0, out_shardings=scale_sharding)
                       if quantized else None)

            def alloc(shape):
                return ((zeros(shape), zeros_s(shape[:3])) if quantized
                        else zeros(shape))
        else:
            def alloc(shape):
                vals = jnp.zeros(shape, dtype=dtype)
                if quantized:
                    return vals, jnp.zeros(shape[:3], jnp.float32)
                return vals

        kv_k, kv_v = alloc(k_shape), alloc(v_shape)
        return PagePool(
            kv_k=kv_k,
            kv_v=kv_v,
            page_size=page_size,
            num_pages=num_pages,
        )


def hash_blocks(token_ids: Sequence[int], page_size: int,
                max_blocks: Optional[int] = None, seed: int = 0,
                lookahead: int = 0) -> list[int]:
    """FNV-1a hash chain over full pages of ``token_ids``.

    Block i's hash folds in block i-1's, so equal hashes imply equal full
    prefixes (up to hash collisions), never equal pages at different depths.
    Dispatches to the C++ implementation when the native library is built.

    ``seed`` partitions the cache namespace: KV pages computed under a LoRA
    adapter hold DIFFERENT values for the same tokens (adapters on wk/wv),
    so each adapter_idx seeds its own chain and can never match another
    adapter's (or the base model's) pages.

    ``lookahead`` (0 or 1): a page's rows also depend on the FIRST token
    after it (a prediction module's row of position ``i`` is made of token
    ``i + 1``: models/joyai.py), so that token is folded into the page's
    hash — not into the chain, whose next block holds it anyway — and a
    page with no token after it yet has no hash.
    """
    if lookahead:
        n_full = (len(token_ids) - lookahead) // page_size
        if max_blocks is not None:
            n_full = min(n_full, max_blocks)
        if n_full <= 0:
            return []
        chain = hash_blocks(token_ids, page_size, n_full, seed)
        return [((h ^ ((token_ids[(b + 1) * page_size] + 1) & 0xFFFFFFFFFFFFFFFF))
                 * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
                for b, h in enumerate(chain)]
    from runbookai_tpu import native

    if seed == 0 and native.available():
        out = native.hash_blocks_native(token_ids, page_size, max_blocks)
        if out is not None:
            return out
    n_full = len(token_ids) // page_size
    if max_blocks is not None:
        n_full = min(n_full, max_blocks)
    out: list[int] = []
    h = 0xCBF29CE484222325 ^ ((seed * 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF)
    for b in range(n_full):
        for t in token_ids[b * page_size : (b + 1) * page_size]:
            h ^= (t + 1) & 0xFFFFFFFFFFFFFFFF
            h = (h * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
        out.append(h)
    return out


def _kv_leaves(tree: Any) -> list:
    """Flat leaves of one side of a pool (a bare array, or (values,
    scales) for quantized pools) — every leaf's axis 1 is the token/row
    axis, so page-row slicing is uniform across pool dtypes."""
    return jax.tree_util.tree_leaves(tree)


def _page_rows(pages: Sequence[int], page_size: int) -> np.ndarray:
    return np.concatenate(
        [np.arange(p * page_size, (p + 1) * page_size) for p in pages])


def _fetch_rows(tree: Any, rows: np.ndarray) -> list[np.ndarray]:
    """Fetch ``rows`` of every pool leaf to the host in one gather each.

    THE page-transfer sync point (docs/lint.md "page transfer" entry):
    every path that moves KV bytes off a device pool — cross-replica
    pull export, prefill→decode handoff, spill-tier capture — funnels
    its device→host copy through here, one batched fetch per transfer,
    never inside the decode loop (callers hold the engine lock between
    steps). tests/test_lint.py pins this as the only sanctioned sync in
    this module.
    """
    # runbook: noqa[RBK002] — sanctioned sync: the page-transfer fetch —
    # one batched device→host copy per pull/handoff/spill, on the
    # admission/routing path under the engine lock, never the decode loop.
    return [np.asarray(jax.device_get(leaf[:, rows]))
            for leaf in _kv_leaves(tree)]


def _block_digest(leaves_k: Sequence[np.ndarray],
                  leaves_v: Sequence[np.ndarray],
                  block: int, page_size: int) -> str:
    """Content digest of one page's K+V bytes in an exported batch.

    Checked again at import time: a pulled page is installed only if it is
    byte-identical to what the exporter read — a corrupted or re-ordered
    transfer must downgrade to recompute, never serve wrong KV."""
    h = hashlib.blake2b(digest_size=16)
    lo, hi = block * page_size, (block + 1) * page_size
    for leaf in (*leaves_k, *leaves_v):
        h.update(np.ascontiguousarray(leaf[:, lo:hi]).tobytes())
    return h.hexdigest()


@dataclass
class ExportedPages:
    """Host-staged KV pages in transit between pools (cross-replica pull,
    prefill→decode handoff, spill readmit). ``leaves_k``/``leaves_v`` hold
    ALL exported pages concatenated on the row axis (block ``i`` owns rows
    ``[i*page_size, (i+1)*page_size)``), fetched in ONE device→host copy.
    """

    page_size: int
    hash_seed: int
    skip_blocks: int  # chain depth of the first exported block
    hashes: list[int]  # chain hash per exported block
    blocks: list[tuple[int, ...]]  # token ids each page actually holds
    leaves_k: list[np.ndarray]
    leaves_v: list[np.ndarray]
    digests: list[str]
    src_version: int
    src_replica: Optional[int] = None

    @property
    def num_pages(self) -> int:
        return len(self.hashes)


@dataclass
class _SpillEntry:
    blocks: tuple[int, ...]
    leaves_k: list[np.ndarray]
    leaves_v: list[np.ndarray]
    digest: str


class HostSpillTier:
    """Bounded host-RAM store of evicted prefix-cache pages.

    HBM pressure evicts retired pages oldest-first; with a spill tier the
    evicted bytes drain here instead of vanishing, so the next request
    with the same prefix re-admits them (one host→device upload) instead
    of recomputing the prefill. LRU-bounded by ``max_pages``; keyed by the
    chain hash (already namespaced by the LoRA ``hash_seed``), with the
    token block stored alongside so a readmit is verified exactly like a
    resident prefix match."""

    def __init__(self, max_pages: int):
        self.max_pages = max(0, int(max_pages))
        self._store: OrderedDict[int, _SpillEntry] = OrderedDict()
        self.pages_spilled = 0  # total puts (runbook_kv_spill_pages_total)
        self.evictions = 0  # LRU drops (runbook_kv_spill_evictions_total)
        self.readmitted = 0  # pages re-admitted into a device pool

    def __len__(self) -> int:
        return len(self._store)

    def put(self, block_hash: int, blocks: tuple[int, ...],
            leaves_k: list[np.ndarray], leaves_v: list[np.ndarray],
            digest: str) -> None:
        if not self.max_pages:
            return
        if block_hash in self._store:
            self._store.move_to_end(block_hash)
            return
        while len(self._store) >= self.max_pages:
            self._store.popitem(last=False)
            self.evictions += 1
        self._store[block_hash] = _SpillEntry(blocks, leaves_k, leaves_v,
                                              digest)
        self.pages_spilled += 1

    def get(self, block_hash: int) -> Optional[_SpillEntry]:
        entry = self._store.get(block_hash)
        if entry is not None:
            self._store.move_to_end(block_hash)
        return entry

    def evict_all(self) -> int:
        """Drop every resident entry (counted as evictions) — the
        spill-pressure fault in chaos/inject.py simulates the host-RAM
        envelope collapsing under an external consumer. Returns the
        number of pages dropped. Call under the owning engine's lock
        (the tier is otherwise only touched from the step thread)."""
        dropped = len(self._store)
        self._store.clear()
        self.evictions += dropped
        return dropped


class PageAllocator:
    """Host-side allocator over physical page ids with a prefix-cache index.

    Page 0 is reserved as the "null" page that padding/unused page-table slots
    point at, so garbage gathers stay in-bounds and get masked downstream.

    Page lifecycle::

        free ──alloc──▶ referenced (ref ≥ 1, owned by live sequences)
          ▲                │ decref→0, has content hash
          │                ▼
          └──evict──── retired LRU (resident, matchable, recyclable)

    ``alloc`` prefers the free list and falls back to evicting the
    least-recently-retired cached page (its hash entry is invalidated).
    """

    NULL_PAGE = 0

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (one reserved null page)")
        self.num_pages = num_pages
        self._free: list[int] = list(range(num_pages - 1, 0, -1))  # stack; 0 reserved
        self._ref: dict[int, int] = {}
        self._retired: OrderedDict[int, None] = OrderedDict()  # LRU, ref == 0
        self._hash_to_page: dict[int, int] = {}
        self._page_to_hash: dict[int, int] = {}

    @property
    def free_pages(self) -> int:
        """Pages allocatable right now (free + evictable retired)."""
        return len(self._free) + len(self._retired)

    @property
    def cached_pages(self) -> int:
        return len(self._retired)

    def alloc(self, n: int) -> list[int]:
        if n > self.free_pages:
            raise MemoryError(
                f"KV page pool exhausted: want {n}, have {self.free_pages}")
        out: list[int] = []
        for _ in range(n):
            if self._free:
                p = self._free.pop()
            else:
                p, _ = self._retired.popitem(last=False)  # oldest retired
                self._invalidate(p)
            self._ref[p] = 1
            out.append(p)
        return out

    def free(self, pages: Sequence[int]) -> None:
        """Decref each page; unreferenced pages retire (if hashed) or free."""
        for p in pages:
            if p == self.NULL_PAGE:
                continue
            r = self._ref.get(p, 0) - 1
            if r > 0:
                self._ref[p] = r
                continue
            self._ref.pop(p, None)
            if p in self._page_to_hash:
                self._retired[p] = None
                self._retired.move_to_end(p)
            else:
                self._free.append(p)

    # ------------------------------------------------------------ prefix cache

    def register(self, page: int, block_hash: int) -> None:
        """Publish a full page's content hash so future prompts can match it."""
        if page == self.NULL_PAGE or block_hash in self._hash_to_page:
            return  # first writer wins; duplicates keep their private copy
        old = self._page_to_hash.get(page)
        if old is not None:
            self._hash_to_page.pop(old, None)
        self._page_to_hash[page] = block_hash
        self._hash_to_page[block_hash] = page

    def lookup(self, block_hash: int) -> Optional[int]:
        return self._hash_to_page.get(block_hash)

    def acquire(self, page: int) -> None:
        """Take a reference on a matched page (reviving it if retired)."""
        if page in self._retired:
            del self._retired[page]
            self._ref[page] = 1
        else:
            self._ref[page] = self._ref.get(page, 0) + 1

    def is_retired(self, page: int) -> bool:
        """True when the page is resident but unreferenced (counts toward
        ``free_pages``; acquiring it consumes allocatable capacity)."""
        return page in self._retired

    def _invalidate(self, page: int) -> None:
        h = self._page_to_hash.pop(page, None)
        if h is not None and self._hash_to_page.get(h) == page:
            del self._hash_to_page[h]


STATE_COUNTERS = ("snapshots_taken", "snapshots_restored", "snapshot_evictions",
                  "hash_tokens_matched", "hash_tokens_granted")


@dataclass
class _Snapshot:
    idx: int  # row of the device snapshot pool
    block_hash: int  # chain hash of the page that ends at the boundary
    page: int  # the page that held it when the snapshot was taken
    hits: int = 0  # admissions restored from it
    last_use: int = 0


class StateSnapshots:
    """Host-side index of the device pool of recurrent-state snapshots.

    A snapshot is the recurrent layers' state after exactly the tokens of
    a page-aligned prefix, keyed by that prefix's chain hash. The engine
    copies a slot's state into row ``idx`` of the pool when a prefill
    chunk ends on a page boundary (:meth:`KVCacheManager.take_snapshot`)
    and copies it back into a slot when an admission is granted that
    boundary. A snapshot leaves with its page (:meth:`sweep`: the hash no
    longer resolves to the page it was taken beside) or before it, when
    the pool is full: the victim is the least recently taken of the
    snapshots that were NEVER restored, and only when every one has been,
    the least recently restored — a shared prefix's boundary outlives the
    boundaries inside the one-off prompts that follow it."""

    def __init__(self, size: int):
        self.size = int(size)
        self._by_hash: dict[int, _Snapshot] = {}
        self._free = list(range(self.size - 1, -1, -1))
        self._tick = 0
        self.reset_counters()

    def __len__(self) -> int:
        return len(self._by_hash)

    def reset_counters(self) -> None:
        """What the engine's ``state_*`` metrics and the step record's
        ``state`` report: snapshots taken, admissions restored from one,
        snapshots evicted by a full pool, and the prompt tokens admissions
        matched by page hash beside those they were granted."""
        self.counters = dict.fromkeys(STATE_COUNTERS, 0)

    def lookup(self, block_hash: int) -> Optional[_Snapshot]:
        return self._by_hash.get(block_hash)

    def sweep(self, allocator) -> None:
        """Drop the snapshots whose page was recycled or re-registered."""
        for h, snap in list(self._by_hash.items()):
            if allocator.lookup(h) != snap.page:
                del self._by_hash[h]
                self._free.append(snap.idx)

    def touch(self, snap: _Snapshot) -> None:
        self._tick += 1
        snap.hits += 1
        snap.last_use = self._tick
        self.counters["snapshots_restored"] += 1

    def take(self, block_hash: int, page: int) -> Optional[int]:
        """The pool row to copy a state into for ``block_hash``, or None
        when it already has a snapshot (or the pool has no rows)."""
        if not self.size or block_hash in self._by_hash:
            return None
        if not self._free:
            victim = min(self._by_hash.values(),
                         key=lambda s: (s.hits > 0, s.last_use))
            del self._by_hash[victim.block_hash]
            self._free.append(victim.idx)
            self.counters["snapshot_evictions"] += 1
        self._tick += 1
        snap = _Snapshot(self._free.pop(), block_hash, page,
                         last_use=self._tick)
        self._by_hash[block_hash] = snap
        self.counters["snapshots_taken"] += 1
        return snap.idx


WINDOW_COUNTERS = ("rows_released", "hash_tokens_matched", "hash_tokens_granted")


@dataclass(frozen=True)
class WindowSpec:
    """The window group of a model's layers, as the manager needs it: a
    property of the CONFIGURATION (``cfg.kv_window_spec``) and of the
    engine's shapes, never a model's name."""

    n_layers: int  # layers whose queries see the last ``window`` positions
    window: int
    max_step: int  # the most tokens one dispatch adds to a sequence
    slots: int  # the most sequences that live at once

    def first_block(self, query: int, page_size: int) -> int:
        """The page that holds the oldest position a query at ``query``
        sees (``query - window + 1``)."""
        return max(0, query - self.window + 1) // page_size

    def chunk_work(self, first: int, n: int) -> tuple[int, int]:
        """(query-key pairs, distinct key rows) the window leaves of ``n``
        queries at positions ``first ..``: a query at ``p`` sees ``min(p + 1,
        window)`` keys, and together they see the rows from the first
        query's edge to the last query."""
        w = self.window
        full = max(0, first + n - max(first, w - 1))  # queries that see a whole window
        short = n - full  # the first ones of a sequence see p + 1
        pairs = full * w + short * (2 * first + short + 1) // 2
        return pairs, min(first + n, n + w - 1)

    def rows_bound(self, page_size: int) -> int:
        """The most token rows a live sequence holds in the window group,
        whatever its context: the window, the dispatch being written, and
        a page's slack at either end."""
        return self.window + self.max_step + 2 * page_size

    def pages(self, page_size: int) -> int:
        """The window pool: every slot at its bound, and the null page."""
        return self.slots * -(-self.rows_bound(page_size) // page_size) + 1


@dataclass
class SequenceAllocation:
    """Pages owned by one live sequence."""

    pages: list[int] = field(default_factory=list)
    ctx_len: int = 0  # tokens currently cached
    registered_blocks: int = 0  # full pages whose hashes are published
    hash_seed: int = 0  # prefix-cache namespace (LoRA adapter_idx)
    # Row of the snapshot pool the admission was granted its prefix from
    # (a model with recurrent state): the engine restores it into the slot.
    restore_from: Optional[int] = None
    # The window group's pages (a model with window layers): block -> page,
    # the blocks its next dispatch can read; ``win_lo`` is the lowest block
    # it may still ask for (what lies under it was given back or never
    # held), ``prompt`` / ``chain`` the tokens and hashes its pages are
    # published under when they are given back.
    win_pages: dict[int, int] = field(default_factory=dict)
    win_lo: int = 0
    prompt: Optional[Sequence[int]] = None
    chain: Optional[list[int]] = None

    def pages_needed(self, new_len: int, page_size: int) -> int:
        have = len(self.pages)
        need = (new_len + page_size - 1) // page_size
        return max(0, need - have)


class KVCacheManager:
    """Pairs the device pool with the allocator and builds page tables."""

    def __init__(
        self,
        n_layers: int,
        num_pages: int,
        page_size: int,
        n_kv_heads: int,
        head_dim: int,
        max_seq_len: int,
        dtype=jnp.bfloat16,
        allocator: Optional[PageAllocator] = None,
        sharding=None,
        spill_pages: int = 0,
        v_side: Optional[tuple[int, int, int]] = None,
        state_snapshots: Optional[int] = None,
        lookahead: int = 0,
        window: Optional[WindowSpec] = None,
    ):
        # Tokens past a page's end that its rows depend on (``hash_blocks``):
        # a page is published, matched and verified with that many more.
        self.lookahead = lookahead
        # None: every layer's state is token rows in pages, and a page hit
        # is the whole hit. A number (0 included): the model also keeps
        # recurrent state, and a hit is granted only to a boundary whose
        # snapshot is among that many (the engine owns the device pool).
        self.snapshots: Optional[StateSnapshots] = (
            None if state_snapshots is None else StateSnapshots(state_snapshots))
        # None: every layer keeps every position, in the pages. A spec: the
        # model's window layers page a pool of their own (``win_allocator``,
        # ``_win_tokens``: the allocator and the verified tokens of THAT
        # pool's pages), sized so that no live sequence ever waits for it.
        self.window = window
        if window is not None and (spill_pages or state_snapshots is not None
                                   or lookahead or sharding is not None):
            raise ValueError(
                "a model with window layers is not built with the host spill "
                "tier, recurrent-state snapshots, a drafter's lookahead or a "
                "sharded pool")
        self.pool = PagePool.create(
            n_layers, num_pages, page_size, n_kv_heads, head_dim, dtype,
            sharding=sharding, v_side=v_side,
            window=(None if window is None
                    else (window.n_layers, window.pages(page_size))))
        from runbookai_tpu.native import make_page_allocator

        if allocator is None:
            allocator = make_page_allocator(num_pages)
        self.allocator = allocator
        self.win_allocator = (None if window is None
                              else make_page_allocator(window.pages(page_size)))
        self._win_tokens: dict[int, tuple[int, ...]] = {}
        self.window_counters = dict.fromkeys(WINDOW_COUNTERS, 0)
        self.page_size = page_size
        self.max_pages_per_seq = (max_seq_len + page_size - 1) // page_size
        self.seqs: dict[str, SequenceAllocation] = {}
        # Monotonic page-table version: bumped whenever any sequence's page
        # list changes (add/extend/release). Consumers that upload page
        # tables to the device (engine decode dispatch, draft worker) key
        # their caches on it, so a steady-state decode step rebuilds
        # nothing and a stale table can never survive an allocation.
        self.version = 0
        # Whether the LAST import_pages call hit a content-digest
        # mismatch (payload corrupted in transit). Set under the engine
        # lock alongside the import itself; the fleet router reads it to
        # attribute the stale-pull reason label
        # (runbook_router_xreplica_stale_total{reason="digest_mismatch"}).
        self.last_import_digest_mismatch = False
        # Token ids actually stored in each published page — matches are
        # verified against these so a 64-bit hash collision can never serve
        # another request's KV (cross-request leakage). Bounded by num_pages.
        self._page_tokens: dict[int, tuple[int, ...]] = {}
        # Host-RAM spill tier (0 = disabled): evicted prefix-cache pages
        # drain here instead of vanishing; readmit_spilled pulls them back
        # under a fresh prefix match. Spill capture needs the allocator's
        # retired-LRU internals, so it is a pure-Python-allocator feature
        # (the native allocator reports no evictable inventory and the
        # tier stays empty — correct, just cold).
        self.spill: Optional[HostSpillTier] = (
            HostSpillTier(spill_pages) if spill_pages > 0 else None)

    # ----------------------------------------------------------- prefix reuse

    def _prompt_hashes(self, prompt_ids: Sequence[int],
                       hashes: Optional[list[int]],
                       hash_seed: int = 0) -> list[int]:
        """Hash chain for matching: capped below ``len(prompt_ids)`` so at
        least one prompt token is always prefilled (the engine needs its
        logits to sample from). ``hashes`` may be a memoized full chain."""
        max_blocks = (len(prompt_ids) - 1) // self.page_size
        if hashes is not None:
            return hashes[:max_blocks]
        return hash_blocks(prompt_ids, self.page_size, max_blocks,
                           seed=hash_seed, lookahead=self.lookahead)

    def _block(self, token_ids: Sequence[int], b: int) -> tuple[int, ...]:
        """The tokens page ``b``'s rows are made of: what a match is
        verified against."""
        return tuple(token_ids[b * self.page_size:
                               (b + 1) * self.page_size + self.lookahead])

    def _match_pages(self, prompt_ids: Sequence[int],
                     hashes: Optional[list[int]],
                     hash_seed: int = 0) -> list[int]:
        """Resident pages holding the prompt's leading full blocks, verified
        token-by-token (a bare hash hit is never trusted)."""
        matched: list[int] = []
        for b, h in enumerate(self._prompt_hashes(prompt_ids, hashes,
                                                  hash_seed)):
            page = self.allocator.lookup(h)
            if page is None:
                break
            if self._page_tokens.get(page) != self._block(prompt_ids, b):
                break  # hash collision or stale publish — treat as a miss
            matched.append(page)
        return matched

    def _grant(self, matched: list[int], prompt_ids: Sequence[int],
               hashes: Optional[list[int]], hash_seed: int = 0,
               sweep: bool = True) -> tuple[list[int], Optional[_Snapshot]]:
        """What of a verified hash match an admission may USE: all of it
        where pages are the whole state; with recurrent state, the pages
        up to the deepest boundary that has a snapshot, and that snapshot
        (nothing, if no boundary of the match has one)."""
        if self.window is not None:
            chain = self._prompt_hashes(prompt_ids, hashes, hash_seed)
            return matched[:self._window_grant(len(matched), prompt_ids, chain)], None
        if self.snapshots is None:
            return matched, None
        if sweep:
            self.snapshots.sweep(self.allocator)
        chain = self._prompt_hashes(prompt_ids, hashes, hash_seed)
        for b in range(len(matched), 0, -1):
            snap = self.snapshots.lookup(chain[b - 1])
            if snap is not None:
                return matched[:b], snap
        return [], None

    def _window_page(self, prompt_ids: Sequence[int], chain: list[int],
                     b: int) -> Optional[int]:
        """The window pool's resident, verified page of the prompt's block
        ``b``, or None."""
        page = self.win_allocator.lookup(chain[b])
        if page is None or self._win_tokens.get(page) != self._block(prompt_ids, b):
            return None
        return page

    def _window_grant(self, n_matched: int, prompt_ids: Sequence[int],
                      chain: list[int]) -> int:
        """The deepest boundary ``b <= n_matched`` (in pages) at which the
        window layers can resume: every page the query at ``b * page_size``
        sees is still resident in the window pool. 0: none is."""
        runs, run = [], 0  # resident window pages in a row, ending at block b
        for b in range(n_matched):
            run = run + 1 if self._window_page(prompt_ids, chain, b) is not None else 0
            runs.append(run)
        for b in range(n_matched, 0, -1):
            if runs[b - 1] >= b - self.window.first_block(b * self.page_size,
                                                          self.page_size):
                return b
        return 0

    def match_prefix(self, prompt_ids: Sequence[int],
                     hashes: Optional[list[int]] = None,
                     hash_seed: int = 0) -> int:
        """Longest reusable page-aligned prefix length (read-only probe:
        routers call it without the engine's lock, so nothing is swept)."""
        matched = self._match_pages(prompt_ids, hashes, hash_seed)
        granted, _ = self._grant(matched, prompt_ids, hashes, hash_seed,
                                 sweep=False)
        return len(granted) * self.page_size

    def probe_admit(self, prompt_ids: Sequence[int], headroom_tokens: int = 0,
                    hashes: Optional[list[int]] = None,
                    hash_seed: int = 0,
                    ) -> tuple[bool, list[int]]:
        """Admission check honoring prefix reuse: ``(fits, matched_pages)``.

        Matched *retired* pages are about to be revived by ``add_sequence`` —
        they both reduce the pages to allocate and consume allocatable
        capacity, so they must be subtracted from ``free_pages`` too (a plain
        ``can_admit(cached_len=...)`` would double-count them). The matched
        pages are returned so ``add_sequence(matched=...)`` needn't re-walk
        the chain (valid only until the next alloc/release).
        """
        matched, _ = self._grant(
            self._match_pages(prompt_ids, hashes, hash_seed), prompt_ids,
            hashes, hash_seed)
        cached = len(matched) * self.page_size
        reserved = sum(1 for p in matched if self.allocator.is_retired(p))
        need = self.add_pages_needed(len(prompt_ids), cached, headroom_tokens)
        return need <= self.allocator.free_pages - reserved, matched

    def add_sequence(self, seq_id: str, prompt_ids: Optional[Sequence[int]] = None,
                     hashes: Optional[list[int]] = None,
                     matched: Optional[list[int]] = None,
                     hash_seed: int = 0) -> int:
        """Register a sequence, reusing cached prefix pages. Returns the
        number of prompt tokens whose KV is already resident. ``matched``
        short-circuits the chain walk with pages a just-run ``probe_admit``
        already verified. ``hash_seed`` (the LoRA adapter row) is REMEMBERED
        on the allocation, so later publishes release into the same cache
        namespace the pages were matched from."""
        alloc = SequenceAllocation(hash_seed=hash_seed)
        cached = 0
        if prompt_ids and self.window is not None:
            # As with snapshots below: matched against granted, counted once
            # an admission; the window pages the boundary's query sees are
            # shared with whoever holds them, like the pages of the match.
            alloc.prompt, alloc.chain = prompt_ids, hashes
            full = self._match_pages(prompt_ids, hashes, hash_seed)
            matched, _ = self._grant(full, prompt_ids, hashes, hash_seed)
            self.window_counters["hash_tokens_matched"] += len(full) * self.page_size
            self.window_counters["hash_tokens_granted"] += len(matched) * self.page_size
            chain = self._prompt_hashes(prompt_ids, hashes, hash_seed)
            alloc.win_lo = self.window.first_block(len(matched) * self.page_size,
                                                   self.page_size)
            for b in range(alloc.win_lo, len(matched)):
                page = self._window_page(prompt_ids, chain, b)
                self.win_allocator.acquire(page)
                alloc.win_pages[b] = page
        if prompt_ids:
            if self.snapshots is not None:
                # The match is walked again: what was matched and what of
                # it is granted are both counted, here, once an admission.
                full = self._match_pages(prompt_ids, hashes, hash_seed)
                pages, snap = self._grant(full, prompt_ids, hashes, hash_seed)
                counters = self.snapshots.counters
                counters["hash_tokens_matched"] += len(full) * self.page_size
                counters["hash_tokens_granted"] += len(pages) * self.page_size
                if snap is not None:
                    self.snapshots.touch(snap)
                    alloc.restore_from = snap.idx
            else:
                pages = (matched if matched is not None
                         else self._match_pages(prompt_ids, hashes, hash_seed))
            for page in pages:
                self.allocator.acquire(page)
                alloc.pages.append(page)
                cached += self.page_size
            alloc.ctx_len = cached
            alloc.registered_blocks = len(alloc.pages)
        self.seqs[seq_id] = alloc
        self.version += 1
        return cached

    def register_prefix(self, seq_id: str, token_ids: Sequence[int],
                        hashes: Optional[list[int]] = None) -> None:
        """Publish hashes for this sequence's newly completed full pages.

        ``token_ids`` must be the tokens whose KV the pages actually hold
        (prompt plus any generated tokens already fed back).
        """
        alloc = self.seqs.get(seq_id)
        if alloc is None:
            return
        max_blocks = min((len(token_ids) - self.lookahead) // self.page_size,
                         len(alloc.pages))
        if hashes is None or len(hashes) < max_blocks:
            hashes = hash_blocks(token_ids, self.page_size, max_blocks,
                                 seed=alloc.hash_seed, lookahead=self.lookahead)
        for b in range(alloc.registered_blocks, max_blocks):
            page = alloc.pages[b]
            self.allocator.register(page, hashes[b])
            if self.allocator.lookup(hashes[b]) == page:  # publish took effect
                self._page_tokens[page] = self._block(token_ids, b)
            if b in alloc.win_pages:
                self._publish_window(alloc.win_pages[b], hashes[b],
                                     self._block(token_ids, b))
        alloc.registered_blocks = max(alloc.registered_blocks, max_blocks)

    def _publish_window(self, page: int, block_hash: int,
                        tokens: tuple[int, ...]) -> None:
        self.win_allocator.register(page, block_hash)
        if self.win_allocator.lookup(block_hash) == page:
            self._win_tokens[page] = tokens

    def take_snapshot(self, seq_id: str, token_ids: Sequence[int],
                      hashes: Optional[list[int]] = None) -> Optional[int]:
        """A prefill chunk of ``seq_id`` ended on a page boundary, after
        ``token_ids``: publish its full pages (a snapshot is only ever
        reached through a verified page match) and name the row of the
        snapshot pool the engine should copy the slot's state into — None
        where that boundary has a snapshot already, or its page lost the
        publish to nobody (no page backs the hash)."""
        if self.snapshots is None or not len(token_ids) \
                or len(token_ids) % self.page_size:
            return None
        self.register_prefix(seq_id, token_ids, hashes)
        block = len(token_ids) // self.page_size - 1
        alloc = self.seqs[seq_id]
        if hashes is None or len(hashes) <= block:
            hashes = hash_blocks(token_ids, self.page_size, seed=alloc.hash_seed,
                                 lookahead=self.lookahead)
        page = self.allocator.lookup(hashes[block])
        if page is None:
            return None
        return self.snapshots.take(hashes[block], page)

    # ------------------------------------------------- page transfer / spill

    def _matched_chain(self, prompt_ids: Sequence[int],
                       hashes: Optional[list[int]], hash_seed: int,
                       ) -> tuple[list[int], list[int], list[tuple[int, ...]]]:
        """Verified resident prefix: ``(pages, chain hashes, token blocks)``
        — the same walk as :meth:`_match_pages`, keeping the hash/token
        metadata a transfer payload needs."""
        pages: list[int] = []
        keep_hashes: list[int] = []
        blocks: list[tuple[int, ...]] = []
        for b, h in enumerate(self._prompt_hashes(prompt_ids, hashes,
                                                  hash_seed)):
            page = self.allocator.lookup(h)
            if page is None:
                break
            blk = self._block(prompt_ids, b)
            if self._page_tokens.get(page) != blk:
                break
            pages.append(page)
            keep_hashes.append(h)
            blocks.append(blk)
        return pages, keep_hashes, blocks

    def export_pages(self, kv_k, kv_v, prompt_ids: Sequence[int],
                     hashes: Optional[list[int]] = None, hash_seed: int = 0,
                     skip_blocks: int = 0, max_pages: Optional[int] = None,
                     ) -> Optional[ExportedPages]:
        """Stage this pool's resident prefix pages for another pool.

        ``kv_k``/``kv_v`` are the CALLER's live pool arrays (the engine's,
        not ``self.pool`` — the engine's dispatch donation leaves the pool
        handle stale after the first step). Staleness is guarded
        PER-CHAIN: a router probe reads the prefix index lock-free, and
        the plan it made is re-validated here under the engine lock by
        re-walking the chain with per-page token verification — pages
        evicted or re-registered since the probe simply fall out of the
        walk, and a plan whose pages are gone exports nothing (the
        requester recomputes). The global ``version`` epoch is NOT
        compared: it moves on every admission/extension/release anywhere
        in the pool, so on a busy source it would reject pulls whose
        pages are still verifiably resident. Matched pages are pinned
        (acquire/free) across the device→host copy so pool pressure
        cannot recycle them mid-export.
        """
        if self.window is not None:
            raise ValueError("page export between replicas is not built for "
                             "a model with window layers")
        pages, keep_hashes, blocks = self._matched_chain(prompt_ids, hashes,
                                                         hash_seed)
        if max_pages is not None:
            end = skip_blocks + max(0, max_pages)
            pages, keep_hashes, blocks = (pages[:end], keep_hashes[:end],
                                          blocks[:end])
        pages = pages[skip_blocks:]
        keep_hashes = keep_hashes[skip_blocks:]
        blocks = blocks[skip_blocks:]
        if not pages:
            return None
        for p in pages:
            self.allocator.acquire(p)
        try:
            rows = _page_rows(pages, self.page_size)
            leaves_k = _fetch_rows(kv_k, rows)
            leaves_v = _fetch_rows(kv_v, rows)
        finally:
            self.allocator.free(pages)
        digests = [_block_digest(leaves_k, leaves_v, j, self.page_size)
                   for j in range(len(pages))]
        return ExportedPages(
            page_size=self.page_size, hash_seed=hash_seed,
            skip_blocks=skip_blocks, hashes=keep_hashes, blocks=blocks,
            leaves_k=leaves_k, leaves_v=leaves_v, digests=digests,
            src_version=self.version)

    def _leaves_compatible(self, kv_k,
                           leaves_k: Sequence[np.ndarray]) -> bool:
        mine = _kv_leaves(kv_k)
        if len(mine) != len(leaves_k):
            return False
        for leaf, data in zip(mine, leaves_k):
            if (leaf.shape[0] != data.shape[0]
                    or leaf.shape[2:] != data.shape[2:]
                    or jnp.dtype(leaf.dtype) != np.dtype(data.dtype)):
                return False
        return True

    @staticmethod
    def _set_rows(tree, rows: np.ndarray, data_leaves: Sequence[np.ndarray],
                  lo: int, hi: int):
        """Write host rows ``[lo:hi)`` of each data leaf into the pool
        tree at ``rows`` (functional update — returns the new tree)."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        new = [leaf.at[:, rows].set(jnp.asarray(d[:, lo:hi], dtype=leaf.dtype))
               for leaf, d in zip(leaves, data_leaves)]
        return jax.tree_util.tree_unflatten(treedef, new)

    def _install_blocks(self, kv_k, kv_v,
                        items: Sequence[tuple]) -> tuple[Any, Any, int]:
        """Install verified blocks: one STRICTLY-FREE page per item, all
        pages written in ONE functional pool update per tree.

        ``items`` = ``(block_hash, tokens, leaves_k, leaves_v, lo, hi)``
        per block, in chain order. A per-page ``.at[].set`` would
        materialize a full pool copy per installed page — under the
        destination engine's step lock that stalls every in-flight
        decode, so the writes batch exactly like the export's single
        ``_fetch_rows`` copy. Only strictly-free pages host installs —
        never the retired prefix cache: evicting resident cache for a
        speculative install would trade a known-hot page for a maybe-hot
        one, and (since installed pages retire immediately) the alloc
        would recycle the blocks installed moments earlier in this very
        call, leaving a broken non-prefix residue. A full pool stops the
        walk, keeping the installed prefix contiguous."""
        ps = self.page_size
        staged: list[tuple[int, tuple]] = []
        for block_hash, blk, leaves_k, leaves_v, lo, hi in items:
            if self.allocator.free_pages - self.allocator.cached_pages < 1:
                break
            try:
                [page] = self.allocator.alloc(1)
            except MemoryError:
                break
            self.allocator.register(page, block_hash)
            if self.allocator.lookup(block_hash) == page:
                self._page_tokens[page] = blk
            staged.append((page, (leaves_k, leaves_v, lo, hi)))
        if not staged:
            return kv_k, kv_v, 0
        dst_rows = _page_rows([p for p, _ in staged], ps)
        n_leaves = len(_kv_leaves(kv_k))
        data_k = [np.concatenate(
            [np.ascontiguousarray(src[0][i][:, src[2]:src[3]])
             for _, src in staged], axis=1) for i in range(n_leaves)]
        data_v = [np.concatenate(
            [np.ascontiguousarray(src[1][i][:, src[2]:src[3]])
             for _, src in staged], axis=1) for i in range(n_leaves)]
        kv_k = self._set_rows(kv_k, dst_rows, data_k, 0, len(staged) * ps)
        kv_v = self._set_rows(kv_v, dst_rows, data_v, 0, len(staged) * ps)
        # Retire immediately: installed pages are matchable exactly like
        # released prefix pages, and stay evictable under pool pressure
        # so installs can never starve live sequences.
        self.allocator.free([p for p, _ in staged])
        return kv_k, kv_v, len(staged)

    def import_pages(self, kv_k, kv_v, exported: ExportedPages,
                     ) -> tuple[Any, Any, int]:
        """Install exported pages into THIS pool (returns updated arrays +
        pages imported). Each block re-verifies its content digest before
        installation; blocks whose hash already resolves to a verified
        local page are skipped (the exporter raced a local prefill — fine,
        first writer wins). Installation semantics (strictly-free pages
        only, one batched pool write, contiguous-prefix stop on a full
        pool) live in :meth:`_install_blocks` — partial prefixes are
        still byte-exact wins."""
        if self.window is not None:
            raise ValueError("page import between replicas is not built for "
                             "a model with window layers")
        self.last_import_digest_mismatch = False
        if exported.page_size != self.page_size \
                or not self._leaves_compatible(kv_k, exported.leaves_k):
            return kv_k, kv_v, 0
        ps = self.page_size
        items = []
        for j in range(exported.num_pages):
            h = exported.hashes[j]
            blk = exported.blocks[j]
            existing = self.allocator.lookup(h)
            if existing is not None and self._page_tokens.get(existing) == blk:
                continue
            if _block_digest(exported.leaves_k, exported.leaves_v, j,
                             ps) != exported.digests[j]:
                # Payload corrupted in transit — recompute instead. The
                # flag lets the puller label WHY its plan fell short.
                self.last_import_digest_mismatch = True
                break
            items.append((h, blk, exported.leaves_k, exported.leaves_v,
                          j * ps, (j + 1) * ps))
        kv_k, kv_v, imported = self._install_blocks(kv_k, kv_v, items)
        if imported:
            self.version += 1
        return kv_k, kv_v, imported

    def spill_evictable(self, kv_k, kv_v, want_pages: int) -> int:
        """Drain the retired pages an upcoming ``alloc(want_pages)`` would
        evict into the host spill tier (one batched device→host copy).

        Called by the engine right before prefill page allocation when the
        free list alone cannot satisfy the request — the only point pages
        leave HBM with their bytes still addressable. Decode-growth
        evictions skip this (no sync in the decode loop); those pages are
        simply lost to the tier, which is a cold-cache miss, not an error.
        """
        if self.spill is None:
            return 0
        free = getattr(self.allocator, "_free", None)
        retired = getattr(self.allocator, "_retired", None)
        to_hash = getattr(self.allocator, "_page_to_hash", None)
        if free is None or retired is None or to_hash is None:
            return 0  # native allocator: no evictable inventory exposed
        n_evict = min(max(0, want_pages - len(free)), len(retired))
        if n_evict <= 0:
            return 0
        victims = [p for p, _ in zip(retired.keys(), range(n_evict))
                   if to_hash.get(p) is not None
                   and self._page_tokens.get(p) is not None]
        if not victims:
            return 0
        rows = _page_rows(victims, self.page_size)
        leaves_k = _fetch_rows(kv_k, rows)
        leaves_v = _fetch_rows(kv_v, rows)
        spilled = 0
        for j, page in enumerate(victims):
            # Copies, not views: a view would keep the WHOLE batched
            # fetch alive per entry, so the tier's max_pages bound would
            # stop bounding host bytes and LRU eviction would free
            # nothing.
            lk = [np.ascontiguousarray(
                      leaf[:, j * self.page_size:(j + 1) * self.page_size])
                  for leaf in leaves_k]
            lv = [np.ascontiguousarray(
                      leaf[:, j * self.page_size:(j + 1) * self.page_size])
                  for leaf in leaves_v]
            self.spill.put(to_hash[page], self._page_tokens[page], lk, lv,
                           _block_digest(lk, lv, 0, self.page_size))
            spilled += 1
        return spilled

    def readmit_spilled(self, kv_k, kv_v, prompt_ids: Sequence[int],
                        hashes: Optional[list[int]] = None,
                        hash_seed: int = 0) -> tuple[Any, Any, int]:
        """Extend this prompt's resident prefix from the spill tier:
        blocks past the resident match whose hash+tokens verify in the
        tier are uploaded back into fresh pages (retired → matchable), so
        the admission that follows sees them as ordinary prefix hits."""
        if self.spill is None or not len(self.spill):
            return kv_k, kv_v, 0
        chain = self._prompt_hashes(prompt_ids, hashes, hash_seed)
        start = len(self._match_pages(prompt_ids, hashes, hash_seed))
        items = []
        for b in range(start, len(chain)):
            entry = self.spill.get(chain[b])
            blk = self._block(prompt_ids, b)
            if entry is None or entry.blocks != blk:
                break
            if _block_digest(entry.leaves_k, entry.leaves_v, 0,
                             self.page_size) != entry.digest:
                break  # host copy corrupted — recompute
            if not self._leaves_compatible(kv_k, entry.leaves_k):
                break
            items.append((chain[b], blk, entry.leaves_k, entry.leaves_v,
                          0, self.page_size))
        kv_k, kv_v, readmitted = self._install_blocks(kv_k, kv_v, items)
        if readmitted:
            self.version += 1
            self.spill.readmitted += readmitted
        return kv_k, kv_v, readmitted

    # -------------------------------------------------------------- pressure

    @property
    def pages_in_use(self) -> int:
        """Pages referenced by live sequences (excludes the reserved null
        page, free pages, and retired-but-resident cache pages)."""
        a = self.allocator
        return a.num_pages - 1 - a.free_pages

    def utilization(self) -> float:
        """Live-reference pressure on the allocatable pool, 0..1 — the
        KV-pressure gauge serving dashboards alert on (retired cache pages
        still count as allocatable, exactly like admission does)."""
        usable = self.allocator.num_pages - 1
        return self.pages_in_use / usable if usable > 0 else 0.0

    # -------------------------------------------------------------- lifecycle

    def add_pages_needed(self, prompt_len: int, cached_len: int = 0,
                         headroom_tokens: int = 0) -> int:
        total = (prompt_len + headroom_tokens + self.page_size - 1) // self.page_size
        return max(0, total - cached_len // self.page_size)

    def extend(self, seq_id: str, new_ctx_len: int) -> None:
        """Ensure pages exist to hold ``new_ctx_len`` tokens."""
        alloc = self.seqs[seq_id]
        if new_ctx_len > self.max_pages_per_seq * self.page_size:
            raise MemoryError(f"sequence {seq_id} exceeds max_seq_len")
        need = alloc.pages_needed(new_ctx_len, self.page_size)
        win_need = self._window_turn(alloc, new_ctx_len)
        if need:
            alloc.pages.extend(self.allocator.alloc(need))
            self.version += 1
        if win_need:
            alloc.win_pages.update(
                zip(win_need, self.win_allocator.alloc(len(win_need))))
        alloc.ctx_len = new_ctx_len

    def _window_turn(self, alloc: SequenceAllocation, new_ctx_len: int) -> list[int]:
        """The window group's side of an extension to ``new_ctx_len``: give
        back the pages no later query can see, and name the blocks the next
        dispatch writes or reads that the sequence does not hold yet. The
        next dispatch's first query is at ``new_ctx_len - max_step`` or
        later, whoever asks (a prefill chunk, a decode window, the mixed
        step asking twice), so the edge is taken from there: the sequence's
        rows stay under ``WindowSpec.rows_bound``. Raises ``MemoryError``,
        with nothing given out, where the window pool cannot hold them (it
        is sized so that it can: ``WindowSpec.pages``)."""
        if self.window is None:
            return []
        ps = self.page_size
        keep = self.window.first_block(max(0, new_ctx_len - self.window.max_step), ps)
        behind = [b for b in alloc.win_pages if b < keep]
        if behind:
            for b in behind:
                page = alloc.win_pages.pop(b)
                if alloc.chain is not None and b < len(alloc.chain) \
                        and (b + 1) * ps <= len(alloc.prompt):
                    # Published as it goes, like a page of the prompt at a
                    # prefill's end: it stays matchable until the pool
                    # needs it back.
                    self._publish_window(page, alloc.chain[b],
                                         self._block(alloc.prompt, b))
                self.win_allocator.free([page])
            self.window_counters["rows_released"] += len(behind) * ps
            self.version += 1
        alloc.win_lo = max(alloc.win_lo, keep)
        want = [b for b in range(alloc.win_lo, -(-new_ctx_len // ps))
                if b not in alloc.win_pages]
        if len(want) > self.win_allocator.free_pages:
            raise MemoryError(
                f"window page pool exhausted: want {len(want)}, have "
                f"{self.win_allocator.free_pages}")
        if want:
            self.version += 1
        return want

    def window_rows(self, seq_id: str) -> int:
        """Token rows ``seq_id`` holds in the window group."""
        return len(self.seqs[seq_id].win_pages) * self.page_size

    def can_extend(self, seq_id: str, new_ctx_len: int) -> bool:
        alloc = self.seqs.get(seq_id)
        if alloc is None:
            return False
        if self.window is not None:
            # (an upper bound: the turn gives pages back before it takes any)
            blocks = -(-new_ctx_len // self.page_size) - max(
                alloc.win_lo, max(alloc.win_pages, default=-1) + 1)
            if blocks > self.win_allocator.free_pages:
                return False
        return alloc.pages_needed(new_ctx_len, self.page_size) <= self.allocator.free_pages

    def can_admit(self, prompt_len: int, headroom_tokens: int = 0,
                  cached_len: int = 0) -> bool:
        need = self.add_pages_needed(prompt_len, cached_len, headroom_tokens)
        return need <= self.allocator.free_pages

    def release(self, seq_id: str, token_ids: Optional[Sequence[int]] = None) -> None:
        """Drop a sequence's references. When ``token_ids`` is given, full
        pages are published to the prefix cache first so the next request
        with the same prefix rides them."""
        alloc = self.seqs.get(seq_id)
        if alloc is None:
            return
        if token_ids is not None:
            self.register_prefix(seq_id, token_ids)
        del self.seqs[seq_id]
        self.allocator.free(alloc.pages)
        if alloc.win_pages:
            self.win_allocator.free(list(alloc.win_pages.values()))
        self.version += 1

    # ------------------------------------------------------------ page tables

    def page_table_row(self, seq_id: str) -> np.ndarray:
        """Padded int32 row of physical page ids for one sequence."""
        row = np.full(self.max_pages_per_seq, PageAllocator.NULL_PAGE, dtype=np.int32)
        pages = self.seqs[seq_id].pages
        row[: len(pages)] = pages
        return row

    def window_table_row(self, seq_id: str) -> np.ndarray:
        """The row's second half, for the window group: the window pool's
        page of every block the sequence holds there, null elsewhere (a
        block behind the window is never read: the walks start at the
        window's edge)."""
        row = np.full(self.max_pages_per_seq, PageAllocator.NULL_PAGE, dtype=np.int32)
        held = self.seqs[seq_id].win_pages
        if held:
            row[np.fromiter(held.keys(), np.int64, len(held))] = np.fromiter(
                held.values(), np.int32, len(held))
        return row

    def page_tables(self, seq_ids: list[str]) -> np.ndarray:
        """[len(seq_ids), max_pages_per_seq] int32; unknown ids -> null rows."""
        rows = []
        for sid in seq_ids:
            if sid in self.seqs:
                rows.append(self.page_table_row(sid))
            else:
                rows.append(np.zeros(self.max_pages_per_seq, dtype=np.int32))
        return np.stack(rows) if rows else np.zeros((0, self.max_pages_per_seq), np.int32)
