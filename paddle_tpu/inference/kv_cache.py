"""Paged KV cache: fixed page pool, block tables, copy-on-write prefix
sharing, LRU eviction.

Decode state (the K/V of every live sequence) is the capacity bottleneck
of autoregressive serving — contiguous per-sequence KV buffers fragment
and strand memory. This module is the vLLM-style answer scaled to the
repo's serving runtime:

- **Fixed pool** — ``(layers, pages, heads, page_size, head_dim)`` host
  arrays, head-major inside a page so the Pallas paged kernels can block
  one head's ``(page_size, head_dim)`` tile (the TPU lowering wants the
  block's last two dims to be the array's); a page id spans all layers,
  so one block table drives every layer's gather. Allocation is a
  free-list pop; there is no growth path,
  which is the point: capacity pressure must surface in admission
  (``can_admit``) as modeled wait / shedding, never as OOM mid-decode.
- **Prefix sharing** — completed pages register under a *chained* chunk
  digest (``digest_i = H(digest_{i-1}, chunk_i)``, so a page's identity
  encodes its whole prefix). A new sequence whose prompt walks the same
  chain reuses the pages ref-counted (+1 per sequence, +1 held by the
  prefix table itself). Hits are verified by FULL token comparison — a
  digest collision can never serve wrong KV.
- **Copy-on-write** — writes only ever target the tail page; a write to
  a tail shared with another sequence (``fork``, or a registered partial
  re-use) copies the written prefix of that page into a fresh page first.
- **LRU eviction** — pages whose only reference is the prefix table
  (ref == 1) are evictable in least-recently-matched order; pages pinned
  by a live sequence (ref > 1) are never evicted. ``_alloc`` evicts on
  demand; :class:`CacheOOM` only escapes when every page is pinned.

Telemetry: ``kv_cache_pages_{used,total}`` gauges,
``kv_cache_prefix_hits_total`` (tokens served from shared pages),
``kv_cache_evictions_total{cause}``.
"""
from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..telemetry import tracing as _tracing

__all__ = ["PagedKVCache", "CacheSeq", "CacheOOM"]


class CacheOOM(RuntimeError):
    """Page allocation failed: pool exhausted and every page is pinned."""


def _default_digest(chain: str, chunk: Tuple[int, ...]) -> str:
    h = hashlib.blake2b(digest_size=16)
    h.update(chain.encode())
    h.update(np.asarray(chunk, np.int64).tobytes())
    return h.hexdigest()


class CacheSeq:
    """One sequence's view of the cache: ordered page list + write tail."""

    __slots__ = ("seq_id", "pages", "length", "cached_tokens", "chain",
                 "tail_tokens", "released")

    def __init__(self, seq_id: int):
        self.seq_id = seq_id
        self.pages: List[int] = []
        self.length = 0               # tokens written (valid KV positions)
        self.cached_tokens = 0        # prefix tokens served from shared pages
        self.chain = ""               # digest of the last registered page
        self.tail_tokens: List[int] = []   # tokens in the partial tail page
        self.released = False


class _PrefixInfo:
    __slots__ = ("digest", "tokens")

    def __init__(self, digest: str, tokens: Tuple[int, ...]):
        self.digest = digest
        self.tokens = tokens


class PagedKVCache:
    """Fixed-pool paged KV store with ref-counted prefix sharing."""

    def __init__(self, num_pages: int, page_size: int, num_heads: int,
                 head_dim: int, num_layers: int = 1,
                 dtype=np.float32,
                 digest_fn: Optional[Callable[[str, Tuple[int, ...]],
                                              str]] = None):
        if num_pages < 1 or page_size < 1:
            raise ValueError("num_pages and page_size must be >= 1")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.num_layers = int(num_layers)
        shape = (self.num_layers, self.num_pages, int(num_heads),
                 self.page_size, int(head_dim))
        self.k = np.zeros(shape, dtype)
        self.v = np.zeros(shape, dtype)
        self.ref = [0] * self.num_pages
        self._free: List[int] = list(range(self.num_pages - 1, -1, -1))
        # digest -> page id, in LRU order (most-recently matched last)
        self._prefix: "OrderedDict[str, int]" = OrderedDict()
        self._registered: Dict[int, _PrefixInfo] = {}
        self._digest = digest_fn or _default_digest
        self._next_seq = 0
        self._lock = threading.RLock()
        self.prefix_hit_tokens = 0
        self.evictions = 0
        self._gauges()

    # -- telemetry ----------------------------------------------------------
    def _gauges(self):
        from .. import telemetry
        if telemetry.enabled():
            telemetry.gauge("kv_cache_pages_total",
                            "KV cache page pool size").set(self.num_pages)
            telemetry.gauge("kv_cache_pages_used",
                            "KV cache pages allocated").set(
                self.num_pages - len(self._free))

    def _count(self, name: str, n: int = 1, **labels):
        from .. import telemetry
        if telemetry.enabled():
            telemetry.counter(name, "").inc(n, **labels)

    # -- page plumbing ------------------------------------------------------
    def _alloc_locked(self) -> int:
        if not self._free:
            if not self._evict_one_locked(cause="capacity"):
                raise CacheOOM(
                    f"KV cache exhausted: {self.num_pages} pages, all "
                    "pinned by live sequences")
        page = self._free.pop()
        self.ref[page] = 1
        return page

    def _deref_locked(self, page: int):
        self.ref[page] -= 1
        assert self.ref[page] >= 0, f"page {page} over-released"
        if self.ref[page] == 0:
            # a registered page always holds the prefix-table ref, so a
            # zero count means it was private (or just unregistered)
            assert page not in self._registered
            self._free.append(page)

    def _evict_one_locked(self, cause: str) -> bool:
        """Drop the least-recently-matched UNPINNED prefix page. Pinned
        pages (referenced by any live sequence) are skipped — eviction
        can never pull KV out from under an in-flight decode."""
        for digest, page in self._prefix.items():
            if self.ref[page] == 1:       # only the prefix table holds it
                del self._prefix[digest]
                del self._registered[page]
                self._deref_locked(page)
                self.evictions += 1
                self._count("kv_cache_evictions_total", cause=cause)
                # lands on whichever request span drove the allocation
                _tracing.add_event("kv_eviction", page=page, cause=cause)
                return True
        return False

    # -- admission model ----------------------------------------------------
    def pages_needed(self, n_tokens: int) -> int:
        return -(-max(int(n_tokens), 0) // self.page_size)

    def evictable_pages(self) -> int:
        with self._lock:
            return sum(1 for p in self._prefix.values() if self.ref[p] == 1)

    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    def can_admit(self, n_pages: int) -> bool:
        """Would ``n_pages`` fresh allocations succeed right now (free
        pool plus evictable prefix pages)? The serving admission model's
        cache-pressure probe."""
        with self._lock:
            return len(self._free) + sum(
                1 for p in self._prefix.values()
                if self.ref[p] == 1) >= n_pages

    def trim(self, n_pages: int) -> int:
        """Explicitly evict up to ``n_pages`` unpinned prefix pages
        (LRU-first); returns how many were actually evicted."""
        done = 0
        with self._lock:
            while done < n_pages and self._evict_one_locked(cause="trim"):
                done += 1
            self._gauges()
        return done

    # -- prefix matching ----------------------------------------------------
    def _walk_locked(self, tokens) -> List[Tuple[str, int]]:
        """Chain-walk full chunks of ``tokens`` through the prefix table
        with full-token verification; returns [(digest, page), ...]."""
        toks = [int(t) for t in tokens]
        out: List[Tuple[str, int]] = []
        chain = ""
        for off in range(0, len(toks) - self.page_size + 1, self.page_size):
            chunk = tuple(toks[off:off + self.page_size])
            digest = self._digest(chain, chunk)
            page = self._prefix.get(digest)
            if page is None or self._registered[page].tokens != chunk:
                break                 # miss, or digest collision caught
            out.append((digest, page))
            chain = digest
        return out

    def match_prefix(self, tokens) -> Tuple[int, List[int]]:
        """Peek (no refs taken): (n_cached_tokens, page ids)."""
        with self._lock:
            hits = self._walk_locked(tokens)
            return len(hits) * self.page_size, [p for _, p in hits]

    # -- sequence lifecycle -------------------------------------------------
    def create(self, prompt_tokens) -> CacheSeq:
        """Open a sequence, pinning every shared prefix page its prompt
        matches. ``seq.cached_tokens`` tokens of KV are already present;
        the caller prefills (appends) from there."""
        with self._lock:
            seq = CacheSeq(self._next_seq)
            self._next_seq += 1
            hits = self._walk_locked(prompt_tokens)
            for digest, page in hits:
                self.ref[page] += 1
                self._prefix.move_to_end(digest)      # LRU touch
                seq.pages.append(page)
            seq.length = seq.cached_tokens = len(hits) * self.page_size
            seq.chain = hits[-1][0] if hits else ""
            if hits:
                self.prefix_hit_tokens += seq.cached_tokens
                self._count("kv_cache_prefix_hits_total",
                            seq.cached_tokens)
                _tracing.add_event("kv_prefix_hit",
                                   tokens=seq.cached_tokens,
                                   pages=len(hits))
            else:
                _tracing.add_event("kv_prefix_miss")
            self._gauges()
            return seq

    def fork(self, seq: CacheSeq) -> CacheSeq:
        """Share ALL of ``seq``'s pages with a new sequence (parallel
        sampling / beam split). A later write to the shared tail page
        copies it first (COW)."""
        with self._lock:
            child = CacheSeq(self._next_seq)
            self._next_seq += 1
            child.pages = list(seq.pages)
            child.length = seq.length
            child.cached_tokens = seq.cached_tokens
            child.chain = seq.chain
            child.tail_tokens = list(seq.tail_tokens)
            for page in child.pages:
                self.ref[page] += 1
            return child

    def append(self, seq: CacheSeq, tokens, k_new: np.ndarray,
               v_new: np.ndarray):
        """Write ``n`` new tokens' K/V at positions ``seq.length ...``.

        k_new/v_new: (layers, n, heads, head_dim). Allocates pages on
        demand (evicting unpinned prefix pages LRU-first); forks a shared
        tail page before writing (COW); registers each page that fills
        under its chain digest, making it shareable by later prompts.
        Raises :class:`CacheOOM` only when the pool is fully pinned.
        """
        toks = [int(t) for t in tokens]
        n = len(toks)
        if k_new.shape[1] < n or v_new.shape[1] < n:
            raise ValueError("append: fewer K/V rows than tokens")
        ps = self.page_size
        with self._lock:
            if seq.released:
                raise ValueError("append to a released sequence")
            for i in range(n):
                slot = seq.length % ps
                if slot == 0:
                    seq.pages.append(self._alloc_locked())
                else:
                    page = seq.pages[-1]
                    if self.ref[page] > 1:
                        # COW: the tail is shared — copy what's written
                        fresh = self._alloc_locked()
                        self.k[:, fresh, :, :slot] = \
                            self.k[:, page, :, :slot]
                        self.v[:, fresh, :, :slot] = \
                            self.v[:, page, :, :slot]
                        self._deref_locked(page)
                        seq.pages[-1] = fresh
                page = seq.pages[-1]
                self.k[:, page, :, slot] = k_new[:, i]
                self.v[:, page, :, slot] = v_new[:, i]
                seq.tail_tokens.append(toks[i])
                seq.length += 1
                if slot == ps - 1:
                    self._register_tail_locked(seq, page)
            _tracing.add_event("kv_append", tokens=n, pages=len(seq.pages))
            self._gauges()

    def _register_tail_locked(self, seq: CacheSeq, page: int):
        chunk = tuple(seq.tail_tokens)
        assert len(chunk) == self.page_size
        digest = self._digest(seq.chain, chunk)
        if digest not in self._prefix and page not in self._registered:
            self._prefix[digest] = page
            self._registered[page] = _PrefixInfo(digest, chunk)
            self.ref[page] += 1           # the table's own reference
        seq.chain = digest
        seq.tail_tokens = []

    def release(self, seq: CacheSeq):
        """Drop the sequence's references. Registered pages whose count
        falls to 1 become evictable; private pages free immediately."""
        with self._lock:
            if seq.released:
                return
            seq.released = True
            for page in seq.pages:
                self._deref_locked(page)
            seq.pages = []
            self._gauges()

    # -- read side ----------------------------------------------------------
    def pools(self, layer: int = 0) -> Tuple[np.ndarray, np.ndarray]:
        """(k_pool, v_pool) views for one layer: (pages, heads, page_size,
        head_dim) — the arrays the attention gather indexes."""
        return self.k[layer], self.v[layer]

    def block_table(self, seq: CacheSeq, width: int) -> np.ndarray:
        """The sequence's page ids padded to ``width`` (int32). Padded
        slots are 0 — consumers mask by ``seq.length``."""
        if len(seq.pages) > width:
            raise ValueError(
                f"sequence spans {len(seq.pages)} pages > table width "
                f"{width}")
        out = np.zeros((width,), np.int32)
        out[:len(seq.pages)] = seq.pages
        return out

    def used_pages(self) -> int:
        with self._lock:
            return self.num_pages - len(self._free)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "pages_total": self.num_pages,
                "pages_used": self.num_pages - len(self._free),
                "pages_free": len(self._free),
                "evictable": sum(1 for p in self._prefix.values()
                                 if self.ref[p] == 1),
                "registered": len(self._prefix),
                "prefix_hit_tokens": self.prefix_hit_tokens,
                "evictions": self.evictions,
            }
