"""Paged KV cache semantics (paddle_tpu/inference/kv_cache.py): page
accounting, copy-on-write on shared tails, ref-counted prefix sharing,
digest-collision safety, and LRU eviction that never touches a pinned
page. Pure numpy — no jax in this file.
"""
import numpy as np
import pytest

from paddle_tpu.inference.kv_cache import CacheOOM, PagedKVCache


def make_cache(num_pages=8, page_size=4, heads=2, dim=4, **kw):
    return PagedKVCache(num_pages, page_size, heads, dim, **kw)


def kv_for(tokens, heads=2, dim=4, layers=1):
    """Deterministic K/V rows derived from token ids, so a page's
    contents can be checked later by value."""
    t = np.asarray(tokens, np.float32).reshape(1, -1, 1, 1)
    k = np.broadcast_to(t, (layers, t.shape[1], heads, dim)).copy()
    return k, -k


def fill(cache, seq, tokens):
    k, v = kv_for(tokens, cache.k.shape[2], cache.k.shape[4],
                  cache.num_layers)
    cache.append(seq, tokens, k, v)


# -- basic paging -------------------------------------------------------------

def test_alloc_block_table_and_release():
    c = make_cache()
    s = c.create([])
    fill(c, s, list(range(10)))          # 2.5 pages
    assert s.length == 10 and len(s.pages) == 3
    assert c.used_pages() == 3
    bt = c.block_table(s, 5)
    assert bt.dtype == np.int32 and list(bt[:3]) == s.pages
    assert list(bt[3:]) == [0, 0]
    with pytest.raises(ValueError):
        c.block_table(s, 2)              # narrower than the sequence
    c.release(s)
    # 2 full pages registered for sharing (evictable), the partial tail
    # page was private and freed immediately
    st = c.stats()
    assert st["pages_used"] == 2 and st["registered"] == 2
    assert st["evictable"] == 2
    c.release(s)                         # idempotent
    with pytest.raises(ValueError):
        fill(c, s, [1])                  # released sequences are closed


def test_partial_tail_never_registered():
    c = make_cache(page_size=4)
    s = c.create([])
    fill(c, s, [1, 2, 3])                # < one page
    assert c.stats()["registered"] == 0
    assert c.match_prefix([1, 2, 3]) == (0, [])
    c.release(s)
    assert c.used_pages() == 0           # private page freed


def test_pages_needed_and_can_admit():
    c = make_cache(num_pages=4, page_size=4)
    assert c.pages_needed(0) == 0
    assert c.pages_needed(1) == 1
    assert c.pages_needed(4) == 1
    assert c.pages_needed(5) == 2
    assert c.can_admit(4) and not c.can_admit(5)
    s = c.create([])
    fill(c, s, list(range(8)))           # 2 pages pinned by s
    assert not c.can_admit(3)
    c.release(s)                         # both registered -> evictable
    assert c.can_admit(4)


# -- prefix sharing + refcounts ----------------------------------------------

def test_prefix_reuse_pins_pages_and_counts_hit_tokens():
    c = make_cache(page_size=4)
    a = c.create(list(range(8)))
    assert a.cached_tokens == 0          # cold cache
    fill(c, a, list(range(8)))
    b = c.create(list(range(8)))
    assert b.cached_tokens == 8 and b.pages == a.pages
    assert c.prefix_hit_tokens == 8
    for p in b.pages:
        # prefix table + a + b
        assert c.ref[p] == 3


def test_refcount_drop_never_frees_still_referenced_page():
    c = make_cache(page_size=4)
    a = c.create([])
    fill(c, a, list(range(8)))
    b = c.create(list(range(8)))         # pins a's registered pages
    shared = list(b.pages)
    c.release(a)
    # pages must survive: b still decodes through them
    assert c.free_pages() == c.num_pages - 2
    assert c.stats()["evictable"] == 0   # pinned by b -> not evictable
    for p in shared:
        assert c.ref[p] == 2             # prefix table + b
        np.testing.assert_array_equal(c.k[0, p, 0],
                                      c.k[0, p, 0])  # still addressable
    c.release(b)
    assert c.stats()["evictable"] == 2   # only the table holds them now
    assert c.trim(10) == 2
    assert c.used_pages() == 0


def test_cow_fork_on_write_to_shared_tail():
    c = make_cache(page_size=4)
    a = c.create([])
    fill(c, a, [1, 2, 3, 4, 5, 6])       # page0 full, tail has (5, 6)
    b = c.fork(a)
    tail = a.pages[-1]
    assert b.pages == a.pages and c.ref[tail] == 2
    fill(c, a, [7])                      # writes the SHARED tail -> COW
    assert a.pages[-1] != tail and b.pages[-1] == tail
    assert c.ref[tail] == 1 and c.ref[a.pages[-1]] == 1
    # the copied prefix of the tail (tokens 5, 6) rode along
    np.testing.assert_array_equal(c.k[0, a.pages[-1], :, :2],
                                  c.k[0, tail, :, :2])
    # and b's view is untouched by a's divergence
    fill(c, b, [8])
    assert float(c.k[0, a.pages[-1], 0, 2, 0]) == 7.0
    assert float(c.k[0, b.pages[-1], 0, 2, 0]) == 8.0
    assert a.length == b.length == 7


def test_fork_then_both_diverge_full_page_registration():
    c = make_cache(page_size=2)
    a = c.create([])
    fill(c, a, [1, 2, 3])                # page full + tail (3,)
    b = c.fork(a)
    fill(c, a, [4])                      # COW, fills a's page -> registers
    fill(c, b, [5])                      # COW, fills b's page -> registers
    assert c.match_prefix([1, 2, 3, 4])[0] == 4
    assert c.match_prefix([1, 2, 3, 5])[0] == 4
    assert c.match_prefix([1, 2, 9, 9])[0] == 2


# -- eviction -----------------------------------------------------------------

def test_eviction_refuses_pinned_pages():
    c = make_cache(num_pages=2, page_size=4)
    a = c.create([])
    fill(c, a, list(range(8)))           # both pages pinned + registered
    with pytest.raises(CacheOOM):
        b = c.create([])
        fill(c, b, [0])                  # nothing evictable -> OOM
    assert c.evictions == 0              # never evicted a pinned page
    c.release(a)
    b = c.create([])
    fill(c, b, [0])                      # now an LRU page gets evicted
    assert c.evictions == 1
    assert c.stats()["registered"] == 1


def test_lru_eviction_is_least_recently_matched_first():
    c = make_cache(num_pages=3, page_size=4)
    chains = {}
    for base in (0, 100, 200):
        s = c.create([])
        toks = list(range(base, base + 4))
        fill(c, s, toks)
        c.release(s)
        chains[base] = (toks, s)
    # touch chain 0 (create pins + LRU-touches; match_prefix is a pure
    # peek) so chain 100 becomes the LRU victim
    t = c.create(chains[0][0])
    assert t.cached_tokens == 4
    c.release(t)
    s = c.create([])
    fill(c, s, [999])                    # full pool -> one eviction
    assert c.evictions == 1
    assert c.match_prefix(chains[0][0])[0] == 4      # survived (touched)
    assert c.match_prefix(chains[100][0])[0] == 0    # evicted
    assert c.match_prefix(chains[200][0])[0] == 4    # survived


def test_trim_counts_and_stops_at_pinned():
    c = make_cache(num_pages=4, page_size=2)
    a = c.create([])
    fill(c, a, [1, 2, 3, 4])             # 2 registered pages
    b = c.create([1, 2])                 # pins the first one
    c.release(a)
    assert c.trim(10) == 1               # only the unpinned page goes
    assert c.stats()["evictable"] == 0
    c.release(b)


# -- digest safety ------------------------------------------------------------

def test_digest_collision_full_token_compare():
    c = make_cache(page_size=4,
                   digest_fn=lambda chain, chunk: "COLLIDE")
    a = c.create([])
    fill(c, a, [1, 2, 3, 4])
    # a different chunk hashes to the same digest; the full-token
    # compare must reject it — wrong KV is never served
    assert c.match_prefix([9, 9, 9, 9]) == (0, [])
    assert c.match_prefix([1, 2, 3, 4])[0] == 4
    s = c.create([9, 9, 9, 9, 5])
    assert s.cached_tokens == 0


def test_chained_digest_distinguishes_same_chunk_after_divergence():
    c = make_cache(page_size=2)
    a = c.create([])
    fill(c, a, [1, 2, 7, 8])             # chain: (1,2) -> (7,8)
    b = c.create([])
    fill(c, b, [3, 4, 7, 8])             # same 2nd chunk, different chain
    # matching (1,2,7,8) must NOT pick up b's (7,8) page
    n, pages = c.match_prefix([1, 2, 7, 8])
    assert n == 4 and pages == a.pages
    n, pages = c.match_prefix([3, 4, 7, 8])
    assert n == 4 and pages == b.pages
    assert a.pages[1] != b.pages[1]
