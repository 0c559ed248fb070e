"""KVBlockPool contract tests (serve/kvpool.py): pure host bookkeeping —
no jax, no engine. These pin the design contracts the prefix cache's
correctness rests on: block-granular trie keys, the match cap that always
leaves a suffix to prefill, pin/release refcounting, LRU refcount-0 LEAF
eviction (prefix closure), and byte accounting for the pool gauge.
"""

from __future__ import annotations

import threading

import pytest

from distributed_tensorflow_tpu.serve.kvpool import KVBlockPool, PrefixMatch


def _prompt(*blocks):
    """Flatten block tuples into one token list."""
    out = []
    for b in blocks:
        out.extend(b)
    return out


A, B, C, D = (1, 2, 3, 4), (5, 6, 7, 8), (9, 10, 11, 12), (13, 14, 15, 16)


def test_ctor_validates():
    with pytest.raises(ValueError, match="block"):
        KVBlockPool(0, 4)
    with pytest.raises(ValueError, match="block_tokens"):
        KVBlockPool(4, 0)


def test_insert_indexes_full_blocks_only():
    pool = KVBlockPool(8, 4)
    # 10 tokens = 2 full blocks + a 2-token tail that must NOT be indexed.
    new = pool.insert(_prompt(A, B) + [99, 98])
    assert [idx for _, idx in new] == [0, 1]
    assert len({blk for blk, _ in new}) == 2
    assert pool.stats()["blocks_used"] == 2
    # Re-inserting the same prompt allocates nothing: already cached.
    assert pool.insert(_prompt(A, B) + [99, 98]) == []
    # A diverging second block shares block 0 and allocates only block 1.
    new = pool.insert(_prompt(A, C))
    assert [idx for _, idx in new] == [1]
    assert pool.stats()["blocks_used"] == 3


def test_match_caps_to_leave_a_suffix():
    pool = KVBlockPool(8, 4)
    pool.insert(_prompt(A, B))
    # Prompt exactly == cached blocks: the LAST block must not match, or
    # there would be no token left to prefill first-token logits from.
    m = pool.match(_prompt(A, B))
    assert m.cached_len == 4 and len(m.blocks) == 1
    pool.release(m)
    # One extra token past the cached blocks: both blocks match.
    m = pool.match(_prompt(A, B) + [42])
    assert m.cached_len == 8 and len(m.blocks) == 2
    pool.release(m)
    # Diverging second block: only the shared head matches.
    m = pool.match(_prompt(A, D) + [42])
    assert m.cached_len == 4
    pool.release(m)
    # Cold prompt / too-short prompt: empty match, no pins.
    for ids in (_prompt(C, D), list(A)):
        m = pool.match(ids)
        assert m.cached_len == 0 and m.blocks == []
        pool.release(m)


def test_release_is_idempotent_and_unpins():
    pool = KVBlockPool(2, 4)
    pool.insert(_prompt(A, B))
    m = pool.match(_prompt(A, B) + [42])
    assert all(n.refs == 1 for n in m._nodes)
    pool.release(m)
    pool.release(m)  # every exit path may release unconditionally
    assert all(n.refs == 0 for n in m._nodes)


def test_lru_evicts_coldest_leaf_keeping_prefix_closure():
    pool = KVBlockPool(3, 4)
    pool.insert(_prompt(A, B))   # chain A -> B
    pool.insert(_prompt(A, C))   # chain A -> C  (pool now full)
    # Touch C so B is the coldest leaf. A is interior — never evictable
    # while it has children, else a cached chain would dangle.
    pool.match(_prompt(A, C) + [42])
    new = pool.insert(_prompt(A, D))
    assert [idx for _, idx in new] == [1]
    assert pool.stats()["evictions"] == 1
    # B's chain is gone; A->C and A->D survive.
    assert pool.match(_prompt(A, B) + [42]).cached_len == 4
    assert pool.match(_prompt(A, D) + [42]).cached_len == 8


def test_pinned_chains_are_not_evicted():
    pool = KVBlockPool(2, 4)
    pool.insert(_prompt(A, B))
    m = pool.match(_prompt(A, B) + [42])  # pins both blocks
    # Nothing evictable (A interior, B pinned): allocation stops early and
    # indexes only what it could get — here, nothing.
    assert pool.insert(_prompt(C, D)) == []
    assert pool.match(_prompt(C, D) + [42]).cached_len == 0
    pool.release(m)
    # Unpinned, eviction cascades back-to-front: B goes first, which
    # makes A a refcount-0 leaf, so the whole cold chain is reclaimed.
    assert len(pool.insert(_prompt(C, D))) == 2


def test_byte_accounting():
    pool = KVBlockPool(4, 4, bytes_per_block=1024)
    pool.insert(_prompt(A, B) + [42])
    st = pool.stats()
    assert st["bytes_used"] == 2 * 1024
    assert st["capacity_bytes"] == 4 * 1024
    assert st["blocks"] == 4 and st["block_tokens"] == 4


def test_concurrent_match_insert_release_is_consistent():
    """Hammer one pool from several threads: no exceptions, refcounts
    return to zero, and occupancy never exceeds the pool."""
    pool = KVBlockPool(6, 4)
    errs = []

    def worker(seed):
        try:
            for i in range(200):
                ids = _prompt((A, B, C, D)[(seed + i) % 4], A) + [seed]
                m = pool.match(ids)
                pool.insert(ids)
                pool.release(m)
        except Exception as e:  # pragma: no cover - surfaced via errs
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errs
    st = pool.stats()
    assert st["blocks_used"] <= pool.n_blocks
    assert all(n.refs == 0 for n in pool._by_block.values())


def test_match_returns_prefixmatch_type():
    pool = KVBlockPool(2, 4)
    assert isinstance(pool.match(list(range(9))), PrefixMatch)


def test_indexed_blocks_are_unpublished_until_the_engine_says_so():
    """insert/index make a block matchable at once; its page holds the
    tokens only after the engine's copy is dispatched. A reader on another
    thread (the disagg page export) waits on exactly this."""
    pool = KVBlockPool(n_blocks=3, block_tokens=2)
    new = pool.insert([1, 2, 3, 4])
    ids = [b for b, _ in new]
    assert len(ids) == 2 and pool.unpublished(ids)
    pool.mark_published(ids[:1])
    assert not pool.unpublished(ids[:1]) and pool.unpublished(ids)
    pool.mark_published(ids)
    assert not pool.unpublished(ids)
    # Already cached: nothing new to publish.
    assert pool.insert([1, 2, 3, 4]) == []
    assert not pool.unpublished(ids)
    more, covered = pool.index([1, 2, 9, 9])
    assert covered == 2 and pool.unpublished([b for b, _ in more])


def test_freed_blocks_leave_the_unpublished_set():
    pool = KVBlockPool(n_blocks=2, block_tokens=2)
    (a, _), (b, _) = pool.insert([1, 2, 3, 4])
    # forget: the undo path of a publish that failed.
    assert pool.forget([1, 2, 3, 4]) == 2
    assert not pool.unpublished([a, b])
    # eviction: the leaf's page goes to another chain and must be
    # published again under its new meaning.
    (c, _), (d, _) = pool.insert([5, 6, 7, 8])
    pool.mark_published([c, d])
    ((e, _),) = pool.insert([9, 9])  # full pool: evicts the leaf, block d
    assert e == d and pool.unpublished([e])
    assert not pool.unpublished([c])
