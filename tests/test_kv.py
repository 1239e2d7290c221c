"""Paged KV-cache block allocator (repro.serving.kv): alloc/free round
trips, refcounted prefix sharing, LRU eviction of retained blocks, OOM,
pool sizing from HBM, and randomized admit/finish schedules (hypothesis)
asserting no leak / no double free."""

import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import transformer as T
from repro.plan import TPU_V5E
from repro.serving import kv


def test_alloc_free_roundtrip():
    a = kv.BlockAllocator(8)  # block 0 reserved for garbage
    got = [a.alloc() for _ in range(7)]
    assert sorted(got) == list(range(1, 8))
    assert kv.GARBAGE_BLOCK not in got
    with pytest.raises(kv.BlockOOM):
        a.alloc()
    for b in got:
        a.free(b)
    assert a.available() == 7 and a.live_blocks() == 0
    a.check()
    # freed blocks are reusable
    assert sorted(a.alloc() for _ in range(7)) == list(range(1, 8))


def test_double_free_and_bad_ref_raise():
    a = kv.BlockAllocator(4)
    b = a.alloc()
    a.free(b)
    with pytest.raises(ValueError, match="double free"):
        a.free(b)
    with pytest.raises(ValueError, match="not live or evictable"):
        a.ref(b)
    a.check()


def test_pool_must_exceed_reserved():
    with pytest.raises(ValueError):
        kv.BlockAllocator(1)


def test_prefix_chain_full_blocks_only_and_chained_keys():
    toks = list(range(10))
    chain = kv.prefix_chain(toks, 4)
    assert len(chain) == 2  # 10 tokens -> 2 full blocks, partial tail private
    assert chain[0] == (None, (0, 1, 2, 3))
    assert chain[1] == (chain[0], (4, 5, 6, 7))
    # same tokens at a different prefix position hash differently
    other = kv.prefix_chain([9, 9, 9, 9, 4, 5, 6, 7], 4)
    assert other[1][1] == chain[1][1] and other[1] != chain[1]
    assert kv.prefix_chain([1, 2], 4) == []


def test_prefix_sharing_refcounts_and_used_words():
    a = kv.BlockAllocator(8)
    key = (None, (1, 2, 3, 4))
    b1 = a.alloc()
    a.register(b1, key)
    assert a.lookup(key) == b1
    # a second request with the same prefix shares the physical block
    assert a.ref(a.lookup(key)) == b1
    assert a.refcount(b1) == 2
    # shared block counted once in pool occupancy
    assert a.used_words(100.0) == 100.0
    a.free(b1)
    assert a.refcount(b1) == 1  # still held by the other request
    a.check()


def test_registered_block_is_retained_then_revived():
    a = kv.BlockAllocator(4)
    key = (None, (7, 7, 7, 7))
    b = a.alloc()
    a.register(b, key)
    a.free(b)  # rc 0: retained as evictable, not returned to the free list
    assert a.refcount(b) == 0 and a.lookup(key) == b
    assert a.available() == 3  # still allocatable if the pool runs dry
    revived = a.ref(a.lookup(key))
    assert revived == b and a.refcount(b) == 1
    a.check()


def test_eviction_is_lru_and_drops_the_key():
    a = kv.BlockAllocator(4)
    keys = [(None, (i,)) for i in range(3)]
    blocks = []
    for key in keys:
        b = a.alloc()
        a.register(b, key)
        blocks.append(b)
    for b in blocks:
        a.free(b)  # all three evictable, oldest-freed first
    got = [a.alloc() for _ in range(3)]  # forces eviction of all three
    assert got == blocks  # oldest first
    assert all(a.lookup(k) is None for k in keys)
    a.check()


def test_live_blocks_are_never_evicted():
    a = kv.BlockAllocator(4)
    held = a.alloc()
    key = (None, (0,))
    b = a.alloc()
    a.register(b, key)
    a.free(b)
    a.alloc()  # takes the last free block
    a.alloc()  # evicts the retained block...
    with pytest.raises(kv.BlockOOM):
        a.alloc()  # ...but never the held one
    assert a.refcount(held) == 1
    a.check()


def test_block_words_and_plan_pool_blocks():
    cfg = get_smoke("stablelm_1_6b")
    bw = kv.block_words(cfg, 16)
    n_attn = cfg.repeats * sum(1 for k in cfg.pattern if k == "attn")
    assert bw == n_attn * 2 * cfg.n_kv_heads * 16 * cfg.hd * 0.5
    # block-granular footprint: cache_footprint_words rounds max_len up
    assert T.cache_footprint_words(cfg, 24, block_size=16) == \
        T.cache_footprint_words(cfg, 32)
    # unclamped: one garbage block + batch * blocks-per-seq
    assert kv.plan_pool_blocks(cfg, max_len=64, batch_size=4) == 1 + 4 * 4
    # an HBM target caps the pool but never below one full sequence
    import dataclasses
    tiny = dataclasses.replace(TPU_V5E, hbm_words=float(8 * bw))
    assert kv.plan_pool_blocks(cfg, 64, 4, target=tiny) == 1 + 4
    big = dataclasses.replace(TPU_V5E, hbm_words=1e12)
    assert kv.plan_pool_blocks(cfg, 64, 4, target=big) == 1 + 4 * 4


def test_register_supersedes_a_chain_tail_left_by_eviction():
    """Evicting the head of a cached chain leaves its tail registered but
    unreachable; the next request for the same prefix re-registers the
    tail key on a new block instead of failing admission."""
    a = kv.BlockAllocator(5)
    chain = kv.prefix_chain([1, 0, 0, 1], 2)
    b1, b2 = a.alloc(), a.alloc()
    a.register(b1, chain[0])
    a.register(b2, chain[1])
    a.free(b1)
    a.free(b2)  # both evictable, b1 the oldest
    held = [a.alloc(), a.alloc(), a.alloc()]  # the third evicts b1
    assert a.lookup(chain[0]) is None and a.lookup(chain[1]) == b2
    for b in held:
        a.free(b)
    n1 = a.alloc()
    a.register(n1, chain[0])
    n2 = a.alloc()
    a.register(n2, chain[1])
    assert a.lookup(chain[1]) == n2 and a.refcount(b2) == 0
    a.check()
    with pytest.raises(ValueError, match="live block"):
        a.register(a.alloc(), chain[1])


def test_randomized_schedules_no_leak_no_double_free():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=60, deadline=None)
    @hyp.given(st.data())
    def run(data):
        num_blocks = data.draw(st.integers(4, 24), label="num_blocks")
        a = kv.BlockAllocator(num_blocks)
        holdings = []  # [(blocks, keys_registered)] per admitted request
        for _ in range(data.draw(st.integers(1, 40), label="steps")):
            a.check()
            if holdings and data.draw(st.booleans(), label="finish"):
                blocks, _ = holdings.pop(
                    data.draw(st.integers(0, len(holdings) - 1), label="who"))
                for b in blocks:
                    a.free(b)
                continue
            # admit: a short token stream, shared-prefix-aware reservation
            toks = data.draw(st.lists(st.integers(0, 3), min_size=1,
                                      max_size=12), label="toks")
            need = max(1, -(-len(toks) // 2))
            chain = kv.prefix_chain(toks, 2)
            blocks, keys = [], []
            for key in chain:
                hit = a.lookup(key)
                if hit is None:
                    break
                blocks.append(hit)
            evictable_hits = sum(1 for b in blocks if a.refcount(b) == 0)
            if a.available() - evictable_hits < need - len(blocks):
                continue  # backpressure: engine re-queues the request
            blocks = [a.ref(b) for b in blocks]
            for key in chain[len(blocks):]:
                b = a.alloc()
                a.register(b, key)
                blocks.append(b)
                keys.append(key)
            while len(blocks) < need:
                blocks.append(a.alloc())
            holdings.append((blocks, keys))
        # drain everything: the pool must return to fully-available
        for blocks, _ in holdings:
            for b in blocks:
                a.free(b)
        a.check()
        assert a.live_blocks() == 0
        assert a.available() == num_blocks - 1
        assert a.used_words(1.0) == 0.0

    run()
