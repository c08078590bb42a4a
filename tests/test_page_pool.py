"""Property tests for the paged KV cache's host allocator (``PagePool``)
and the copy-on-write seam.

The pool's ``check()`` is the oracle: the free list and the referenced
pages must partition the id space after every operation.  On top of that:

* a random admit/share/evict workload never leaks a page — when the last
  slot releases and the prefix registry drains, every page is free again;
* a page shared by ``k`` sharers is recycled exactly when the ``k``-th
  reference drops, never earlier;
* ``fork`` + ``copy_page`` (copy-on-write) never mutates the shared
  source page, bit for bit, and exclusive pages fork in place.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.runtime import kv_cache as qkv


def _pages_needed(plen, ps):
    return -(-plen // ps)


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=1, max_value=4),       # pages per prompt max
       st.integers(min_value=0, max_value=5),       # rng seed
       st.integers(min_value=6, max_value=12))      # pool size
def test_random_workload_never_leaks(max_pages, seed, n_pages):
    """Admit prompts (longest-registered-prefix hit -> ref shared, alloc
    the rest, register the chain), interleave slot releases, then drain:
    the pool must end with every page free and no invariant ever broken."""
    r = np.random.RandomState(seed)
    ps = 4
    pool = qkv.PagePool(n_pages, ps)
    # a tiny prompt universe so prefixes actually collide
    vocab = [bytes([b]) * 3 for b in range(4)]
    live = {}           # slot id -> page list held by that slot
    next_slot = 0
    for _ in range(30):
        pool.check()
        if live and r.rand() < 0.4:
            slot = r.choice(list(live))
            pool.release(live.pop(slot))
            continue
        n = int(r.randint(1, max_pages + 1))
        chain = [b"".join(vocab[r.randint(len(vocab))] for _ in range(j + 1))
                 for j in range(n)]
        for j in range(1, n):   # chains must be prefix-consistent
            chain[j] = chain[j - 1] + chain[j]
        shared = list(pool.lookup_prefix(chain))
        need = n - len(shared)
        # pin the hit before allocating, as the engine does: allocation
        # may evict LRU prefixes, the one just hit included
        pool.ref(shared)
        try:
            fresh, _ = pool.alloc_with_freed(need)
        except RuntimeError:
            pool.release(shared)
            continue            # pool genuinely full of live slots: skip
        pages = shared + fresh
        pool.register_prefix(chain, pages)
        live[next_slot] = pages
        next_slot += 1
    for pages in live.values():
        pool.release(pages)
    while pool.registered_prefixes:
        pool.drop_lru_prefix()
    pool.check()
    assert pool.free_count == n_pages, "pages leaked after full drain"
    assert pool.unique_pages_in_use == 0


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=1, max_value=5),       # sharers
       st.integers(min_value=0, max_value=3))       # seed (release order)
def test_refcount_zero_exactly_at_last_release(k, seed):
    """A page shared by ``k`` slots is freed by the ``k``-th release and
    only the ``k``-th — early releases recycle nothing."""
    pool = qkv.PagePool(4, 8)
    [pid], _ = pool.alloc_with_freed(1)
    for _ in range(k - 1):
        pool.ref([pid])
    order = np.random.RandomState(seed).permutation(k)
    for i, _ in enumerate(order):
        freed = pool.release([pid])
        pool.check()
        if i < k - 1:
            assert freed == [], f"page freed after {i + 1}/{k} releases"
            assert pool.refcount[pid] == k - 1 - i
        else:
            assert freed == [pid]
            assert pool.free_count == 4
    with pytest.raises(AssertionError):
        pool.release([pid])     # double free must be caught, not ignored


def test_fork_cow_never_mutates_shared_page():
    """The copy-on-write contract end to end: two sharers of one physical
    page; the writer forks (fresh id), ``copy_page`` clones the bits, and
    a subsequent write to the fork leaves the shared original untouched."""
    r = np.random.RandomState(2)
    ps, KV, hd = 4, 2, 8
    pool = qkv.PagePool(4, ps)
    cache = qkv.init_paged_kv_cache(4, ps, KV, hd, slots=1,
                                    pages_per_slot=1)
    [pid], _ = pool.alloc_with_freed(1)
    pool.ref([pid])             # second sharer

    # fill the shared page with real rows
    k = jnp.asarray(r.normal(size=(1, ps, KV, hd)), jnp.float32)
    v = jnp.asarray(r.normal(size=(1, ps, KV, hd)), jnp.float32)
    cache = cache.map_slot(0, jnp.asarray([pid], jnp.int32)).append_rows(
        k, v, jnp.arange(ps, dtype=jnp.int32), 0)
    before = {f: np.asarray(getattr(cache, f)[pid]).copy()
              for f in ("k", "v", "k_scale", "v_scale", "pos")}

    new_pid, needs_copy, _ = pool.fork(pid)
    assert needs_copy and new_pid != pid
    assert pool.refcount[pid] == 1      # writer's ref moved to the fork
    pool.check()
    cache = cache.copy_page(pid, new_pid)
    for f, want in before.items():      # clone is bit-identical
        np.testing.assert_array_equal(
            np.asarray(getattr(cache, f)[new_pid]), want, f)

    # the forker overwrites its copy; the shared original must not move
    cache = cache.map_slot(0, jnp.asarray([new_pid], jnp.int32))
    k2 = jnp.asarray(r.normal(size=(1, 1, KV, hd)), jnp.float32)
    cache = cache.append_rows(k2, k2, jnp.asarray([1], jnp.int32), 0)
    for f, want in before.items():
        np.testing.assert_array_equal(np.asarray(getattr(cache, f)[pid]),
                                      want,
                                      f"{f}: shared page mutated by fork")
    assert not np.array_equal(np.asarray(cache.k[new_pid]), before["k"])

    # exclusive page: fork is the identity, no copy
    same, copy2, _ = pool.fork(new_pid)
    assert same == new_pid and not copy2


def test_alloc_evicts_lru_prefix_then_raises():
    """Allocation pressure drops registered prefixes LRU-first (returning
    the recycled ids so the engine can clear device pos rows) and raises
    only when live slots truly exhaust the pool."""
    pool = qkv.PagePool(4, 8)
    a = pool.alloc(2)
    pool.register_prefix([b"old"], [a[0]])
    pool.register_prefix([b"new"], [a[1]])
    pool.release(a)             # slots gone; only the registry pins pages
    pool.lookup_prefix([b"old"])            # "old" becomes most-recent
    ids, freed = pool.alloc_with_freed(3)   # evicts LRU "new" only
    assert freed == [a[1]] and len(ids) == 3
    assert pool.registered_prefixes == 1    # "old" survives the pressure
    pool.check()
    with pytest.raises(RuntimeError):
        pool.alloc(2)           # 3 live + 1 pinned: evicting "old" frees
        # one page, still short of two — must raise, not leak
    assert pool.registered_prefixes == 0    # the failed alloc evicted it
    pool.check()
    pool.release(ids)
    assert pool.free_count == 4


def test_register_prefix_pins_each_chain_level():
    """Every chain level pins its own pages, so a shorter shared prefix
    keeps matching after a longer one is evicted."""
    pool = qkv.PagePool(6, 8)
    pages = pool.alloc(3)
    pool.register_prefix([b"p1", b"p2", b"p3"], pages)
    pool.release(pages)         # the admitting slot leaves
    # page 0 is pinned by all three levels, page 2 by one
    assert pool.refcount[pages[0]] == 3
    assert pool.refcount[pages[2]] == 1
    assert pool.lookup_prefix([b"p1"]) == tuple(pages[:1])
    # the lookup marked the 1-page chain most-recent, so the 2-page chain
    # is LRU and goes first — the shorter prefix must keep matching
    pool.drop_lru_prefix()
    assert pool.lookup_prefix([b"p1", b"p2"]) == tuple(pages[:1])
    pool.check()


def test_pool_meta_bytes_in_paged_inventory():
    """The accounting bugfix: a paged cache's ``inventory()`` itemizes the
    slot page table AND the pool's free-list/refcount meta, and
    ``cache_bytes`` is exactly their sum — the roofline reconciliation
    gate sees the real resident footprint, not just codes."""
    ps, n_pages, KV, hd = 8, 6, 2, 4
    cache = qkv.init_paged_kv_cache(n_pages, ps, KV, hd, slots=3,
                                    pages_per_slot=2)
    inv = qkv.inventory(cache)
    assert inv["codes"] == 2 * n_pages * ps * KV * hd
    assert inv["scales"] == 2 * n_pages * ps * KV * 4
    assert inv["pos"] == n_pages * ps * 4
    assert inv["table"] == 3 * 2 * 4
    assert inv["meta"] == qkv.PagePool(n_pages, ps).meta_bytes()
    assert qkv.cache_bytes(cache) == sum(inv.values())
