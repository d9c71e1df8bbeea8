"""Paged KV cache edge cases: fragmentation, COW divergence mid-block,
prefix hits shorter/longer than a block, cache-full admission
backpressure, and slot-release leak accounting.

The host-side allocator tests need no JAX; the engine-level tests run the
reduced 1.8B on a 1-device mesh like tests/test_serving.py.
"""
import numpy as np
import pytest

from repro.serving.paged_cache import PagedKVCache


def _cache(**kw):
    return PagedKVCache(**{"pages": 16, "page_size": 4, "slots": 2,
                           "max_seq": 16, "prefix_cache": True, **kw})


# ----------------------------------------------------------------------
# allocator / release accounting
# ----------------------------------------------------------------------

def test_release_returns_every_page():
    pc = _cache(prefix_cache=False)
    pc.admit(0, prompt_len=6, max_new=8)
    pc.ensure_writable(0, 0, 9)           # maps blocks 0..2
    assert pc.mapped(0) == 3
    assert pc.free_pages == pc.pages - 1 - 3
    pc.release(0)
    assert pc.mapped(0) == 0
    assert pc.free_pages == pc.pages - 1   # no leak
    pc.check()


def test_release_keeps_index_shared_pages_alive():
    pc = _cache()
    pc.admit(0, prompt_len=6, max_new=2)
    pc.ensure_writable(0, 0, 5)
    prompt = np.arange(10, 16, dtype=np.int32)
    pc.insert(0, prompt)                   # index takes its own refs
    pc.release(0)
    pc.check()
    # the two prompt blocks survive in the index, not the free list
    assert pc.index_size == 2
    assert pc.free_pages == pc.pages - 1 - 2
    pages, span = pc.lookup(prompt)
    assert span == 6 and len(pages) == 2


def test_fragmented_free_list_after_mixed_length_release():
    """Mixed-length slots released out of order fragment the free list;
    subsequent admissions map non-contiguous physical pages and the
    accounting audit still balances."""
    pc = PagedKVCache(pages=12, page_size=4, slots=3, max_seq=16)
    lens = {0: 14, 1: 3, 2: 9}             # 4, 1 and 3 blocks
    for s, ln in lens.items():
        pc.admit(s, prompt_len=ln, max_new=0)
        pc.ensure_writable(s, 0, ln - 1)
    assert pc.free_pages == 11 - 8
    pc.release(1)                          # middle slot first
    pc.release(0)
    pc.check()
    # re-admit into the fragmented pool: pages come back in release order,
    # so the new slot's table is physically non-contiguous
    pc.admit(0, prompt_len=14, max_new=1)
    pc.ensure_writable(0, 0, 13)
    row = [int(p) for p in pc.table[0] if p]
    assert len(row) == 4
    assert row != sorted(row)              # genuinely fragmented
    pc.check()
    pc.release(0)
    pc.release(2)
    assert pc.free_pages == 11
    pc.check()


def test_pool_exhaustion_is_loud():
    pc = PagedKVCache(pages=5, page_size=4, slots=1, max_seq=16)
    pc.admit(0, prompt_len=16, max_new=0)
    pc.ensure_writable(0, 0, 15)           # all 4 allocatable pages
    pc2 = PagedKVCache(pages=5, page_size=4, slots=2, max_seq=16)
    pc2.admit(0, prompt_len=16, max_new=0)
    pc2.ensure_writable(0, 0, 15)
    with pytest.raises(RuntimeError, match="exhausted"):
        pc2.ensure_writable(1, 0, 0)


# ----------------------------------------------------------------------
# copy-on-write
# ----------------------------------------------------------------------

def test_cow_on_shared_tail_block():
    """A reader that diverges mid-block must not scribble on the donor's
    page: the first write into a shared block swaps in a fresh page and
    reports the (src, dst) copy."""
    pc = _cache()
    prompt = np.arange(20, 26, dtype=np.int32)      # 6 tokens: 1 full + tail
    pc.admit(0, prompt_len=6, max_new=4)
    pc.ensure_writable(0, 0, 5)
    pc.insert(0, prompt)
    pages, span = pc.lookup(prompt)
    assert span == 6
    pc.admit(1, prompt_len=6, max_new=4, shared=pages)
    shared_tail = int(pc.table[1, 1])
    assert shared_tail == int(pc.table[0, 1])       # same physical page
    assert pc.ref[shared_tail] == 3                 # slot0 + slot1 + index

    # slot 1 writes position 5 (inside the shared tail block) -> COW
    cow = pc.ensure_writable(1, 5, 5)
    assert len(cow) == 1 and cow[0][0] == shared_tail
    assert int(pc.table[1, 1]) == cow[0][1] != shared_tail
    assert pc.ref[shared_tail] == 2                 # slot1 dropped its ref
    assert pc.stats["cow"] == 1
    pc.check()

    # writing again into the now-exclusive page is free
    assert pc.ensure_writable(1, 5, 7) == []
    pc.check()


def test_no_cow_for_exclusive_blocks():
    pc = _cache(prefix_cache=False)
    pc.admit(0, prompt_len=8, max_new=4)
    pc.ensure_writable(0, 0, 7)
    assert pc.ensure_writable(0, 0, 7) == []
    assert pc.stats["cow"] == 0


# ----------------------------------------------------------------------
# prefix index granularity
# ----------------------------------------------------------------------

def test_prefix_hit_shorter_than_a_block():
    """A 3-token prompt with page_size=4 lives entirely in a tail entry;
    an identical prompt hits the full 3-token span."""
    pc = _cache()
    prompt = np.array([7, 8, 9], np.int32)
    pc.admit(0, prompt_len=3, max_new=2)
    pc.ensure_writable(0, 0, 2)
    pc.insert(0, prompt)
    pages, span = pc.lookup(prompt)
    assert span == 3 and len(pages) == 1
    # a shorter query is a *different* tail key: no partial-tail hit
    _, span2 = pc.lookup(prompt[:2])
    assert span2 == 0


def test_prefix_hit_longer_than_a_block():
    """A 10-token prompt spans 2 full blocks + a 2-token tail; lookups hit
    at every granularity the chain records."""
    pc = _cache()
    prompt = np.arange(40, 50, dtype=np.int32)
    pc.admit(0, prompt_len=10, max_new=2)
    pc.ensure_writable(0, 0, 9)
    pc.insert(0, prompt)
    pages, span = pc.lookup(prompt)
    assert span == 10 and len(pages) == 3           # 2 full + tail
    # a query that only shares the full blocks hits the 8-token span
    other = np.concatenate([prompt[:8], np.array([99, 98], np.int32)])
    pages8, span8 = pc.lookup(other)
    assert span8 == 8 and len(pages8) == 2
    # a query diverging inside block 0 misses entirely
    div = prompt.copy()
    div[1] = 77
    _, span0 = pc.lookup(div)
    assert span0 == 0


def test_lru_eviction_is_leaf_first():
    """Evicting to free pages drops LRU *leaves*, never an interior chain
    block — every surviving chain stays reachable from block 0."""
    pc = PagedKVCache(pages=8, page_size=4, slots=1, max_seq=16,
                      prefix_cache=True)
    long = np.arange(60, 72, dtype=np.int32)        # 3 blocks
    pc.admit(0, prompt_len=12, max_new=0)
    pc.ensure_writable(0, 0, 11)
    pc.insert(0, long)
    pc.release(0)
    assert pc.index_size == 3 and pc.free_pages == 4
    # demand 6 fresh pages: two LRU leaves must be evicted, root survives
    pc.admit(0, prompt_len=16, max_new=0)
    pc.ensure_writable(0, 0, 15)                    # needs 4, free has 4
    pc.release(0)
    pc._evict(need=6)
    pc.check()
    assert pc.stats["evicted"] == 2
    pages, span = pc.lookup(long)
    assert span == 4 and len(pages) == 1            # root block still hits


# ----------------------------------------------------------------------
# admission reservations / engine backpressure
# ----------------------------------------------------------------------

def test_can_admit_reserves_for_active_slots():
    pc = PagedKVCache(pages=7, page_size=8, slots=2, max_seq=48)
    assert pc.can_admit(10, 16)                     # 4 blocks, 6 free
    pc.admit(0, prompt_len=10, max_new=16)
    # slot 0's outstanding worst case (4 pages, none mapped yet) counts
    assert not pc.can_admit(10, 16)
    assert pc.can_admit(10, 4)                      # 2 blocks still fit
    pc.ensure_writable(0, 0, 25)                    # slot 0 fully mapped
    assert not pc.can_admit(10, 16)                 # only 2 pages left


def _mk_paged_engine(**kw):
    from repro.configs.registry import get_config
    from repro.core import compat
    from repro.serving import ServingEngine
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    cfg = get_config("internlm2-1.8b").reduced().replace(dtype="float32")
    eng = ServingEngine(cfg, mesh, **{"slots": 2, "max_seq": 48,
                                      "paged": True, "page_size": 8, **kw})
    eng.load(seed=0)
    return eng


def test_cache_full_admission_backpressure():
    """A pool sized for one big request parks the second in the one-deep
    pending buffer (FIFO preserved) until the first releases its pages."""
    from repro.serving import Request
    eng = _mk_paged_engine(pages=7)                 # 6 allocatable pages
    reqs = [Request(rid=i, prompt=np.arange(3 + i, 13 + i, dtype=np.int32),
                    max_new_tokens=16) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    # each request reserves ceil((10+16+1)/8) = 4 pages: only one fits
    assert eng.stats["admitted"] == 1
    assert eng._pending is reqs[1]                  # parked, not dropped
    assert eng.queued == 2
    stats = eng.run_until_drained()
    assert stats["admitted"] == 3
    assert all(r.done and len(r.out_tokens) >= 1 for r in reqs)
    assert eng._pending is None and eng.queued == 0
    assert eng.paged.free_pages == eng.paged.pages - 1   # drained clean


def test_pending_request_admits_before_later_arrivals():
    """The parked request keeps its place at the head of the line."""
    from repro.serving import Request
    eng = _mk_paged_engine(pages=7)
    r0 = Request(rid=0, prompt=np.arange(3, 13, dtype=np.int32),
                 max_new_tokens=16)
    r1 = Request(rid=1, prompt=np.arange(4, 14, dtype=np.int32),
                 max_new_tokens=16)
    r2 = Request(rid=2, prompt=np.arange(5, 15, dtype=np.int32),
                 max_new_tokens=16)
    for r in (r0, r1, r2):
        eng.submit(r)
    eng.step()
    assert eng._pending is r1
    while not r1.done and eng.stats["steps"] < 200:
        eng.step()
        if eng.active[0] is not None and eng.active[0].rid == 2:
            raise AssertionError("r2 overtook the parked r1")
        if eng.active[1] is not None and eng.active[1].rid == 2 \
                and not (r1.done or any(
                    a is not None and a.rid == 1 for a in eng.active)):
            raise AssertionError("r2 overtook the parked r1")
    eng.run_until_drained()
    assert r0.done and r1.done and r2.done


def test_paged_prefix_engine_matches_dense_mid_block_divergence():
    """End-to-end: two requests share a prefix and diverge mid-block; the
    paged+prefix engine (COW path) emits exactly the dense engine's
    tokens."""
    from repro.serving import Request

    base = np.arange(3, 13, dtype=np.int32)         # 10 tokens, ps=8
    fork = base.copy()
    fork[9] = 99                                    # diverges inside block 1

    def run(**kw):
        eng = _mk_paged_engine(**kw) if kw else None
        if eng is None:
            from repro.configs.registry import get_config
            from repro.core import compat
            from repro.serving import ServingEngine
            mesh = compat.make_mesh((1, 1), ("data", "model"))
            cfg = get_config("internlm2-1.8b").reduced().replace(
                dtype="float32")
            eng = ServingEngine(cfg, mesh, slots=2, max_seq=48)
            eng.load(seed=0)
        reqs = [Request(rid=0, prompt=base, max_new_tokens=6),
                Request(rid=1, prompt=fork, max_new_tokens=6),
                Request(rid=2, prompt=base.copy(), max_new_tokens=6)]
        for r in reqs:
            eng.submit(r)
        stats = eng.run_until_drained()
        return [r.out_tokens for r in reqs], stats

    dense, _ = run()
    paged, pstats = run(prefix_cache=True)
    assert paged == dense
    assert pstats["prefix_hits"] >= 1               # rid=1/2 reused blocks
    assert pstats["paged"]["cow"] >= 1              # divergence forced COW
