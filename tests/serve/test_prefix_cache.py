"""PrefixBlockPool unit tests: radix matching, refcount lifecycle,
insert races, LRU leaf eviction under pool pressure, and the audit
invariant (every block exactly one of free/active/cached) — all pure
host bookkeeping, no model or cluster."""

import pytest

from ray_tpu.serve.prefix_cache import PrefixBlockPool

pytestmark = pytest.mark.serve_llm


def _pool(blocks=9, bs=4):
    # blocks includes the reserved trash block 0, like the engine's
    return PrefixBlockPool(blocks, bs, reserved=(0,))


def _index_prompt(pool, prompt, node=None):
    """Allocate + insert every full chunk of ``prompt`` (what the
    engine's prefill loop does), returning the blocks."""
    bs = pool.block_size
    nfull = len(prompt) // bs
    blocks = pool.allocate(nfull)
    assert blocks is not None
    if node is None:
        node = pool.match_prefix(prompt[:0])[2]    # root
    for i in range(nfull):
        node, _ = pool.insert_child(node, prompt[i * bs:(i + 1) * bs],
                                    blocks[i])
    return blocks


def test_match_walks_full_chunks_and_increfs():
    p = _pool()
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9]      # 2 full chunks + tail
    blocks = _index_prompt(p, prompt)
    assert p.audit() == []
    m, mtok, _ = p.match_prefix(prompt)
    assert m == blocks and mtok == 8
    # partial-chunk prompts never match past their full chunks
    m2, mtok2, _ = p.match_prefix(prompt[:6])
    assert m2 == blocks[:1] and mtok2 == 4
    # diverging second chunk stops the walk
    m3, mtok3, _ = p.match_prefix([1, 2, 3, 4, 9, 9, 9, 9])
    assert m3 == blocks[:1] and mtok3 == 4
    p.release(blocks + m + m2 + m3)
    assert p.audit() == []
    s = p.stats()
    assert s["active"] == 0 and s["cached"] == 2
    assert s["reclaimable"] == p.total_managed


def test_decref_to_zero_keeps_trie_blocks_cached_frees_private():
    p = _pool()
    shared = _index_prompt(p, [1, 2, 3, 4])
    private = p.allocate(2)
    p.release(shared + private)
    s = p.stats()
    assert s["cached"] == 1            # trie block stays warm
    assert s["free"] == p.total_managed - 1
    # matching resurrects the cached block with a fresh reference
    m, _, _ = p.match_prefix([1, 2, 3, 4, 5])
    assert m == shared
    assert p.stats()["active"] == 1
    p.release(m)
    assert p.audit() == []


def test_insert_race_keeps_existing_node():
    p = _pool()
    a = _index_prompt(p, [1, 2, 3, 4])
    # a concurrent request with the same prompt lost the race: its
    # block stays private, the walk continues on the existing node
    b = p.allocate(1)
    root = p.match_prefix([])[2]
    node, inserted = p.insert_child(root, [1, 2, 3, 4], b[0])
    assert not inserted and node.block == a[0]
    p.release(a + b)
    s = p.stats()
    assert s["cached"] == 1 and s["free"] == p.total_managed - 1
    assert p.audit() == []


def test_insert_under_evicted_parent_aborts():
    p = _pool()
    a = _index_prompt(p, [1, 2, 3, 4])
    root = p.match_prefix([])[2]
    parent = root.children[(1, 2, 3, 4)]
    p.release(a)
    # pressure: drain the free list so allocation must evict the leaf
    grab = p.allocate(p.total_managed)
    assert grab is not None and p.stats()["evictions_total"] == 1
    node, inserted = p.insert_child(parent, [5, 6, 7, 8], grab[0])
    assert node is None and not inserted
    p.release(grab)
    assert p.audit() == []


def test_eviction_is_lru_and_leaves_first():
    p = _pool(blocks=5, bs=4)          # 4 managed blocks
    a = _index_prompt(p, [1, 1, 1, 1])
    b = _index_prompt(p, [2, 2, 2, 2])
    p.release(a)
    p.release(b)
    # touch a AFTER b: b becomes the LRU candidate
    m, _, _ = p.match_prefix([1, 1, 1, 1])
    p.release(m)
    got = p.allocate(3)                # 2 free + 1 eviction
    assert got is not None
    assert p.stats()["evictions_total"] == 1
    # a survived (recently touched), b was evicted
    assert p.match_prefix([1, 1, 1, 1])[1] == 4
    assert p.match_prefix([2, 2, 2, 2])[1] == 0
    p.release(p.match_prefix([1, 1, 1, 1])[0])
    p.release(got)
    assert p.audit() == []


def test_parent_with_children_never_evicted():
    p = _pool(blocks=4, bs=2)          # 3 managed blocks
    blocks = _index_prompt(p, [1, 2, 3, 4])   # chain of 2 nodes
    p.release(blocks)
    # the deep leaf is evictable, its parent only after it
    got = p.allocate(3)
    assert got is not None and p.stats()["evictions_total"] == 2
    assert p.match_prefix([1, 2])[1] == 0
    p.release(got)
    assert p.audit() == []


def test_allocate_all_or_nothing_when_starved():
    p = _pool(blocks=4, bs=4)          # 3 managed blocks
    held = p.allocate(3)
    assert p.allocate(1) is None       # starved
    assert p.stats()["free"] == 0
    p.release(held[:1])
    assert p.allocate(2) is None       # still short: nothing taken
    assert p.stats()["free"] == 1      # the failed attempt restored
    got = p.allocate(1)
    assert got is not None
    p.release(held[1:] + got)
    assert p.audit() == []


def test_shared_count_tracks_multi_reference():
    p = _pool()
    a = _index_prompt(p, [7, 7, 7, 7])
    assert p.stats()["shared"] == 0
    m, _, _ = p.match_prefix([7, 7, 7, 7, 1])
    assert p.stats()["shared"] == 1    # refcount 2 on the block
    p.release(m)
    assert p.stats()["shared"] == 0
    p.release(a)
    assert p.audit() == []


def test_audit_catches_inconsistencies():
    p = _pool()
    blocks = _index_prompt(p, [1, 2, 3, 4])
    # simulate a dangling trie entry (block freed but left indexed)
    del p._ref[blocks[0]]
    p._free.append(blocks[0])
    problems = p.audit()
    assert problems and any("free and trie-resident" in m
                            for m in problems)


# ------------------------------------------- eviction against its oracle
class _Recording(PrefixBlockPool):
    """The pool under test, noting the block each eviction frees."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.victims = []

    def _evict_one(self):
        evicted = super()._evict_one()
        if evicted:
            self.victims.append(self._free[-1])
        return evicted


class _ScanPool(_Recording):
    """The oracle: eviction, ``stats()`` and ``root_fingerprints`` as
    they were before the pool kept an evictable heap and running counts
    — a scan of every cached node for every block evicted, sums over the
    whole pool, a sort of all the root's children. It reads only the
    maps (``_node_of``, ``_ref``, ``children``, ``touch``)."""

    def _evict_one(self):
        best = None
        for block, node in self._node_of.items():
            if block in self._ref or node.children:
                continue
            if best is None or node.touch < best[1].touch:
                best = (block, node)
        if best is None:
            return False
        block, node = best
        node.detached = True
        if node.parent is not None:
            node.parent.children.pop(node.key, None)
        del self._node_of[block]
        self._free.append(block)
        self.evictions_total += 1
        self.victims.append(block)
        return True

    def root_fingerprints(self, limit=64):
        from ray_tpu.serve.prefix_cache import prefix_fingerprint
        kids = sorted(self._root.children.values(),
                      key=lambda n: -n.touch)[:limit]
        fps = [prefix_fingerprint(n.key, self.block_size) for n in kids]
        return [fp for fp in fps if fp is not None]

    def stats(self):
        cached = sum(1 for b in self._node_of if b not in self._ref)
        shared = sum(1 for b, r in self._ref.items() if r > 1)
        return dict(super().stats(), cached=cached, shared=shared,
                    reclaimable=len(self._free) + cached)


class _Client:
    """One request's hold on one pool: what the engine's admission,
    prefill loop and release do with it."""

    def __init__(self, pool, prompt, need):
        self.pool, self.prompt, self.cursor = pool, prompt, 0
        matched, mtok, self.node = pool.match_prefix(prompt)
        priv = pool.allocate(need - len(matched))
        self.admitted = priv is not None
        if not self.admitted:           # admission waits: match undone
            pool.release(matched)
            self.blocks = []
        else:
            self.blocks = matched + priv
            self.cursor = len(matched)
        self.result = (matched, mtok, priv)

    def index(self, n):
        """Insert the next ``n`` full chunks the prefill has covered."""
        bs, out = self.pool.block_size, []
        while n and self.node is not None \
                and (self.cursor + 1) * bs <= len(self.prompt):
            i = self.cursor
            self.node, inserted = self.pool.insert_child(
                self.node, self.prompt[i * bs:(i + 1) * bs],
                self.blocks[i])
            out.append(inserted)
            self.cursor += 1
            n -= 1
        return out


@pytest.mark.parametrize("seed", range(6))
def test_eviction_matches_the_scan_oracle(seed):
    """A seeded random run of admissions, chunk inserts and releases on
    a pool that stays under pressure, chains deep enough that a freed
    leaf exposes its parent: the heap evicts the scan's victim every
    time, and every result and ``stats()`` along the way is the same."""
    import random
    rng = random.Random(seed)
    bs, blocks = 2, 49
    new, ref = _Recording(blocks, bs), _ScanPool(blocks, bs)
    docs = [[rng.randrange(50) for _ in range(bs * rng.randint(2, 12))]
            for _ in range(14)]
    docs += [d[:bs * 2] + [rng.randrange(50) for _ in range(bs * 5)]
             for d in docs[:6]]         # forks off a shared first blocks
    live = []                           # (client on new, client on ref)
    for step in range(4000):
        op = rng.random()
        if op < 0.4 and len(live) < 5:
            doc = rng.choice(docs)
            prompt = doc[:rng.randint(1, len(doc))] \
                + [rng.randrange(50) for _ in range(rng.randint(0, 3))]
            need = -(-(len(prompt) + rng.randint(1, 4)) // bs)
            pair = _Client(new, prompt, need), _Client(ref, prompt, need)
            assert pair[0].result == pair[1].result, step
            if pair[0].admitted:
                live.append(pair)
        elif op < 0.75 and live:
            pair, n = rng.choice(live), rng.randint(1, 4)
            assert pair[0].index(n) == pair[1].index(n), step
        elif live:
            pair = live.pop(rng.randrange(len(live)))
            for client in pair:
                client.pool.release(client.blocks)
        assert new.victims == ref.victims, step
        assert new.stats() == ref.stats(), step
        assert new.root_fingerprints(4) == ref.root_fingerprints(4), step
        if step % 97 == 0:
            assert new.audit() == [], step
    assert new.audit() == []
    assert len(new.victims) > 300       # it was under pressure
    assert new.root_fingerprints() == ref.root_fingerprints()
    # a leaf's eviction did expose its parent: some chain went whole
    assert new.stats()["trie_blocks"] < new.inserts_total - 300


def test_allocate_costs_what_it_evicts_not_the_pool(monkeypatch):
    """4,096 cached nodes (256 chains of 16), none free: ``allocate(256)``
    is a few heap operations a block on a heap no larger than the trie,
    O(256 log n), where the scan walked the pool for every block."""
    import heapq
    from ray_tpu.serve import prefix_cache
    p = PrefixBlockPool(4097, 1)
    held = []
    for chain in range(256):
        held += _index_prompt(p, [chain] + list(range(1000, 1015)))
    p.release(held)
    assert p.stats()["cached"] == 4096 and p.stats()["free"] == 0

    ops = []

    class Counting:
        @staticmethod
        def heappush(heap, item):
            ops.append(len(heap))
            heapq.heappush(heap, item)

        @staticmethod
        def heappop(heap):
            ops.append(len(heap))
            return heapq.heappop(heap)

        heapify = staticmethod(heapq.heapify)

    monkeypatch.setattr(prefix_cache, "heapq", Counting)
    got = p.allocate(256)
    assert got is not None and len(set(got)) == 256
    assert p.evictions_total == 256
    assert len(ops) <= 3 * 256 and max(ops) <= 4096
    assert p.audit() == []


def test_window_pages_two_classes_and_the_exact_hit():
    """A trie node names two kinds of page. The window layers' pages are
    cached in two classes, those far behind their prompt's end evicted
    first, and a hit backs off to the deepest boundary whose window tail
    is whole, or to none."""
    from ray_tpu.serve.prefix_cache import PrefixBlockPool, WindowPagePool
    wpool = WindowPagePool(7)
    pool = PrefixBlockPool(16, 2, window_pool=wpool, window=4)
    node, blocks, pages = pool._root, pool.allocate(5), wpool.allocate(5)
    for i, (b, w) in enumerate(zip(blocks, pages)):
        node, _ = pool.insert_child(node, (i, i), b, w)
    # released in order, the two last as near their prompt's end
    for i, w in enumerate(pages):
        wpool.decref(w, near=i >= 3)
    pool.release(blocks)
    assert wpool.stats()["cached"] == 5 and pool.audit() == []
    tokens = [t for i in range(5) for t in (i, i)]
    with pytest.raises(ValueError, match="match_prefix_window"):
        pool.match_prefix(tokens)
    got, mtok, _, tail, cut = pool.match_prefix_window(tokens)
    assert mtok == 10 and not cut
    assert tail == {3: pages[3], 4: pages[4]}
    pool.release(got)
    for w in tail.values():
        wpool.decref(w)
    # four pages wanted, one free: the three far ones go, oldest first
    taken = wpool.allocate(4)
    assert set(taken) == {6, *pages[:3]} and wpool.evictions_total == 3
    got, mtok, _, tail, cut = pool.match_prefix_window(tokens)
    assert mtok == 10 and len(tail) == 2 and not cut
    pool.release(got)
    for w in tail.values():
        wpool.decref(w)
    # one more: the older of the near ones; the boundary at 10 has lost
    # its tail and so has the one at 8; the hit backs off to 6... whose
    # tail went first: nothing is taken
    assert wpool.allocate(1) == [pages[3]]
    got, mtok, node, tail, cut = pool.match_prefix_window(tokens)
    assert (got, mtok, tail) == ([], 0, {}) and cut
    assert node is pool._root and pool.audit() == []
