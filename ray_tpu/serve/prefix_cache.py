"""Refcounted KV-block pool with radix-trie prefix sharing.

The serving engine's paged block tables already indirect every cache
read through per-sequence block ids, so two sequences whose prompts
share a prefix can point their leading table entries at the SAME
physical blocks (vLLM's prefix caching / SGLang's radix attention).
This module owns the bookkeeping:

- every managed block carries a **refcount** (requests using it); the
  free list only holds blocks with no references and no trie entry;
- **full** ``block_size``-token prompt chunks are indexed in a radix
  trie keyed on the chunk's token tuple — matching a new prompt walks
  the trie chunk by chunk and hands back the shared blocks (incref'd),
  so prefill skips them entirely;
- a request finishing (EOS / cancel / error) **decrefs** instead of
  freeing: a block whose refcount hits zero but that is still indexed
  in the trie stays resident as reusable cache, and is evicted
  **LRU, leaves first**, only when an allocation actually needs the
  space (pool pressure) — an idle pool keeps every prefix warm.

Only full prompt chunks are ever inserted, which makes shared blocks
immutable by construction: a sequence's own writes (later prompt
chunks, generated tokens, speculative drafts) always land at positions
``>= matched_tokens``, i.e. in blocks the trie has never seen. The
partial tail of a fully-matched prompt is handled by the engine with a
copy-on-write block copy (see ``LLMEngine._admit``).

Cost: the engine calls the pool on its step thread, with the device
idle, so nothing here walks the pool. The evictable set (ref-0 leaves)
is a heap ordered by ``touch`` with lazy deletion — an entry is dropped
when it is popped and its node is no longer an untouched ref-0 leaf —
so an eviction is O(log n); ``stats()`` reads counts kept where
refcounts change; the root's children are kept in touch order with
their fingerprints, so ``root_fingerprints`` is O(limit).

Two kinds of page (a model with sliding-window layers): the window
layers' K/V live in pools of their own with ids, a free list and
refcounts of their own (:class:`WindowPagePool`). A trie node then names
up to two pages, the full layers' (``block``, as above) and the window
layers' (``wblock``), and they live differently: a sequence holds its
full pages to its end, and a window page only while a row of it lies
inside the window of the sequence's next position. A window page that
slides out while the trie indexes it stays as evictable cache, like a
finished request's pages, and is evicted on its own, leaving the node
and its full page in place. A prefix hit must be exact, so
``match_prefix`` stops at the deepest page boundary ``P`` whose window
pages covering ``[P - window, P)`` are all there, or at none.

State snapshots (a model with recurrent state): a layer that carries a
state and no page gives a prefix hit nothing to point at, so the engine
keeps the state AS OF a boundary in snapshot rows beside the pools, one
row a trie node whose page ends on a multiple of a fixed stride
(``node.snap``; row 0 is the trash row). The rows are a third thing the
pool hands out: a free list, a row's node, and eviction of the least
recently touched node's snapshot when a chunk's call needs rows and none
is free (the node and its page stay; a node evicted from the trie gives
its row back). A hit must bring a whole state, so
:meth:`PrefixBlockPool.match_prefix_state` cuts the match back to the
deepest node with a snapshot.

Thread model: the pool is NOT internally locked — the engine calls it
with its scheduler lock held (all mutations happen on the step
thread).
"""

from __future__ import annotations

import collections
import heapq
import itertools
import zlib
from typing import Dict, List, Optional, Sequence, Tuple


def prefix_fingerprint(tokens: Sequence[int],
                       block_size: int) -> Optional[int]:
    """Stable fingerprint of a prompt's FIRST full KV-block chunk
    (crc32 of the token bytes — deterministic across processes, unlike
    ``hash``). ``None`` when the prompt has no full block. Routers
    compare this against :meth:`PrefixBlockPool.root_fingerprints` to
    place a COLD session on the replica whose radix trie already holds
    its prefix."""
    if block_size < 1 or len(tokens) < block_size:
        return None
    data = b"".join(int(t).to_bytes(8, "little", signed=True)
                    for t in tokens[:block_size])
    return zlib.crc32(data)


class _TrieNode:
    """One full token chunk in the radix trie. ``key`` is the chunk's
    token tuple (its edge label from ``parent``); ``block`` the
    physical block holding that chunk's KV."""

    __slots__ = ("children", "parent", "key", "block", "touch",
                 "detached", "hits", "wblock", "snap")

    def __init__(self, parent: Optional["_TrieNode"],
                 key: Optional[tuple], block: Optional[int]):
        self.children: Dict[tuple, "_TrieNode"] = {}
        self.parent = parent
        self.key = key
        self.block = block
        self.touch = 0          # LRU clock stamp
        self.detached = False   # evicted — inserts under it must abort
        self.hits = 0           # prefix-match count (migration floor)
        self.wblock: Optional[int] = None   # the window layers' page
        self.snap: Optional[int] = None     # the state's snapshot row


class WindowPagePool:
    """Refcounted allocator of the window layers' pages. A page is free,
    referenced (a running sequence has a row of it inside its window),
    or cached: unreferenced and named by a trie node (``node.wblock``),
    from where a later prefix hit can take it. Cached pages are evicted
    under pressure in TWO CLASSES, LRU within each: first the pages far
    behind their request's prompt end (a long prefill sheds thousands of
    them, and only a hit in mid-prompt could want one), then the pages
    near it (the tail a re-ask of the same document resumes from).

    It is a class beside :class:`PrefixBlockPool` and not a base under
    both: what the two have in common is a deque of free ids and a dict
    of refcounts, and every method over them differs. The full pool
    evicts through the trie (a ref-0 LEAF, never a page a longer prefix
    hangs under, and the node goes with it) and keeps its ``cached`` /
    ``shared`` counts where refcounts change; a window page is evicted
    on its own, by class, and its node and full page stay. A shared
    core would be hooks at every step of the accepted pool's
    ``incref`` / ``decref`` / ``allocate``, which run under the tick of
    every serving cell, for some fifteen lines."""

    def __init__(self, num_blocks: int, reserved: Sequence[int] = (0,)):
        self._reserved = frozenset(reserved)
        managed = [b for b in range(num_blocks)
                   if b not in self._reserved]
        self.total_managed = len(managed)
        self._free: "collections.deque[int]" = collections.deque(managed)
        self._ref: Dict[int, int] = {}
        self._node_of: Dict[int, _TrieNode] = {}   # trie-resident pages
        # cached page -> (class, touch): what its live heap entry says
        self._stamp: Dict[int, Tuple[int, int]] = {}
        self._evictable: List[Tuple[int, int, int]] = []
        self._clock = 0
        self.evictions_total = 0
        self.released_total = 0    # refs dropped behind a window

    def allocate(self, n: int) -> Optional[List[int]]:
        """``n`` private pages (refcount 1), evicting cached ones under
        pressure; None, with nothing taken, when that cannot cover it."""
        got: List[int] = []
        while len(got) < n:
            if not self._free and not self._evict_one():
                for b in got:
                    del self._ref[b]
                    self._free.append(b)
                return None
            b = self._free.popleft()
            self._ref[b] = 1
            got.append(b)
        return got

    def incref(self, block: int) -> None:
        self._ref[block] = self._ref.get(block, 0) + 1
        self._stamp.pop(block, None)       # cached no longer

    def decref(self, block: int, near: bool = True) -> None:
        """Drop one reference. The last one frees the page, or leaves it
        cached where a trie node names it: ``near`` (the prompt's end)
        is the class evicted last."""
        n = self._ref[block] - 1
        if n > 0:
            self._ref[block] = n
            return
        del self._ref[block]
        if block not in self._node_of:
            self._free.append(block)
            return
        self._clock += 1
        stamp = self._stamp[block] = (int(near), self._clock)
        heapq.heappush(self._evictable, stamp + (block,))
        if len(self._evictable) > 2 * len(self._node_of) + 64:
            self._evictable = [e for e in self._evictable
                               if self._stamp.get(e[2]) == e[:2]]
            heapq.heapify(self._evictable)

    def attach(self, block: int, node: _TrieNode) -> None:
        """``node`` names this (referenced) page from now on."""
        node.wblock = block
        self._node_of[block] = node

    def forget(self, node: _TrieNode) -> None:
        """``node`` leaves the trie: its page, if it names one, is no
        cache any more (freed if nobody holds it)."""
        block, node.wblock = node.wblock, None
        if block is None:
            return
        del self._node_of[block]
        if self._stamp.pop(block, None) is not None:
            self._free.append(block)

    def _evict_one(self) -> bool:
        while self._evictable:
            cls, touch, block = heapq.heappop(self._evictable)
            if self._stamp.get(block) == (cls, touch):
                break
        else:
            return False
        del self._stamp[block]
        self._node_of.pop(block).wblock = None
        self._free.append(block)
        self.evictions_total += 1
        return True

    def stats(self) -> Dict[str, int]:
        return {"free": len(self._free), "cached": len(self._stamp),
                "reclaimable": len(self._free) + len(self._stamp),
                "active": len(self._ref),
                "evictions_total": self.evictions_total,
                "released_total": self.released_total}

    def audit(self) -> List[str]:
        problems: List[str] = []
        free, ref, cached = set(self._free), set(self._ref), \
            set(self._stamp)
        if len(free) != len(self._free):
            problems.append("window: duplicate pages on the free list")
        for a, b, what in ((free, ref, "free and referenced"),
                           (free, cached, "free and cached"),
                           (ref, cached, "referenced and cached")):
            if a & b:
                problems.append(f"window pages both {what}: "
                                f"{sorted(a & b)}")
        managed = {b for b in range(
            self.total_managed + len(self._reserved))
            if b not in self._reserved}
        if free | ref | cached != managed:
            problems.append(
                f"window pages leaked {sorted(managed - free - ref - cached)}"
                f" or unmanaged {sorted((free | ref | cached) - managed)}")
        if set(self._node_of) != cached | (ref & set(self._node_of)) \
                or cached - set(self._node_of):
            problems.append("window: cached pages no trie node names")
        for block, node in self._node_of.items():
            if node.wblock != block or node.detached:
                problems.append(f"window page {block}: its node names "
                                f"{node.wblock}, detached {node.detached}")
        queued = {b for c, t, b in self._evictable
                  if self._stamp.get(b) == (c, t)}
        if cached - queued:
            problems.append(f"cached window pages no eviction can find: "
                            f"{sorted(cached - queued)}")
        return problems


class PrefixBlockPool:
    """Refcounted block allocator + radix prefix index over one paged
    KV pool of ``num_blocks`` blocks (``reserved`` ids — the engine's
    trash block — are never handed out)."""

    def __init__(self, num_blocks: int, block_size: int,
                 reserved: Sequence[int] = (0,),
                 window_pool: Optional[WindowPagePool] = None,
                 window: int = 0, snapshot_stride: int = 0,
                 num_snapshots: int = 0):
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        if snapshot_stride % block_size or snapshot_stride < 0:
            raise ValueError(f"snapshot_stride {snapshot_stride}: whole "
                             f"pages of {block_size} tokens")
        self.block_size = block_size
        # a recurrent state's snapshot rows: a node whose page ends on a
        # multiple of the stride may name one (rows 1..num_snapshots)
        self.snapshot_stride = snapshot_stride
        self.num_snapshots = num_snapshots if snapshot_stride else 0
        self._snap_free: "collections.deque[int]" = collections.deque(
            range(1, self.num_snapshots + 1))
        self._snap_node: Dict[int, _TrieNode] = {}    # live rows
        self._snap_out: set = set()        # handed to a call, not booked
        self.snapshots_taken_total = 0
        self.snapshots_evicted_total = 0
        # the window layers' pages and the positions a query sees back
        self.window_pool = window_pool
        self.window = window
        self._reserved = frozenset(reserved)
        managed = [b for b in range(num_blocks)
                   if b not in self._reserved]
        self.total_managed = len(managed)
        self._free: "collections.deque[int]" = collections.deque(managed)
        self._ref: Dict[int, int] = {}          # block -> refcount >= 1
        self._node_of: Dict[int, _TrieNode] = {}  # trie-resident blocks
        self._root = _TrieNode(None, None, None)
        self._clock = 0
        # ref-0 leaves as (touch, push number, node), least recently
        # touched first; the push number keeps two entries of one node
        # and one touch from comparing nodes
        self._evictable: List[Tuple[int, int, _TrieNode]] = []
        self._pushes = 0
        # the root's children -> first-block fingerprint, in touch order
        self._root_fps: "collections.OrderedDict[_TrieNode, Optional[int]]" \
            = collections.OrderedDict()
        self._cached = 0           # ref-0, trie-resident
        self._shared = 0           # refcount > 1
        # -- counters (engine surfaces these in stats())
        self.hits_total = 0        # blocks handed out via prefix match
        self.inserts_total = 0
        self.evictions_total = 0

    # ------------------------------------------------------- refcounts
    def incref(self, block: int) -> None:
        n = self._ref.get(block, 0)
        self._ref[block] = n + 1
        if n == 1:
            self._shared += 1
        elif n == 0:
            # resurrecting a cached (ref-0, trie-resident) block; its
            # heap entries are dropped when they are popped
            self._cached -= 1

    def decref(self, block: int) -> None:
        n = self._ref[block] - 1
        if n > 0:
            self._ref[block] = n
            if n == 1:
                self._shared -= 1
            return
        del self._ref[block]
        node = self._node_of.get(block)
        if node is None:
            self._free.append(block)
            return
        # stays resident in the trie as reusable cache
        self._cached += 1
        self._offer(node)

    def release(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            self.decref(b)

    # ------------------------------------------------------- matching
    def _touch(self, node: _TrieNode) -> None:
        self._clock += 1
        node.touch = self._clock
        if node.parent is self._root:
            self._root_fps.move_to_end(node)
        self._offer(node)

    def _offer(self, node: _TrieNode) -> None:
        """Queue ``node`` for eviction if it is a ref-0 leaf. Called
        wherever a node may have become one or been touched as one: a
        release to zero, a touch, the eviction of its last child."""
        if node.children or node.block in self._ref:
            return
        self._pushes += 1
        heapq.heappush(self._evictable, (node.touch, self._pushes, node))
        # entries outlive what they name (a re-touched or re-referenced
        # node, a leaf that grew a child): bound them by the trie's size
        if len(self._evictable) > 2 * len(self._node_of) + 64:
            self._evictable = [e for e in self._evictable
                               if self._is_victim(e[0], e[2])]
            heapq.heapify(self._evictable)

    def _is_victim(self, stamp: int, node: _TrieNode) -> bool:
        """Whether a heap entry still names an untouched ref-0 leaf."""
        return not (node.detached or node.children
                    or node.block in self._ref or node.touch != stamp)

    def match_prefix(self, tokens: Sequence[int]
                     ) -> Tuple[List[int], int, _TrieNode]:
        """Walk the trie along ``tokens`` in full-chunk steps. Returns
        ``(blocks, matched_tokens, node)`` — matched blocks are
        incref'd (caller owns one reference each; release on abort) and
        ``node`` is the deepest matched trie node (the parent for this
        request's own inserts). A pool with two kinds of page is asked
        through :meth:`match_prefix_window`."""
        if self.window_pool is not None:
            raise ValueError("two kinds of page: match_prefix_window")
        return self._take(self._walk(tokens))

    def match_prefix_window(self, tokens: Sequence[int]):
        """:meth:`match_prefix` where a node names two kinds of page: a
        hit is exact or it is not taken, so the match is cut back to the
        deepest page boundary whose window pages (those covering
        ``[boundary - window, boundary)``) are all in the trie, or to
        nothing. Returns ``(blocks, matched_tokens, node, tail, cut)``:
        ``tail`` is ``{page index: window page}`` of that boundary,
        incref'd like ``blocks`` (the caller's to ``decref``), ``cut``
        whether a missing tail made the match stop short."""
        path = self._walk(tokens)
        keep = self._cut_to_window_tail(path)
        first = self.window_tail_from(keep * self.block_size)
        tail = {j: path[j].wblock for j in range(first, keep)}
        for wblock in tail.values():
            self.window_pool.incref(wblock)
        return self._take(path[:keep]) + (tail, keep < len(path))

    def match_prefix_state(self, tokens: Sequence[int]):
        """:meth:`match_prefix` for a model with recurrent state: a hit
        resumes from a whole state or it is not taken, so the match is
        cut back to the deepest node that has a snapshot, strictly short
        of the prompt's last token (its logits come from a chunk's
        call). Returns ``(blocks, matched_tokens, node, snap_row,
        cut_blocks)``: ``snap_row`` the row to copy into the slot (0: a
        miss, ``blocks`` empty), ``cut_blocks`` the pages the trie
        matched beyond the cut, which are recomputed."""
        path = self._walk(tokens)
        keep = len(path)
        while keep and (path[keep - 1].snap is None
                        or keep * self.block_size >= len(tokens)):
            keep -= 1
        row = path[keep - 1].snap if keep else 0
        return self._take(path[:keep]) + (row, len(path) - keep)

    # ------------------------------------------------------- snapshots
    def take_snapshot_rows(self, n: int) -> List[int]:
        """Up to ``n`` rows for a chunk's call to write, the caller's
        until :meth:`attach_snapshot` or :meth:`return_snapshot_rows`:
        free ones, then those of the least recently touched nodes (the
        node keeps its page and loses its snapshot). Fewer than ``n``
        where every row is out with a call."""
        got: List[int] = []
        while len(got) < n:
            if self._snap_free:
                row = self._snap_free.popleft()
            elif self._snap_node:
                row, node = min(self._snap_node.items(),
                                key=lambda kv: kv[1].touch)
                del self._snap_node[row]
                node.snap = None
                self.snapshots_evicted_total += 1
            else:
                break
            self._snap_out.add(row)
            got.append(row)
        return got

    def attach_snapshot(self, node: Optional[_TrieNode], row: int) -> bool:
        """``node`` (the page that ends on the boundary ``row`` holds the
        state of: a multiple of the stride, which the caller sees to and
        :meth:`audit` checks) names the row from now on; the row goes
        back to the free list where the node is gone or already has
        one."""
        self._snap_out.discard(row)
        if node is None or node.detached or node.snap is not None:
            self._snap_free.append(row)
            return False
        node.snap = row
        self._snap_node[row] = node
        self.snapshots_taken_total += 1
        return True

    def return_snapshot_rows(self, rows: Sequence[int]) -> None:
        for row in rows:
            self._snap_out.discard(row)
            self._snap_free.append(row)

    def _forget_snapshot(self, node: _TrieNode) -> None:
        """``node`` leaves the trie: its row is free again."""
        if node.snap is not None:
            del self._snap_node[node.snap]
            self._snap_free.append(node.snap)
            node.snap = None
            self.snapshots_evicted_total += 1

    @staticmethod
    def _depth(node: _TrieNode) -> int:
        """Pages from the root down to ``node``, its own included."""
        n = 0
        while node.parent is not None:
            n, node = n + 1, node.parent
        return n

    def _walk(self, tokens: Sequence[int]) -> List[_TrieNode]:
        node = self._root
        path: List[_TrieNode] = []
        bs = self.block_size
        for i in range(len(tokens) // bs):
            node = node.children.get(tuple(tokens[i * bs:(i + 1) * bs]))
            if node is None:
                break
            path.append(node)
        return path

    def _take(self, path: List[_TrieNode]
              ) -> Tuple[List[int], int, _TrieNode]:
        for node in path:
            self.incref(node.block)
            self._touch(node)
            node.hits += 1
        # hits_total is NOT bumped here: a match may be released when
        # allocation fails (admission wait) and retried — the engine
        # counts hits once, on successful admission (count_hits)
        return [n.block for n in path], len(path) * self.block_size, \
            path[-1] if path else self._root

    def window_tail_from(self, boundary: int) -> int:
        """The first page a sequence resumed at position ``boundary``
        needs of the window layers: the one with key ``boundary -
        window`` (what the query at ``boundary - 1``, a copy-on-write's,
        still sees)."""
        return max(0, boundary - self.window) // self.block_size

    def _cut_to_window_tail(self, path: List[_TrieNode]) -> int:
        """How many nodes of ``path`` an exact hit may take: up to the
        deepest page boundary with every window page of its tail."""
        missing = -1                    # the last page < i without one
        last_missing = []
        for i, node in enumerate(path):
            last_missing.append(missing)
            if node.wblock is None:
                missing = i
        last_missing.append(missing)
        i = len(path)
        while i > 0 and last_missing[i] >= self.window_tail_from(
                i * self.block_size):
            i -= 1
        return i

    def count_hits(self, n: int) -> None:
        self.hits_total += n

    # ----------------------------------------------------- allocation
    def allocate(self, n: int) -> Optional[List[int]]:
        """Take ``n`` private blocks (refcount 1 each), evicting LRU
        ref-0 trie leaves under pressure. Returns None — with nothing
        taken — when even eviction can't cover ``n`` (the engine's
        admission-wait signal)."""
        got: List[int] = []
        while len(got) < n:
            if self._free:
                b = self._free.popleft()
                self._ref[b] = 1
                got.append(b)
                continue
            if not self._evict_one():
                for b in got:           # restore, all-or-nothing
                    del self._ref[b]
                    self._free.append(b)
                return None
        return got

    def _evict_one(self) -> bool:
        """Evict the least-recently-touched ref-0 LEAF (a node with
        referenced or cached children is load-bearing for deeper
        matches and never evicted; freeing a leaf may expose its
        parent as the next candidate)."""
        while self._evictable:
            stamp, _, node = heapq.heappop(self._evictable)
            if self._is_victim(stamp, node):
                break
        else:
            return False
        node.detached = True
        parent = node.parent
        parent.children.pop(node.key, None)
        if parent is self._root:
            del self._root_fps[node]
        else:
            self._offer(parent)
        del self._node_of[node.block]
        self._free.append(node.block)
        self._cached -= 1
        self.evictions_total += 1
        if self.window_pool is not None:
            self.window_pool.forget(node)
        self._forget_snapshot(node)
        return True

    # ------------------------------------------------------ insertion
    def insert_child(self, parent: Optional[_TrieNode],
                     chunk: Sequence[int], block: int,
                     wblock: Optional[int] = None
                     ) -> Tuple[Optional[_TrieNode], bool]:
        """Index ``block`` (full, holding exactly ``chunk``) under
        ``parent``, and ``wblock``, the window layers' page of the same
        chunk (referenced by the caller), with it: also under a node
        that was there already and has lost its own to eviction.
        Returns ``(node, inserted)``:

        - fresh insert → the new node, True;
        - the path already exists (a concurrent request with the same
          prompt won the race) → the existing node, False — the
          caller's block stays private and is freed normally;
        - ``parent`` was evicted meanwhile (or None) → (None, False) —
          the caller stops indexing this request.
        """
        if parent is None or parent.detached:
            return None, False
        key = tuple(chunk)
        existing = parent.children.get(key)
        if existing is not None:
            self._touch(existing)
            if wblock is not None and existing.wblock is None:
                self.window_pool.attach(wblock, existing)
            return existing, False
        node = _TrieNode(parent, key, block)
        if wblock is not None:
            self.window_pool.attach(wblock, node)
        parent.children[key] = node
        self._node_of[block] = node
        if block not in self._ref:
            self._cached += 1
        if parent is self._root:
            self._root_fps[node] = prefix_fingerprint(key, self.block_size)
        self._touch(node)
        self.inserts_total += 1
        return node, True

    # ------------------------------------------------------- migration
    def export_chains(self, min_hits: int = 1,
                      max_blocks: int = 0) -> List[List[Tuple[tuple, int]]]:
        """Warm prefix chains worth migrating off a draining replica.

        A chain is a contiguous root-anchored trie path of ref-0
        (cached) nodes whose ``hits`` meet the floor — exactly the
        blocks that would die with this replica but have proven reuse.
        Chains truncate at the first node that is referenced (a live
        request still writes against it), below the hit floor, or
        detached: an importer re-inserts from its own root, so a gap
        would orphan everything deeper. Returns
        ``[[(chunk_tokens, block_id), ...], ...]`` ordered hottest
        chain first; ``max_blocks > 0`` caps the total block count.
        """
        chains: List[List[Tuple[tuple, int]]] = []

        def walk(node: _TrieNode, path: List[Tuple[tuple, int]]):
            extended = False
            for child in sorted(node.children.values(),
                                key=lambda n: -n.hits):
                if (child.detached or child.block in self._ref
                        or child.hits < min_hits):
                    continue
                walk(child, path + [(child.key, child.block)])
                extended = True
            if not extended and path:
                chains.append(path)

        walk(self._root, [])
        chains.sort(key=lambda c: -len(c))
        if max_blocks > 0:
            out, n = [], 0
            for c in chains:
                if n + len(c) > max_blocks:
                    c = c[:max_blocks - n]
                if not c:
                    break
                out.append(c)
                n += len(c)
            chains = out
        return chains

    # -------------------------------------------------------- introspection
    def root_fingerprints(self, limit: int = 64) -> List[int]:
        """Fingerprints of the trie ROOT's children — the first-block
        chunks this pool holds warm. O(``limit``), most-recently-touched
        first (each was computed when its node was made): cheap enough
        for every ``Replica.stats()`` probe, rich enough for a router to
        place a cold session where its system prompt already lives."""
        newest = itertools.islice(reversed(self._root_fps.values()), limit)
        return [fp for fp in newest if fp is not None]

    def stats(self) -> Dict[str, int]:
        return {
            "free": len(self._free),
            "cached": self._cached,         # ref-0, trie-resident
            "reclaimable": len(self._free) + self._cached,
            "active": len(self._ref),
            "shared": self._shared,         # refcount > 1 right now
            "trie_blocks": len(self._node_of),
            "hits_total": self.hits_total,
            "inserts_total": self.inserts_total,
            "evictions_total": self.evictions_total,
            "snapshots_total": self.num_snapshots,
            "snapshots_live": len(self._snap_node),
            "snapshots_taken_total": self.snapshots_taken_total,
            "snapshots_evicted_total": self.snapshots_evicted_total,
        }

    def audit(self) -> List[str]:
        """Integrity check (leak regression tests): every managed block
        is in EXACTLY one of {free, referenced, cached-in-trie}; every
        trie node is reachable, attached, and consistent with
        ``_node_of``. Returns a list of problems (empty = clean)."""
        problems: List[str] = []
        free = set(self._free)
        if len(free) != len(self._free):
            problems.append("duplicate blocks on the free list")
        ref = set(self._ref)
        trie = set(self._node_of)
        if free & ref:
            problems.append(f"blocks both free and referenced: "
                            f"{sorted(free & ref)}")
        if free & trie:
            problems.append(f"blocks both free and trie-resident: "
                            f"{sorted(free & trie)}")
        accounted = free | ref | trie
        managed = {b for b in range(
            self.total_managed + len(self._reserved))
            if b not in self._reserved}
        missing = managed - accounted
        if missing:
            problems.append(f"leaked blocks (nowhere): {sorted(missing)}")
        extra = accounted - managed
        if extra:
            problems.append(f"unmanaged blocks tracked: {sorted(extra)}")
        # trie reachability + pointer consistency
        reachable = set()
        stack = [self._root]
        while stack:
            node = stack.pop()
            for key, child in node.children.items():
                if child.parent is not node or child.key != key:
                    problems.append(f"trie pointer mismatch at {key}")
                if child.detached:
                    problems.append(f"detached node still linked: {key}")
                if child.block is None:
                    problems.append(f"trie node without block: {key}")
                elif self._node_of.get(child.block) is not child:
                    problems.append(
                        f"_node_of mismatch for block {child.block}")
                else:
                    reachable.add(child.block)
                stack.append(child)
        dangling = trie - reachable
        if dangling:
            problems.append(f"unreachable trie blocks: {sorted(dangling)}")
        # the books kept beside the maps
        cached = len(trie - ref)
        shared = sum(1 for r in self._ref.values() if r > 1)
        if (cached, shared) != (self._cached, self._shared):
            problems.append(
                f"cached/shared counts {self._cached}/{self._shared}, "
                f"the maps say {cached}/{shared}")
        queued = {node.block for stamp, _, node in self._evictable
                  if node.touch == stamp and not node.detached}
        unqueued = {b for b in trie - ref
                    if not self._node_of[b].children} - queued
        if unqueued:
            problems.append(
                f"ref-0 leaves no eviction can find: {sorted(unqueued)}")
        if list(self._root_fps) != sorted(self._root.children.values(),
                                          key=lambda n: n.touch):
            problems.append("root children out of touch order")
        # snapshot rows: free, live (one node each) or out with a call
        free_rows, live, out = set(self._snap_free), set(self._snap_node), \
            self._snap_out
        if len(free_rows) != len(self._snap_free) or free_rows & live \
                or free_rows & out or live & out or free_rows | live | out \
                != set(range(1, self.num_snapshots + 1)):
            problems.append(
                f"snapshot rows: free {sorted(free_rows)}, live "
                f"{sorted(live)}, out {sorted(out)} of "
                f"{self.num_snapshots}")
        named = {n.snap: n for n in self._node_of.values()
                 if n.snap is not None}
        if named != self._snap_node:
            problems.append(
                f"snapshot rows the trie's nodes name {sorted(named)} "
                f"and the live rows {sorted(live)} disagree")
        for row, node in self._snap_node.items():
            if self._depth(node) * self.block_size % self.snapshot_stride:
                problems.append(f"snapshot row {row} on a node off the "
                                f"stride")
        if self.window_pool is not None:
            problems += self.window_pool.audit()
            held = {n.wblock for n in self._node_of.values()
                    if n.wblock is not None}
            if held != set(self.window_pool._node_of):
                problems.append("window pages and the trie's nodes "
                                "disagree on who names which")
        return problems
