"""Radix-tree prefix KV cache over the paged allocator.

Conversation-level reuse (PAPERS.md: "Observation, Not Prediction",
arXiv 2606.01839) on top of the paged-KV substrate the engine
already runs: finished sequences publish their
page-aligned KV prefix into a radix tree keyed on token-ID blocks, and
new admissions that share a prefix — the next turn of the same
conversation, or an unrelated request with the same system prompt —
adopt the cached pages instead of re-prefilling them.

Design:

- **One node per page-aligned block.** Each tree edge is exactly
  ``page_size`` token ids and each node owns exactly one physical KV
  page. Positions are implied by depth (block *i* covers absolute token
  positions ``[i·page_size, (i+1)·page_size)``), which is what makes a
  cached page reusable at all: RoPE bakes absolute positions into the
  cached keys, so a prefix match from the root is the only alignment at
  which sharing is sound.
- **Sharing is ref-counted, never copied.** The tree holds one
  :class:`PageAllocator` reference per cached page; every sequence whose
  block table adopts a shared page holds another (``match`` retains).
  A page returns to the pool only when its last holder lets go.
- **Copy-on-write at block granularity.** Shared pages are immutable by
  protocol: a sequence's writes always target positions at or past its
  matched length, which land in freshly-allocated blocks — divergence
  "copies" by re-prefilling the divergent tail into the sequence's own
  pages rather than mutating a shared one. The partial-block tail of a
  prefix (fewer than ``page_size`` tokens) is never published, so no
  shared page is ever half-written.
- **Eviction takes zero-ref leaves only.** A node matched by an
  in-flight sequence carries a ``lock_ref`` pin and is skipped; interior
  nodes are unreachable for eviction until their children go (children's
  pages are useless without the parent's — a match walks from the
  root). Policy is LRU by default ("lru"), insertion-order with "fifo".
- **Explicit invalidation.** ``invalidate(ids)`` walks a token stream's
  path and prunes its unlocked, childless tail — the conversation-delete
  hook. Shared ancestors (another conversation's live prefix, or any
  locked node) survive.

- **Tails** (``tail_slots`` > 0; ``docs/prefix_cache.md`` "Tails"). For
  a model family that keeps ROW STATE beside its pages, pages alone
  rebuild nothing — but for a window family the last W tokens' K and V
  before a block boundary do. A node may carry a TAIL: the id of a slot
  in the executor's tail pool that holds that state at the node's end.
  ``match(ids, need_tail=True)`` ends at the deepest matched node that
  has one (``PrefixMatch.tail``; what it gave up is ``cut_tokens``).
  The tree owns the slot ids (``take_tail_slot`` hands one out, the
  least recently used tail's when none is free; ``attach_tail`` hangs
  it on a node); a tail goes with its node, and each counts
  ``tail_cost_pages`` against ``max_pages``.

The int8-KV path needs nothing special here: per-page quantization
scales live in pools indexed by the same page id as the KV they scale
(models/llama.init_kv_pages), so sharing a page id shares its scale
rows by construction.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from llmq_tpu.core.config import VALID_PREFIX_EVICTION as EVICTION_POLICIES
from llmq_tpu.engine.kv_allocator import PageAllocator
from llmq_tpu.utils.logging import get_logger

log = get_logger("prefixcache")


class RadixNode:
    __slots__ = ("key", "page", "parent", "children", "lock_ref",
                 "last_used", "created", "tail", "tail_used")

    def __init__(self, key: Optional[Tuple[int, ...]], page: int,
                 parent: Optional["RadixNode"], now: float,
                 seq_no: int) -> None:
        self.key = key
        self.page = page
        self.parent = parent
        self.children: Dict[Tuple[int, ...], RadixNode] = {}
        #: In-flight pin count: matches held by admitted sequences whose
        #: block tables reference this page. Locked nodes are immune to
        #: every eviction path.
        self.lock_ref = 0
        self.last_used = now
        self.created = seq_no
        #: The tail slot that rebuilds a row's state at this node's end,
        #: or None; ``tail_used``: when it was taken or last adopted
        #: (what the tails' own LRU goes by: a walk through the node on
        #: the way to a deeper tail does not count).
        self.tail: Optional[int] = None
        self.tail_used = 0.0


@dataclass
class PrefixMatch:
    """Result of :meth:`PrefixCache.match` — the caller now holds one
    allocator reference per page and one lock per node; release both
    with :meth:`PrefixCache.unlock` (pages are released through the
    caller's normal ``allocator.free`` of its block table)."""

    length: int                      # tokens covered (page-aligned)
    pages: List[int] = field(default_factory=list)
    nodes: List[RadixNode] = field(default_factory=list)
    #: ``match(need_tail=True)``: the tail slot at ``length`` (None with
    #: an empty match), and the tokens of the walk given up because the
    #: deepest tail lay before the deepest matched block.
    tail: Optional[int] = None
    cut_tokens: int = 0


class PrefixCache:
    """Radix tree mapping page-aligned token-ID prefixes to ref-counted
    KV pages in ``allocator``'s id space."""

    def __init__(self, allocator: PageAllocator, page_size: int, *,
                 max_pages: int = 0, policy: str = "lru",
                 clock=None, tail_slots: int = 0,
                 tail_cost_pages: int = 0) -> None:
        if policy not in EVICTION_POLICIES:
            raise ValueError(
                f"unknown prefix-cache eviction policy {policy!r}; "
                f"valid: {EVICTION_POLICIES}")
        self.allocator = allocator
        self.page_size = page_size
        #: Cap on pages held by the tree; 0 = bounded only by the pool
        #: (pool pressure evicts through :meth:`evict_pages`).
        self.max_pages = max_pages
        self.policy = policy
        self._now = clock if clock is not None else time.monotonic
        self._root = RadixNode(None, 0, None, 0.0, 0)
        self._pages = 0                  # nodes (== pages) in the tree
        #: Tails: the slot ids not on a node nor lent out, the nodes
        #: that carry one, and what one counts against ``max_pages``.
        self.tail_slots = int(tail_slots)
        self.tail_cost_pages = int(tail_cost_pages)
        self._free_tails: List[int] = list(range(self.tail_slots))[::-1]
        self._tailed: Dict[int, RadixNode] = {}
        self.tails_attached = 0
        self.tails_evicted = 0
        self._seq = 0                    # insertion order for fifo
        self._mu = threading.RLock()
        #: Demotion seam (llmq_tpu/tiering/, docs/tiering.md): when an
        #: EVICTED leaf's page is about to leave HBM for good (the
        #: tree holds the last reference), the callback observes
        #: ``(token_path, page)`` BEFORE the free — the tiering plane
        #: captures the payload there. None (the default) keeps the
        #: exact pre-seam behavior: evict = free, nothing else.
        #: Deliberately NOT fired from :meth:`invalidate` /
        #: conversation delete — deleted content must not linger in a
        #: lower tier.
        self._on_demote: Optional[Callable[[List[int], int], None]] = None
        # Counters (read by engine metrics/stats):
        self.hits = 0
        self.misses = 0
        self.cached_tokens_served = 0
        self.inserted_pages = 0
        self.evicted_pages = 0

    # -- lookup --------------------------------------------------------------

    def match(self, ids: List[int], need_tail: bool = False) -> PrefixMatch:
        """Longest page-aligned cached prefix of ``ids``, capped at
        ``len(ids) - 1`` tokens — at least one token is always left for
        the caller to prefill (sampling the first output token needs
        live logits). Matched pages are retained in the allocator and
        their nodes lock-pinned; the caller owns both until
        :meth:`unlock` (nodes) and its own page free (pages).

        ``need_tail`` (a family with row state): the match is cut back
        to the deepest matched node that carries a tail — ``m.tail`` its
        slot, ``m.cut_tokens`` what the cut gave up; with no tail on the
        path the match is empty (a miss) and all of the walk is cut."""
        ps = self.page_size
        n_blocks = max(0, (len(ids) - 1) // ps)
        m = PrefixMatch(0)
        with self._mu:
            node = self._root
            now = self._now()
            for b in range(n_blocks):
                key = tuple(ids[b * ps:(b + 1) * ps])
                child = node.children.get(key)
                if child is None:
                    break
                node = child
                m.nodes.append(node)
                m.pages.append(node.page)
            if need_tail:
                walked = len(m.nodes)
                while m.nodes and m.nodes[-1].tail is None:
                    m.nodes.pop()
                    m.pages.pop()
                m.cut_tokens = (walked - len(m.nodes)) * ps
                if m.nodes:
                    m.tail = m.nodes[-1].tail
                    m.nodes[-1].tail_used = now
            if not m.nodes:
                self.misses += 1
                return m
            self.allocator.retain(m.pages)
            for nd in m.nodes:
                nd.lock_ref += 1
                nd.last_used = now
            m.length = len(m.nodes) * ps
            self.hits += 1
            self.cached_tokens_served += m.length
        return m

    def cached_blocks(self, ids: List[int]) -> int:
        """Read-only probe: how many full page-aligned blocks of
        ``ids`` the tree currently holds, WITHOUT retaining pages or
        locking nodes (sizing heuristics — the tiering plane's
        gone-for-good check — not admission)."""
        ps = self.page_size
        n = 0
        with self._mu:
            node = self._root
            for b in range(len(ids) // ps):
                child = node.children.get(tuple(ids[b * ps:(b + 1) * ps]))
                if child is None:
                    break
                node = child
                n += 1
        return n

    def unlock(self, match: Optional[PrefixMatch]) -> None:
        """Drop the in-flight pins of a match (idempotent via the
        caller clearing its reference). Page references are NOT touched
        — the sequence releases those through its normal block-table
        free."""
        if match is None or not match.nodes:
            return
        with self._mu:
            now = self._now()
            for nd in match.nodes:
                if nd.lock_ref > 0:
                    nd.lock_ref -= 1
                nd.last_used = now

    # -- tails ---------------------------------------------------------------

    def _walk(self, ids: List[int], end: int) -> Optional[RadixNode]:
        """The node that ends at token ``end`` (a multiple of the page
        size) on the path of ``ids``, or None. Caller holds the lock."""
        ps = self.page_size
        node = self._root
        for b in range(end // ps):
            node = node.children.get(tuple(ids[b * ps:(b + 1) * ps]))
            if node is None:
                return None
        return node if end > 0 else None

    def has_tail(self, ids: List[int], end: int) -> bool:
        """Whether the node that ends at token ``end`` of ``ids`` is in
        the tree with a tail (read-only: nothing retained or locked)."""
        with self._mu:
            node = self._walk(ids, end)
            return node is not None and node.tail is not None

    def take_tail_slot(self) -> Optional[int]:
        """A slot id to copy a tail into: a free one, else the slot of
        the least recently used tail in the tree (its node keeps its
        page and loses the tail). The caller owns the id until
        :meth:`attach_tail` or :meth:`free_tail_slot`. None with no
        slots configured."""
        with self._mu:
            if self._free_tails:
                return self._free_tails.pop()
            if not self._tailed:
                return None
            slot, node = min(self._tailed.items(),
                             key=lambda kv: kv[1].tail_used)
            node.tail = None
            del self._tailed[slot]
            self.tails_evicted += 1
            return slot

    def free_tail_slot(self, slot: int) -> None:
        with self._mu:
            self._free_tails.append(slot)

    def attach_tail(self, ids: List[int], end: int, slot: int) -> bool:
        """Hang tail ``slot`` on the node that ends at token ``end`` of
        ``ids``. False — and the slot is free again — where that node is
        not in the tree or already carries a tail."""
        with self._mu:
            node = self._walk(ids, end)
            if node is None or node.tail is not None:
                self._free_tails.append(slot)
                return False
            node.tail = slot
            node.tail_used = self._now()
            self._tailed[slot] = node
            self.tails_attached += 1
            self._trim_locked()
            return True

    def _held(self) -> int:
        """What the tree holds against ``max_pages``: its pages, and
        ``tail_cost_pages`` for every tail."""
        return self._pages + len(self._tailed) * self.tail_cost_pages

    def _trim_locked(self) -> None:
        """Evict down to ``max_pages`` (0: bounded by the pool alone)."""
        if self.max_pages > 0 and self._held() > self.max_pages:
            self._evict_locked(target_nodes=self._held() - self.max_pages)

    def _drop_tail(self, node: RadixNode) -> None:
        """A node leaves the tree: its tail goes with it."""
        if node.tail is not None:
            self._tailed.pop(node.tail, None)
            self._free_tails.append(node.tail)
            node.tail = None

    @property
    def tail_slots_in_use(self) -> int:
        """Slots that hold a tail: on a node, or lent out and waiting
        for theirs."""
        with self._mu:
            return self.tail_slots - len(self._free_tails)

    # -- publication ---------------------------------------------------------

    def insert(self, ids: List[int], pages: List[int]) -> int:
        """Publish the full-block prefix of ``ids`` (backed by ``pages``,
        the sequence's block table in order). The tree retains every page
        it newly adopts — the caller keeps its own references and frees
        them as usual, so ownership composes with conversation pinning.
        Blocks already present keep the tree's existing page (a
        concurrent duplicate prefill's page is simply not adopted; the
        caller's free reclaims it). Returns the number of pages newly
        cached."""
        ps = self.page_size
        n_blocks = min(len(ids) // ps, len(pages))
        if n_blocks <= 0:
            return 0
        added = 0
        with self._mu:
            node = self._root
            now = self._now()
            for b in range(n_blocks):
                key = tuple(ids[b * ps:(b + 1) * ps])
                child = node.children.get(key)
                if child is None:
                    page = pages[b]
                    self.allocator.retain([page])
                    self._seq += 1
                    child = RadixNode(key, page, node, now, self._seq)
                    node.children[key] = child
                    self._pages += 1
                    added += 1
                else:
                    child.last_used = now
                node = child
            self.inserted_pages += added
            self._trim_locked()
        return added

    # -- eviction ------------------------------------------------------------

    def set_demotion_callback(
            self, cb: Optional[Callable[[List[int], int], None]]) -> None:
        """Install (or clear) the eviction→demotion seam. See the
        ``_on_demote`` field doc; the callback runs under the cache
        lock and must be cheap and never call back into the cache."""
        with self._mu:
            self._on_demote = cb

    def _node_path(self, node: RadixNode) -> List[int]:
        """The token-id path root→``node`` (the content identity of the
        node's page — what a lower tier keys the payload on)."""
        keys: List[Tuple[int, ...]] = []
        cur: Optional[RadixNode] = node
        while cur is not None and cur.key is not None:
            keys.append(cur.key)
            cur = cur.parent
        out: List[int] = []
        for k in reversed(keys):
            out.extend(k)
        return out

    def _demote_hook(self, victim: RadixNode) -> None:
        """Fire the demotion seam for an evicted leaf whose page the
        tree holds the LAST reference of (a still-shared page isn't
        leaving HBM — demoting it would duplicate resident content)."""
        if self._on_demote is None:
            return
        if self.allocator.refcount(victim.page) != 1:
            return
        try:
            self._on_demote(self._node_path(victim), victim.page)
        except Exception:  # noqa: BLE001 — the seam must not break
            log.exception("prefix-cache demotion callback failed")

    def _evictable(self) -> List[RadixNode]:
        out: List[RadixNode] = []
        stack = list(self._root.children.values())
        while stack:
            nd = stack.pop()
            if nd.children:
                stack.extend(nd.children.values())
            elif nd.lock_ref == 0:
                out.append(nd)
        return out

    def _evict_locked(self, target_nodes: int = 0,
                      target_pool_pages: int = 0) -> int:
        """Remove zero-ref leaves until ``target_nodes`` nodes are gone
        or the allocator gained ``target_pool_pages`` free pages.
        Returns pages actually returned to the pool (a released tree
        reference on a still-shared page frees nothing yet)."""
        import heapq

        removed = 0
        pool_freed = 0
        keyf = ((lambda nd: nd.last_used) if self.policy == "lru"
                else (lambda nd: nd.created))
        # Pool-pressure mode only takes leaves whose page the tree is
        # the LAST holder of — evicting a still-shared leaf (e.g. one a
        # conversation pin also holds) would churn cache entries for
        # zero pool gain.
        eligible = (lambda nd: self.allocator.refcount(nd.page) == 1
                    ) if target_pool_pages else (lambda nd: True)
        # ONE tree traversal per call: candidates go into a policy-keyed
        # heap; a parent that becomes an unlocked childless leaf joins
        # incrementally. (Stale entries — nodes locked or re-shared
        # after heaping — are re-checked at pop.)
        heap = [(keyf(nd), id(nd), nd) for nd in self._evictable()
                if eligible(nd)]
        heapq.heapify(heap)
        while heap:
            if target_nodes and removed >= target_nodes:
                break
            if target_pool_pages and pool_freed >= target_pool_pages:
                break
            _, _, victim = heapq.heappop(heap)
            if victim.children or victim.lock_ref > 0 or not eligible(victim):
                continue
            last_holder = self.allocator.refcount(victim.page) == 1
            assert victim.parent is not None
            self._demote_hook(victim)
            del victim.parent.children[victim.key]
            self.allocator.free([victim.page])
            self._pages -= 1
            # (a tail counts as ``tail_cost_pages`` nodes gone)
            removed += 1 + (self.tail_cost_pages
                            if victim.tail is not None else 0)
            self._drop_tail(victim)
            self.evicted_pages += 1
            if last_holder:
                pool_freed += 1
            parent = victim.parent
            if (parent is not self._root and not parent.children
                    and parent.lock_ref == 0 and eligible(parent)):
                heapq.heappush(heap, (keyf(parent), id(parent), parent))
        return pool_freed

    def evict_pages(self, n: int) -> int:
        """Pool-pressure hook: free up to ``n`` pages back to the pool
        by evicting unlocked leaves. Returns pages actually freed."""
        if n <= 0:
            return 0
        with self._mu:
            return self._evict_locked(target_pool_pages=n)

    def invalidate(self, ids: List[int]) -> int:
        """Prune the cached path of ``ids`` bottom-up: the deepest
        unlocked, childless nodes go; the prune stops at the first node
        that is locked or still has other children (a prefix shared with
        someone else). Conversation-delete hook. Returns nodes
        removed."""
        ps = self.page_size
        removed = 0
        with self._mu:
            node = self._root
            path: List[RadixNode] = []
            for b in range(len(ids) // ps):
                child = node.children.get(tuple(ids[b * ps:(b + 1) * ps]))
                if child is None:
                    break
                path.append(child)
                node = child
            for nd in reversed(path):
                if nd.children or nd.lock_ref > 0:
                    break
                assert nd.parent is not None
                del nd.parent.children[nd.key]
                self.allocator.free([nd.page])
                self._drop_tail(nd)
                self._pages -= 1
                self.evicted_pages += 1
                removed += 1
        return removed

    def invalidate_all(self) -> int:
        """Drop every unlocked cached page (hard reset hook)."""
        with self._mu:
            before = self._pages
            while self._evict_locked(target_nodes=self._pages):
                pass
            return before - self._pages

    # -- stats ---------------------------------------------------------------

    @property
    def pages(self) -> int:
        with self._mu:
            return self._pages

    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def get_stats(self) -> Dict:
        with self._mu:
            return {
                "pages": self._pages,
                "max_pages": self.max_pages,
                "policy": self.policy,
                "hits": self.hits,
                "misses": self.misses,
                "hit_rate": round(self.hit_rate(), 4),
                "cached_tokens_served": self.cached_tokens_served,
                "inserted_pages": self.inserted_pages,
                "evicted_pages": self.evicted_pages,
                **({"tail_slots": self.tail_slots,
                    "tails": len(self._tailed),
                    "tails_attached": self.tails_attached,
                    "tails_evicted": self.tails_evicted}
                   if self.tail_slots else {}),
            }
