"""Radix-tree prefix KV cache (llmq_tpu/prefixcache/): ref-counted
block sharing, LRU/FIFO eviction with in-flight pinning, invalidation,
and engine integration — including the acceptance gates: a two-turn
conversation replay through the real (CPU-mode JAX) engine prefills
strictly fewer tokens on turn 2, decodes identically to the cache-off
path, and ``enabled: false`` restores exact pre-cache behavior."""

import jax
import jax.numpy as jnp
import pytest

from llmq_tpu.core.config import PrefixCacheConfig
from llmq_tpu.core.types import Priority
from llmq_tpu.engine.engine import GenRequest, InferenceEngine
from llmq_tpu.engine.executor import EchoExecutor, JaxExecutor
from llmq_tpu.engine.kv_allocator import PageAllocator
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.prefixcache import PrefixCache


# -- allocator ref-counting ----------------------------------------------------


class TestAllocatorRefcounts:
    def test_retain_free_lifecycle(self):
        a = PageAllocator(8, 16)
        pages = a.alloc(3)
        assert all(a.refcount(p) == 1 for p in pages)
        a.retain(pages)
        assert all(a.refcount(p) == 2 for p in pages)
        a.free(pages)                       # one holder left
        assert a.available() == 7 - 3
        a.free(pages)                       # last holder → pool
        assert a.available() == 7
        assert all(a.refcount(p) == 0 for p in pages)

    def test_double_free_raises(self):
        a = PageAllocator(8, 16)
        pages = a.alloc(1)
        a.free(pages)
        with pytest.raises(ValueError):
            a.free(pages)

    def test_retain_unallocated_raises(self):
        a = PageAllocator(8, 16)
        with pytest.raises(ValueError):
            a.retain([3])

    def test_shared_pages_stat(self):
        a = PageAllocator(8, 16)
        pages = a.alloc(2)
        assert a.shared_pages() == 0
        a.retain(pages[:1])
        assert a.shared_pages() == 1


# -- radix tree ----------------------------------------------------------------


def make_cache(num_pages=64, page_size=4, **kw):
    alloc = PageAllocator(num_pages, page_size)
    return alloc, PrefixCache(alloc, page_size, **kw)


def seq_pages(alloc, n):
    pages = alloc.alloc(n)
    assert pages is not None
    return pages


class TestRadixTree:
    def test_insert_then_match_shares_pages(self):
        alloc, pc = make_cache()
        ids = list(range(10))              # 2 full blocks + tail of 2
        pages = seq_pages(alloc, 3)
        assert pc.insert(ids, pages) == 2
        assert pc.pages == 2
        # The tree holds its own refs on the two full-block pages.
        assert alloc.refcount(pages[0]) == 2
        assert alloc.refcount(pages[2]) == 1     # tail not published
        m = pc.match(ids)
        assert m.length == 8 and m.pages == pages[:2]
        assert alloc.refcount(pages[0]) == 3     # tree + owner + match

    def test_match_leaves_at_least_one_token(self):
        alloc, pc = make_cache()
        ids = list(range(8))               # exactly 2 blocks
        pages = seq_pages(alloc, 2)
        pc.insert(ids, pages)
        m = pc.match(ids)                  # (8-1)//4 = 1 block max
        assert m.length == 4

    def test_miss_and_hit_counters(self):
        alloc, pc = make_cache()
        assert pc.match(list(range(9))).length == 0
        pages = seq_pages(alloc, 2)
        pc.insert(list(range(8)), pages)
        assert pc.match(list(range(9))).length == 8
        assert pc.hits == 1 and pc.misses == 1

    def test_duplicate_insert_keeps_existing_pages(self):
        alloc, pc = make_cache()
        ids = list(range(8))
        first = seq_pages(alloc, 2)
        pc.insert(ids, first)
        dup = seq_pages(alloc, 2)
        assert pc.insert(ids, dup) == 0            # nothing new cached
        assert alloc.refcount(dup[0]) == 1          # not adopted
        assert alloc.refcount(first[0]) == 2        # tree kept the original

    def test_divergence_forks_below_shared_prefix(self):
        """Two streams share block 0 then diverge: the tree holds one
        shared node plus two distinct children (COW at block
        granularity — nobody ever wrote a shared page)."""
        alloc, pc = make_cache()
        a = [1, 2, 3, 4, 10, 11, 12, 13]
        b = [1, 2, 3, 4, 20, 21, 22, 23]
        pa = seq_pages(alloc, 2)
        pc.insert(a, pa)
        # stream b matched block 0, re-used pa[0], wrote its own block 1
        m = pc.match(b)
        assert m.length == 4 and m.pages == [pa[0]]
        pb = seq_pages(alloc, 1)
        pc.insert(b, [pa[0], pb[0]])
        assert pc.pages == 3
        assert alloc.refcount(pa[0]) >= 3   # tree + owner a + matcher b
        assert alloc.refcount(pa[1]) == 2   # a's exclusive branch
        assert alloc.refcount(pb[0]) == 2   # b's exclusive branch

    def test_eviction_skips_locked_leaves(self):
        """Eviction racing an in-flight match: pinned pages survive."""
        alloc, pc = make_cache()
        ids = list(range(9))
        pages = seq_pages(alloc, 3)
        pc.insert(ids, pages)
        m = pc.match(ids)                   # locks both nodes
        assert pc.evict_pages(10) == 0      # everything pinned
        assert pc.pages == 2
        pc.unlock(m)
        alloc.free(m.pages)                 # matcher lets go
        alloc.free(pages)                   # original owner lets go
        assert pc.evict_pages(10) == 2      # now evictable, pages real-freed
        assert pc.pages == 0

    def test_lru_capacity_eviction(self):
        alloc, pc = make_cache(page_size=4, max_pages=2)
        old = seq_pages(alloc, 1)
        pc.insert([1, 2, 3, 4], old)
        alloc.free(old)                     # tree is sole owner
        new_pages = seq_pages(alloc, 2)
        pc.insert([9, 8, 7, 6, 5, 4, 3, 2], new_pages)
        alloc.free(new_pages)
        assert pc.pages == 2                # capacity held
        # the LRU entry (the first insert) was evicted
        assert pc.match([1, 2, 3, 4, 0]).length == 0

    def test_fifo_policy(self):
        alloc, pc = make_cache(page_size=4, policy="fifo", max_pages=2)
        a = seq_pages(alloc, 1)
        pc.insert([1, 2, 3, 4], a)
        b = seq_pages(alloc, 1)
        pc.insert([5, 6, 7, 8], b)
        # Touch the oldest so LRU would keep it; FIFO must not care.
        m = pc.match([1, 2, 3, 4, 0])
        pc.unlock(m)
        alloc.free(m.pages)
        c = seq_pages(alloc, 1)
        pc.insert([9, 10, 11, 12], c)
        assert pc.match([1, 2, 3, 4, 0]).length == 0   # first in, first out

    def test_bad_policy_rejected(self):
        alloc = PageAllocator(8, 4)
        with pytest.raises(ValueError):
            PrefixCache(alloc, 4, policy="random")

    def test_invalidate_prunes_exclusive_tail_only(self):
        """Conversation-delete semantics: the deleted stream's exclusive
        tail goes; a block shared with another stream (it has another
        child under it) survives."""
        alloc, pc = make_cache()
        a = [1, 2, 3, 4, 10, 11, 12, 13]
        b = [1, 2, 3, 4, 20, 21, 22, 23]
        pa = seq_pages(alloc, 2)
        pb = seq_pages(alloc, 2)
        pc.insert(a, pa)
        pc.insert(b, [pa[0], pb[1]])
        assert pc.pages == 3
        assert pc.invalidate(a) == 1        # only a's exclusive block
        assert pc.pages == 2
        assert pc.match(b + [0]).length == 8   # b's path fully intact

    def test_invalidate_all(self):
        alloc, pc = make_cache()
        pages = seq_pages(alloc, 2)
        pc.insert(list(range(8)), pages)
        alloc.free(pages)
        assert pc.invalidate_all() == 2
        assert pc.pages == 0 and alloc.available() == alloc.total


class TestTails:
    """A node may carry a TAIL — a slot of the executor's tail pool that
    rebuilds a row-state family's row at the node's end
    (docs/prefix_cache.md "Tails")."""

    def tree(self, **kw):
        """Three blocks of one stream published, a tail at the end of
        the second."""
        alloc, pc = make_cache(tail_slots=2, **kw)
        ids = list(range(12))
        pages = seq_pages(alloc, 3)
        pc.insert(ids, pages)
        slot = pc.take_tail_slot()
        assert pc.attach_tail(ids, 8, slot)
        return alloc, pc, ids, pages, slot

    def test_a_match_is_cut_to_the_deepest_tail(self):
        alloc, pc, ids, pages, slot = self.tree()
        m = pc.match(ids + [99], need_tail=True)
        # three blocks match; the tail hangs at the end of the second
        assert (m.length, m.tail, m.cut_tokens) == (8, slot, 4)
        assert m.pages == pages[:2] and len(m.nodes) == 2
        assert alloc.refcount(pages[2]) == 2      # the cut block: not retained
        # without the need the same walk goes all the way
        assert pc.match(ids + [99]).length == 12

    def test_no_tail_on_the_path_is_a_miss_and_all_of_it_is_cut(self):
        alloc, pc = make_cache(tail_slots=2)
        ids = list(range(12))
        pages = seq_pages(alloc, 3)
        pc.insert(ids, pages)
        m = pc.match(ids + [99], need_tail=True)
        assert (m.length, m.tail, m.cut_tokens, m.nodes) == (0, None, 12, [])
        assert pc.misses == 1 and pc.hits == 0
        assert all(alloc.refcount(p) == 2 for p in pages)    # nothing held

    def test_unlock_gives_everything_back(self):
        alloc, pc, ids, pages, _ = self.tree()
        m = pc.match(ids + [99], need_tail=True)
        assert pc.evict_pages(10) == 0             # its nodes are pinned
        pc.unlock(m)
        alloc.free(m.pages)
        alloc.free(pages)
        assert pc.evict_pages(10) == 3 and pc.pages == 0
        assert alloc.available() == alloc.total
        assert pc.tail_slots_in_use == 0           # the tail went with its node

    def test_a_tail_goes_with_its_node(self):
        alloc, pc, ids, pages, slot = self.tree()
        alloc.free(pages)
        assert pc.tail_slots_in_use == 1
        assert pc.invalidate(ids) == 3
        assert pc.tail_slots_in_use == 0 and not pc.has_tail(ids, 8)
        # the slot is handed out again
        assert sorted([pc.take_tail_slot(), pc.take_tail_slot()]) == [0, 1]

    def test_a_tail_counts_against_the_budget(self):
        """``max_pages`` 6 with a tail worth 3 pages: three blocks and a
        tail fit (3 + 3); one more block evicts by LRU until they do —
        and the tailed node's eviction gives back its tail's share."""
        alloc, pc, ids, pages, _ = self.tree(max_pages=6, tail_cost_pages=3)
        alloc.free(pages)
        assert pc.pages == 3 and pc._held() == 6
        other = seq_pages(alloc, 1)
        pc.insert([50, 51, 52, 53], other)
        alloc.free(other)
        assert pc._held() <= 6
        # a tail that does not fit evicts for itself
        slot = pc.take_tail_slot()
        assert pc.attach_tail([50, 51, 52, 53], 4, slot)
        assert pc._held() <= 6 and pc.has_tail([50, 51, 52, 53], 4)

    def test_the_slots_are_the_tree_s_to_lend(self):
        alloc, pc, ids, pages, slot = self.tree()
        assert pc.has_tail(ids, 8) and not pc.has_tail(ids, 4)
        # a node that has one keeps it; the offered slot is free again
        second = pc.take_tail_slot()
        assert second != slot and not pc.attach_tail(ids, 8, second)
        assert not pc.attach_tail(ids + [1, 2, 3, 4], 16, pc.take_tail_slot())
        assert pc.tail_slots_in_use == 1
        # both slots out: the least recently used tail gives up its own
        assert pc.attach_tail(ids, 4, pc.take_tail_slot())
        pc.unlock(pc.match(ids + [99], need_tail=True))      # uses the one at 8
        assert pc.take_tail_slot() is not None
        assert pc.has_tail(ids, 8) and not pc.has_tail(ids, 4)
        assert pc.get_stats()["tails_evicted"] == 1
        # no slots configured: none to lend, and the stats say nothing
        _, plain = make_cache()
        assert plain.take_tail_slot() is None
        assert "tail_slots" not in plain.get_stats()


# -- engine integration (echo executor: page accounting) -----------------------


def make_echo_engine(**kw):
    tok = ByteTokenizer()
    ex = EchoExecutor(batch_size=2, page_size=4, num_pages=kw.pop("num_pages", 64),
                      max_pages_per_seq=16, eos_id=tok.eos_id)
    return InferenceEngine(ex, tok, enable_metrics=False,
                           max_decode_steps=8, **kw)


class TestEngineIntegration:
    def test_two_turn_replay_uses_cache(self):
        eng = make_echo_engine(prefix_cache=PrefixCacheConfig(enabled=True))
        h1 = eng.submit(GenRequest(id="t1", prompt="abcdefgh",
                                   conversation_id="c1"))
        eng.run_until_idle()
        assert h1.result.cached_tokens == 0
        before = eng.cached_prefill_tokens_total
        h2 = eng.submit(GenRequest(id="t2", prompt="ijkl",
                                   conversation_id="c1"))
        eng.run_until_idle()
        assert h2.result.cached_tokens > 0
        assert eng.cached_prefill_tokens_total > before

    def test_cross_conversation_radix_share(self):
        """Concurrent fork: two conversations share a prompt prefix then
        diverge — the second adopts the first's published pages."""
        eng = make_echo_engine(prefix_cache=PrefixCacheConfig(enabled=True))
        h1 = eng.submit(GenRequest(id="a", prompt="shared prefix! A tail",
                                   conversation_id="ca"))
        eng.run_until_idle()
        h2 = eng.submit(GenRequest(id="b", prompt="shared prefix! B tail",
                                   conversation_id="cb"))
        eng.run_until_idle()
        assert h1.result.finish_reason in ("eos", "length")
        assert h2.result.cached_tokens > 0          # radix hit, not conv pin
        assert eng.allocator.shared_pages() > 0
        st = eng.get_stats()["prefix_cache"]
        assert st["hits"] >= 1 and st["pages"] > 0

    def test_disabled_is_hard_off(self):
        eng = make_echo_engine()                     # default: no cache
        assert eng._prefix_cache is None
        eng.submit(GenRequest(id="a", prompt="abcd",
                              conversation_id="c"))
        eng.run_until_idle()
        assert "prefix_cache" not in eng.get_stats()
        assert eng.prefix_hits == 0 and eng.prefix_misses == 0
        cfg = PrefixCacheConfig(enabled=False)
        eng2 = make_echo_engine(prefix_cache=cfg)
        assert eng2._prefix_cache is None

    def test_pin_ttl_expiry_keeps_tree_prefix(self, fake_clock):
        """Losing the HBM pin (TTL) must NOT invalidate the radix tree —
        the tree is exactly the fallback that lets turn N+1 still reuse
        the prefix after its pin is reclaimed."""
        eng = make_echo_engine(
            prefix_cache=PrefixCacheConfig(enabled=True),
            kv_pin_ttl=5.0, clock=fake_clock)
        h = eng.submit(GenRequest(id="a", prompt="ttl survivor prompt",
                                  conversation_id="ct"))
        eng.run_until_idle()
        assert h.result.finish_reason in ("eos", "length")
        fake_clock.advance(10.0)
        eng.step()                                   # expires the pin
        assert "ct" not in eng.cached_conversations()
        assert eng.get_stats()["prefix_cache"]["pages"] > 0
        h2 = eng.submit(GenRequest(id="b", prompt="ttl survivor prompt",
                                   conversation_id="ct2"))
        eng.run_until_idle()
        assert h2.result.cached_tokens > 0           # served by the tree

    def test_delete_after_pin_expiry_still_invalidates(self, fake_clock):
        """The delete contract must hold even when the HBM pin was
        already reclaimed: the engine remembers the evicted stream and
        prunes the tree when the conversation is actually deleted."""
        eng = make_echo_engine(
            prefix_cache=PrefixCacheConfig(enabled=True),
            kv_pin_ttl=5.0, clock=fake_clock)
        h = eng.submit(GenRequest(id="a", prompt="expire then delete me",
                                  conversation_id="cx"))
        eng.run_until_idle()
        assert h.result.finish_reason in ("eos", "length")
        fake_clock.advance(10.0)
        eng.step()                                    # pin expires
        assert eng.get_stats()["prefix_cache"]["pages"] > 0
        eng.drop_conversation("cx")                   # actual delete
        assert eng.get_stats()["prefix_cache"]["pages"] == 0
        assert eng.allocator.used() == 0

    def test_delete_prunes_divergent_branches(self, fake_clock):
        """An expired pin followed by a no-history turn publishes a
        DIVERGENT branch (the re-prefilled turn echoes only its tail).
        Delete must prune every stream the conversation ever published,
        not just the newest."""
        eng = make_echo_engine(
            prefix_cache=PrefixCacheConfig(enabled=True),
            kv_pin_ttl=5.0, clock=fake_clock)
        eng.submit(GenRequest(id="a", prompt="drive the delete contract",
                              conversation_id="cm"))
        eng.run_until_idle()
        fake_clock.advance(10.0)
        eng.step()                          # pin expires; tree keeps blocks
        eng.submit(GenRequest(id="b", prompt="drive the delete contract",
                              conversation_id="cm"))
        eng.run_until_idle()                # turn-2 completes and re-pins
        eng.drop_conversation("cm")
        assert eng.get_stats()["prefix_cache"]["pages"] == 0
        assert eng.allocator.used() == 0

    def test_delete_mid_turn_with_radix_match_prunes_at_finish(
            self, fake_clock):
        """Delete arriving while a turn admitted via radix match is
        in flight: the finishing sequence must unlock its OWN match
        pins before pruning, or the invalidation no-ops against them."""
        eng = make_echo_engine(
            prefix_cache=PrefixCacheConfig(enabled=True),
            kv_pin_ttl=5.0, clock=fake_clock)
        h1 = eng.submit(GenRequest(id="a", prompt="mid turn delete case",
                                   conversation_id="cm"))
        eng.run_until_idle()
        assert h1.result.finish_reason in ("eos", "length")
        fake_clock.advance(10.0)
        eng.step()                          # pin expires; tree keeps blocks
        assert eng.get_stats()["prefix_cache"]["pages"] > 0
        h2 = eng.submit(GenRequest(id="b", prompt="mid turn delete case",
                                   conversation_id="cm"))
        for _ in range(3):
            eng.step()                      # admitted, matched, decoding
        assert h2.result is None            # still in flight
        eng.drop_conversation("cm")         # delete mid-turn
        eng.run_until_idle()
        assert h2.done
        assert eng.get_stats()["prefix_cache"]["pages"] == 0
        assert eng.allocator.used() == 0

    def test_conversation_delete_invalidates(self):
        eng = make_echo_engine(prefix_cache=PrefixCacheConfig(enabled=True))
        h = eng.submit(GenRequest(id="a", prompt="delete me soon",
                                  conversation_id="cd"))
        eng.run_until_idle()
        assert h.result.finish_reason in ("eos", "length")
        st = eng.get_stats()["prefix_cache"]
        assert st["pages"] > 0
        eng.drop_conversation("cd")
        st = eng.get_stats()["prefix_cache"]
        assert st["pages"] == 0                      # exclusive path pruned
        assert eng.allocator.used() == 0             # every ref released

    def test_pool_pressure_evicts_tree_not_inflight(self):
        """Pool exhaustion sheds zero-ref tree leaves; pages matched by
        an in-flight sequence are pinned and survive."""
        eng = make_echo_engine(
            num_pages=17,                            # 16 allocatable
            prefix_cache=PrefixCacheConfig(enabled=True))
        # Publish a prefix, then drop its conversation pin so only the
        # tree holds it.
        h = eng.submit(GenRequest(id="a", prompt="x" * 24,
                                  conversation_id="c1"))
        eng.run_until_idle()
        assert h.result.finish_reason in ("eos", "length")
        eng.touch_conversation("c1")
        # Second request fills the rest of the pool → pressure must
        # reclaim the conversation pin and/or tree pages, not deadlock.
        h2 = eng.submit(GenRequest(id="b", prompt="y" * 40,
                                   max_new_tokens=4))
        eng.run_until_idle()
        assert h2.result.finish_reason in ("eos", "length")

    def test_handle_recorded_in_state_manager(self):
        from llmq_tpu.conversation.state_manager import StateManager
        from llmq_tpu.core.config import ConversationConfig

        sm = StateManager(ConversationConfig(cleanup_interval=0))
        eng = make_echo_engine(prefix_cache=PrefixCacheConfig(enabled=True))
        eng.attach_conversation_manager(sm)
        sm.create(user_id="u", conversation_id="ch")
        h = eng.submit(GenRequest(id="a", prompt="handled prompt",
                                  conversation_id="ch"))
        eng.run_until_idle()
        assert h.result.finish_reason in ("eos", "length")
        handle = sm.prefix_handle("ch")
        assert handle is not None
        assert handle["length"] > 0 and handle["pages"] > 0

    def test_sweep_with_eviction_pressure_stays_consistent(self):
        """Randomized soak under a small pool: conversations, shared
        prompts, cancellations — at idle every page ref balances
        (used == pinned conversations + tree-only pages)."""
        import random

        rng = random.Random(7)
        eng = make_echo_engine(
            num_pages=33,
            prefix_cache=PrefixCacheConfig(enabled=True,
                                           max_cached_pages=8))
        prompts = ["common preamble " + str(i % 3) + " x" * rng.randrange(12)
                   for i in range(30)]
        handles = []
        for i, p in enumerate(prompts):
            conv = f"c{rng.randrange(5)}" if rng.random() < 0.5 else ""
            h = eng.submit(GenRequest(id=f"s{i}", prompt=p,
                                      conversation_id=conv,
                                      priority=rng.choice(list(Priority)),
                                      max_new_tokens=rng.randrange(1, 6)))
            handles.append(h)
            for _ in range(rng.randrange(3)):
                eng.step()
            if rng.random() < 0.1:
                rng.choice(handles).cancel()
        eng.run_until_idle()
        assert all(h.done for h in handles)
        st = eng.get_stats()
        assert st["prefix_cache"]["pages"] <= 8      # capacity respected
        # Every page still out of the pool is attributable: pinned
        # conversation KV or tree-cached (shared refs collapse — used
        # counts physical pages).
        for cid in list(eng.cached_conversations()):
            eng.drop_conversation(cid)
        eng._prefix_cache.invalidate_all()
        assert eng.allocator.used() == 0


# -- real-engine (CPU-mode JAX) acceptance -------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    from llmq_tpu.models.llama import init_params, llama3_tiny

    cfg = llama3_tiny(dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                      ffn_dim=128, vocab_size=512, max_seq_len=256)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def run_two_turns(cfg, params, prefix_cache, cache_dtype=None):
    tok = ByteTokenizer()
    ex = JaxExecutor(cfg, params, batch_size=2, page_size=8, num_pages=64,
                     prefill_buckets=[16, 64], eos_id=tok.eos_id,
                     chunk_size=4, cache_dtype=cache_dtype)
    eng = InferenceEngine(ex, tok, enable_metrics=False,
                          max_decode_steps=12, prefix_cache=prefix_cache)
    h1 = eng.submit(GenRequest(id="t1", prompt="the quick brown fox",
                               conversation_id="c", max_new_tokens=10))
    eng.run_until_idle()
    h2 = eng.submit(GenRequest(id="t2", prompt=" jumps over",
                               conversation_id="c", max_new_tokens=10))
    eng.run_until_idle()
    h3 = eng.submit(GenRequest(id="t3", prompt="the quick brown fox",
                               conversation_id="d", max_new_tokens=10))
    eng.run_until_idle()
    return eng, (h1, h2, h3)


class TestJaxAcceptance:
    def test_two_turn_replay_fewer_prefill_tokens_same_tokens(self,
                                                              tiny_model):
        cfg, params = tiny_model
        eng_on, on = run_two_turns(cfg, params,
                                   PrefixCacheConfig(enabled=True))
        eng_off, off = run_two_turns(cfg, params, None)
        # Turn 2 starts from turn 1's committed pages: strictly fewer
        # tokens prefilled than its full history (the cached prefix),
        # observable through the cached_prefill_tokens metric.
        assert eng_on.cached_prefill_tokens_total > 0
        assert on[1].result.cached_tokens > 0
        # Cross-conversation radix hit (same prompt, different conv):
        assert on[2].result.cached_tokens > 0
        assert off[2].result.cached_tokens == 0
        # Decode output must match the cache-off path exactly (greedy).
        for h_on, h_off in zip(on, off):
            assert h_on.result.tokens == h_off.result.tokens
        # Off-path engine shows no cache movement at all.
        assert eng_off.prefix_hits == 0 and eng_off.prefix_misses == 0

    def test_int8_kv_scale_pages_shared(self, tiny_model):
        """int8-KV path: per-page quantization scales live in pools
        indexed by the same page id as the KV — a radix-shared page
        shares its scales by construction, and decode through shared
        int8 pages matches the cache-off int8 run."""
        cfg, params = tiny_model
        import dataclasses
        cfg = dataclasses.replace(cfg, pallas=False)
        eng_on, on = run_two_turns(cfg, params,
                                   PrefixCacheConfig(enabled=True),
                                   cache_dtype=jnp.int8)
        eng_off, off = run_two_turns(cfg, params, None,
                                     cache_dtype=jnp.int8)
        assert set(eng_on.executor.cache) == {"k", "v", "k_scale",
                                              "v_scale"}
        assert on[1].result.cached_tokens > 0
        assert on[2].result.cached_tokens > 0       # radix share, int8
        for h_on, h_off in zip(on, off):
            assert h_on.result.tokens == h_off.result.tokens


# -- CPU-mode bench smoke (CI satellite) ---------------------------------------


class TestBenchSmoke:
    def test_two_turn_replay_hit_rate_positive(self, tiny_model):
        """The CI smoke: a two-turn conversation replay through the real
        engine must report prefix_cache_hit_rate > 0."""
        cfg, params = tiny_model
        eng, handles = run_two_turns(cfg, params,
                                     PrefixCacheConfig(enabled=True))
        st = eng.get_stats()["prefix_cache"]
        assert st["admission_hit_rate"] > 0
        assert st["cached_prefill_tokens"] > 0


# -- scheduler seam ------------------------------------------------------------


class TestCacheAwareScheduling:
    def test_tokens_discounted_by_estimator(self):
        from llmq_tpu.scheduling.resource_scheduler import (
            Resource, ResourceRequest, ResourceScheduler, ResourceType)

        sched = ResourceScheduler()
        sched.register_resource(Resource(
            id="r1", capabilities={"tpu"},
            capacity={ResourceType.TOKENS: 100.0}))
        # Without the estimator a 160-token request cannot fit.
        req = ResourceRequest(amounts={ResourceType.TOKENS: 160.0},
                              metadata={"conversation_id": "c",
                                        "prompt_tokens": 160})
        assert sched._try_allocate(req) is None
        # With 75% of the context expected cached, only 40 are charged.
        sched.set_prefill_estimator(lambda md: (120, 40))
        alloc = sched._try_allocate(req)
        assert alloc is not None
        r = sched.get_resource("r1")
        assert r.used[ResourceType.TOKENS] == pytest.approx(40.0)
        # Release refunds exactly what was charged.
        sched.release_allocation(alloc.id, alloc.token)
        assert r.used[ResourceType.TOKENS] == pytest.approx(0.0)

    def test_zero_information_estimate_charges_raw(self):
        """An estimator answering (anything, 0) — e.g. metadata without
        a prompt size — must not collapse the charge to ~1 token and
        disable admission control."""
        from llmq_tpu.scheduling.resource_scheduler import (
            Resource, ResourceRequest, ResourceScheduler, ResourceType)

        sched = ResourceScheduler()
        sched.register_resource(Resource(
            id="r1", capabilities=set(),
            capacity={ResourceType.TOKENS: 100.0}))
        sched.set_prefill_estimator(lambda md: (0, 0))
        assert sched._try_allocate(ResourceRequest(
            amounts={ResourceType.TOKENS: 160.0})) is None
        sched.set_prefill_estimator(lambda md: (500, 0))
        assert sched._try_allocate(ResourceRequest(
            amounts={ResourceType.TOKENS: 160.0})) is None

    def test_estimator_failure_falls_back_to_raw(self):
        from llmq_tpu.scheduling.resource_scheduler import (
            Resource, ResourceRequest, ResourceScheduler, ResourceType)

        sched = ResourceScheduler()
        sched.register_resource(Resource(
            id="r1", capabilities=set(),
            capacity={ResourceType.TOKENS: 100.0}))
        sched.set_prefill_estimator(
            lambda md: (_ for _ in ()).throw(RuntimeError("boom")))
        req = ResourceRequest(amounts={ResourceType.TOKENS: 60.0})
        alloc = sched._try_allocate(req)
        assert alloc is not None
        r = sched.get_resource("r1")
        assert r.used[ResourceType.TOKENS] == pytest.approx(60.0)

    def test_engine_prefill_estimate(self):
        eng = make_echo_engine(prefix_cache=PrefixCacheConfig(enabled=True))
        h = eng.submit(GenRequest(id="a", prompt="warm this conv up",
                                  conversation_id="ce"))
        eng.run_until_idle()
        assert h.result.finish_reason in ("eos", "length")
        cached, new = eng.prefill_estimate("ce", 10)
        assert cached > 0 and new == 10
        assert eng.prefill_estimate("missing", 10) == (0, 10)

    def test_prefill_estimate_uses_handle_after_pin_expiry(self,
                                                           fake_clock):
        """With the pin reclaimed, the estimate falls back to the
        conversation service's recorded handle (full blocks only) —
        the radix tree still serves those blocks."""
        from llmq_tpu.conversation.state_manager import StateManager
        from llmq_tpu.core.config import ConversationConfig

        sm = StateManager(ConversationConfig(cleanup_interval=0))
        eng = make_echo_engine(
            prefix_cache=PrefixCacheConfig(enabled=True),
            kv_pin_ttl=5.0, clock=fake_clock)
        eng.attach_conversation_manager(sm)
        sm.create(user_id="u", conversation_id="ch")
        h = eng.submit(GenRequest(id="a", prompt="persistent handle case",
                                  conversation_id="ch"))
        eng.run_until_idle()
        assert h.result.finish_reason in ("eos", "length")
        fake_clock.advance(10.0)
        eng.step()                          # pin expires
        cached, new = eng.prefill_estimate("ch", 7)
        handle = sm.prefix_handle("ch")
        ps = eng.spec.page_size
        assert cached == (handle["length"] // ps) * ps > 0
        assert new == 7
