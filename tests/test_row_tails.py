"""The prefix cache adopts a hit for a WINDOW family
(docs/prefix_cache.md "Tails"): ``models/afmoe`` and ``models/mellum``
keep a sliding layer's K and V as a ring slab a batch row, and the last
W tokens' K and V before a page boundary rebuild that row — so a radix
node may carry a tail, a match ends at the deepest one, and the engine
copies it into the new row's ring instead of declining. Through the
engine at a tiny size, both families: a prompt served after its prefix
was served gives what the same prompt gives served cold; the ring has
wrapped before the tail is taken; a family whose state is a recurrence
still declines and is still counted; and a declined pinned conversation
prefills its history ONCE."""

import pytest

import jax
import jax.numpy as jnp

from llmq_tpu.core.config import MixedBatchConfig, PrefixCacheConfig
from llmq_tpu.engine.engine import GenRequest, InferenceEngine
from llmq_tpu.engine.executor import JaxExecutor
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models import family_of, get_config

PAGE = 8
#: 107 bytes: passes the tiny window (24) and the ring (8 pages of 8).
SHARED = ("the same hundred-odd characters of a project context, long "
          "enough to pass the window and wrap the ring : ")
#: Another stream of the same length, for the row in between.
OTHER = "".join(chr(97 + (7 * i) % 26) for i in range(120))
ON = PrefixCacheConfig(enabled=True, row_tail_slots=6)


@pytest.fixture(scope="module", params=["mellum-tiny", "afmoe-tiny"])
def tiny(request):
    cfg = get_config(request.param, dtype=jnp.float32, max_seq_len=256)
    return cfg, family_of(cfg).init_params(jax.random.PRNGKey(41), cfg)


def make_engine(tiny, prefix_cache=None, batch=1, buckets=(16, 32)):
    cfg, params = tiny
    tok = ByteTokenizer()
    slots = prefix_cache.row_tail_slots if prefix_cache is not None else 0
    ex = JaxExecutor(cfg, params, batch_size=batch, page_size=PAGE,
                     num_pages=200, prefill_buckets=list(buckets),
                     eos_id=tok.eos_id, chunk_size=4,
                     mixed_prefill_slices=2, mixed_slice_tokens=8,
                     row_tail_slots=slots)
    return InferenceEngine(
        ex, tok, enable_metrics=False, max_decode_steps=64,
        prefix_cache=prefix_cache,
        mixed_batch=MixedBatchConfig(enabled=True, prefill_token_budget=16,
                                     max_slices=2)), ex


def generate(eng, rid, prompt, n=12, **kw):
    h = eng.submit(GenRequest(id=rid, prompt=prompt, max_new_tokens=n,
                              temperature=0.0, **kw))
    eng.run_until_idle()
    assert h.done
    return h.result


def test_a_prompt_served_after_its_prefix_is_the_prompt_served_cold(tiny):
    """ONE batch row: a serves the shared context, x overwrites the
    row's ring with another stream, then b — which shares the context —
    adopts: the full layers' pages by reference, the sliding layers'
    K and V out of a tail taken at a stride boundary (32 tokens) on a's
    way through its prefill, after the ring had wrapped (96 > W + the
    slack). b's tokens are those of b served by an engine that never
    saw the context; with the import left out they are not."""
    cold, _ = make_engine(tiny)
    want = generate(cold, "b", SHARED + "second question")

    def served(broken=False):
        eng, ex = make_engine(tiny, ON)
        if broken:
            ex.import_row_tail = lambda *a: None
        a = generate(eng, "a", SHARED + "first question")
        generate(eng, "x", OTHER)
        b = generate(eng, "b", SHARED + "second question")
        assert a.cached_tokens == 0
        return b, eng.get_stats()

    b, stats = served()
    tail = stats["row_state"]
    assert tail["tail_stride"] == 32 and tail["tail_slots"] == 6
    # a's blocks match to 104 (13 pages); the deepest tail on that path
    # is the one its prefill took at 96: 8 tokens are given up
    assert b.cached_tokens == 96 and b.tokens == want.tokens
    assert tail["adopted"] == 1 and tail["declined"]["prefix"] == 0
    assert (tail["matched_tokens"], tail["match_cut_tokens"]) == (104, 8)
    # x matched nothing: nothing of its walk was cut
    assert tail["tails_taken"] >= 3 and tail["tail_slots_live"] <= 6
    assert stats["prefix_cache"]["tails"] == tail["tail_slots_live"]
    assert stats["prefix_cache"]["admission_hits"] == 1
    broken, _ = served(broken=True)
    assert broken.cached_tokens == 96 and broken.tokens != want.tokens


def test_the_next_turn_adopts_the_stream_its_answer_included(tiny):
    """A conversation's second turn, ``history_text`` beside it as the
    benchmark's sessions send it: the pin is declined (pages without the
    row), the remembered stream — prompt AND answer — goes into
    ``carry``, meets the radix match that the first turn published with
    a tail at its page-aligned end, and only the rest is prefilled. The
    history is prefilled ONCE: the prompt is the turn's own 13 tokens,
    not the history's text again on top of the carry."""
    first, second = SHARED + "turn one", " and turn two"
    eng, _ = make_engine(tiny, ON)
    one = generate(eng, "t1", first, conversation_id="c")
    two = generate(eng, "t2", second, conversation_id="c",
                   history_text=first)
    stream = len(first) + 12            # the answer's 12 tokens included
    assert one.cached_tokens == 0 and two.prompt_tokens == len(second)
    assert two.cached_tokens == stream // PAGE * PAGE
    st = eng.get_stats()
    assert st["row_state"]["declined"]["conversation"] == 1
    assert st["row_state"]["adopted"] == 1
    assert st["row_state"]["match_cut_tokens"] == 0
    # ... and gives what the whole stream gives served cold
    cold, _ = make_engine(tiny)
    c1 = generate(cold, "t1", first, conversation_id="c")
    c2 = generate(cold, "t2", second, conversation_id="c",
                  history_text=first)
    assert (c1.tokens, c2.tokens) == (one.tokens, two.tokens)
    assert c2.prompt_tokens == len(second) and c2.cached_tokens == 0


def test_without_slots_a_hit_is_declined_as_before(tiny):
    """``row_tail_slots`` 0, the default: no pool, no programs, and a
    match is given back whole (its page references too) and counted."""
    eng, ex = make_engine(tiny, PrefixCacheConfig(enabled=True))
    assert ex.row_tail is None and ex.row_tails is None
    generate(eng, "a", SHARED + "first question")
    free = eng.allocator.available()
    b = generate(eng, "b", SHARED + "second question")
    st = eng.get_stats()["row_state"]
    assert b.cached_tokens == 0 and st["declined"]["prefix"] == 1
    assert "adopted" not in st
    # b's own blocks were published over a's and freed; the declined
    # match left no reference behind
    eng._prefix_cache.invalidate_all()
    assert eng.allocator.available() == eng.allocator.total > free


def test_a_tail_waits_for_its_node_and_is_freed_without_one(tiny):
    """A tail taken on the way through a prefill hangs on its sequence
    until the stream is published; a sequence that ends otherwise (here:
    cancelled) gives its slots back."""
    eng, _ = make_engine(tiny, ON)
    h = eng.submit(GenRequest(id="a", prompt=SHARED + "cancelled",
                              max_new_tokens=40, temperature=0.0))
    for _ in range(200):
        eng.step()
        if h.result is None and eng.get_stats()["row_state"][
                "tails_taken"] >= 2:
            break
    assert eng._prefix_cache.tail_slots_in_use >= 2
    assert eng.get_stats()["prefix_cache"]["tails"] == 0    # no node yet
    h.cancel()
    eng.run_until_idle()
    assert eng._prefix_cache.tail_slots_in_use == 0


def test_a_recurrent_family_still_declines_and_is_still_counted():
    """``granitemoehybrid``: its row state is a recurrence, no window of
    K/V rebuilds it, the family names no tail — slots asked for or not,
    the hit is declined and counted as it was."""
    cfg = get_config("granite4h-tiny", dtype=jnp.float32, max_seq_len=256)
    fam = family_of(cfg)
    assert not hasattr(fam, "row_tail")
    eng, ex = make_engine((cfg, fam.init_params(jax.random.PRNGKey(1), cfg)),
                          ON, buckets=(16, 64))
    assert ex.row_tail is None
    generate(eng, "a", SHARED + "first question")
    b = generate(eng, "b", SHARED + "second question")
    st = eng.get_stats()["row_state"]
    assert b.cached_tokens == 0
    assert st["declined"] == {"prefix": 1, "conversation": 0, "tiering": 0,
                              "disagg": 0}
    assert "adopted" not in st and "tail_slots" not in st


def test_a_declined_pin_prefills_the_history_once():
    """The repair in the branch the adoption rewrote: a second turn that
    carries ``history_text`` and is declined its pinned conversation
    used to prefill the history TWICE — the remembered stream in
    ``carry`` and ``history_text`` again before the prompt. Counted in
    prefilled tokens, for a family that declines everything."""
    cfg = get_config("granite4h-tiny", dtype=jnp.float32, max_seq_len=256)
    fam = family_of(cfg)
    eng, _ = make_engine((cfg, fam.init_params(jax.random.PRNGKey(1), cfg)),
                         buckets=(16, 64))
    first, second = "first turn of a conversation", " and a second"
    one = generate(eng, "t1", first, conversation_id="c")
    prefilled = []
    note = eng._note_prefill_dispatch
    eng._note_prefill_dispatch = lambda n, *a, **kw: (
        prefilled.append(n), note(n, *a, **kw))[1]
    two = generate(eng, "t2", second, conversation_id="c",
                   history_text=first)
    assert two.prompt_tokens == len(second)
    assert eng.get_stats()["row_state"]["declined"]["conversation"] == 1
    # the stream (prompt and answer) and the turn's own tokens, once
    stream = len(first) + len(one.tokens)
    assert sum(prefilled) == stream + len(second)
