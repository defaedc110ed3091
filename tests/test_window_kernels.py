"""The window start of the two GQA kernels (``ops/pallas/fused_decode``,
``ops/pallas/prefill_attention``), interpret mode on the CPU.

A sliding-window layer's call passes a static ``window``: a query sees
its last ``window`` keys, itself counted, and the kernel visits no
chunk that lies wholly before them. Held here to the pure masked form
(``ops/attention``'s fall-backs, which take the same ``window``): for
contexts below, at and far beyond the window, rows of unlike lengths
in one tile, a window edge inside a page and inside a chunk. Every page
of a chunk that lies wholly before a row's window is POISONED with NaN
before the kernel runs — a visit there would reach the result through
``p * v`` whatever the mask — so "equal to the masked form" also means
"never went there", and ``decode_work`` (the schedule counted on the
host) is held to the same count. With ``window=None`` both kernels
trace to the program they were before there was a window: the jaxpr's
digest is the one recorded at the parent commit.
"""

import hashlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from llmq_tpu.ops import attention  # noqa: E402
from llmq_tpu.ops.pallas.fused_decode import (  # noqa: E402
    _tile_plan, decode_work, fused_decode_attention_pallas,
    fused_decode_attention_q8_pallas, window_chunks)
from llmq_tpu.ops.pallas.prefill_attention import (  # noqa: E402
    paged_prefill_attention_pallas, paged_prefill_attention_q8_pallas,
    prefill_tile_plan)

H, G, D, PS, L = 8, 2, 64, 16, 2
GD = G * D


@pytest.fixture(autouse=True)
def _pure_reference(monkeypatch):
    monkeypatch.setenv("LLMQ_PALLAS", "0")


def _pools(rng, pages):
    return [jnp.asarray(rng.standard_normal((L, pages, PS, GD)),
                        jnp.bfloat16) for _ in range(2)]


#: (contexts of one or two 8-row tiles, window, pages a chunk)
DECODE_CASES = {
    "below-at-and-beyond": ([1, 16, 40, 41, 100, 200, 256, 7], 40, 2),
    "a-dead-row-one-page-chunks": ([1, 16, 40, 41, 100, 200, 256, 0], 40, 1),
    "edge-inside-a-page-and-a-chunk": (
        [250, 251, 252, 253, 254, 255, 256, 249], 33, 4),
    "two-tiles-mostly-dead": ([5] + [0] * 7 + [200, 200] + [0] * 5 + [256],
                              64, 2),
    "window-of-one-page": ([16, 17, 31, 32, 33, 129, 255, 256], 16, 2),
    # Chunk 1 (positions 32-63) holds all eight rows — a FULL step: four
    # see their whole context and end there (merge and tail inside it),
    # one passes the window inside chunk 0, three start at chunk 1.
    "a-full-step-with-rows-on-both-sides-of-the-window": (
        [33, 36, 40, 64, 70, 72, 90, 95], 40, 2),
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_windowed_decode_is_the_masked_form_and_visits_no_chunk_before_it(
        case):
    seq_lens, W, ppc = DECODE_CASES[case]
    rng = np.random.default_rng(sorted(DECODE_CASES).index(case))
    seq = np.asarray(seq_lens, np.int32)
    B, mp, S = len(seq), 16, ppc * PS
    k, v = _pools(rng, 1 + B * mp)
    bt = 1 + np.arange(B * mp, dtype=np.int32).reshape(B, mp)
    pos = np.maximum(seq - 1, 0)
    page_of = np.where(seq > 0, bt[np.arange(B), pos // PS], 0)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
    kn, vn = (jnp.asarray(rng.standard_normal((B, G, D)), jnp.bfloat16)
              for _ in range(2))
    poisoned = [np.array(x, np.float32) for x in (k, v)]
    wholly_before = 0
    for b in range(B):
        first = max(int(seq[b]) - W, 0) // S
        wholly_before += first
        for x in poisoned:
            x[:, bt[b, :first * ppc]] = np.nan
    out, (k2, v2) = fused_decode_attention_pallas(
        q, kn, vn, *(jnp.asarray(x, jnp.bfloat16) for x in poisoned),
        jnp.asarray(bt), jnp.asarray(seq), jnp.asarray(page_of, jnp.int32),
        1, pages_per_chunk=ppc, interpret=True, window=W)
    kw, vw = attention.paged_kv_write(
        k, v, kn, vn, jnp.asarray(page_of, jnp.int32),
        jnp.asarray(pos % PS, jnp.int32), 1)
    ref = attention.paged_decode_attention_pooled(
        q, kw, vw, jnp.asarray(bt), jnp.asarray(seq), 1, W)
    live = seq > 0
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(ref, np.float32)[live],
                               atol=3e-2, rtol=3e-2)
    # the write: the current token's row, in its own page, and no other
    for got, want in ((k2, kw), (v2, vw)):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        for b in np.flatnonzero(live):
            np.testing.assert_array_equal(got[1, page_of[b], pos[b] % PS],
                                          want[1, page_of[b], pos[b] % PS])
    # the schedule counted on the host: products for the rows' visible
    # chunks and no more, none of them wholly before a window
    plan = _tile_plan(B, PS, mp, GD, 2, ppc)
    steps, computed, visible, _ = decode_work(seq, plan, W)
    _, _, held, _ = decode_work(seq, plan)
    assert computed == visible == held - wholly_before
    assert window_chunks(seq, S, W) == (visible, wholly_before)
    assert steps <= decode_work(seq, plan)[0]
    if case.startswith("a-full-step"):
        assert decode_work(seq, plan, W)[3] == B    # chunk 1, and no other


def test_decode_work_without_a_window_counts_what_it_counted():
    plan = _tile_plan(8, PS, 16, GD, 2, 2)
    seq = [1, 16, 40, 41, 100, 200, 256, 0]
    # (no step of this tile is full: its last row is dead)
    assert decode_work(seq, plan) == decode_work(seq, plan, None) == (
        8, 25, 25, 0)
    assert window_chunks(seq, 32) == (25, 0)


#: (first position, tokens, window, pages a chunk, q block)
PREFILL_CASES = {
    "from-zero-inside-the-window": (0, 64, 40, 2, 0),
    "continuation-past-the-window": (100, 64, 40, 2, 16),
    "edge-inside-a-page": (300, 128, 33, 4, 32),
    "window-wider-than-the-context": (37, 64, 200, 1, 32),
    "a-block-s-first-chunk-hidden-from-its-last-rows": (256, 64, 17, 1, 64),
}


@pytest.mark.parametrize("case", sorted(PREFILL_CASES))
def test_windowed_prefill_is_the_masked_form_and_visits_no_chunk_before_it(
        case):
    start, T, W, ppc, qb = PREFILL_CASES[case]
    rng = np.random.default_rng(100 + sorted(PREFILL_CASES).index(case))
    mp, S = 32, ppc * PS
    k, v = _pools(rng, 1 + mp)
    bt = 1 + np.arange(mp, dtype=np.int32)
    q = jnp.asarray(rng.standard_normal((T, H, D)), jnp.bfloat16)
    poisoned = [np.array(x, np.float32) for x in (k, v)]
    first = max(start - W + 1, 0) // S          # of the FIRST q block
    for x in poisoned:
        x[:, bt[:first * ppc]] = np.nan
    out = paged_prefill_attention_pallas(
        q, *(jnp.asarray(x, jnp.bfloat16) for x in poisoned),
        jnp.asarray(bt), jnp.int32(start), 1, pages_per_chunk=ppc,
        q_block=qb, interpret=True, window=W)
    hist = [x[1, bt].reshape(1, mp * PS, G, D) for x in (k, v)]
    positions = (start + np.arange(T, dtype=np.int32))[None]
    ref = attention.blockwise_prefill_attention(
        q[None], *hist, jnp.asarray(positions), jnp.asarray([start + T]),
        window=W)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref[0], np.float32), atol=3e-2,
                               rtol=3e-2)
    plan = prefill_tile_plan(T, H, G, D, PS, mp, 2, q_block=qb,
                             pages_per_chunk=ppc)
    assert plan.steps(T, start, window=W) <= plan.steps(T, start)
    if start >= W + S:
        assert plan.steps(T, start, window=W) < plan.steps(T, start)


def test_the_dispatchers_pass_the_window_to_kernel_and_fall_back_alike(
        monkeypatch):
    """``paged_decode_step`` and ``dispatch_prefill_attention`` with a
    window: the kernel route (``LLMQ_PALLAS=interpret``) and the pure
    route give the same attention, and a window as wide as the table is
    no window."""
    rng = np.random.default_rng(7)
    B, mp = 8, 16
    seq = np.asarray([3, 40, 41, 90, 200, 17, 256, 64], np.int32)
    k, v = _pools(rng, 1 + B * mp)
    bt = jnp.asarray(1 + np.arange(B * mp, dtype=np.int32).reshape(B, mp))
    pos = seq - 1
    page_of = jnp.asarray(np.asarray(bt)[np.arange(B), pos // PS])
    slot_of = jnp.asarray(pos % PS)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
    kn, vn = (jnp.asarray(rng.standard_normal((B, G, D)), jnp.bfloat16)
              for _ in range(2))

    def decode(window):
        return np.asarray(attention.paged_decode_step(
            q, kn, vn, k, v, bt, jnp.asarray(seq), page_of, slot_of,
            jnp.int32(1), window=window)[0], np.float32)

    qp = jnp.asarray(rng.standard_normal((1, 32, H, D)), jnp.bfloat16)
    positions = jnp.asarray(120 + np.arange(32, dtype=np.int32))[None]

    def prefill(window):
        return np.asarray(attention.dispatch_prefill_attention(
            qp, k, v, bt[4:5], positions, jnp.asarray([152]), jnp.int32(1),
            window=window), np.float32)

    pure = decode(40), prefill(40), decode(None), prefill(None)
    np.testing.assert_allclose(decode(mp * PS), pure[2], atol=1e-6)
    assert np.abs(pure[0] - pure[2]).max() > 0.1       # the window shows
    monkeypatch.setenv("LLMQ_PALLAS", "interpret")
    for got, want in zip((decode(40), prefill(40), decode(None),
                          prefill(None)), pure):
        np.testing.assert_allclose(got, want, atol=3e-2, rtol=3e-2)


#: sha256[:16] of ``str(jax.make_jaxpr(kernel)(...))`` at the shapes
#: below with ``window`` not given, recorded at the parent of the PR
#: that brought the window (PR 40's tree). A jaxpr's text carries no
#: source lines. A PR that changes a kernel on purpose records the new
#: digest here and says so: the accepted families (SmolLM2, Mistral,
#: Granite) call these kernels without a window, and their cells are
#: held to what this program does. PR 43 changed the two DECODE kernels
#: on purpose (a full step's static block of products; their digests
#: are that tree's), and left the prefill kernels' as they were.
PARENT_DIGESTS = {"decode": "8ba2a49188a5b544", "prefill": "2e999255c2e7b7de",
                  "decode_q8": "80d6b928e02b8776",
                  "prefill_q8": "930a29e3d50cb305"}


@pytest.mark.parametrize("kernel", sorted(PARENT_DIGESTS))
def test_without_a_window_the_kernels_trace_to_the_parent_s_program(kernel):
    def digest(fn, *args, **kw):
        text = str(jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args))
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    z = jnp.zeros
    B, mp, P = 16, 8, 40
    bt, one = z((B, mp), jnp.int32), jnp.ones((B,), jnp.int32)
    if not kernel.endswith("q8"):
        pool = z((L, P, PS, GD), jnp.bfloat16)
        if kernel == "decode":
            row = z((B, G, D), jnp.bfloat16)
            got = digest(fused_decode_attention_pallas,
                         z((B, H, D), jnp.bfloat16), row, row, pool, pool,
                         bt, one, one, 1, interpret=True)
        else:
            got = digest(paged_prefill_attention_pallas,
                         z((64, H, D), jnp.bfloat16), pool, pool, bt[0],
                         jnp.int32(3), 1, interpret=True)
    else:
        ps, g, d, h = 128, 8, 128, 32
        pools = ((z((L, P, ps, g * d), jnp.int8),) * 2
                 + (z((L, P, g, ps), jnp.bfloat16),) * 2)
        if kernel == "decode_q8":
            row, scale = z((B, g, d), jnp.int8), z((B, g), jnp.bfloat16)
            got = digest(fused_decode_attention_q8_pallas,
                         z((B, h, d), jnp.bfloat16), row, scale, row, scale,
                         pools, bt, one, one, 1, interpret=True)
        else:
            got = digest(paged_prefill_attention_q8_pallas,
                         z((128, h, d), jnp.bfloat16), pools, bt[0],
                         jnp.int32(3), jnp.int32(100), 1, interpret=True)
    assert got == PARENT_DIGESTS[kernel]
