"""The latent decode and write kernels (``ops/pallas/latent_decode.py``)
and the grouped product's kernel (``ops/moe.py``) in interpret mode
against plain ``numpy``, at the edges of pages and of the batch: a
token in the last slot of its last page, one live row among dead ones,
a dead row between live ones (so a row's first chunk is fetched cold,
not prefetched by its predecessor), contexts that end on a chunk's and
on a page's boundary, the block table's full width.

Interpret mode checks the arithmetic and the schedule's bookkeeping
(every DMA started is waited for, slot parity across rows); what the
TPU's compiler refuses is ``tests/test_tpu_compile.py``'s business."""

import numpy as np
import pytest

import jax.numpy as jnp

from llmq_tpu.ops import moe
from llmq_tpu.ops.pallas.latent_decode import (
    WRITE_AHEAD, latent_decode_attention_pallas, latent_write_pallas,
    pages_per_chunk, tile_rows, write_rows)

L, PS, W, RANK, H = 2, 16, 256, 128, 4
MP = 40                     # 640 tokens a row: more than one 512-token chunk


def pool_and_tables(lens, rng):
    n_pages = [-(-int(n) // PS) for n in lens]
    P = 1 + sum(n_pages)
    pool = rng.standard_normal((L, P, PS, W)).astype(np.float32)
    bt, nxt = np.zeros((len(lens), MP), np.int32), 1
    for b, k in enumerate(n_pages):
        bt[b, :k] = rng.permutation(np.arange(nxt, nxt + k))
        nxt += k
    return pool, bt


def attend(q, pool, bt, n, layer):
    rows = np.concatenate([pool[layer, p] for p in bt])[:n]
    s = q @ rows.T
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ rows[:, :RANK]


@pytest.mark.parametrize("lens", [
    [PS * MP, 0, 0, 0, 0, 0, 0, 0],            # last slot of the last page
    [0, 0, 0, 37, 0, 0, 0, 0],                 # one live row in the batch
    [512, 0, 513, 1, 0, PS, PS + 1, 511],      # dead rows between, edges
    [0] * 8,                                   # nothing lives
], ids=["last-slot-of-last-page", "one-live-row", "edges-and-dead-rows",
        "all-dead"])
def test_latent_decode_kernel_at_the_edges(lens):
    assert pages_per_chunk(PS, MP) * PS == 512
    rng = np.random.default_rng(len(lens) + sum(lens))
    pool, bt = pool_and_tables(lens, rng)
    q = (rng.standard_normal((len(lens), H, W)) * 0.2).astype(np.float32)
    for layer in range(L):
        out = np.asarray(latent_decode_attention_pallas(
            jnp.asarray(q), jnp.asarray(pool), jnp.asarray(bt),
            jnp.asarray(lens, jnp.int32), layer, rank=RANK, interpret=True))
        assert out.shape == (len(lens), H, RANK) and np.isfinite(out).all()
        for b, n in enumerate(lens):
            if n == 0:
                assert not out[b].any()
            else:
                np.testing.assert_allclose(
                    out[b], attend(q[b], pool, bt[b], n, layer), atol=2e-5)


def test_latent_decode_kernel_ignores_what_lies_past_a_row_s_end():
    """Stale rows behind ``seq_len`` in a live page, NaN among them,
    never reach the output."""
    rng = np.random.default_rng(0)
    lens = [PS + 3, 5]
    pool, bt = pool_and_tables(lens, rng)
    pool[:, bt[0, 1], 3:] = np.nan
    pool[:, bt[1, 0], 5:] = np.inf
    q = (rng.standard_normal((2, H, W)) * 0.2).astype(np.float32)
    out = np.asarray(latent_decode_attention_pallas(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(bt),
        jnp.asarray(lens, jnp.int32), 1, rank=RANK, interpret=True))
    clean = np.nan_to_num(pool, nan=0.0, posinf=0.0)
    for b, n in enumerate(lens):
        np.testing.assert_allclose(out[b], attend(q[b], clean, bt[b], n, 1),
                                   atol=2e-5)


@pytest.mark.parametrize("n_rows", [1, 5, 8, 11])
def test_latent_write_kernel(n_rows):
    """One row a sequence into its page's slot, in place: the last slot
    of a page, slot 0, and rows that are not live all on page 0."""
    rng = np.random.default_rng(n_rows)
    P = 2 * n_rows + 2
    pool = rng.standard_normal((L, P, PS, W)).astype(np.float32)
    new = rng.standard_normal((n_rows, W)).astype(np.float32)
    page_of = 1 + rng.permutation(P - 1)[:n_rows]
    slot_of = rng.integers(0, PS, n_rows)
    slot_of[0], slot_of[-1] = PS - 1, 0
    dead = np.arange(n_rows) % 4 == 3
    page_of[dead] = 0
    out = np.asarray(latent_write_pallas(
        jnp.asarray(pool), jnp.asarray(new), jnp.asarray(page_of),
        jnp.asarray(slot_of), 1, interpret=True))
    want = pool.copy()
    for i in np.flatnonzero(~dead):
        want[1, page_of[i], slot_of[i]] = new[i]
    assert np.array_equal(out[:, 1:], want[:, 1:])      # page 0 is trash
    assert np.array_equal(out[0], pool[0])


R = tile_rows(np.float32)    # rows of a sublane tile: two a page here

#: name -> (slots of the rows, rows that are not live, layer, dtype,
#: page size). A row that is not live aims at page 0, each at a tile of
#: its own (two in one tile of nobody's page may lose each other: the
#: case after these).
WRITE_CASES = {
    "tile-first-row": ([0], [], 0, np.float32, PS),
    "tile-last-row": ([R - 1], [], 0, np.float32, PS),
    "next-tile-first-row": ([R], [], 0, np.float32, PS),
    "page-last-row": ([PS - 1], [], 0, np.float32, PS),
    "both-sides-of-a-tile-edge": ([R - 1, R, 0, PS - 1], [], 0,
                                  np.float32, PS),
    "five-rows": ([3, R, 1, PS - 1, R - 1], [], 0, np.float32, PS),
    "thirteen-rows-layer-1": (list(range(13)), [], 1, np.float32, PS),
    "dead-rows-beside-live": ([0, 3, R - 1, R + 1, PS - 1, 5, R],
                              [1, 6], 0, np.float32, PS),
    "all-dead-but-one": ([R, 2, R + 2], [1, 2], 1, np.float32, PS),
    "more-rows-than-scratch-slots": (
        [(7 * i) % PS for i in range(4 * WRITE_AHEAD + 5)], [9], 1,
        np.float32, PS),
    "bf16-sixteen-row-tiles": ([0, 15, 16, 31, 17, 8], [5], 1,
                               jnp.bfloat16, 32),
    "bf16-page-of-no-whole-tile": ([0, 15, 16, 23, 7], [], 1,
                                   jnp.bfloat16, 24),
}


@pytest.mark.parametrize("case", sorted(WRITE_CASES))
def test_latent_write_kernel_holds_the_whole_pool(case):
    """The WHOLE pool after the write is bit for bit what ``pool.at[l,
    page_of, slot_of].set(new)`` stores: the new row in its slot, every
    other row of its tile and of its page, every other page and every
    other layer as they were."""
    slots, dead, layer, dtype, ps = WRITE_CASES[case]
    n = len(slots)
    rng = np.random.default_rng(n + layer)
    P = n + 3
    pool = jnp.asarray(rng.standard_normal((L, P, ps, W)), dtype)
    new = jnp.asarray(rng.standard_normal((n, W)), dtype)
    page_of = 1 + rng.permutation(P - 1)[:n]
    page_of[dead] = 0
    slot_of = np.asarray(slots)
    tiles = slot_of[dead] // tile_rows(dtype)
    assert len(set(tiles)) == len(tiles)
    assert write_rows(pool) == (ps if case.endswith("no-whole-tile")
                                else tile_rows(dtype))
    out = latent_write_pallas(pool, new, jnp.asarray(page_of),
                              jnp.asarray(slot_of), layer, interpret=True)
    want = pool.at[layer, page_of, slot_of].set(new)
    assert out.dtype == pool.dtype
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(want, np.float32))
    assert not np.array_equal(np.asarray(out, np.float32),
                              np.asarray(pool, np.float32))


def test_latent_write_kernel_page_0_is_nobody_s():
    """Rows that are not live may share a tile of page 0: each of its
    rows then holds what it held or a row aimed at it, and nothing else
    of the pool moves."""
    rng = np.random.default_rng(7)
    n, P = 12, 8
    pool = rng.standard_normal((L, P, PS, W)).astype(np.float32)
    new = rng.standard_normal((n, W)).astype(np.float32)
    page_of = np.zeros(n, np.int32)
    page_of[[2, 9]] = [5, 3]
    slot_of = rng.integers(0, PS, n)
    out = np.asarray(latent_write_pallas(
        jnp.asarray(pool), jnp.asarray(new), jnp.asarray(page_of),
        jnp.asarray(slot_of), 1, interpret=True))
    want = pool.copy()
    for i in (2, 9):
        want[1, page_of[i], slot_of[i]] = new[i]
    assert np.array_equal(out[:, 1:], want[:, 1:])
    assert np.array_equal(out[0], pool[0])
    for s in range(PS):
        aimed = [new[i] for i in range(n) if page_of[i] == 0
                 and slot_of[i] == s]
        assert any(np.array_equal(out[1, 0, s], r)
                   for r in [pool[1, 0, s]] + aimed)


@pytest.mark.parametrize("dtype,ps,plan", [
    (np.float32, 16, "tile_rows=8"), (jnp.bfloat16, 128, "tile_rows=16"),
    (jnp.bfloat16, 24, "page_rows=24")],
    ids=["float32", "bf16-served-page", "bf16-no-whole-tile"])
def test_the_routes_line_names_the_write_s_plan(monkeypatch, dtype, ps, plan):
    """What says the tile move is in force: ``decode_write`` of the line
    the engine logs at start names the rows of its page a row's write
    moves, read off the pool's dtype and page size."""
    from types import SimpleNamespace

    from llmq_tpu.models import latent

    class Dims(latent.LatentDims, SimpleNamespace):
        pass

    cfg = Dims(kv_lora_rank=RANK, qk_rope_head_dim=64)
    pool = {"ckv": jnp.zeros((L, 2, ps, cfg.latent_width), dtype)}
    geometry = dict(batch=4, page_size=ps, max_pages=4, decode=True)
    monkeypatch.setenv("LLMQ_PALLAS", "interpret")
    assert latent.routes(cfg, pool, **geometry)["decode_write"] == (
        f"pallas-interpret:_latent_write_kernel({plan})")
    monkeypatch.setenv("LLMQ_PALLAS", "0")
    assert latent.routes(cfg, None, **geometry)["decode_write"] == "xla"


@pytest.mark.parametrize("rows", [3, 130])
def test_grouped_product_kernel_against_ragged_dot(rows):
    """``moe_grouped_matmul_pallas`` (megablox, rows padded to its tile)
    gives ``jax.lax.ragged_dot``'s rows for every group, empty groups
    and a tail behind the last group included."""
    import jax
    rng = np.random.default_rng(rows)
    E, K, N = 6, 128, 256
    counts = rng.multinomial(rows - 1, [0.3, 0, 0.2, 0.5, 0, 0])
    xs = rng.standard_normal((rows, K)).astype(np.float32)
    w = (rng.standard_normal((E, K, N)) * 0.1).astype(np.float32)
    got = moe.moe_grouped_matmul_pallas(
        jnp.asarray(xs), jnp.asarray(w), jnp.asarray(counts, jnp.int32),
        interpret=True)
    want = jax.lax.ragged_dot(jnp.asarray(xs), jnp.asarray(w),
                              jnp.asarray(counts, jnp.int32))
    np.testing.assert_allclose(np.asarray(got)[:rows - 1],
                               np.asarray(want)[:rows - 1], atol=1e-4)
