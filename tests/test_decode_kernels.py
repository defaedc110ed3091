"""The two decode kernels every cell runs, at the geometries served.

``fused_decode_attention_pallas`` (bf16 pools) and
``fused_decode_attention_q8_pallas`` (int8 pools with bf16 scale pools)
are the top device operation of the saturated cells (PERF.md §5). Head
layout, page size and KV type are READ from the benchmark's
configuration files (the ``served_geometry`` fixture), so each kernel is
held to the pure path at the one geometry it serves — SmolLM2: 32 heads
over 32 KV heads of 64 in 16-token pages; Mistral: 32 over 8 of 128 in
128-token pages — not at a toy one. Interpret mode: the kernel body
(DMA schedule, merge and writeback, online softmax, masking) runs on
the CPU; the batch is one 8-row tile (four where a case says so) and
the block table holds two of the plan's own chunks (wider where a case
says so). ``decode_work`` — the same schedule counted on the host — is
held to "computed = live, steps whatever the block table's width".

Since PR 43 a step in which a whole tile is live runs its products over
static rows, and the rows reach the kernel ordered by context
(``ops/attention.decode_order``): the FULL_STEP cases hold that path —
every step full, full then ragged, a merge and a zeroed tail inside a
full step — and the ORDER cases hold "the order is invisible outside
the call": each row's attention and cache write in seat order, in the
order's and through the dispatcher's ``order=``.
"""

import copy
from types import SimpleNamespace

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from llmq_tpu.ops import attention  # noqa: E402
from llmq_tpu.ops.pallas.fused_decode import (  # noqa: E402
    CHUNK_TOKENS, SCRATCH_BUDGET_BYTES, _tile_plan, decode_work,
    fused_kernel_viable)
from llmq_tpu.ops.quant import quantize_kv_rows  # noqa: E402

CONFIGS = ("smollm2-1.7b-bf16", "mistral-7b-v0.3-w8kv8")
ROWS, LAYERS = 8, 2


@pytest.fixture(autouse=True)
def _pure_reference(monkeypatch):
    """The reference dispatchers take the pure path; the kernels are
    called directly, with ``interpret=True``."""
    monkeypatch.setenv("LLMQ_PALLAS", "0")


def _geom(served_geometry, name):
    cfg, ex, q8 = served_geometry(name)
    ps = ex["page_size"]
    return SimpleNamespace(
        H=cfg.n_heads, Hkv=cfg.n_kv_heads, D=cfg.head_dim,
        GD=cfg.n_kv_heads * cfg.head_dim, ps=ps, q8=q8,
        itemsize=1 if q8 else 2,
        mp=2 * CHUNK_TOKENS // ps,           # the test's block table
        rows_full=ex["max_batch_size"], mp_full=cfg.max_seq_len // ps)


def _inputs(g, seed, seq_lens, mp=None):
    """Pools full of history, one new token a row. Every page a row
    holds a position in belongs to that row alone; the block tables'
    entries beyond (``dead``) point at spare pages no row reads, and
    page 0 is the reserved one."""
    rng = np.random.default_rng(seed)
    mp = mp or g.mp
    seq_lens = np.asarray(seq_lens, np.int32)
    rows = len(seq_lens)
    assert rows % 8 == 0 and seq_lens.max() <= mp * g.ps
    n_live = -(-seq_lens // g.ps)
    P = 1 + int(n_live.sum()) + rows
    hist = [jnp.asarray(rng.standard_normal(
        (LAYERS, P, g.ps, g.Hkv, g.D), np.float32)) for _ in range(2)]
    if g.q8:
        data, scales = zip(*(quantize_kv_rows(h) for h in hist))
        pools = (tuple(d.reshape(LAYERS, P, g.ps, g.GD) for d in data)
                 + tuple(jnp.moveaxis(s, 3, 2) for s in scales))
        new_dtype = jnp.float32              # quantised on the way in
    else:
        pools = tuple(h.reshape(LAYERS, P, g.ps, g.GD)
                      .astype(jnp.bfloat16) for h in hist)
        new_dtype = jnp.bfloat16
    ids = 1 + rng.permutation(P - 1)
    dead, ids = ids[:rows], ids[rows:]
    bt = rng.choice(dead, (rows, mp))
    for b, start in enumerate(np.cumsum(n_live) - n_live):
        bt[b, :n_live[b]] = ids[start:start + n_live[b]]
    live = seq_lens > 0
    pos = np.maximum(seq_lens - 1, 0)
    page_of = np.where(live, bt[np.arange(rows), pos // g.ps], 0)
    new = [jnp.asarray(rng.standard_normal((rows, g.Hkv, g.D))
                       * live[:, None, None], new_dtype) for _ in range(2)]
    return SimpleNamespace(
        pools=pools, bt=jnp.asarray(bt, jnp.int32), np_bt=bt, dead=dead,
        q=jnp.asarray(rng.standard_normal((rows, g.H, g.D)), jnp.bfloat16),
        kn=new[0], vn=new[1], seq_lens=jnp.asarray(seq_lens), live=live,
        page_of=jnp.asarray(page_of, jnp.int32), np_page_of=page_of,
        slot_of=jnp.asarray(pos % g.ps, jnp.int32), np_slot_of=pos % g.ps,
        layer=1)


def _kernel(g, x, pools=None, ppc=0):
    """The kernel under test, through the dispatcher's own nested jit
    (one trace a configuration and chunk width for the whole file)."""
    pools = x.pools if pools is None else pools
    if g.q8:
        kq, ks = quantize_kv_rows(x.kn)
        vq, vs = quantize_kv_rows(x.vn)
        attn, out = attention._jit_fused_decode_q8()(
            x.q, kq, ks, vq, vs, pools, x.bt, x.seq_lens, x.page_of,
            x.layer, pages_per_chunk=ppc, interpret=True)
    else:
        attn, out = attention._jit_fused_decode()(
            x.q, x.kn, x.vn, *pools, x.bt, x.seq_lens, x.page_of,
            x.layer, pages_per_chunk=ppc, interpret=True)
    return np.asarray(attn, np.float32), [np.asarray(p, np.float32)
                                          for p in out]


def _pure(g, x):
    if g.q8:
        attn, out = attention.paged_decode_step_q8(
            x.q, x.kn, x.vn, x.pools, x.bt, x.seq_lens, x.page_of,
            x.slot_of, x.layer)
    else:
        out = attention.paged_kv_write(*x.pools, x.kn, x.vn, x.page_of,
                                       x.slot_of, x.layer)
        attn = attention.paged_decode_attention_pooled(
            x.q, *out, x.bt, x.seq_lens, x.layer)
    return np.asarray(attn, np.float32), [np.asarray(p, np.float32)
                                          for p in out]


def _np_pools(x):
    return [np.asarray(p, np.float32) for p in x.pools]


def _close(attn, ref, tol=3e-2):
    np.testing.assert_allclose(attn, ref, atol=tol, rtol=tol)


def _mixed_lens(g):
    """Eight contexts from one token to the whole block table."""
    top = g.mp * g.ps
    return [1, g.ps, g.ps + 1, 2 * g.ps - 1, 3 * g.ps, top - g.ps,
            top - g.ps + 1, 7]


# -- the cases, one property each ----------------------------------------------


def _page_edges(g):
    """Contexts of 1, one short of a page edge, on it, one past it, and
    in the LAST slot of the LAST page of the block table (the row at its
    table's end that no cell reaches: PERF.md §7)."""
    top = g.mp * g.ps
    x = _inputs(g, 1, [1, g.ps - 1, g.ps, g.ps + 1, top, 2 * g.ps,
                       top - 1, 2 * g.ps + g.ps // 2])
    attn, out = _kernel(g, x)
    ref, want = _pure(g, x)
    _close(attn, ref)
    for got, exp in zip(out, want):
        np.testing.assert_array_equal(got, exp)


def _dead_rows(g):
    """Rows with ``seq_len`` 0 among live rows emit exactly 0 and leave
    their pages untouched; the live rows are served as ever."""
    x = _inputs(g, 2, [5, 0, g.ps + 3, 0, g.mp * g.ps, 0, 2 * g.ps, 1])
    attn, out = _kernel(g, x)
    ref, want = _pure(g, x)
    _close(attn[x.live], ref[x.live])
    assert np.all(attn[~x.live] == 0)
    idle = x.np_bt[~x.live].ravel()
    for got, exp, before in zip(out, want, _np_pools(x)):
        np.testing.assert_array_equal(got[:, idle], before[:, idle])
        # The reference parks a dead row's zeros on reserved page 0.
        np.testing.assert_array_equal(got[:, 1:], exp[:, 1:])


def _stacked_layer(g):
    """The layer index picks the pages read AND written: layer 1 of the
    stacked pool written, layer 0 bit-identical, and the other way
    round."""
    x = _inputs(g, 3, _mixed_lens(g))
    for layer in (1, 0):
        x.layer = layer
        attn, out = _kernel(g, x)
        ref, want = _pure(g, x)
        _close(attn, ref)
        for got, exp, before in zip(out, want, _np_pools(x)):
            np.testing.assert_array_equal(got[1 - layer],
                                          before[1 - layer])
            np.testing.assert_array_equal(got[layer], exp[layer])
            assert (got[layer] != before[layer]).any()


def _chunk_width(g):
    """One page a chunk against the plan's own choice: the grid is cut
    differently, the result is the same."""
    assert _tile_plan(ROWS, g.ps, g.mp, g.GD, g.itemsize)[1] > 1
    x = _inputs(g, 4, _mixed_lens(g))
    a, out_a = _kernel(g, x)
    b, out_b = _kernel(g, x, ppc=1)
    _close(a, b, 2e-2)
    for got, exp in zip(out_a, out_b):
        np.testing.assert_array_equal(got, exp)


def _dead_pages(g):
    """Pages beyond a row's length are skipped by the DMA schedule, not
    merely masked: NaN there (in the scales, for int8 pools) never
    reaches the output."""
    x = _inputs(g, 5, _mixed_lens(g))
    n_live = -(-np.asarray(x.seq_lens) // g.ps)
    dead = np.concatenate([x.np_bt[b, n_live[b]:] for b in range(ROWS)])
    assert dead.size >= ROWS
    poisoned = [np.array(p) for p in x.pools]
    for pool in poisoned[-2:]:               # K and V, or their scales
        pool[:, dead] = np.nan
    attn, _ = _kernel(g, x, pools=tuple(
        jnp.asarray(p, q.dtype) for p, q in zip(poisoned, x.pools)))
    ref, _ = _pure(g, x)                     # over the clean pools
    assert np.isfinite(attn).all()
    _close(attn, ref)


def _write_lands_once(g):
    """The new token's K/V — and for int8 its scale — lands at
    ``(page_of, slot)`` of its layer and nowhere else."""
    x = _inputs(g, 6, _mixed_lens(g))
    _, out = _kernel(g, x)
    before = _np_pools(x)
    if g.q8:
        kq, ks = quantize_kv_rows(x.kn)
        vq, vs = quantize_kv_rows(x.vn)
        new = [kq.reshape(ROWS, g.GD), vq.reshape(ROWS, g.GD), ks, vs]
    else:
        new = [x.kn.reshape(ROWS, g.GD), x.vn.reshape(ROWS, g.GD)]
    new = [np.asarray(n, np.float32) for n in new]
    where = {(x.layer, int(p), int(s))
             for p, s in zip(x.np_page_of, x.np_slot_of)}
    for i, (got, was, row) in enumerate(zip(out, before, new)):
        if i < 2:                            # (L, P, ps, GD) data
            touched = np.argwhere((got != was).any(-1))
            at = got[x.layer, x.np_page_of, x.np_slot_of]
        else:                                # (L, P, H_kv, ps) scales
            touched = np.argwhere((got != was).any(2))
            at = got[x.layer, x.np_page_of, :, x.np_slot_of]
        assert {tuple(int(v) for v in t) for t in touched} == where
        np.testing.assert_array_equal(at, row)


def _kv_head_groups(g):
    """Each query head reads its own KV head: flip the sign of ONE KV
    head's history and only its group's outputs move (a group of 1 for
    SmolLM2, of 4 for Mistral)."""
    n_rep, head = g.H // g.Hkv, g.Hkv // 2
    x = _inputs(g, 7, [g.ps + b for b in range(ROWS)])
    lanes = slice(head * g.D, (head + 1) * g.D)
    flipped = tuple(p.at[..., lanes].set(-p[..., lanes])
                    for p in x.pools[:2]) + tuple(x.pools[2:])
    a, _ = _kernel(g, x)
    b, _ = _kernel(g, x, pools=flipped)
    group = np.zeros(g.H, bool)
    group[head * n_rep:(head + 1) * n_rep] = True
    np.testing.assert_array_equal(a[:, ~group], b[:, ~group])
    assert (np.abs(a[:, group] - b[:, group]).max(axis=-1) > 0.05).all()


def _held_to_pure(g, x, ppc=0):
    attn, out = _kernel(g, x, ppc=ppc)
    ref, want = _pure(g, x)
    _close(attn[x.live], ref[x.live])
    assert np.all(attn[~x.live] == 0)
    for got, exp in zip(out, want):
        # The reference parks a dead row's zeros on reserved page 0.
        np.testing.assert_array_equal(got[:, 1:], exp[:, 1:])
    return attn


def _long_row_among_short(g):
    """One 1.5k-token row beside seven short rows in its tile: the tile
    runs to the long row's last chunk, the short rows' products stop at
    their own."""
    x = _inputs(g, 8, [40, 1500, 17, 1, 300, 33, 129, 64],
                mp=1536 // g.ps)
    _held_to_pure(g, x)


def _only_last_row_live(g):
    """A tile whose only live row is its last."""
    x = _inputs(g, 9, [0] * 7 + [CHUNK_TOKENS + g.ps + 5])
    _held_to_pure(g, x)


def _one_row_a_tile(g):
    """Four tiles, one live row each, at a different place and of a
    different length in each: every hand-over of the prefetch chain is
    to a tile whose first fetch is one row's."""
    lens = [0] * 32
    for b, n in ((3, 300), (8, 17), (22, CHUNK_TOKENS), (31, 411)):
        lens[b] = n
    _held_to_pure(g, _inputs(g, 10, lens))


def _dead_middle_tiles(g):
    """Four tiles with live rows in the first and the last alone: the
    prefetch chain crosses two tiles that fetch nothing."""
    lens = [0] * 32
    lens[1], lens[6], lens[29] = 260, 31, CHUNK_TOKENS + 1
    _held_to_pure(g, _inputs(g, 11, lens))


def _chunk_edges(g):
    """Contexts exactly on a chunk's edge, one short of it and one past
    it (the plan's own chunk at this geometry)."""
    S = _tile_plan(ROWS, g.ps, g.mp, g.GD, g.itemsize).chunk_tokens
    assert g.mp * g.ps >= 2 * S
    _held_to_pure(g, _inputs(g, 12, [S, S + 1, S - 1, 2 * S, 2 * S - 1,
                                     1, S, 2 * S]))


def _wide_table(g):
    """A block table four times wider with the same lengths: the same
    output, bit for bit, and the same pools."""
    x = _inputs(g, 13, _mixed_lens(g))
    a, out_a = _kernel(g, x)
    rng = np.random.default_rng(13)
    wide = np.concatenate(
        [x.np_bt, rng.choice(x.dead, (ROWS, 3 * g.mp))], axis=1)
    x.bt = jnp.asarray(wide, jnp.int32)
    b, out_b = _kernel(g, x)
    np.testing.assert_array_equal(a, b)
    for got, exp in zip(out_a, out_b):
        np.testing.assert_array_equal(got, exp)
    _close(a, _pure(g, x)[0])


def _plan_chunk(g):
    return _tile_plan(ROWS, g.ps, g.mp, g.GD, g.itemsize).chunk_tokens


def _every_step_full(g):
    """Eight live rows of one length, two chunks and a page long: every
    step of the tile is a full step, the last with every row's merge."""
    S = _plan_chunk(g)
    lens = [2 * S + g.ps] * ROWS
    x = _inputs(g, 14, lens, mp=4 * S // g.ps)
    plan = _tile_plan(ROWS, g.ps, 4 * S // g.ps, g.GD, g.itemsize)
    assert decode_work(lens, plan) == (3, 24, 24, 24)
    _held_to_pure(g, x)


def _full_then_ragged(g):
    """A tile full in its first chunk and ragged in its second: the
    static path and the listed visits share a row's running softmax."""
    S = _plan_chunk(g)
    lens = [S + 5, S, 2 * S, S - 1, S + g.ps + 1, 7, 2 * S - 1, S + 1]
    plan = _tile_plan(ROWS, g.ps, g.mp, g.GD, g.itemsize)
    assert decode_work(lens, plan) == (2, 13, 13, 8)
    _held_to_pure(g, _inputs(g, 15, lens))


def _merge_and_tail_inside_a_full_step(g):
    """Rows of a full step whose last chunks and live pages differ:
    chunk 0 holds all eight rows, three of which end there — one in the
    chunk's first page (its other pages a zeroed tail), one a page
    further, one on the chunk's last position — beside rows that run on."""
    S = _plan_chunk(g)
    lens = [3, g.ps + 1, S, 2 * S, S + 1, 2 * S - g.ps, S + g.ps, 2 * S]
    plan = _tile_plan(ROWS, g.ps, g.mp, g.GD, g.itemsize)
    assert decode_work(lens, plan) == (2, 13, 13, 8)
    _held_to_pure(g, _inputs(g, 16, lens))


def _full_steps_of_every_chunk_width(g):
    """A full step's block at both chunks the chunk-width case runs (the
    plan's own and one page): eight live rows that end in different
    chunks at each, held to the pure path, and one pool whatever the
    chunk."""
    S = _plan_chunk(g)
    x = _inputs(g, 17, [S + 5, S, 2 * S, S - 1, S + g.ps + 1, S + 7,
                        2 * S - 1, S + 1])
    ref, want = _pure(g, x)
    for ppc in (0, 1):
        attn, out = _kernel(g, x, ppc=ppc)
        _close(attn, ref)
        for got, exp in zip(out, want):
            np.testing.assert_array_equal(got, exp)


CASES = {"page-edges": _page_edges, "dead-rows": _dead_rows,
         "stacked-layer": _stacked_layer, "chunk-width": _chunk_width,
         "dead-pages": _dead_pages, "write-lands-once": _write_lands_once,
         "kv-head-groups": _kv_head_groups,
         "long-row-among-short": _long_row_among_short,
         "only-last-row-live": _only_last_row_live,
         "one-row-a-tile": _one_row_a_tile,
         "dead-middle-tiles": _dead_middle_tiles,
         "chunk-edges": _chunk_edges, "wide-table": _wide_table,
         "every-step-full": _every_step_full,
         "full-then-ragged": _full_then_ragged,
         "merge-and-tail-inside-a-full-step":
             _merge_and_tail_inside_a_full_step,
         "full-steps-of-every-chunk-width":
             _full_steps_of_every_chunk_width}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("config", CONFIGS)
def test_fused_decode_served_geometries(served_geometry, config, case):
    g = _geom(served_geometry, config)
    if config.startswith("smollm2"):
        assert (g.H, g.Hkv, g.D, g.ps, g.q8) == (32, 32, 64, 16, False)
    else:
        assert (g.H, g.Hkv, g.D, g.ps, g.q8) == (32, 8, 128, 128, True)
    CASES[case](g)


# -- the order the kernel is handed its rows in --------------------------------


def _host_order(seq_lens):
    """``ops/attention.decode_order`` on the host: longest first, ties
    by row."""
    by = np.argsort(-np.asarray(seq_lens, np.int64), kind="stable")
    return by, np.argsort(by)


def _by_place(x, by):
    """The same rows handed over in another order: everything that has
    one entry a row, moved; the pools stay."""
    y = copy.copy(x)
    for name in ("q", "kn", "vn", "bt", "seq_lens", "page_of", "slot_of"):
        setattr(y, name, jnp.asarray(np.asarray(getattr(x, name))[by]))
    for name in ("live", "np_bt", "np_page_of", "np_slot_of"):
        setattr(y, name, getattr(x, name)[by])
    return y


def _same_rows_in_any_order(g, x, monkeypatch):
    """Each row's attention and its cache write in seat order, in the
    order's, and through the dispatcher's ``order=`` as the models call
    it: the pure path's, and the pools equal to the bit — the order is
    invisible outside the call. Returns the order."""
    lens = np.asarray(x.seq_lens)
    by, places = _host_order(lens)
    seat, seat_out = _kernel(g, x)
    placed, placed_out = _kernel(g, _by_place(x, by))
    ref, want = _pure(g, x)
    for attn in (seat, placed[places]):
        _close(attn[x.live], ref[x.live])
        assert np.all(attn[~x.live] == 0)
    for a, b, exp in zip(seat_out, placed_out, want):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a[:, 1:], exp[:, 1:])
    # the device makes the order the host counts by
    monkeypatch.setenv("LLMQ_PALLAS", "interpret")
    order = attention.decode_order(x.seq_lens, x.pools, g.mp, g.D)
    np.testing.assert_array_equal(order.rows, by)
    np.testing.assert_array_equal(order.places, places)
    bt, seq_lens, page_of = attention.rows_by_place(order, x.bt, x.seq_lens,
                                                    x.page_of)
    if g.q8:
        attn, out = attention.paged_decode_step_q8(
            x.q, x.kn, x.vn, x.pools, bt, seq_lens, page_of, x.slot_of,
            x.layer, order=order)
    else:
        attn, *out = attention.paged_decode_step(
            x.q, x.kn, x.vn, *x.pools, bt, seq_lens, page_of, x.slot_of,
            x.layer, order=order)
    np.testing.assert_array_equal(np.asarray(attn, np.float32),
                                  placed[places])
    for a, b in zip(out, placed_out):
        np.testing.assert_array_equal(np.asarray(a, np.float32), b)
    monkeypatch.setenv("LLMQ_PALLAS", "0")
    assert attention.decode_order(x.seq_lens, x.pools, g.mp, g.D) is None
    return by


def _shuffled_four_tiles(g, monkeypatch):
    """32 rows of 29 lengths from one token to the block table's end and
    three dead ones, shuffled over the seats: ordered, more row-chunks
    run in full steps and fewer steps run."""
    rng = np.random.default_rng(18)
    top = g.mp * g.ps
    lens = rng.permutation([1 + (top - 1) * i // 28 for i in range(29)]
                           + [0] * 3)
    by = _same_rows_in_any_order(g, _inputs(g, 18, lens), monkeypatch)
    plan = _tile_plan(32, g.ps, g.mp, g.GD, g.itemsize)
    seat, ordered = decode_work(lens, plan), decode_work(lens, plan,
                                                         ordered=True)
    assert ordered == decode_work(lens[by], plan)
    assert ordered[1:3] == seat[1:3]            # the same work
    assert ordered[0] < seat[0] and ordered[3] > seat[3]


def _dead_rows_land_in_the_last_tile(g, monkeypatch):
    """Five dead rows scattered among sixteen seats stand last in the
    order, by row: the first tile is all live."""
    S = _plan_chunk(g)
    lens = np.asarray([S, 0, 2 * S, 9, 0, S + 1, S - 1, 0,
                       g.ps, 0, 2 * S - 3, 40, S + g.ps, 0, 5, S])
    by = _same_rows_in_any_order(g, _inputs(g, 19, lens), monkeypatch)
    assert list(by[-5:]) == [1, 4, 7, 9, 13]
    assert (lens[by[:8]] > 0).all()


def _ties_stand_by_row(g, monkeypatch):
    """Equal contexts keep their rows' order, whatever stands between
    them: the order is a function of ``seq_lens`` alone."""
    S = _plan_chunk(g)
    lens = np.asarray([S, 7, S, 7, 2 * S, 7, S, 2 * S,
                       7, S, 2 * S, S, 7, 7, 2 * S, S])
    by = _same_rows_in_any_order(g, _inputs(g, 20, lens), monkeypatch)
    assert list(by) == [4, 7, 10, 14, 0, 2, 6, 9, 11, 15, 1, 3, 5, 8, 12,
                        13]


ORDER_CASES = {"shuffled-four-tiles": _shuffled_four_tiles,
               "dead-rows-land-in-the-last-tile":
                   _dead_rows_land_in_the_last_tile,
               "ties-stand-by-row": _ties_stand_by_row}


@pytest.mark.parametrize("case", list(ORDER_CASES))
@pytest.mark.parametrize("config", CONFIGS)
def test_the_order_is_invisible_outside_the_call(served_geometry,
                                                 monkeypatch, config, case):
    ORDER_CASES[case](_geom(served_geometry, config), monkeypatch)


@pytest.mark.parametrize("rows", ["served", "one-tile"])
@pytest.mark.parametrize("config", CONFIGS)
def test_tile_plan_served_geometries(served_geometry, config, rows):
    """``_tile_plan`` is a pure function of the shapes: at each
    configuration's full geometry (32 rows x 256 pages of 16; 64 rows x
    16 pages of 128) and at one 8-row tile a plan exists, its row tile
    is 8 or the batch, its chunk divides the block table and fills the
    MXU's token axis (>= 128 tokens), the scratch it implies is the
    scratch it states, within the budget the module states, and the
    VMEM it asks of the compiler covers that scratch inside what a v5e
    core has."""
    g = _geom(served_geometry, config)
    B = g.rows_full if rows == "served" else ROWS
    assert fused_kernel_viable(B, g.ps, g.mp_full, g.GD, g.itemsize)
    plan = _tile_plan(B, g.ps, g.mp_full, g.GD, g.itemsize)
    assert plan.rows in (8, B) and B % plan.rows == 0
    assert g.mp_full % plan.pages_per_chunk == 0
    assert plan.chunk_tokens == plan.pages_per_chunk * g.ps
    assert 128 <= plan.chunk_tokens <= CHUNK_TOKENS
    assert plan.scratch_bytes == (2 * 2 * plan.rows * plan.chunk_tokens
                                  * g.GD * g.itemsize)
    assert plan.scratch_bytes <= SCRATCH_BUDGET_BYTES
    assert plan.scratch_bytes < plan.vmem_limit_bytes <= 64 * 2**20
    # a full step's products are one block of the tile's rows; over int8
    # pools the plan asks room for their bf16 copies beside the scratch
    assert plan.vmem_limit_bytes - plan.scratch_bytes >= (
        4 * plan.rows * plan.chunk_tokens * g.GD if g.q8 else 0)
    # A wider block table or a deeper batch changes no call's cut.
    assert _tile_plan(B, g.ps, 2 * g.mp_full, g.GD, g.itemsize) == plan
    if config.startswith("smollm2"):
        assert (g.rows_full, g.mp_full) == (32, 256)
    else:
        assert (g.rows_full, g.mp_full) == (64, 16)


def _occupancy(name, rows):
    """The three occupancies of the micro-bench (PERF.md §6, PR 29), at
    a configuration's own batch: nearly full over 0.3-1.5k, four rows of
    360, two rows of 2,000."""
    if name == "full":
        live = rows - 1
        lens = [300 + 1200 * i // (live - 1) for i in range(live)]
    else:
        lens = {"four-of-360": [360] * 4, "two-of-2000": [2000] * 2}[name]
    return lens + [0] * (rows - len(lens))


@pytest.mark.parametrize("occupancy", ["full", "four-of-360", "two-of-2000"])
@pytest.mark.parametrize("config", CONFIGS)
def test_decode_work_follows_the_batch(served_geometry, config, occupancy):
    """``decode_work`` counts the kernel's own schedule: products run
    for exactly the (row, chunk) pairs in which the row holds a
    position, and neither they nor the steps depend on the block
    table's width."""
    g = _geom(served_geometry, config)
    lens = _occupancy(occupancy, g.rows_full)
    width = max(g.mp_full, 2048 // g.ps)     # Mistral serves 2,048
    plan = _tile_plan(g.rows_full, g.ps, width, g.GD, g.itemsize)
    steps, computed, live, full = decode_work(lens, plan)
    S = plan.chunk_tokens
    assert computed == live == sum(-(-n // S) for n in lens)
    tiles = np.asarray(lens).reshape(-1, plan.rows)
    assert steps == sum(max(1, -(-int(t.max()) // S)) for t in tiles)
    assert steps < g.rows_full // plan.rows * (width // plan.pages_per_chunk)
    wider = _tile_plan(g.rows_full, g.ps, 2 * width, g.GD, g.itemsize)
    assert decode_work(lens, wider) == (steps, computed, live, full)
    if occupancy == "four-of-360":
        # One live tile of ceil(360 / S) chunks; the others one step each.
        assert steps == -(-360 // S) + g.rows_full // plan.rows - 1


@pytest.mark.parametrize("occupancy", ["full", "four-of-360", "two-of-2000"])
@pytest.mark.parametrize("config", CONFIGS)
def test_decode_work_counts_the_full_steps(served_geometry, config,
                                           occupancy):
    """The fourth count in closed form: a step is full while the tile's
    SHORTEST row is live, so a tile gives its rows times its shortest
    row's chunks — in the order handed over, and with ``ordered`` in
    ``decode_order``'s, where the lengths' spread over tiles is gone."""
    g = _geom(served_geometry, config)
    lens = np.asarray(_occupancy(occupancy, g.rows_full))
    width = max(g.mp_full, 2048 // g.ps)
    plan = _tile_plan(g.rows_full, g.ps, width, g.GD, g.itemsize)
    S, R = plan.chunk_tokens, plan.rows

    def closed_form(by_place):
        tiles = -(-by_place.reshape(-1, R) // S)        # chunks a row
        return R * int(tiles.min(axis=1).sum())

    rng = np.random.default_rng(21)
    for handed in (lens, rng.permutation(lens)):
        assert decode_work(handed, plan)[3] == closed_form(handed)
        got = decode_work(handed, plan, ordered=True)
        assert got[3] == closed_form(np.sort(lens)[::-1])
        assert got[1:3] == decode_work(lens, plan)[1:3]
        assert got[3] <= got[1]
    if occupancy != "full":
        # four or two live rows never fill a tile of eight
        assert decode_work(lens, plan, ordered=True)[3] == 0
    else:
        # every tile but the last (its dead row) runs its shortest
        # row's chunks full: nine row-chunks in ten
        full, computed = got[3], got[1]
        assert full / computed > (0.85 if g.rows_full == 64 else 0.7)
