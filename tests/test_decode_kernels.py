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
the CPU; the batch is one 8-row tile and the block table is a few pages
wide, at the plan's own pages-per-chunk.
"""

from types import SimpleNamespace

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from llmq_tpu.ops import attention  # noqa: E402
from llmq_tpu.ops.pallas.fused_decode import (  # noqa: E402
    _tile_plan, fused_kernel_viable)
from llmq_tpu.ops.quant import quantize_kv_rows  # noqa: E402

CONFIGS = ("smollm2-1.7b-bf16", "mistral-7b-v0.3-w8kv8")
ROWS, LAYERS = 8, 2
#: The K/V scratch ``_tile_plan`` budgets (two slots of K and of V).
SCRATCH_BYTES = 12 * 2**20


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
        mp=max(4, 128 // ps),                # the test's block table
        rows_full=ex["max_batch_size"], mp_full=cfg.max_seq_len // ps)


def _inputs(g, seed, seq_lens):
    """Pools full of history, one new token a row. Every page but the
    reserved page 0 belongs to exactly one row."""
    rng = np.random.default_rng(seed)
    P = 1 + ROWS * g.mp
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
    bt = 1 + rng.permutation(ROWS * g.mp).reshape(ROWS, g.mp)
    seq_lens = np.asarray(seq_lens, np.int32)
    assert seq_lens.shape == (ROWS,) and seq_lens.max() <= g.mp * g.ps
    live = seq_lens > 0
    pos = np.maximum(seq_lens - 1, 0)
    page_of = np.where(live, bt[np.arange(ROWS), pos // g.ps], 0)
    new = [jnp.asarray(rng.standard_normal((ROWS, g.Hkv, g.D))
                       * live[:, None, None], new_dtype) for _ in range(2)]
    return SimpleNamespace(
        pools=pools, bt=jnp.asarray(bt, jnp.int32), np_bt=bt,
        q=jnp.asarray(rng.standard_normal((ROWS, g.H, g.D)), jnp.bfloat16),
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


CASES = {"page-edges": _page_edges, "dead-rows": _dead_rows,
         "stacked-layer": _stacked_layer, "chunk-width": _chunk_width,
         "dead-pages": _dead_pages, "write-lands-once": _write_lands_once,
         "kv-head-groups": _kv_head_groups}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("config", CONFIGS)
def test_fused_decode_served_geometries(served_geometry, config, case):
    g = _geom(served_geometry, config)
    if config.startswith("smollm2"):
        assert (g.H, g.Hkv, g.D, g.ps, g.q8) == (32, 32, 64, 16, False)
    else:
        assert (g.H, g.Hkv, g.D, g.ps, g.q8) == (32, 8, 128, 128, True)
    CASES[case](g)


@pytest.mark.parametrize("rows", ["served", "one-tile"])
@pytest.mark.parametrize("config", CONFIGS)
def test_tile_plan_served_geometries(served_geometry, config, rows):
    """``_tile_plan`` is a pure function of the shapes: at each
    configuration's full geometry (32 rows x 256 pages of 16; 64 rows x
    16 pages of 128) and at one 8-row tile a plan exists, its row tile
    is 8 or the batch, its chunk divides the block table and the K/V
    scratch it implies is within what the function budgets."""
    g = _geom(served_geometry, config)
    B = g.rows_full if rows == "served" else ROWS
    assert fused_kernel_viable(B, g.ps, g.mp_full, g.GD, g.itemsize)
    R, ppc = _tile_plan(B, g.ps, g.mp_full, g.GD, g.itemsize)
    assert R in (8, B) and B % R == 0
    assert g.mp_full % ppc == 0 and ppc >= 1
    scratch = 2 * 2 * R * ppc * g.ps * g.GD * g.itemsize
    assert scratch <= SCRATCH_BYTES
    if config.startswith("smollm2"):
        # The default 256-token chunk (16 pages) would want 33.5 MB.
        assert (g.rows_full, g.mp_full, ppc) == (32, 256, 4)
        assert 4 * scratch > SCRATCH_BYTES
    else:
        assert (g.rows_full, g.mp_full, ppc) == (64, 16, 2)
