"""One mixed step whose slices lie TIGHT (``ops/rows.py``) against
``forward_prefill`` of each slice and ``forward_decode`` of the rows
over the same pool: the decode rows' logits, each slice's last
position's, and every page written. What the four families' test files
share (``test_mixed_batch.py``, ``test_mistral_w8kv8.py``,
``test_deepseek_v3.py``, ``test_longcat_flash.py``): each runs
``CASES`` as ONE parametrised test over its own tiny model.

The step is traced with ``TILE``-row tiles in slices ``WIDTH`` wide, so
that a tile's edge falls inside a slice; its own ``jax.jit``, so that
no other test meets a program traced with the small tile.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llmq_tpu.ops import rows

TILE, SLICES, WIDTH = 8, 3, 16
#: 10 cached tokens under the slice that continues
HISTORY = 10
#: case -> (length, start position) of each used slice, in order
CASES = {
    "all-slices-full": [(16, 0), (16, 0), (16, 0)],
    "tile-edges-inside-slices": [(TILE - 1, 0), (TILE, 0), (TILE + 1, 0)],
    "an-unused-slice": [(9, 0), (4, 0)],
    "one-token": [(1, 0)],
    "a-continuation": [(6, HISTORY), (5, 0)],
    "ends-on-a-tile": [(5, 0), (11, 0)],
}
#: the decode rows' contexts; the last row is not active
DECODE = (5, 12, 3)

_steps = {}


@pytest.fixture
def tight_step(monkeypatch):
    """``step(fam) -> forward_mixed`` of that family under a jit of its
    own, traced (at its first call, inside the test) with the small
    tile."""
    monkeypatch.setattr(rows, "ROW_TILE", TILE)

    def step(fam):
        if fam not in _steps:
            _steps[fam] = jax.jit(fam.forward_mixed.__wrapped__,
                                  static_argnames=("cfg",))
        return _steps[fam]
    return step


def _prefill(fam, cfg, params, cache, bt, toks, start):
    """One slice alone: ``toks`` at ``start``.. through
    ``forward_prefill`` in a bucket ``WIDTH`` wide."""
    n = len(toks)
    padded = np.zeros((1, WIDTH), np.int32)
    padded[0, :n] = toks
    pos = start + np.minimum(np.arange(WIDTH, dtype=np.int32), n - 1)[None]
    logits, cache = fam.forward_prefill(
        params, cfg, jnp.asarray(padded), jnp.asarray(pos),
        jnp.asarray([n], jnp.int32), cache, jnp.asarray(bt[None]),
        last_only=True)
    return np.asarray(logits)[0], cache


def both_ways(step, fam, cfg, params, case, *, page, cache_dtype=None):
    """``CASES[case]`` apart and together: ``(parts, mixed)``, each
    ``{"dec": the active rows' logits, "pf": the used slices' last
    logits, "pages": {pool: all but page 0}}``; ``step=None`` leaves
    the mixed step out."""
    plan = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    B, S, T = len(DECODE), SLICES, WIDTH
    mp = cfg.max_seq_len // page
    bts = (1 + np.arange((B + S) * mp).reshape(B + S, mp)).astype(np.int32)
    cache = fam.init_kv_pages(cfg, 1 + (B + S) * mp, page,
                              dtype=cache_dtype)

    def draw(n):
        return rng.integers(3, cfg.vocab_size, n, dtype=np.int32)

    def result(dec, pf, pool):          # page 0 is everyone's trash
        return {"dec": np.asarray(dec)[active],
                "pf": np.asarray(pf)[:len(plan)],
                "pages": {name: np.asarray(
                    pool[name][:, 1:].astype(jnp.float32)) for name in pool}}

    for b, n in enumerate(DECODE):      # what the decode rows attend to
        _, cache = _prefill(fam, cfg, params, cache, bts[b], draw(n), 0)
    for s, (_, start) in enumerate(plan):
        if start:                       # what a continuing slice attends to
            _, cache = _prefill(fam, cfg, params, cache, bts[B + s],
                                draw(start), 0)
    slices = [draw(n) for n, _ in plan]
    dec_tok, dec_pos = draw(B), np.asarray(DECODE, np.int32)
    active = np.arange(B) < B - 1

    # apart: each slice through forward_prefill, then the rows' step
    ref = jax.tree.map(jnp.copy, cache)
    ref_pf = []
    for s, (toks, (_, start)) in enumerate(zip(slices, plan)):
        logits, ref = _prefill(fam, cfg, params, ref, bts[B + s], toks,
                               start)
        ref_pf.append(logits)
    ref_dec, ref = fam.forward_decode(
        params, cfg, jnp.asarray(dec_tok), jnp.asarray(dec_pos), ref,
        jnp.asarray(bts[:B]), active=jnp.asarray(active))
    parts = result(ref_dec, np.stack(ref_pf), ref)
    if step is None:
        return parts, None

    # together: the executor's hand-over (mixed_chunk_start)
    grid = np.zeros((2, S, T), np.int32)
    lens = np.ones(S, np.int32)
    pf_bts = np.zeros((S, mp), np.int32)
    for s, (toks, (n, start)) in enumerate(zip(slices, plan)):
        grid[0, s, :n], grid[1, s, :n] = toks, start + np.arange(n)
        lens[s], pf_bts[s] = n, bts[B + s]
    tok, pos, starts = rows.pack_grid(grid[0], grid[1], lens,
                                      used=len(plan))
    assert starts[-1] == sum(n for n, _ in plan)
    dec, pf, got = step(fam)(
        params, cfg, jnp.asarray(dec_tok), jnp.asarray(dec_pos), cache,
        jnp.asarray(bts[:B]), jnp.asarray(tok), jnp.asarray(pos),
        jnp.asarray(lens), jnp.asarray(starts), jnp.asarray(pf_bts),
        dec_active=jnp.asarray(active))
    assert pf.shape == (S, parts["pf"].shape[-1])
    assert np.isfinite(np.asarray(pf)).all()
    return parts, result(dec, pf, got)


def check(step, fam, cfg, params, case, *, page, cache_dtype=None,
          atol=1e-4):
    """Run ``CASES[case]`` both ways and hold them together."""
    parts, mixed = both_ways(step, fam, cfg, params, case, page=page,
                             cache_dtype=cache_dtype)
    for name in ("dec", "pf"):
        np.testing.assert_allclose(mixed[name], parts[name], atol=atol,
                                   err_msg=name)
    for name in parts["pages"]:
        np.testing.assert_allclose(mixed["pages"][name],
                                   parts["pages"][name], atol=atol,
                                   err_msg=name)


def check_served(step, fam, cfg, params, case, *, page, cache_dtype=None,
                 atol, pages_atol):
    """The same in the precision that is SERVED (``cfg.dtype`` bfloat16),
    where the two ways round their sums in different orders and a bound
    on their distance alone would have to be loose: besides
    ``atol`` / ``pages_atol`` between them, the mixed step's logits lie
    no further from the parts' in FLOAT32 (the same values, every leaf
    of bfloat16 widened) than the bfloat16 parts' own do, by more than
    half again and two hundredths. A cast lost in a loop's body or a
    carry of the wrong type shows there."""
    assert cfg.dtype == jnp.bfloat16
    parts, mixed = both_ways(step, fam, cfg, params, case, page=page,
                             cache_dtype=cache_dtype)
    wide = jax.tree.map(
        lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x,
        params)
    truth, _ = both_ways(None, fam,
                         dataclasses.replace(cfg, dtype=jnp.float32), wide,
                         case, page=page, cache_dtype=cache_dtype)
    for name in ("dec", "pf"):
        np.testing.assert_allclose(mixed[name], parts[name], atol=atol,
                                   err_msg=name)
        off = np.abs(mixed[name] - truth[name]).max()
        allowed = 1.5 * np.abs(parts[name] - truth[name]).max() + 2e-2
        assert off <= allowed, (name, off, allowed)
    for name in parts["pages"]:
        np.testing.assert_allclose(mixed["pages"][name],
                                   parts["pages"][name], atol=pages_atol,
                                   err_msg=name)
