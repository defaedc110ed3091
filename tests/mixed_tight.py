"""One mixed step whose slices lie TIGHT (``ops/rows.py``) against
``forward_prefill`` of each slice and ``forward_decode`` of the rows
over the same pool: the decode rows' logits, each slice's last
position's, and every page written. What the five families' test files
share (``test_mixed_batch.py``, ``test_mistral_w8kv8.py``,
``test_deepseek_v3.py``, ``test_longcat_flash.py``, ``test_mellum.py``):
each runs ``CASES`` as ONE parametrised test over its own tiny model. A
family that keeps ROW STATE beside its pages (``init_row_state`` gives
one: ``models/mellum``'s slabs) has it carried through both ways — the
decode rows own batch rows 0 .. B - 1, slice ``s`` row B + s — and
compared leaf by leaf with the pages.

The step is traced with ``TILE``-row tiles in slices ``WIDTH`` wide, so
that a tile's edge falls inside a slice; its own ``jax.jit``, so that
no other test meets a program traced with the small tile.

``JOINED`` are the cases of a family whose decode rows LEAD the slice
rows through one product (``models/llama.forward_mixed``), at the two
served shapes in small: two slices a tile wide (SmolLM2's 2 x 256:
nothing loops) and two slices two tiles wide (Mistral's 2 x 512: the
rows loop, and the ``len(DECODE)`` rows that lead move the tiles'
edges).
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
#: case -> (slices, width, plan): the served shapes in small, each with
#: a row that is not active (``DECODE``) and, where the plan is shorter
#: than ``slices``, a slice that is not used
JOINED = {
    # S x T = 2 tiles: all 3 + 16 rows run whole, no loop
    "two-tiles-whole": (2, TILE, [(5, 0)]),
    "two-tiles-whole-full": (2, TILE, [(TILE, 0), (TILE, HISTORY)]),
    # S x T = 4 tiles. 14 live rows are 2 tiles alone, 3 behind the
    # decode rows that lead: B + n_live crosses an edge
    "four-tiles-lead-crosses-an-edge": (2, 2 * TILE,
                                        [(9, HISTORY), (5, 0)]),
    # 12 live rows are 2 tiles with the lead and without
    "four-tiles-lead-crosses-no-edge": (2, 2 * TILE, [(12, 0)]),
    # all live: 35 rows, the fifth tile is moved back to end at the last
    "four-tiles-full-last-tile-moved-back": (2, 2 * TILE,
                                             [(16, 0), (16, 0)]),
}
#: for a family whose rows keep a window in a ring (``models/mellum``
#: at a window of 4 in slabs of 24 tokens): the first slice's 16 tokens
#: behind 10 cached ones pass the window and wrap the ring
RING = {
    "past-the-window-round-the-ring": (2, 2 * TILE,
                                       [(16, HISTORY), (7, 0)]),
}

_STATIC = {"forward_mixed": ("cfg",), "forward_decode": ("cfg",),
           "forward_prefill": ("cfg", "last_only")}
_jits = {}


def _forward(fam, name, as_written=False):
    """``fam``'s forward function ``name``: its mixed step under a jit
    of this module's own and, ``as_written``, all three compiled to
    round where their source rounds. XLA may otherwise keep a bfloat16
    chain wide inside a fusion (``xla_allow_excess_precision``), and
    does so differently in two programs of different shape; where
    activations are quantised on the fly (int8 weights) one such
    rounding moves a value by a quantum and a logit by 0.05, and the
    two ways are then two choices of the compiler apart, not two
    algorithms."""
    if name != "forward_mixed" and not as_written:
        return getattr(fam, name)            # the family's own jit
    key = (fam, name, as_written)
    if key not in _jits:
        _jits[key] = jax.jit(
            getattr(fam, name).__wrapped__, static_argnames=_STATIC[name],
            compiler_options=({"xla_allow_excess_precision": False}
                              if as_written else None))
    return _jits[key]


@pytest.fixture
def tight_step(monkeypatch):
    """``step(fam) -> forward_mixed`` of that family under a jit of its
    own, traced (at its first call, inside the test) with the small
    tile."""
    monkeypatch.setattr(rows, "ROW_TILE", TILE)
    return lambda fam, as_written=False: _forward(fam, "forward_mixed",
                                                  as_written)


def shape_of(case):
    """``(slices, width, plan)`` of a case of ``CASES``, ``JOINED`` or
    ``RING``."""
    if case in CASES:
        return SLICES, WIDTH, CASES[case]
    return {**JOINED, **RING}[case]


def _prefill(forward_prefill, cfg, params, held, bt, toks, start,
             width=WIDTH, row=None):
    """One slice alone: ``toks`` at ``start``.. through
    ``forward_prefill`` in a bucket ``width`` wide (never under
    ``WIDTH``: the history's rows are up to 12 tokens). ``held``: (the
    pool, the row state or None); with a state the slice is batch row
    ``row``'s."""
    n = len(toks)
    padded = np.zeros((1, width), np.int32)
    padded[0, :n] = toks
    pos = start + np.minimum(np.arange(width, dtype=np.int32), n - 1)[None]
    cache, state = held
    logits, *held = forward_prefill(
        params, cfg, jnp.asarray(padded), jnp.asarray(pos),
        jnp.asarray([n], jnp.int32), cache, jnp.asarray(bt[None]),
        last_only=True, **_state_args(state, rows=[row]))
    return np.asarray(logits)[0], _held(held)


def _state_args(state, **rows):
    """What a row-state family's forward function takes besides."""
    if state is None:
        return {}
    return {"row_state": state,
            **{k: jnp.asarray(v, jnp.int32) for k, v in rows.items()}}


def _held(out):
    """``(pool, row state or None)`` of a forward function's returns
    behind its logits."""
    return (out[0], out[1] if len(out) > 1 else None)


def both_ways(step, fam, cfg, params, case, *, page, cache_dtype=None,
              as_written=False):
    """The case's plan apart and together: ``(parts, mixed)``, each
    ``{"dec": the active rows' logits, "pf": the used slices' last
    logits, "pages": {pool or row-state leaf: all but page 0}}``;
    ``step=None`` leaves the mixed step out."""
    S, T, plan = shape_of(case)
    rng = np.random.default_rng(
        [*sorted(CASES), *sorted(JOINED), *sorted(RING)].index(case))
    B = len(DECODE)
    mp = cfg.max_seq_len // page
    bts = (1 + np.arange((B + S) * mp).reshape(B + S, mp)).astype(np.int32)
    forward_prefill = _forward(fam, "forward_prefill", as_written)
    held = (fam.init_kv_pages(cfg, 1 + (B + S) * mp, page,
                              dtype=cache_dtype),
            fam.init_row_state(cfg, B + S))

    def draw(n):
        return rng.integers(3, cfg.vocab_size, n, dtype=np.int32)

    def result(dec, pf, held):          # page 0 is everyone's trash
        pool, state = held
        return {"dec": np.asarray(dec)[active],
                "pf": np.asarray(pf)[:len(plan)],
                "pages": {name: np.asarray(
                    leaf[:, 1:].astype(jnp.float32))
                    for name, leaf in {**pool, **(state or {})}.items()}}

    for b, n in enumerate(DECODE):      # what the decode rows attend to
        _, held = _prefill(forward_prefill, cfg, params, held, bts[b],
                           draw(n), 0, row=b)
    for s, (_, start) in enumerate(plan):
        if start:                       # what a continuing slice attends to
            _, held = _prefill(forward_prefill, cfg, params, held,
                               bts[B + s], draw(start), 0, row=B + s)
    slices = [draw(n) for n, _ in plan]
    dec_tok, dec_pos = draw(B), np.asarray(DECODE, np.int32)
    active = np.arange(B) < B - 1

    # apart: each slice through forward_prefill, then the rows' step
    ref = jax.tree.map(jnp.copy, held)
    ref_pf = []
    for s, (toks, (_, start)) in enumerate(zip(slices, plan)):
        logits, ref = _prefill(forward_prefill, cfg, params, ref,
                               bts[B + s], toks, start, T, row=B + s)
        ref_pf.append(logits)
    ref_dec, *ref = _forward(fam, "forward_decode", as_written)(
        params, cfg, jnp.asarray(dec_tok), jnp.asarray(dec_pos), ref[0],
        jnp.asarray(bts[:B]), active=jnp.asarray(active),
        **_state_args(ref[1]))
    parts = result(ref_dec, np.stack(ref_pf), _held(ref))
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
    # (an unused slice names one past the last batch row)
    pf_rows = [B + s if s < len(plan) else B + S for s in range(S)]
    dec, pf, *got = step(fam, as_written)(
        params, cfg, jnp.asarray(dec_tok), jnp.asarray(dec_pos), held[0],
        jnp.asarray(bts[:B]), jnp.asarray(tok), jnp.asarray(pos),
        jnp.asarray(lens), jnp.asarray(starts), jnp.asarray(pf_bts),
        dec_active=jnp.asarray(active),
        **_state_args(held[1], pf_rows=pf_rows))
    assert pf.shape == (S, parts["pf"].shape[-1])
    assert np.isfinite(np.asarray(pf)).all()
    return parts, result(dec, pf, _held(got))


def check(step, fam, cfg, params, case, *, page, cache_dtype=None,
          atol=1e-4, as_written=False):
    """Run the case both ways and hold them together."""
    parts, mixed = both_ways(step, fam, cfg, params, case, page=page,
                             cache_dtype=cache_dtype, as_written=as_written)
    for name in ("dec", "pf"):
        np.testing.assert_allclose(mixed[name], parts[name], atol=atol,
                                   err_msg=name)
    for name in parts["pages"]:
        np.testing.assert_allclose(mixed["pages"][name],
                                   parts["pages"][name], atol=atol,
                                   err_msg=name)


def check_served(step, fam, cfg, params, case, *, page, cache_dtype=None,
                 atol, pages_atol, as_written=False):
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
                             cache_dtype=cache_dtype, as_written=as_written)
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
