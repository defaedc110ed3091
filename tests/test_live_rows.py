"""``ops/rows.py`` alone: the loop over the live rows' tiles, the moves
between the tight layout and the (S, T) grid, and the host's packing."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llmq_tpu.ops import rows


def _fn(calls):
    w = jnp.arange(12.0).reshape(3, 4)

    def fn(x, y):
        calls.append(x.shape)
        return x @ w + y[:, None], (x * 2).astype(jnp.int32)
    return fn


@pytest.mark.parametrize("n_live", [0, 1, 7, 8, 9, 16, 20, 24])
def test_live_tiles_are_computed_and_the_rest_is_zero(n_live):
    """Rows of the tiles that hold a live row equal ``fn`` of the whole
    arrays; rows past the last live tile are zero; one trace of ``fn``
    at the tile's shapes serves every ``n_live`` (a traced scalar)."""
    calls = []
    fn = _fn(calls)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((24, 3)), jnp.float32)
    y = jnp.asarray(rng.standard_normal(24), jnp.float32)
    run = jax.jit(lambda n: rows.live_rows(fn, n, 8, x, y))
    a, b = run(jnp.int32(n_live))
    assert calls == [(8, 3)]                       # traced once, a tile
    whole_a, whole_b = fn(x, y)
    done = -(-n_live // 8) * 8
    np.testing.assert_allclose(a[:done], whole_a[:done], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(b[:done], whole_b[:done])
    assert not np.asarray(a[done:]).any() and not np.asarray(b[done:]).any()
    assert a.shape == (24, 4) and b.dtype == jnp.int32


@pytest.mark.parametrize("m", [8, 12, 16])
def test_two_tiles_or_fewer_run_whole(m):
    """Rows that are not ``worth_a_loop`` — a loop could skip one tile
    at most and the program would hold a ``while`` for it — run whole:
    no control flow in the program, every row computed whatever
    ``n_live``, and ``tile_rows`` counts them all."""
    calls = []
    fn = _fn(calls)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((m, 3)), jnp.float32)
    y = jnp.asarray(rng.standard_normal(m), jnp.float32)
    run = jax.jit(lambda n: rows.live_rows(fn, n, 8, x, y))
    a, b = run(jnp.int32(1))
    whole_a, whole_b = fn(x, y)
    np.testing.assert_allclose(a, whole_a, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(b, whole_b)
    text = run.lower(jnp.int32(1)).as_text()
    assert not re.search(r"stablehlo\.(while|case)", text)
    assert not rows.worth_a_loop(m, 8) and rows.worth_a_loop(17, 8)
    assert rows.tile_rows(1, 8, m) == m
    # rows that lead are always live and never tip the rule
    assert rows.tile_rows(1, 8, m, lead=8) == m
    assert rows.tile_rows(1, 8, 17, lead=8) == 8


@pytest.mark.parametrize("slices,width,lead,tile,loops", [
    (2, 256, 32, 256, False),    # SmolLM2: 2 x 256 behind 32 rows, 544 whole
    (2, 512, 64, 256, True),     # Mistral: 2 x 512 behind 64 rows
    (4, 512, 128, 256, True),    # LongCat: 4 x 512 behind 128 rows
    (2, 8, 3, 8, False), (3, 8, 3, 8, True)])
def test_the_rows_behind_the_lead_alone_decide_whether_rows_loop(
        slices, width, lead, tile, loops):
    """``worth_a_loop`` is the one rule, and it is asked of the rows
    BEHIND the lead: the program (``live_rows``: a ``while`` in its
    text or none) and the host's count (``tile_rows``, and
    ``llama.mixed_live_rows`` at a served shape) agree, with the lead
    and at every ``n_live``."""
    from llmq_tpu.models import llama
    slice_rows = slices * width
    assert rows.worth_a_loop(slice_rows, tile) is loops
    m = lead + slice_rows
    x = jax.ShapeDtypeStruct((m, 2), jnp.float32)
    run = jax.jit(lambda x, n: rows.live_rows(lambda t: t + 1, n, tile, x,
                                              lead=lead))
    text = run.lower(x, jax.ShapeDtypeStruct((), jnp.int32)).as_text()
    assert bool(re.search(r"stablehlo\.(while|case)", text)) is loops
    for n_live in sorted({0, 1, tile - lead, tile - lead + 1, slice_rows // 2,
                          slice_rows - 1, slice_rows}):
        out = np.asarray(run(jnp.zeros((m, 2), jnp.float32),
                             jnp.int32(lead + n_live)))
        ran = int(out[:, 0].sum())                 # rows that were computed
        assert ran == lead + rows.tile_rows(n_live, tile, slice_rows,
                                            lead=lead), n_live
        assert out[:lead + n_live].all()
        if tile == rows.row_tile(width):        # a served shape
            assert llama.mixed_live_rows(n_live, lead, slices,
                                         width) == ran - lead


@pytest.mark.parametrize("tokens", [0, 1, 511, 512, 4100, 8192])
def test_solar_open2_counts_its_slice_rows_live_tiles(tokens):
    """``solar_open2.mixed_live_rows`` at the served shape (sixteen
    512-token slices, 32 decode rows that go through products of their
    own, so none leads): ``tile_rows``' rule, which is the trip count of
    the loop ``forward_mixed`` runs its row-wise products in
    (``live_rows`` over the S x T tight rows, no lead) times the tile —
    never under the prompt's tokens, under a tile over them, and never
    the 8,192 grid rows unless they all hold a token."""
    from llmq_tpu.models import solar_open2
    S, T, B = 16, 512, 32
    tile = rows.row_tile(T)
    assert tile == 256 and rows.worth_a_loop(S * T, tile)
    got = solar_open2.mixed_live_rows(tokens, B, S, T)
    assert got == rows.tile_rows(tokens, tile, S * T) == -(-tokens // tile) * tile
    assert tokens <= got < tokens + tile and got <= S * T
    run = jax.jit(lambda x, n: rows.live_rows(lambda t: t + 1, n, tile, x))
    out = np.asarray(run(jnp.zeros((S * T, 1), jnp.float32),
                         jnp.int32(tokens)))
    assert int(out.sum()) == got                 # rows that were computed


def test_each_result_keeps_the_type_fn_gives_it():
    """The loop's buffers take ``fn``'s own result types — a bfloat16
    product beside the float32 residual it was added to, as a latent
    family's blocks return them — and hold what ``fn`` of the whole
    arrays gives, bit for bit (nothing is widened, narrowed or summed
    on the way through the loop)."""
    w = jnp.asarray(np.random.default_rng(1).standard_normal((6, 6)),
                    jnp.bfloat16)

    def fn(h, x):
        y = jnp.dot(x, w)                          # bf16 x bf16 -> bf16
        return h + y, y

    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((24, 6)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((24, 6)), jnp.bfloat16)
    a, b = jax.jit(lambda n: rows.live_rows(fn, n, 8, h, x))(jnp.int32(24))
    whole_a, whole_b = jax.jit(fn)(h, x)
    assert (a.dtype, b.dtype) == (jnp.float32, jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(b, np.float32),
                                  np.asarray(whole_b, np.float32))
    np.testing.assert_array_equal(a, whole_a)


def test_fn_stands_once_in_the_program_whatever_the_rows():
    """One ``while`` and one product in the lowered program, at 3 tiles
    as at 30."""
    w = jnp.ones((16, 16), jnp.float32)

    def text(m):
        x = jax.ShapeDtypeStruct((m, 16), jnp.float32)
        return jax.jit(lambda x, n: rows.live_rows(
            lambda t: jnp.dot(t, w), n, 8, x)).lower(
                x, jax.ShapeDtypeStruct((), jnp.int32)).as_text()

    for m in (24, 240):
        t = text(m)
        assert len(re.findall(r"stablehlo\.while", t)) == 1
        assert len(re.findall(r"stablehlo\.dot_general", t)) == 1


def test_a_last_tile_that_would_overrun_is_moved_back():
    """20 rows in tiles of 8: the third tile covers rows 12-19, and the
    rows it computes again come out as they were."""
    x = jnp.arange(20.0)[:, None] * jnp.ones((1, 2))
    out = rows.live_rows(lambda t: t + 1, jnp.int32(20), 8, x)
    np.testing.assert_array_equal(out, x + 1)
    out = rows.live_rows(lambda t: t + 1, jnp.int32(16), 8, x)
    np.testing.assert_array_equal(out[:16], x[:16] + 1)
    assert not np.asarray(out[16:]).any()


def test_a_pytree_of_results_keeps_its_shape():
    out = rows.live_rows(lambda t: {"a": t, "b": (t, t[:, 0])},
                         jnp.int32(3), 4, jnp.ones((12, 2)))
    assert set(out) == {"a", "b"} and out["b"][1].shape == (12,)
    assert np.asarray(out["a"][:4]).all() and not np.asarray(
        out["a"][4:]).any()


@pytest.mark.parametrize("lead", [0, 3])
def test_the_grid_and_back(lead):
    """Tight rows cut onto the (S, T) grid hold each slice's tokens in
    its first ``length`` rows; laid back in order they are the tight
    rows again (what lay past a slice's length is overwritten by the
    slice behind it), an unused slice costing no live row."""
    S, T = 4, 8
    lens = np.array([8, 3, 5, 1])              # the last is unused
    tok = np.arange(S * T).reshape(S, T) + 100
    tight_tok, tight_pos, starts = rows.pack_grid(
        tok, np.tile(np.arange(T), (S, 1)), lens, used=3)
    assert list(starts) == [0, 8, 11, 16, 16]
    assert list(tight_tok[:16]) == (list(tok[0]) + list(tok[1, :3])
                                    + list(tok[2, :5]))
    assert not tight_tok[16:].any() and not tight_pos[16:].any()
    assert list(tight_pos[8:11]) == [0, 1, 2]
    x = jnp.concatenate([jnp.full((lead, 2), -1.0),
                         jnp.asarray(tight_tok, jnp.float32)[:, None]
                         * jnp.ones((1, 2))])
    grid = rows.rows_to_grid(x, jnp.asarray(starts), T, lead=lead)
    assert grid.shape == (S, T, 2)
    for s in range(3):
        np.testing.assert_array_equal(grid[s, :lens[s], 0],
                                      tok[s, :lens[s]])
    assert float(grid[3, 0, 0]) == 0.0         # the first dead row
    # what a consumer leaves past the lengths must not come back
    dirty = jnp.where(jnp.arange(T)[None, :, None] < lens[:, None, None],
                      grid, 1e9)
    back = rows.grid_to_rows(dirty, jnp.asarray(starts), jnp.zeros_like(x)
                             .at[:lead].set(-1.0), lead=lead)
    np.testing.assert_array_equal(back[:lead + 16], x[:lead + 16])


def test_the_grids_positions_from_the_tight_ones():
    """A fresh slice, a continuation from position 40, and an unused
    slice: contiguous from each slice's first position, held at its
    last valid one, the unused slice one token at position 0."""
    tok = np.zeros((3, 4), np.int32)
    pos = np.array([[0, 1, 2, 3], [40, 41, 0, 0], [0, 0, 0, 0]])
    lens = np.array([4, 2, 1])
    _, tight_pos, starts = rows.pack_grid(tok, pos, lens, used=2)
    grid, ctx = rows.grid_positions(jnp.asarray(tight_pos),
                                    jnp.asarray(lens), jnp.asarray(starts), 4)
    np.testing.assert_array_equal(grid, [[0, 1, 2, 3], [40, 41, 41, 41],
                                         [0, 0, 0, 0]])
    np.testing.assert_array_equal(ctx, [4, 42, 1])


def test_tile_rows_is_the_loops_rule():
    assert rows.row_tile(512) == rows.row_tile(256) == 256 == rows.ROW_TILE
    assert rows.row_tile(64) == 64
    assert rows.tile_rows(1000, 256, 2048) == 1024
    assert rows.tile_rows(1000, 256, 2048, lead=128) == 5 * 256 - 128
    assert rows.tile_rows(2048, 256, 2048, lead=128) == 2048
    assert rows.tile_rows(0, 256, 2048) == 0
