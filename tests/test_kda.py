"""Delta-rule linear attention with a decay a channel (``ops/kda.py``,
``ops/pallas/kda_update.py``), and what the PR that brought it added to
ops the other families share: ``ops/moe.route``'s group limit and
``models/latent.head_gate``.

The chunked scan — XLA's and the kernel's (``ops/pallas/kda_scan.py``,
in interpret mode) — is held to the one-token update applied T times
(the two disagreeing is the family's likeliest fault), down to the
decay's floor; the in-place kernel, in interpret mode, to the update's
XLA form;
and with its defaults ``route`` traces to the program it was before
there were groups, as the latent attention does for a tree without a
gate leaf: the jaxpr's digest is the one recorded at the parent commit.
"""

import hashlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from llmq_tpu.models import deepseek_v3 as ds  # noqa: E402
from llmq_tpu.models import latent  # noqa: E402
from llmq_tpu.ops import kda, moe  # noqa: E402
from llmq_tpu.ops.pallas.kda_scan import (kda_scan_heads,  # noqa: E402
                                          kda_scan_pallas, kda_scan_viable)
from llmq_tpu.ops.pallas.kda_update import (head_blocks,  # noqa: E402
                                            kda_update_pallas,
                                            kda_update_viable)
from llmq_tpu.ops.ssm import decode_walk  # noqa: E402


def _inputs(rng, S, T, H, dk, dv, floor=False):
    f32 = jnp.float32
    q = kda.l2_norm(jnp.asarray(rng.normal(size=(S, T, H, dk)), f32))
    k = kda.l2_norm(jnp.asarray(rng.normal(size=(S, T, H, dk)), f32))
    v = jnp.asarray(rng.normal(size=(S, T, H, dv)), f32)
    g = (jnp.full((S, T, H, dk), -5.0) if floor else -5 * jax.nn.sigmoid(
        jnp.asarray(3 * rng.normal(size=(S, T, H, dk)) - 2, f32)))
    beta = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(S, T, H)), f32))
    return q * dk ** -0.5, k, v, g, beta


def _token_by_token(state, q, k, v, g, beta, lengths):
    outs = []
    for t in range(q.shape[1]):
        o, state = kda.kda_update(state, q[:, t], k[:, t], v[:, t], g[:, t],
                                  beta[:, t], jnp.asarray(lengths) > t)
        outs.append(o)
    return jnp.stack(outs, 1), state


def _kernel_scan(heads=0):
    def scan(state, q, k, v, g, beta, lengths, chunk):
        return kda_scan_pallas(state, q, k, v, g, beta, lengths, block=chunk,
                               heads=heads, interpret=True)
    return scan


#: (scan, its chunk, (S, T, H, d_k, d_v), lengths, the state before).
#: XLA's scan at small widths; the kernel (interpret mode) at the one
#: width it is written for, 64-token steps around blocks of ``chunk``.
SCANS = [
    pytest.param(kda.kda_scan, 8, (2, 37, 2, 16, 8), [37, 21], "random",
                 id="8"),
    pytest.param(kda.kda_scan, 16, (2, 37, 2, 16, 8), [37, 21], "random",
                 id="16"),
    pytest.param(kda.kda_scan, 64, (2, 37, 2, 16, 8), [37, 21], "random",
                 id="64"),
    # a length inside a 16-token block, on a block's edge inside a step,
    # on a step's edge, and a full slice
    pytest.param(_kernel_scan(), 16, (4, 128, 2, 128, 128),
                 [70, 80, 64, 128], "random", id="kernel-lengths"),
    # a slice of length 0 beside a full one: its state back to the bit
    pytest.param(_kernel_scan(), 16, (2, 128, 1, 128, 128), [0, 128],
                 "random", id="kernel-empty-slice"),
    # H * d_v of two head blocks, from a zero state, blocks of 8
    pytest.param(_kernel_scan(heads=2), 8, (1, 64, 4, 128, 128), [50],
                 "zero", id="kernel-head-blocks"),
    pytest.param(_kernel_scan(heads=1), 32, (2, 64, 1, 128, 128), [64, 33],
                 "random", id="kernel-blocks-of-32"),
]


@pytest.mark.parametrize("scan, chunk, shape, lengths, before", SCANS)
def test_the_scan_is_the_update_applied_a_token_at_a_time(
        scan, chunk, shape, lengths, before):
    """Slices that end in the middle of a chunk, on its edges and not at
    all: outputs at the valid positions and the state behind each
    slice's LAST VALID token, from a state that is not zero; an empty
    slice's state comes back as it went in."""
    rng = np.random.default_rng(0)
    S, T, H, dk, dv = shape
    ins = _inputs(rng, S, T, H, dk, dv)
    state = jnp.asarray(rng.normal(size=(S, dk, H * dv)), jnp.float32)
    if before == "zero":
        state = jnp.zeros_like(state)
    want_o, want_s = _token_by_token(state, *ins, lengths)
    got_o, got_s = scan(state, *ins, jnp.asarray(lengths), chunk)
    valid = (np.arange(T)[None] < np.asarray(lengths)[:, None])
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(np.asarray(got_o)[valid],
                               np.asarray(want_o)[valid], atol=2e-6)
    np.testing.assert_allclose(got_s, want_s, atol=5e-6)
    for s, n in enumerate(lengths):
        if n == 0:
            assert (np.asarray(got_s[s]) == np.asarray(state[s])).all()


@pytest.mark.parametrize("scan, shape", [
    pytest.param(kda.kda_scan, (1, 256, 2, 16, 8), id="xla"),
    pytest.param(_kernel_scan(), (1, 256, 1, 128, 128), id="kernel")])
def test_the_scan_at_the_decays_floor_neither_overflows_nor_underflows(
        scan, shape):
    """g = -5 every token and channel, 256 tokens: a cumulative decay
    of exp(-1280), which no float32 holds; the scan works in differences
    inside a chunk (the kernel: inside a block, and through a reference
    point from block to block) and agrees with the update to float32."""
    rng = np.random.default_rng(1)
    S, T, H, dk, dv = shape
    ins = _inputs(rng, S, T, H, dk, dv, floor=True)
    state = jnp.asarray(rng.normal(size=(S, dk, H * dv)), jnp.float32)
    want_o, want_s = _token_by_token(state, *ins, [T])
    got_o, got_s = scan(state, *ins, jnp.asarray([T]), 16)
    assert np.isfinite(np.asarray(got_o)).all()
    assert np.isfinite(np.asarray(got_s)).all()
    np.testing.assert_allclose(got_o, want_o, atol=2e-6)
    np.testing.assert_allclose(got_s, want_s, atol=2e-6)


def test_the_scan_kernel_takes_the_served_shape_and_leaves_the_rest_to_xla(
        monkeypatch):
    """``kda_scan_viable`` on the shapes the kernel is written for, and
    ``kda_scan_slices`` by ``_kernel_route``'s policy: XLA's scan where
    the kernel is off or the shape is not its, the kernel's result under
    ``LLMQ_PALLAS=interpret``."""
    assert kda_scan_viable(128, 32, 128, 512, 16)      # the served shape
    for dk, H, dv, T, chunk in ((16, 32, 128, 512, 16), (128, 32, 64, 512, 16),
                                (128, 32, 128, 500, 16), (128, 32, 128, 32, 16),
                                (128, 32, 128, 512, 12), (128, 32, 128, 512, 4),
                                (128, 32, 128, 512, 128)):
        assert not kda_scan_viable(dk, H, dv, T, chunk)
    assert [kda_scan_heads(H) for H in (32, 6, 3, 1)] == [4, 2, 1, 1]
    rng = np.random.default_rng(5)
    S, T, H, d = 2, 64, 1, 128
    ins = _inputs(rng, S, T, H, d, d)
    state = jnp.asarray(rng.normal(size=(S, d, H * d)), jnp.float32)
    lengths = jnp.asarray([64, 9])
    monkeypatch.setenv("LLMQ_PALLAS", "0")
    assert kda.scan_route(d, H, d, T, 16) == (False, False)
    o_x, s_x = kda.kda_scan_slices(state, *ins, lengths, 16)
    want_o, want_s = kda.kda_scan(state, *ins, lengths, 16)
    assert (np.asarray(o_x) == np.asarray(want_o)).all()
    assert (np.asarray(s_x) == np.asarray(want_s)).all()
    monkeypatch.setenv("LLMQ_PALLAS", "interpret")
    assert kda.scan_route(d, H, d, T, 16) == (True, True)
    assert kda.scan_route(d, H, d, T, 16, enabled=False) == (False, False)
    assert kda.scan_route(d, H, d, T + 8, 16) == (False, False)
    o_k, s_k = kda.kda_scan_slices(state, *ins, lengths, 16)
    assert (np.asarray(o_k[1, 9:]) != np.asarray(o_x[1, 9:])).any()  # kernel's
    valid = np.arange(T)[None] < np.asarray(lengths)[:, None]
    np.testing.assert_allclose(np.asarray(o_k)[valid],
                               np.asarray(o_x)[valid], atol=2e-6)
    np.testing.assert_allclose(s_k, s_x, atol=5e-6)


def test_the_update_kernel_is_the_update_and_touches_only_the_live_rows():
    """Interpret mode, the kernel's shapes (a head's values one 128-lane
    tile): the live rows' outputs and states are ``kda_update``'s, every
    other row, the row that is nobody's and the other layers come back
    to the bit."""
    rng = np.random.default_rng(2)
    B, H, dk, dv, L = 5, 2, 16, 128, 3
    assert kda_update_viable(dk, H, dv) and not kda_update_viable(dk, H, 64)
    assert kda_update_viable(128, 32, 128)        # the served shape
    pool = jnp.asarray(rng.normal(size=(L, B + 1, dk, H * dv)), jnp.float32)
    q, k, v, g, beta = (x[:, 0] for x in _inputs(rng, B, 1, H, dk, dv))
    active = jnp.asarray([True, False, True, True, False])
    o_k, pool_k = kda_update_pallas(pool, 1, q, k, v, g, beta,
                                    *decode_walk(active), interpret=True)
    o_x, new = kda.kda_update(pool[1, :B], q, k, v, g, beta, active)
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(o_k)[live], np.asarray(o_x)[live],
                               atol=2e-6)
    np.testing.assert_allclose(pool_k[1, :B], new, atol=2e-6)
    assert (np.asarray(o_k)[~live] == 0).all()
    for l, rows in ((0, slice(None)), (2, slice(None)), (1, [1, 4, 5])):
        assert (np.asarray(pool_k[l, rows]) == np.asarray(pool[l, rows])).all()
    # no live row: nothing is moved
    none = jnp.zeros((B,), bool)
    o_0, pool_0 = kda_update_pallas(pool, 1, q, k, v, g, beta,
                                    *decode_walk(none), interpret=True)
    assert (np.asarray(pool_0) == np.asarray(pool)).all()
    assert (np.asarray(o_0) == 0).all()


def _kimi_inputs(rng, S, T, H, dk, dv, strong=False):
    """``_inputs`` with the decay and beta as the published Kimi form
    makes them (``ops/kda.kimi_decay``; beta = 2 sigmoid): a log-decay
    unbounded below — ``strong``: -30 to -60 a token in half the
    channels, so that a block's factor ``exp(G)`` underflows float32
    within two tokens, and about -0.01 in the rest, which remember —
    and beta on both sides of 1."""
    f32 = jnp.float32
    q, k, v, _, _ = _inputs(rng, S, T, H, dk, dv)
    a_log = jnp.asarray(rng.uniform(np.log(0.5), np.log(4.0), (H,)), f32)
    bias = rng.uniform(-8.0, 2.0, (H, dk))
    if strong:
        bias = np.where(rng.random((H, dk)) < 0.5, 30.0, -6.0)
    g = kda.kimi_decay(jnp.asarray(rng.normal(size=(S * T, H * dk)), f32),
                       a_log, jnp.asarray(bias, f32).reshape(-1)
                       ).reshape(S, T, H, dk)
    beta = 2 * jax.nn.sigmoid(jnp.asarray(1.5 * rng.normal(size=(S, T, H)),
                                          f32))
    assert float(beta.max()) > 1.5 and float(beta.min()) < 0.5
    assert not strong or float(g.min()) < -30
    return q, k, v, g, beta


@pytest.mark.parametrize("strong", [False, True],
                         ids=["spans-its-range", "underflows-a-block"])
@pytest.mark.parametrize("scan, shape, lengths", [
    pytest.param(kda.kda_scan, (2, 70, 2, 16, 8), [70, 37], id="xla"),
    pytest.param(_kernel_scan(), (2, 128, 2, 128, 128), [128, 70],
                 id="kernel")])
def test_the_scan_with_beta_up_to_two_and_an_unbounded_decay(
        scan, shape, lengths, strong):
    """The published Kimi form (``models/solar_open2.py``): beta in
    (0, 2), so ``I - beta k k^T`` has a NEGATIVE eigenvalue for beta > 1,
    and a decay with no floor. Both reach the scan as data; its
    unit-lower solve is exact for any beta, and every factor it forms is
    of a sum that only falls — one that underflows is of a term whose
    true value underflows too. Held to the update a token at a time."""
    rng = np.random.default_rng(11)
    S, T, H, dk, dv = shape
    ins = _kimi_inputs(rng, S, T, H, dk, dv, strong)
    state = jnp.asarray(rng.normal(size=(S, dk, H * dv)), jnp.float32)
    want_o, want_s = _token_by_token(state, *ins, lengths)
    got_o, got_s = scan(state, *ins, jnp.asarray(lengths), 16)
    valid = (np.arange(T)[None] < np.asarray(lengths)[:, None])
    assert np.isfinite(np.asarray(got_o)).all()
    assert np.isfinite(np.asarray(got_s)).all()
    np.testing.assert_allclose(np.asarray(got_o)[valid],
                               np.asarray(want_o)[valid], atol=4e-6)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)


def test_the_low_rank_pair_and_the_kimi_decay():
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.normal(size=(5, 32)), jnp.float32)
    down = jnp.asarray(rng.normal(size=(32, 4)), jnp.float32)
    up = jnp.asarray(rng.normal(size=(4, 24)), jnp.float32)
    want = np.asarray(x) @ np.asarray(down) @ np.asarray(up)
    for exact in (False, True):
        np.testing.assert_allclose(kda.low_rank(x, down, up, exact=exact),
                                   want, rtol=1e-5, atol=1e-5)
    # bfloat16 operands: the exact form keeps the float32 intermediate
    xb, db, ub = (y.astype(jnp.bfloat16) for y in (x, down, up))
    mid = np.asarray(xb, np.float32) @ np.asarray(db, np.float32)
    np.testing.assert_allclose(
        kda.low_rank(xb, db, ub, exact=True),
        mid @ np.asarray(ub, np.float32), rtol=1e-5, atol=1e-5)
    f = jnp.asarray(rng.normal(size=(5, 3, 8)), jnp.float32)
    a_log = jnp.asarray([0.0, 1.0, -1.0])
    bias = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    # heads side by side on the lanes, in and out
    g = kda.kimi_decay(f.reshape(5, 24), a_log, bias.reshape(24))
    assert g.shape == (5, 24)
    np.testing.assert_allclose(
        g.reshape(5, 3, 8), -np.exp(np.asarray(a_log))[:, None] * np.log1p(
            np.exp(np.asarray(f) + np.asarray(bias))), rtol=1e-5)
    assert float(g.max()) < 0


@pytest.mark.parametrize("H, blocks", [(64, 2), (32, 1)])
def test_the_update_kernel_walks_a_row_in_head_blocks(H, blocks):
    """Interpret mode at a short d_k: 64 heads are two blocks of 32 a
    live row (the packed columns a block, a block's lanes of every
    sublane of the row), 32 heads one block — the whole row, the call
    the 32-head family ran before there were blocks. Either way the live
    rows' outputs and states are ``kda_update``'s with beta up to 2 and
    an unbounded decay, and nothing else is moved."""
    rng = np.random.default_rng(13)
    B, dk, dv, L = 4, 8, 128, 2
    assert head_blocks(H) == blocks
    assert kda_update_viable(dk, H, dv)
    pool = jnp.asarray(rng.normal(size=(L, B + 1, dk, H * dv)), jnp.float32)
    q, k, v, g, beta = (x[:, 0] for x in _kimi_inputs(rng, B, 1, H, dk, dv))
    active = jnp.asarray([True, False, True, True])
    o_k, pool_k = kda_update_pallas(pool, 1, q, k, v, g, beta,
                                    *decode_walk(active), interpret=True)
    o_x, new = kda.kda_update(pool[1, :B], q, k, v, g, beta, active)
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(o_k)[live], np.asarray(o_x)[live],
                               atol=2e-6)
    np.testing.assert_allclose(pool_k[1, :B], new, atol=2e-6)
    assert (np.asarray(o_k)[~live] == 0).all()
    for l, rows in ((0, slice(None)), (1, [1, 4])):
        assert (np.asarray(pool_k[l, rows]) == np.asarray(pool[l, rows])).all()


def test_the_update_kernel_s_head_block_rule():
    """``kda_update_viable``: whole rows up to 32 heads (their four
    columns a head fit 128 lanes), whole blocks of 32 above, three
    blocks within the walk's 8 MiB."""
    assert kda_update_viable(128, 64, 128)        # two 2 MiB blocks a row
    assert kda_update_viable(128, 32, 128)        # one: the row
    assert kda_update_viable(128, 96, 128) and head_blocks(96) == 3
    assert [head_blocks(h) for h in (1, 2, 31, 32, 33, 48, 64)] == [
        1, 1, 1, 1, 0, 0, 2]
    assert not kda_update_viable(128, 48, 128)    # no whole blocks
    assert not kda_update_viable(256, 64, 128)    # three blocks of 4 MiB
    assert not kda_update_viable(128, 64, 64)


def test_the_update_layer_routes_to_the_kernel_under_interpret(monkeypatch):
    rng = np.random.default_rng(3)
    B, H, dk, dv = 3, 2, 8, 128
    pool = jnp.asarray(rng.normal(size=(2, B + 1, dk, H * dv)), jnp.float32)
    q, k, v, g, beta = (x[:, 0] for x in _inputs(rng, B, 1, H, dk, dv))
    active = jnp.asarray([True, True, False])
    monkeypatch.setenv("LLMQ_PALLAS", "0")
    assert kda.update_route(dk, H, dv) == (False, False)
    o_x, pool_x = kda.kda_update_layer(pool, 0, q, k, v, g, beta, active)
    monkeypatch.setenv("LLMQ_PALLAS", "interpret")
    assert kda.update_route(dk, H, dv) == (True, True)
    assert kda.update_route(dk, H + 31, dv) == (False, False)  # 4 H > 128
    o_k, pool_k = kda.kda_update_layer(pool, 0, q, k, v, g, beta, active)
    np.testing.assert_allclose(np.asarray(o_k)[:2], np.asarray(o_x)[:2],
                               atol=2e-6)
    np.testing.assert_allclose(pool_k, pool_x, atol=2e-6)


def test_the_conv_step_moves_the_live_rows_windows_alone():
    from llmq_tpu.ops import ssm
    rng = np.random.default_rng(4)
    B, C, K = 4, 24, 4
    pool = jnp.asarray(rng.normal(size=(2, B + 1, (K - 1) * C)),
                       jnp.bfloat16)
    x = jnp.asarray(rng.normal(size=(B, C)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(C, K)), jnp.float32)
    active = jnp.asarray([True, False, True, True])
    y, new = kda.conv_step(pool, 1, x, w, active)
    want_y, want_win = ssm.conv_step(pool[1, :B].reshape(B, K - 1, C), x, w,
                                     jnp.zeros((C,)))
    np.testing.assert_allclose(y, want_y, atol=1e-6)
    got = np.asarray(new[1, :B], np.float32).reshape(B, K - 1, C)
    live = np.asarray(active)
    assert (got[live] == np.asarray(want_win, np.float32)[live]).all()
    assert (np.asarray(new[1, 1]) == np.asarray(pool[1, 1])).all()
    assert (np.asarray(new[0]) == np.asarray(pool[0])).all()


# -- ``ops/moe.route``'s group limit ---------------------------------------------


def test_the_group_limit_keeps_the_best_groups_by_their_top_two():
    """8 experts in 4 groups, the best 2 groups kept, top 3: group 0
    holds the single best expert but loses on its top two; the choice
    comes from groups 1 and 3 alone, and the gates are the chosen
    sigmoid scores WITHOUT the bias, normalised and scaled."""
    logits = jnp.asarray([[4.0, -4.0, 2.0, 1.9, -1.0, -1.2, 1.5, 1.4]])
    x, w = jnp.ones((1, 1)), logits
    bias = jnp.asarray([0.0, 0, 0, 0, 0, 0, 0.3, 0])
    experts, gates = moe.route(x, w, bias, top_k=3, scale=2.5, n_group=4,
                               topk_group=2)
    assert sorted(np.asarray(experts)[0].tolist()) == [2, 3, 6]
    s = jax.nn.sigmoid(logits[0])
    want = s[jnp.asarray([2, 3, 6])]
    np.testing.assert_allclose(np.sort(np.asarray(gates)[0]),
                               np.sort(np.asarray(want / want.sum() * 2.5)),
                               rtol=1e-6)
    free, _ = moe.route(x, w, bias, top_k=3, scale=2.5)
    assert 0 in np.asarray(free)[0]
    with pytest.raises(ValueError, match="groups"):
        moe.route(x, w, bias, top_k=3, scale=1.0, n_group=3, topk_group=1)


#: sha256[:16] of ``str(jax.make_jaxpr(fn)(...))`` at the shapes below,
#: recorded at the parent of the PR that brought the groups and the gate
#: (PR 44's tree). A jaxpr's text carries no source lines. The accepted
#: routed families (Kanana, LongCat, Trinity) call ``route`` without
#: groups and the latent ones have no gate leaf: their cells are held to
#: what these programs do.
PARENT_DIGESTS = {"route_sigmoid": "da337cdcad95554f",
                  "route_softmax": "4f853eafa2a29113",
                  "latent_decode_step": "32101a1e901fa961"}


def _digest(fn, *args):
    return hashlib.sha256(str(jax.make_jaxpr(fn)(*args)).encode()
                          ).hexdigest()[:16]


def _digests():
    z = jnp.zeros
    x, w, b = z((24, 64), jnp.float32), z((64, 16), jnp.bfloat16), z((16,))
    cfg = ds.deepseek_v3_tiny(max_seq_len=64)
    params = jax.eval_shape(lambda: ds.init_params(jax.random.PRNGKey(0),
                                                   cfg))
    cache = jax.eval_shape(lambda: ds.init_kv_pages(cfg, 9, 8))

    def step(params, cache, tok, pos, bts):
        return ds.forward_decode.__wrapped__(params, cfg, tok, pos, cache,
                                             bts)

    return {
        "route_sigmoid": _digest(
            lambda x, w, b: moe.route(x, w, b, top_k=4, scale=2.5), x, w, b),
        "route_softmax": _digest(
            lambda x, w, b: moe.route(x, w, b, top_k=4, scale=1.0,
                                      norm_topk=False, scoring="softmax"),
            x, w, b),
        "latent_decode_step": _digest(
            step, params, cache, z((4,), jnp.int32), z((4,), jnp.int32),
            z((4, 8), jnp.int32))}


@pytest.mark.parametrize("program", sorted(PARENT_DIGESTS))
def test_the_defaults_trace_to_the_parent_s_program(program):
    assert _digests()[program] == PARENT_DIGESTS[program]


def test_the_head_gate_without_its_leaf_is_nothing():
    cfg = ds.deepseek_v3_tiny()
    o = jnp.ones((3, cfg.n_heads * cfg.v_head_dim), jnp.bfloat16)
    x = jnp.ones((3, cfg.dim), jnp.bfloat16)
    assert latent.head_gate(cfg, {}, 0, x, o) is o
    gate = {"w_head_gate": jnp.zeros((1, cfg.dim, cfg.n_heads),
                                     jnp.bfloat16)}
    np.testing.assert_allclose(
        np.asarray(latent.head_gate(cfg, gate, 0, x, o), np.float32), 0.5)


def test_the_prefill_program_through_the_scan_kernel_is_the_program(
        monkeypatch):
    """A prompt in two slices of the prefill program (64 tokens, then 36:
    the state carried through the row-state leaf, the second slice ending
    inside a block) at the kernel's head width: routed to the scan kernel
    (interpret mode) the logits and the KDA layers' states are what XLA's
    scan gives."""
    from llmq_tpu.models import ling_hybrid as lh
    cfg = lh.ling_hybrid_tiny(dtype=jnp.float32, max_seq_len=128, n_layers=3,
                              kda_head_dim=128, kda_chunk=16)
    params = lh.init_params(jax.random.PRNGKey(46), cfg)
    seq = np.random.default_rng(46).integers(3, cfg.vocab_size, 100,
                                             dtype=np.int32)
    table = jnp.asarray(1 + np.arange(cfg.max_seq_len // 8)[None], jnp.int32)

    def served(mode):
        monkeypatch.setenv("LLMQ_PALLAS", mode)
        jax.clear_caches()              # the route is read at trace time
        assert lh.routes(cfg, None, batch=1, page_size=8, max_pages=16,
                         prefill_rows=1)["ssm_scan"].startswith(
            "xla" if mode == "0" else "pallas-interpret:kda_scan_pallas")
        cache = lh.init_kv_pages(cfg, 1 + cfg.max_seq_len // 8, 8)
        state = lh.init_row_state(cfg, 1)
        for start, end in ((0, 64), (64, 100)):
            toks = np.zeros((1, 64), np.int32)
            toks[0, :end - start] = seq[start:end]
            pos = start + np.minimum(np.arange(64, dtype=np.int32),
                                     end - start - 1)[None]
            logits, cache, state = lh.forward_prefill(
                params, cfg, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray([end - start], jnp.int32), cache, table,
                last_only=True, row_state=state,
                rows=jnp.zeros((1,), jnp.int32))
        return np.asarray(logits), np.asarray(state["kda"])

    try:
        want_logits, want_state = served("0")
        got_logits, got_state = served("interpret")
    finally:
        jax.clear_caches()      # no interpret-mode trace for other tests
    np.testing.assert_allclose(got_logits, want_logits, atol=1e-4)
    np.testing.assert_allclose(got_state, want_state, atol=1e-5)
    assert np.abs(want_state[:, 0]).max() > 0.1
