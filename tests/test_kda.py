"""Delta-rule linear attention with a decay a channel (``ops/kda.py``,
``ops/pallas/kda_update.py``), and what the PR that brought it added to
ops the other families share: ``ops/moe.route``'s group limit and
``models/latent.head_gate``.

The chunked scan is held to the one-token update applied T times (the
two disagreeing is the family's likeliest fault), down to the decay's
floor; the in-place kernel, in interpret mode, to the update's XLA form;
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
from llmq_tpu.ops.pallas.kda_update import (kda_update_pallas,  # noqa: E402
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


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_the_scan_is_the_update_applied_a_token_at_a_time(chunk):
    """Two slices, one that ends in the middle of a chunk: outputs at
    the valid positions and the state behind each slice's LAST VALID
    token, from a state that is not zero."""
    rng = np.random.default_rng(0)
    S, T, H, dk, dv = 2, 37, 2, 16, 8
    ins = _inputs(rng, S, T, H, dk, dv)
    state = jnp.asarray(rng.normal(size=(S, dk, H * dv)), jnp.float32)
    lengths = [37, 21]
    want_o, want_s = _token_by_token(state, *ins, lengths)
    got_o, got_s = kda.kda_scan(state, *ins, jnp.asarray(lengths), chunk)
    valid = (np.arange(T)[None] < np.asarray(lengths)[:, None])
    np.testing.assert_allclose(np.asarray(got_o)[valid],
                               np.asarray(want_o)[valid], atol=2e-6)
    np.testing.assert_allclose(got_s, want_s, atol=5e-6)


def test_the_scan_at_the_decays_floor_neither_overflows_nor_underflows():
    """g = -5 every token and channel, 256 tokens: a cumulative decay
    of exp(-1280), which no float32 holds; the scan works in differences
    inside a chunk and agrees with the update to float32."""
    rng = np.random.default_rng(1)
    S, T, H, dk, dv = 1, 256, 2, 16, 8
    ins = _inputs(rng, S, T, H, dk, dv, floor=True)
    state = jnp.asarray(rng.normal(size=(S, dk, H * dv)), jnp.float32)
    want_o, want_s = _token_by_token(state, *ins, [T])
    got_o, got_s = kda.kda_scan(state, *ins, jnp.asarray([T]), 16)
    assert np.isfinite(np.asarray(got_o)).all()
    assert np.isfinite(np.asarray(got_s)).all()
    np.testing.assert_allclose(got_o, want_o, atol=2e-6)
    np.testing.assert_allclose(got_s, want_s, atol=2e-6)


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
