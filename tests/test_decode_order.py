"""A decode step's rows reach the attention kernel ordered by context,
and nothing outside the attention call can tell (PR 43).

Every family that runs the fused decode kernel makes
``ops/attention.decode_order`` once a step and hands it to each layer's
``paged_decode_step*`` with the step's block tables, ``seq_lens`` and
``page_of`` laid out by it; the hidden rows, the cache writes and the
logits stay by batch row. Held here on the CPU with the kernel in
interpret mode (an int8 pool in the 128-token pages under 8 KV heads
that its kernel serves): a step of sixteen shuffled rows (ties, one row
that is not active) through ``forward_decode`` — and ``llama``'s
``forward_mixed`` — gives the logits and the caches of the same step
with no order made, TO THE BIT, for ``llama`` over bf16 and int8 pools,
``granitemoehybrid`` and ``afmoe`` (pool and slabs, a window).
``tests/test_decode_kernels.py`` holds the kernel and the dispatchers
themselves.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from llmq_tpu.models import afmoe, granitemoehybrid, llama  # noqa: E402
from llmq_tpu.ops import attention  # noqa: E402
from llmq_tpu.ops.rows import pack_grid  # noqa: E402

ROWS = 16


def _rows(seed, pages, page):
    """Sixteen decode rows over a context of up to ``pages`` pages, rows
    3 and 7 tied, row 5 not active; their pages are 1 … of a pool, and
    two slices' rows follow them."""
    rng = np.random.default_rng(seed)
    pos = rng.integers(0, pages * page - 1, ROWS)
    pos[3] = pos[7] = 40
    active = np.ones(ROWS, bool)
    active[5] = False
    bt = 1 + np.arange((ROWS + 2) * pages).reshape(ROWS + 2, pages)
    return (jnp.asarray(rng.integers(3, 200, ROWS), jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(bt, jnp.int32),
            jnp.asarray(active), rng)


def _filled(tree, rng):
    """A cache of noise: a row's context is whatever its pages hold."""
    return jax.tree.map(
        lambda x: jnp.asarray(rng.standard_normal(x.shape) * (
            20 if x.dtype == jnp.int8 else 1), x.dtype), tree)


def _llama(kv_dtype, mixed):
    # the int8 kernel serves pages of 128 tokens under 8 KV heads alone
    heads, kv_heads, page, pages = ((8, 8, 128, 4) if kv_dtype
                                    else (4, 2, 16, 16))
    cfg = llama.get_config("llama3-tiny", dim=256, n_heads=heads,
                           n_kv_heads=kv_heads, max_seq_len=page * pages)
    params = llama.init_params(jax.random.key(0), cfg)
    tok, pos, bt, active, rng = _rows(0, pages, page)
    cache = _filled(llama.init_kv_pages(cfg, 1 + (ROWS + 2) * pages, page,
                                        dtype=kv_dtype), rng)
    if kv_dtype == jnp.int8:      # scales as scales are: small, positive
        for name in ("k_scale", "v_scale"):
            cache[name] = jnp.abs(cache[name]) * 0.01 + 0.001
    if not mixed:
        return llama, lambda: llama.forward_decode(
            params, cfg, tok, pos, dict(cache), bt[:ROWS], active=active)
    T = 8
    lengths = jnp.asarray([8, 5], jnp.int32)
    pf_tok = jnp.asarray(rng.integers(3, 200, (2, T)), jnp.int32)
    pf_pos = jnp.asarray([[10 + i for i in range(T)], list(range(T))],
                         jnp.int32)
    t, p, starts = pack_grid(pf_tok, pf_pos, lengths)
    return llama, lambda: llama.forward_mixed(
        params, cfg, tok, pos, dict(cache), bt[:ROWS], t, p, lengths, starts,
        bt[ROWS:], dec_active=active)


def _granite(page=16, pages=16):
    cfg = granitemoehybrid.granite4h_tiny(
        dtype=jnp.bfloat16, max_seq_len=page * pages, n_kv_heads=2,
        head_dim=64, pallas=True)
    params = granitemoehybrid.init_params(jax.random.key(1), cfg)
    tok, pos, bt, active, rng = _rows(1, pages, page)
    cache = _filled(granitemoehybrid.init_kv_pages(
        cfg, 1 + ROWS * pages, page), rng)
    state = granitemoehybrid.init_row_state(cfg, ROWS)
    return granitemoehybrid, lambda: granitemoehybrid.forward_decode(
        params, cfg, tok, pos, dict(cache), bt[:ROWS], active=active,
        row_state=state)


def _afmoe(page=16, pages=16):
    cfg = afmoe.bind_cache(
        afmoe.afmoe_tiny(dtype=jnp.bfloat16, max_seq_len=page * pages,
                         held_experts=(8, 16)),
        page_size=page, step_tokens=1)
    params = afmoe.init_params(jax.random.key(2), cfg)
    tok, pos, bt, active, rng = _rows(2, pages, page)
    cache = _filled(afmoe.init_kv_pages(cfg, 1 + ROWS * pages, page), rng)
    state = _filled(afmoe.init_row_state(cfg, ROWS), rng)
    return afmoe, lambda: afmoe.forward_decode(
        params, cfg, tok, pos, dict(cache), bt[:ROWS], active=active,
        row_state=state)


CASES = {"llama-bf16": lambda: _llama(None, False),
         "llama-int8-kv": lambda: _llama(jnp.int8, False),
         "llama-bf16-mixed": lambda: _llama(None, True),
         "llama-int8-kv-mixed": lambda: _llama(jnp.int8, True),
         "granitemoehybrid": _granite, "afmoe": _afmoe}


@pytest.mark.parametrize("case", list(CASES))
def test_a_step_in_the_kernel_s_order_is_the_step(monkeypatch, case):
    monkeypatch.setenv("LLMQ_PALLAS", "interpret")
    module, step = CASES[case]()
    made = []

    def spy(seq_lens, *args, **kw):
        made.append(attention.decode_order(seq_lens, *args, **kw))
        return made[-1]

    try:
        jax.clear_caches()
        monkeypatch.setattr(module, "decode_order", spy)
        ordered = jax.tree.leaves(step())
        # the kernel serves here, the order was made once, and it is no
        # identity: the rows did move
        assert len(made) == 1 and made[0] is not None
        assert not np.array_equal(made[0].rows, np.arange(ROWS))
        jax.clear_caches()
        monkeypatch.setattr(module, "decode_order", lambda *a, **k: None)
        seated = jax.tree.leaves(step())
    finally:
        jax.clear_caches()      # no interpret-mode trace for other tests
    live = np.arange(ROWS) != 5
    np.testing.assert_array_equal(np.asarray(ordered[0])[live],
                                  np.asarray(seated[0])[live])
    for a, b in zip(ordered[1:], seated[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
