"""The grouped product's tiles (``ops/moe.gmm_tiling``): a function of
the call's shapes alone, held here at the two products of every routed
configuration the benchmark serves, and once through the kernel in
interpret mode at tiles that are not the ones one model was tuned at."""
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from llmq_tpu.ops import moe

_CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs", "*.json")


def _products():
    """(configuration, product, K, N) of every configuration file that
    has routed experts: gate-up (hidden -> 2 x expert width) and down."""
    out = []
    for path in sorted(glob.glob(_CONFIGS)):
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        F = doc.get("moe_intermediate_size") or doc.get(
            "expert_ffn_hidden_size")
        if F:
            D = doc["hidden_size"]
            out += [(doc["name"], "gate-up", D, 2 * F),
                    (doc["name"], "down", F, D)]
    return out


PRODUCTS = _products()


def test_the_benchmark_serves_eight_routed_configurations():
    assert len(PRODUCTS) == 16
    assert ("mellum2-12b-a2.5b-bf16", "down", 896, 2304) in PRODUCTS
    # the eighth (PR 58): experts of 3,584 x 2,048 and 1,024 x 3,584
    assert ("xing4.0-29b-a4b-bf16-pp7", "gate-up", 3584, 2048) in PRODUCTS
    assert moe.gmm_tiling(2048, 3584, 2048) == (128, 3584, 512)
    assert moe.gmm_tiling(128, 1024, 3584) == (128, 1024, 1792)


@pytest.mark.parametrize("m", [128, 256, 2048, 6528])
@pytest.mark.parametrize("name,product,K,N", PRODUCTS,
                         ids=[f"{p[0]}-{p[1]}" for p in PRODUCTS])
def test_tiles_divide_their_matrix_and_fit_vmem(name, product, K, N, m):
    tm, tk, tn = moe.gmm_tiling(m, K, N)
    assert tm % 128 == 0 and tk % 128 == 0 and tn % 128 == 0
    assert K % tk == 0 and N % tn == 0          # no masked step, no overhang
    assert moe.gmm_tile_bytes(tm, tk, tn) <= moe.VMEM_BUDGET
    assert moe.VMEM_BUDGET < moe.VMEM_SCOPED


def test_the_rule_keeps_the_tiles_that_fit_their_matrix():
    """768 x 2,048 (Kanana's down product) is one block under the
    budget: the tiles it was served at before the rule."""
    assert moe.gmm_tiling(384, 768, 2048) == (128, 768, 2048)


def test_a_width_that_is_no_multiple_of_128_is_one_block():
    """A tiny model's widths: the whole dimension, never a block that
    overhangs it."""
    assert moe.gmm_tiling(128, 64, 96) == (128, 64, 96)


@pytest.mark.parametrize("budget,rows", [(None, 300), (300_000, 330)],
                         ids=["one-block", "several-steps"])
def test_the_kernel_at_the_rule_s_tiles_is_the_ragged_product(monkeypatch,
                                                              budget, rows):
    """Interpret mode at 256 x 384, tiles that are not (128, 768,
    2048) — under a small budget (128, 128, 128): two contraction steps
    and three output tiles —, with an empty group and rows behind the
    last group, against ``lax.ragged_dot``. (The rows differ by case:
    megablox's ``gmm`` is jitted with the rule as a static argument, so
    equal shapes would be served the first case's trace.)"""
    if budget:
        monkeypatch.setattr(moe, "VMEM_BUDGET", budget)
    moe.gmm_tiling.cache_clear()
    try:
        K, N = 256, 384
        tiles = moe.gmm_tiling(384, K, N)
        assert tiles != (128, 768, 2048)
        assert tiles == ((128, 128, 128) if budget else (128, K, N))
        counts = jnp.array([100, 0, 150, 30], jnp.int32)  # 280 of rows
        kx, kw = jax.random.split(jax.random.key(0))
        xs = jax.random.normal(kx, (rows, K), jnp.bfloat16)
        w = jax.random.normal(kw, (4, K, N), jnp.bfloat16) / K ** 0.5
        got = moe.moe_grouped_matmul_pallas(xs, w, counts, interpret=True)
        want = lax.ragged_dot(xs, w, counts)
        assert got.shape == (rows, N) and got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got[:280], np.float32),
            np.asarray(want[:280], np.float32), atol=0.05, rtol=0.02)
    finally:
        moe.gmm_tiling.cache_clear()
