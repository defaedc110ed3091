"""Mosaic's verdict without a chip: the serving path's prefill attention
kernel compiled for a DESCRIBED v5e at the served widths.

Interpret mode (tests/test_pallas.py) checks the kernel's arithmetic
and cannot see what the TPU compiler refuses: a lane slice off the
tiling, a scratch set over the VMEM limit, a DMA it cannot express.
The compiler is installed with JAX and compiles for a topology that is
described, not attached; nothing runs, so this says nothing about
results or times. Every such compile lives in THIS file: the worker
that is given it loads the TPU library once, inside the fixture, and no
module describes a topology while it is imported.
"""

import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


#: name -> (T, H, H_kv, D, page_size, max_pages, layers, pool pages):
#: the benchmark's cells (both buckets), the smoke's llama3-1b, and
#: llama3-8b at its 128-token serving page.
_GEOMETRIES = {
    "smollm2-1.7b-b256": (256, 32, 32, 64, 16, 256, 24, 3328),
    "smollm2-1.7b-b1024": (1024, 32, 32, 64, 16, 256, 24, 3328),
    "llama3-1b-b512": (512, 32, 8, 64, 16, 128, 16, 512),
    "llama3-8b-ps128-b512": (512, 32, 8, 128, 128, 16, 32, 264),
}


@pytest.mark.parametrize("name", sorted(_GEOMETRIES))
def test_prefill_attention_compiles_for_v5e(one_chip, name):
    from llmq_tpu.ops.pallas.prefill_attention import (
        paged_prefill_attention_pallas)

    T, H, Hkv, D, ps, mp, L, P = _GEOMETRIES[name]

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = arg((L, P, ps, Hkv * D), jnp.bfloat16)
    compiled = jax.jit(paged_prefill_attention_pallas).lower(
        arg((T, H, D), jnp.bfloat16), pool, pool, arg((mp,), jnp.int32),
        arg((), jnp.int32), arg((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
