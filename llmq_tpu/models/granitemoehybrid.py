"""The Granite-4.0-H block (``model_type`` ``granitemoehybrid``) in pure
JAX: Mamba-2 state-space layers beside grouped-query attention layers in
a published order, a shared SwiGLU in every layer, no routed experts
(``num_local_experts`` 0: the dense members of the family).

With N, N' a layer's two RMSNorms and r = ``residual_multiplier``::

    x0 = embedding_multiplier * E[token]
    h  = x + r * Mixer_l(N(x))      # Mamba2 or Attn, by layer_types[l]
    y  = h + r * W_down(silu(W_gate N'(h)) * W_up N'(h))
    logits = (RMSNorm(x_L) E^T) / logits_scaling

    Attn:   no rotary embedding (``position_embedding_type`` "nope"),
            scores scaled by ``attention_multiplier`` (NOT 1/sqrt(d)).
    Mamba2: [z ; xBC ; dt] = x W_in;  xBC = silu(conv(xBC));
            [X ; B ; C] = xBC;  dt = softplus(dt + dt_bias);
            H_t = exp(dt A) H_{t-1} + dt X (x) B;  Y = H C + D X;
            out = W_out RMSNorm(Y * silu(z))        # gate BEFORE the norm

**Two kinds of cache.** The attention layers write K and V into the
page pool like every family (``init_kv_pages``: leaves over the
ATTENTION layers only). A Mamba layer carries, from token to token, its
state ``H`` and the last ``d_conv - 1`` inputs of its convolution:
ROW STATE, indexed by batch row and as large for a row of 10 tokens as
for one of 10,000 (``init_row_state``: ``ssm`` ``(L_m, rows, N, H*P)``
float32 — ``ops/ssm.py`` has the layout's reason — and ``conv``
``(L_m, rows, (d_conv - 1) * conv width)``; each leaf holds one row more
than the batch, nobody's). Every forward function takes
it as ``row_state`` beside the page pool and returns it after the pool.
A sequence's first tokens (position 0) start from a zero state inside
the program; a decode row that is not ``active`` keeps its state; a
prompt slice's state ends at its last VALID token (``ops/ssm.py``).
Pages alone no longer rebuild a sequence, so the engine adopts no cached
prefix, pinned conversation, tiering promotion or hand-over for this
family (``engine/engine.py``: ``row_state``).

The residual stream is float32, products take bf16, the recurrence is
float32 (``state_dtype``: what the leaf holds). ``forward_decode`` and
``forward_prefill`` run ONE ``fori_loop`` over the repeating period of
``layer_types`` (a period's layers unrolled in its body); the mixed step
unrolls all its layers. Measured on the chip (PERF.md section 6, PR 39):
the rolled decode step takes what the unrolled one takes (26.49 against
26.53 ms) in an executable a fifth the size (15 MB against 83), while a
rolled MIXED step is a third slower (110.8 against 83.3 ms: inside the
row-tile loops a layer's matrices, indexed by a traced layer, are
sliced out again every trip).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from llmq_tpu.models.llama import _mlp
from llmq_tpu.ops.attention import (decode_order,
                                    dispatch_prefill_attention,
                                    kernel_routes, paged_decode_step,
                                    paged_kv_write_prefill, rows_by_place)
from llmq_tpu.ops.norms import rms_norm
from llmq_tpu.ops.rows import (grid_positions, grid_to_rows, live_rows,
                               row_tile, rows_to_grid, tile_rows,
                               worth_a_loop)
from llmq_tpu.ops.ssm import (conv_slices, conv_step, decode_walk, own_rows,
                              rows_read, rows_write, ssm_scan,
                              ssm_update_layer, update_route)
from llmq_tpu.utils.profiling import scope

Params = Dict[str, Any]
KVCache = Dict[str, jnp.ndarray]
RowState = Dict[str, jnp.ndarray]

MAMBA, ATTENTION = "mamba", "attention"


@dataclass(frozen=True)
class GraniteHybridConfig:
    FAMILY: ClassVar[str] = "granitemoehybrid"   # models/__init__.py
    name: str = "granite4h-tiny"
    vocab_size: int = 512
    dim: int = 128
    #: The published ``layer_types``: "mamba" or "attention" a layer.
    layer_types: Tuple[str, ...] = (MAMBA, MAMBA, ATTENTION, MAMBA) * 2
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 32
    ffn_dim: int = 256               # shared_intermediate_size
    mamba_n_heads: int = 8
    mamba_d_head: int = 32           # n_heads * d_head = expand * dim
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 8
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0625
    logits_scaling: float = 8.0
    max_seq_len: int = 2048
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    #: What the recurrent state is HELD in between tokens (the
    #: recurrence computes in float32 either way). bfloat16 is the
    #: benchmark's control, one precision down.
    state_dtype: Any = jnp.float32
    pallas: bool = True
    pallas_batched_prefill: bool = False

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_mamba(self) -> int:
        return self.layer_types.count(MAMBA)

    @property
    def n_attention(self) -> int:
        return self.layer_types.count(ATTENTION)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: X, B and C (one group)."""
        return self.mamba_inner + 2 * self.mamba_d_state

    @property
    def period(self) -> int:
        """The shortest prefix of ``layer_types`` that repeats to the
        whole of it."""
        L = self.n_layers
        for p in range(1, L + 1):
            if L % p == 0 and all(self.layer_types[i]
                                  == self.layer_types[i % p]
                                  for i in range(L)):
                return p
        return L


def granite4h_tiny(**kw) -> GraniteHybridConfig:
    """CPU-test size: two periods of ``m m a m``, so both kinds of layer
    and both orders of neighbour occur."""
    return replace(GraniteHybridConfig(), **kw)


def granite_4_0_h_micro(**kw) -> GraniteHybridConfig:
    """ibm-granite/granite-4.0-h-micro at its published sizes
    (https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json):
    40 layers, 36 Mamba-2 and 4 attention (at 5, 15, 25, 35), hidden
    2,048, 32 query heads over 8 KV heads of 64, Mamba inner width 4,096
    as 64 heads of 64 over a state of 128 in one group, convolution of
    4, SwiGLU of 8,192 in every layer, vocabulary 100,352 tied, context
    131,072. 3,191,396,096 parameters: 6.38 GB of bf16, whole on one
    16 GB chip."""
    types = tuple(ATTENTION if i % 10 == 5 else MAMBA for i in range(40))
    return replace(GraniteHybridConfig(
        name="granite-4.0-h-micro", vocab_size=100352, dim=2048,
        layer_types=types, n_heads=32, n_kv_heads=8, head_dim=64,
        ffn_dim=8192, mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
        mamba_d_conv=4, mamba_chunk_size=256, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.015625,
        logits_scaling=8.0, max_seq_len=131072, norm_eps=1e-5), **kw)


MODEL_CONFIGS = {
    "granite4h-tiny": granite4h_tiny,
    "granite-4.0-h-micro": granite_4_0_h_micro,
}


# -- the family surface (models/__init__.py) -----------------------------------

def serving_config(cfg: GraniteHybridConfig) -> GraniteHybridConfig:
    return replace(cfg, pallas_batched_prefill=True)


def check_serving(cfg: GraniteHybridConfig, *, quantization: str = "",
                  kv_quantization: str = "", mesh: bool = False) -> None:
    """Refuse what is not written for this family, naming the setting."""
    what = None
    if quantization:
        what = (f"model.quantization={quantization!r} (no int8 form of "
                f"the Mamba mixer's projections)")
    elif kv_quantization:
        what = (f"model.kv_quantization={kv_quantization!r} (int8 pages "
                f"beside a float32 row state)")
    elif mesh:
        what = "executor.mesh (no partition rules for the row state)"
    if what:
        raise ValueError(f"model {cfg.name!r} (family granitemoehybrid) "
                         f"does not support {what}; unset it")


def import_hf(model_dir: str, cfg: GraniteHybridConfig, **kw) -> Params:
    from llmq_tpu.models.checkpoint import import_hf_granitemoehybrid
    return import_hf_granitemoehybrid(model_dir, cfg, **kw)


def step_stats_layout(cfg: GraniteHybridConfig) -> Dict[str, Any]:
    return {}


def step_stats_size(cfg: GraniteHybridConfig) -> int:
    return 0


def mixed_live_rows(tokens: int, batch: int, slices: int, width: int) -> int:
    """As ``models/llama.mixed_live_rows``: the slice rows' live tiles
    (the decode rows go through products of their own)."""
    return tile_rows(tokens, row_tile(width), slices * width)


def param_shapes(cfg: GraniteHybridConfig) -> Dict[str, Tuple[tuple, int]]:
    """Leaf of ``params["layers"]`` -> (shape, fan_in): norms and the
    SwiGLU stacked over all L layers, the attention's matrices over the
    attention layers, the mixer's over the Mamba layers. ``fan_in`` 0:
    not a matrix drawn at 1 / fan_in (``init_params`` has each)."""
    L, La, Lm, D, F = (cfg.n_layers, cfg.n_attention, cfg.n_mamba, cfg.dim,
                       cfg.ffn_dim)
    H, G, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    I, C, Hm, K = (cfg.mamba_inner, cfg.conv_width, cfg.mamba_n_heads,
                   cfg.mamba_d_conv)
    return {
        "attn_norm": ((L, D), 0), "mlp_norm": ((L, D), 0),
        "w_gate": ((L, D, F), D), "w_up": ((L, D, F), D),
        "w_down": ((L, F, D), F),
        "wq": ((La, D, H * hd), D), "wk": ((La, D, G * hd), D),
        "wv": ((La, D, G * hd), D), "wo": ((La, H * hd, D), H * hd),
        "in_proj": ((Lm, D, I + C + Hm), D),
        "conv_w": ((Lm, C, K), K), "conv_b": ((Lm, C), 0),
        "dt_bias": ((Lm, Hm), 0), "a_log": ((Lm, Hm), 0),
        "d_skip": ((Lm, Hm), 0),
        "ssm_norm": ((Lm, I), 0), "out_proj": ((Lm, I, D), I),
    }


#: The ranges ``init_params`` draws the recurrence's own parameters
#: from (Mamba-2's published initialisation): ``A`` uniform in
#: (1, 16), ``dt`` log-uniform in (0.001, 0.1) through the inverse of
#: its softplus, ``D`` ones — so ``exp(dt A)`` spreads over (0.2, 0.999)
#: as a trained model's does; a state that forgets at once, or never,
#: hides a wrong decay.
A_RANGE, DT_RANGE = (1.0, 16.0), (1e-3, 1e-1)


def recurrence_init(key: jax.Array, shape) -> Dict[str, jnp.ndarray]:
    ka, kd = jax.random.split(key)
    a = jax.random.uniform(ka, shape, jnp.float32, *A_RANGE)
    dt = jnp.exp(jax.random.uniform(kd, shape, jnp.float32,
                                    math.log(DT_RANGE[0]),
                                    math.log(DT_RANGE[1])))
    return {"a_log": jnp.log(a),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1
            "d_skip": jnp.ones(shape, jnp.float32)}


def init_params(key: jax.Array, cfg: GraniteHybridConfig) -> Params:
    shapes = param_shapes(cfg)
    keys = dict(zip(sorted(shapes), jax.random.split(key, len(shapes))))
    layers: Params = {}
    for name, (shape, fan_in) in shapes.items():
        if fan_in:
            layers[name] = (jax.random.normal(keys[name], shape, jnp.float32)
                            * fan_in ** -0.5).astype(cfg.dtype)
        elif name.endswith("norm"):
            layers[name] = jnp.ones(shape, cfg.dtype)
    layers["conv_b"] = (0.1 * jax.random.normal(
        keys["conv_b"], shapes["conv_b"][0], jnp.float32)).astype(cfg.dtype)
    layers.update(recurrence_init(keys["a_log"], shapes["a_log"][0]))
    k_embed = jax.random.fold_in(key, 1)
    return {"embed": (jax.random.normal(k_embed, (cfg.vocab_size, cfg.dim),
                                        jnp.float32)
                      * cfg.dim ** -0.5).astype(cfg.dtype),
            "layers": layers,
            "final_norm": jnp.ones((cfg.dim,), cfg.dtype)}


def init_params_quantized(key: jax.Array, cfg: GraniteHybridConfig) -> Params:
    check_serving(cfg, quantization="int8")
    raise AssertionError("unreachable")


def param_count(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


def param_count_analytic(cfg: GraniteHybridConfig) -> int:
    total = cfg.vocab_size * cfg.dim + cfg.dim
    for shape, _ in param_shapes(cfg).values():
        n = 1
        for s in shape:
            n *= s
        total += n
    return total


#: A dense block: every parameter multiplies with every token.
active_param_count = param_count_analytic


def weight_bytes(cfg: GraniteHybridConfig) -> int:
    return param_count_analytic(cfg) * jnp.dtype(cfg.dtype).itemsize


def kv_bytes_per_token(cfg: GraniteHybridConfig,
                       cache_dtype: Optional[Any] = None) -> int:
    """K and V of the ATTENTION layers: all a token adds to the cache."""
    itemsize = jnp.dtype(cache_dtype or cfg.dtype).itemsize
    return 2 * cfg.n_attention * cfg.n_kv_heads * cfg.head_dim * itemsize


def init_kv_pages(cfg: GraniteHybridConfig, num_pages: int, page_size: int,
                  dtype: Optional[Any] = None) -> KVCache:
    """The page pool of the attention layers, ``models/llama``'s layout:
    ``(L_a, P, page_size, H_kv * head_dim)`` for K and for V, page 0
    reserved."""
    dt = dtype or cfg.dtype
    if jnp.dtype(dt) == jnp.int8:
        check_serving(cfg, kv_quantization="int8")
    shape = (cfg.n_attention, num_pages, page_size,
             cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def init_row_state(cfg: GraniteHybridConfig, batch: int) -> RowState:
    """The row-state leaves for ``batch`` rows, zero: ``ssm`` each Mamba
    layer's state ``(L_m, batch + 1, N, H*P)`` in ``state_dtype`` and
    ``conv`` its convolution's last ``d_conv - 1`` inputs, laid end to
    end, ``(L_m, batch + 1, (d_conv - 1) * conv width)`` in the
    activations' type (one axis: as ``(..., d_conv - 1, width)`` XLA
    wanted the 3 minor-most and copied the leaf in and out of that
    layout around every layer that touched it). The
    last row is NOBODY'S (as page 0 of the pool is): where a program's
    unused slice leaves its state, so that no write has to be
    guarded."""
    return {
        "ssm": jnp.zeros((cfg.n_mamba, batch + 1, cfg.mamba_d_state,
                          cfg.mamba_inner), cfg.state_dtype),
        "conv": jnp.zeros((cfg.n_mamba, batch + 1,
                           (cfg.mamba_d_conv - 1) * cfg.conv_width),
                          cfg.dtype),
    }


def row_state_bytes_per_row(cfg: GraniteHybridConfig) -> int:
    """What one batch row holds in ``init_row_state``'s leaves, whatever
    its sequence's length."""
    return cfg.n_mamba * (
        cfg.mamba_d_state * cfg.mamba_inner
        * jnp.dtype(cfg.state_dtype).itemsize
        + (cfg.mamba_d_conv - 1) * cfg.conv_width
        * jnp.dtype(cfg.dtype).itemsize)


def routes(cfg: GraniteHybridConfig, cache: KVCache, *, batch: int,
           page_size: int, max_pages: int, decode: bool = False,
           prefill_rows: int = 0) -> Dict[str, str]:
    """The attention layers' routes (``ops/attention.kernel_routes``)
    and the Mamba layers': ``ssm_update`` of a program that decodes,
    ``ssm_scan`` of one that runs prompt tokens (plain JAX: einsums)."""
    out = kernel_routes(
        batch=batch, page_size=page_size, max_pages=max_pages,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        kv_itemsize=cache["k"].dtype.itemsize, quant_kv=False,
        enabled=cfg.pallas, multi_ok=cfg.pallas_batched_prefill,
        decode=decode, prefill_rows=prefill_rows)
    if prefill_rows:
        out["ssm_scan"] = "xla"
    if decode:
        use, interp = update_route(cfg.mamba_d_state, cfg.mamba_inner,
                                   cfg.state_dtype,
                                   enabled=cfg.pallas)
        out["ssm_update"] = (
            f"pallas{'-interpret' if interp else ''}:ssm_update_pallas"
            if use else "xla")
    return out


#: Stacked leaves of ``params["layers"]`` -> how their matrices want to
#: lie on the device (``models/__init__.py``). ``in_proj`` is 8,512 =
#: 66.5 lane tiles wide, so the TPU's own choice for the leaf puts the
#: 2,048 axis — the one every product contracts — minor, and the decode
#: loop, which wants the output axis minor, copied all 1.26 GB across at
#: the start of every run of ``decode_chunk`` and ``mixed_chunk`` and
#: held a second ``in_proj`` for the length of the loop (PERF.md,
#: PR 49). The attention layers' three lie as ``llama``'s, for its
#: reason.
DEVICE_LAYOUT = {"in_proj": "row_major", "wq": "transposed",
                 "wk": "transposed", "wv": "transposed"}


# -- forward -------------------------------------------------------------------

def _run_layers(cfg: GraniteHybridConfig, layer_fn, carry, rolled: bool):
    """``layer_fn(carry, l, kind, i)`` over the layers in order: ``l``
    the layer, ``i`` its index among the layers of its ``kind``. Python
    integers when unrolled; ``rolled``: one ``fori_loop`` over the
    periods of ``layer_types`` whose body unrolls ONE period, ``l`` and
    ``i`` traced (a pattern that does not repeat is unrolled)."""
    types, per = cfg.layer_types, cfg.period
    if not rolled or per == cfg.n_layers:
        seen = {MAMBA: 0, ATTENTION: 0}
        for l, kind in enumerate(types):
            carry = layer_fn(carry, l, kind, seen[kind])
            seen[kind] += 1
        return carry
    each = {k: types[:per].count(k) for k in (MAMBA, ATTENTION)}

    def body(p, carry):
        seen = {MAMBA: 0, ATTENTION: 0}
        for j, kind in enumerate(types[:per]):
            carry = layer_fn(carry, p * per + j, kind,
                             p * each[kind] + seen[kind])
            seen[kind] += 1
        return carry

    return lax.fori_loop(0, cfg.n_layers // per, body, carry)


def _embed(params: Params, cfg: GraniteHybridConfig, tokens) -> jnp.ndarray:
    with scope("embed"):
        return (params["embed"][tokens].astype(jnp.float32)
                * cfg.embedding_multiplier)


def _head(params: Params, cfg: GraniteHybridConfig, h) -> jnp.ndarray:
    hn = rms_norm(h, params["final_norm"], cfg.norm_eps).astype(cfg.dtype)
    return (jnp.dot(hn, params["embed"].T).astype(jnp.float32)
            / cfg.logits_scaling)


def _normed(h, w, cfg: GraniteHybridConfig) -> jnp.ndarray:
    return rms_norm(h, w, cfg.norm_eps).astype(cfg.dtype)


def _mlp_block(h, lp: Params, l, cfg: GraniteHybridConfig) -> jnp.ndarray:
    with scope("mlp"):
        hn = _normed(h, lp["mlp_norm"][l], cfg)
        return h + cfg.residual_multiplier * _mlp(
            hn, lp["w_gate"][l], lp["w_up"][l],
            lp["w_down"][l]).astype(jnp.float32)


def _qkv(h, lp: Params, l, i, cfg: GraniteHybridConfig):
    """Attention layer ``i``: (rows, heads, head_dim) each. The scores'
    scale goes into q — the kernels scale by 1 / sqrt(head_dim), so q is
    multiplied by ``attention_multiplier * sqrt(head_dim)`` (at the
    published sizes 1 / 8: exact in bfloat16) — and nothing is
    rotated."""
    with scope("qkv"):
        hn = _normed(h, lp["attn_norm"][l], cfg)
        q, k, v = (jnp.dot(hn, lp[w][i]).reshape(h.shape[0], -1,
                                                 cfg.head_dim)
                   for w in ("wq", "wk", "wv"))
        q = (q.astype(jnp.float32) * (cfg.attention_multiplier
                                      * cfg.head_dim ** 0.5)
             ).astype(cfg.dtype)
        return q, k, v


def _attn_out(h, attn, lp: Params, i, cfg: GraniteHybridConfig):
    with scope("attn_out"):
        return h + cfg.residual_multiplier * jnp.dot(
            attn.reshape(h.shape[0], -1).astype(cfg.dtype),
            lp["wo"][i]).astype(jnp.float32)


def _mamba_proj(h, lp: Params, l, i, cfg: GraniteHybridConfig, lo=0,
                hi=None):
    """Mamba layer ``i``'s norm over rows ``h`` (M, D) and their product
    with columns ``lo:hi`` of its input projection ``[z | xBC | dt]``
    (the matrix lies row-major on the device, ``DEVICE_LAYOUT``, and
    ``z`` ends at a lane tile's edge: a product reads a column block
    where it lies)."""
    with scope("qkv"):
        hn = _normed(h, lp["attn_norm"][l], cfg)
        return jnp.dot(hn, lp["in_proj"][i, :, lo:hi])


def _mamba_in(h, lp: Params, l, i, cfg: GraniteHybridConfig):
    """Mamba layer ``i``'s norm and input projection over rows
    ``h`` (M, D), ONE product: ``(z (M, I), xBC (M, C), dt (M, H_m))``."""
    zxd = _mamba_proj(h, lp, l, i, cfg)
    I, C = cfg.mamba_inner, cfg.conv_width
    return zxd[:, :I], zxd[:, I:I + C], zxd[:, I + C:]


def _mamba_out(h, y, z, lp: Params, i, cfg: GraniteHybridConfig):
    """The gate, the norm over the whole inner width and the output
    projection: ``y`` (M, I) float32, ``z`` (M, I)."""
    with scope("attn_out"):
        g = y * jax.nn.silu(z.astype(jnp.float32))
        g = _normed(g, lp["ssm_norm"][i], cfg)
        return h + cfg.residual_multiplier * jnp.dot(
            g, lp["out_proj"][i]).astype(jnp.float32)


def _split(xbc, dt, lp: Params, i, cfg: GraniteHybridConfig):
    """The convolved channels as ``(X (..., H_m, P), B, C (..., N))``,
    ``dt`` after its bias and softplus, ``A`` — all float32."""
    I, N = cfg.mamba_inner, cfg.mamba_d_state
    x = xbc[..., :I].reshape(xbc.shape[:-1] + (cfg.mamba_n_heads,
                                               cfg.mamba_d_head))
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + lp["dt_bias"][i].astype(jnp.float32))
    a = -jnp.exp(lp["a_log"][i].astype(jnp.float32))
    return x, xbc[..., I:I + N], xbc[..., I + N:], dt, a


def _mamba_decode(h, lp: Params, l, i, rs: RowState, active, walk,
                  cfg: GraniteHybridConfig):
    """One token a row through Mamba layer ``i``; rows that are not
    ``active`` keep their window and their state (``walk``: the step's
    ``decode_walk`` of ``active``, one for all its layers)."""
    z, xbc, dt = _mamba_in(h, lp, l, i, cfg)
    ssm, conv = rs["ssm"], rs["conv"]
    with scope("ssm_conv"):
        B = h.shape[0]
        old = conv[i, :B].reshape(B, cfg.mamba_d_conv - 1, -1)
        xbc, win = conv_step(old, xbc, lp["conv_w"][i], lp["conv_b"][i])
        conv = conv.at[i, :B].set(jnp.where(
            active[:, None, None], win, old).reshape(B, -1))
    with scope("ssm_update"):
        x, bm, cm, dt, a = _split(xbc, dt, lp, i, cfg)
        y, ssm = ssm_update_layer(ssm, i, x, dt, a, bm, cm, lp["d_skip"][i],
                                  active, walk=walk, enabled=cfg.pallas)
    h = _mamba_out(h, y.reshape(h.shape[0], -1), z, lp, i, cfg)
    return h, {"ssm": ssm, "conv": conv}


def _mamba_slices(xbc, dt, lp: Params, i, rs: RowState, rows, first,
                  lengths, cfg: GraniteHybridConfig):
    """S slices of T tokens through Mamba layer ``i``'s convolution and
    scan: ``xbc`` (S, T, C), ``dt`` (S, T, H_m) on the grid; ``rows``
    (S,) the batch row each slice's sequence owns (one past the batch's
    last row: the leaf's last, nobody's), ``first`` (S,) whether
    the slice starts its sequence (a zero state), ``lengths`` (S,).
    Returns ``(y (S, T, I) float32, row state)``."""
    ssm, conv = rs["ssm"], rs["conv"]
    keep = ~first[:, None, None]
    with scope("ssm_conv"):
        win = rows_read(conv, i, rows).reshape(
            rows.shape[0], cfg.mamba_d_conv - 1, -1)
        xbc, win = conv_slices(jnp.where(keep, win, 0), xbc, lengths,
                               lp["conv_w"][i], lp["conv_b"][i])
        conv = rows_write(conv, i, rows, win.reshape(rows.shape[0], -1))
    with scope("ssm_scan"):
        x, bm, cm, dt, a = _split(xbc, dt, lp, i, cfg)
        before = rows_read(ssm, i, rows, enabled=cfg.pallas)
        y, st = ssm_scan(jnp.where(keep, before, 0), x, dt, a, bm, cm,
                         lp["d_skip"][i], lengths, cfg.mamba_chunk_size)
        ssm = rows_write(ssm, i, rows, st, enabled=cfg.pallas)
    return y.reshape(y.shape[:2] + (-1,)), {"ssm": ssm, "conv": conv}


@partial(jax.jit, static_argnames=("cfg", "last_only"))
def forward_prefill(params: Params, cfg: GraniteHybridConfig,
                    tokens: jnp.ndarray, positions: jnp.ndarray,
                    lengths: jnp.ndarray, kv_cache: KVCache,
                    block_tables: jnp.ndarray, last_only: bool = False,
                    row_state: Optional[RowState] = None,
                    rows: Optional[jnp.ndarray] = None):
    """``models/llama.forward_prefill``'s signature and conventions,
    and beside them ``row_state`` and ``rows`` (B,): the batch row each
    sequence owns. A chunk that starts at position 0 starts from a zero
    state; any other continues what its row holds. Returns ``(logits,
    cache, row_state)``."""
    B, T = tokens.shape
    row_state, rows = own_rows(partial(init_row_state, cfg), B, row_state,
                               rows)
    h = _embed(params, cfg, tokens).reshape(B * T, -1)
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    seq_lens = jnp.max(jnp.where(valid, positions, -1), axis=1) + 1
    first = positions[:, 0] == 0
    lp = params["layers"]

    def layer(carry, l, kind, i):
        h, k_pool, v_pool, rs = carry
        if kind == MAMBA:
            z, xbc, dt = _mamba_in(h, lp, l, i, cfg)
            y, rs = _mamba_slices(xbc.reshape(B, T, -1),
                                  dt.reshape(B, T, -1), lp, i, rs, rows,
                                  first, lengths, cfg)
            h = _mamba_out(h, y.reshape(B * T, -1), z, lp, i, cfg)
        else:
            q, k, v = (x.reshape(B, T, -1, cfg.head_dim)
                       for x in _qkv(h, lp, l, i, cfg))
            with scope("kv_write"):
                k_pool, v_pool = paged_kv_write_prefill(
                    k_pool, v_pool, k, v, block_tables, positions, lengths,
                    jnp.asarray(i, jnp.int32), enabled=cfg.pallas,
                    multi_ok=cfg.pallas_batched_prefill)
            with scope("attn"):
                attn = dispatch_prefill_attention(
                    q, k_pool, v_pool, block_tables, positions, seq_lens,
                    i, enabled=cfg.pallas,
                    multi_ok=cfg.pallas_batched_prefill)
            h = _attn_out(h, attn.reshape(B * T, -1), lp, i, cfg)
        return _mlp_block(h, lp, l, cfg), k_pool, v_pool, rs

    h, k_pool, v_pool, row_state = _run_layers(
        cfg, layer, (h, kv_cache["k"], kv_cache["v"], row_state), True)
    with scope("head"):
        h = h.reshape(B, T, -1)
        if last_only:
            h = h[jnp.arange(B), lengths - 1]
        return _head(params, cfg, h), {"k": k_pool, "v": v_pool}, row_state


def _decode_geometry(positions, block_tables, kv_cache: KVCache, active,
                     cfg: GraniteHybridConfig):
    """What the attention layers need of a decode step's rows:
    ``(block_tables, page_of, slot_of, seq_lens, order)``. The hidden
    rows of this family stay by batch row (the Mamba layers' state is a
    row's), so the ``order`` the attention kernel wants
    (``ops/attention.decode_order``, made here once a step) is handed
    to each attention call, and the three operands that are the same
    for every layer are laid out by place here."""
    B = positions.shape[0]
    page_of = block_tables[jnp.arange(B), positions // kv_cache["k"].shape[2]]
    if active is not None:
        page_of = jnp.where(active, page_of, 0)
    seq_lens = positions + 1
    order = decode_order(seq_lens, (kv_cache["k"], kv_cache["v"]),
                         block_tables.shape[1], cfg.head_dim,
                         enabled=cfg.pallas)
    block_tables, page_of, seq_lens = rows_by_place(order, block_tables,
                                                    page_of, seq_lens)
    return (block_tables, page_of, positions % kv_cache["k"].shape[2],
            seq_lens, order)


def _decode_layer(h, lp: Params, l, kind, i, k_pool, v_pool, rs, geom,
                  active, walk, cfg: GraniteHybridConfig):
    """One decode token a row through layer ``l`` (the decode program's
    layer and the decode rows' half of the mixed step's)."""
    if kind == MAMBA:
        h, rs = _mamba_decode(h, lp, l, i, rs, active, walk, cfg)
    else:
        block_tables, page_of, slot_of, seq_lens, order = geom
        q, k, v = _qkv(h, lp, l, i, cfg)
        with scope("attn"):
            attn, k_pool, v_pool = paged_decode_step(
                q, k, v, k_pool, v_pool, block_tables, seq_lens, page_of,
                slot_of, jnp.asarray(i, jnp.int32), enabled=cfg.pallas,
                order=order)
        h = _attn_out(h, attn, lp, i, cfg)
    return _mlp_block(h, lp, l, cfg), k_pool, v_pool, rs


@partial(jax.jit, static_argnames=("cfg",))
def forward_decode(params: Params, cfg: GraniteHybridConfig,
                   tokens: jnp.ndarray, positions: jnp.ndarray,
                   kv_cache: KVCache, block_tables: jnp.ndarray,
                   active: Optional[jnp.ndarray] = None,
                   row_state: Optional[RowState] = None):
    """``models/llama.forward_decode``'s signature and conventions;
    batch row ``b`` updates row ``b`` of ``row_state``, and a row that
    is not ``active`` leaves its state as it found it. Returns
    ``(logits (B, V), cache, row_state)``."""
    B = tokens.shape[0]
    row_state, _ = own_rows(partial(init_row_state, cfg), B, row_state,
                            None)
    live = jnp.ones((B,), bool) if active is None else active
    h = _embed(params, cfg, tokens)
    geom = _decode_geometry(positions, block_tables, kv_cache, active, cfg)
    walk = decode_walk(live)
    lp = params["layers"]

    def layer(carry, l, kind, i):
        h, k_pool, v_pool, rs = carry
        return _decode_layer(h, lp, l, kind, i, k_pool, v_pool, rs, geom,
                             live, walk, cfg)

    h, k_pool, v_pool, row_state = _run_layers(
        cfg, layer, (h, kv_cache["k"], kv_cache["v"], row_state), True)
    with scope("head"):
        return _head(params, cfg, h), {"k": k_pool, "v": v_pool}, row_state


@partial(jax.jit, static_argnames=("cfg",))
def forward_mixed(params: Params, cfg: GraniteHybridConfig,
                  dec_tokens: jnp.ndarray, dec_positions: jnp.ndarray,
                  kv_cache: KVCache, dec_block_tables: jnp.ndarray,
                  pf_tokens: jnp.ndarray, pf_positions: jnp.ndarray,
                  pf_lengths: jnp.ndarray, pf_starts: jnp.ndarray,
                  pf_block_tables: jnp.ndarray,
                  dec_active: Optional[jnp.ndarray] = None,
                  row_state: Optional[RowState] = None,
                  pf_rows: Optional[jnp.ndarray] = None):
    """``models/llama.forward_mixed``'s signature, layout and
    conventions (the slices' tokens TIGHT, ``ops/rows.py``), and beside
    them ``row_state`` and ``pf_rows`` (S,): the batch row each slice's
    sequence owns; an unused slice names one past the last row. A slice
    is never one of the step's active decode rows, so the two halves of
    a layer touch different rows of the state.

    What is a row's own — both norms, the mixers' projections, the
    SwiGLU — runs over the tight rows (the gated norm, the output
    projection and the SwiGLU a live tile at a time; the attention's
    input projections all S*T rows, as ``llama``'s); the convolution,
    the scan, the KV write and the attention take the (S, T) grid, a
    slice a row. A Mamba layer's input projection is multiplied ONCE,
    in two column blocks: ``xBC | dt`` over all S*T rows before the
    convolution, which reads it at once, and ``z`` INSIDE the live
    tile beside the gated norm, its only reader (the tile's norm of
    ``h`` is made again: 256 x D values). As one product whose ``z``
    had to outlive the convolution and the scan, XLA kept no 17 MB
    result that long and multiplied three times a layer — 105 products
    of the projection's size where the model has 36 (PERF.md, PR 49).
    Returns ``(dec_logits (B, V), pf_logits (S, V), cache,
    row_state)``."""
    B = dec_tokens.shape[0]
    S = pf_lengths.shape[0]
    T = pf_tokens.shape[0] // S
    row_state, _ = own_rows(partial(init_row_state, cfg), B, row_state,
                            None)
    if pf_rows is None:
        pf_rows = jnp.full((S,), B, jnp.int32)
    live = jnp.ones((B,), bool) if dec_active is None else dec_active
    tile = row_tile(T)
    if not worth_a_loop(S * T, tile):
        pf_tokens = rows_to_grid(pf_tokens, pf_starts, T).reshape(-1)
        pf_positions = grid_positions(pf_positions, pf_lengths, pf_starts,
                                      T)[0].reshape(-1)
        pf_starts = jnp.arange(S + 1, dtype=jnp.int32) * T
    n_live = pf_starts[S]

    with scope("decode_rows"):
        h_d = _embed(params, cfg, dec_tokens)
        geom = _decode_geometry(dec_positions, dec_block_tables, kv_cache,
                                dec_active, cfg)
        walk = decode_walk(live)
    with scope("slices"):
        h_p = _embed(params, cfg, pf_tokens)
        pf_grid_pos, pf_seq_lens = grid_positions(pf_positions, pf_lengths,
                                                  pf_starts, T)
        first = pf_grid_pos[:, 0] == 0
    lp = params["layers"]

    def to_grid(x):
        return rows_to_grid(x, pf_starts, T)

    def layer(carry, l, kind, i):
        h_p, h_d, k_pool, v_pool, rs = carry
        with scope("slices"):
            if kind == MAMBA:
                # xBC | dt before the convolution, which reads it at
                # once; z inside the tile, beside its only reader
                xd = _mamba_proj(h_p, lp, l, i, cfg, lo=cfg.mamba_inner)
                y, rs = _mamba_slices(to_grid(xd[:, :cfg.conv_width]),
                                      to_grid(xd[:, cfg.conv_width:]), lp,
                                      i, rs, pf_rows, first, pf_lengths,
                                      cfg)
                mixed = grid_to_rows(
                    y, pf_starts, jnp.zeros((S * T, y.shape[-1]), y.dtype))

                def out(h, y):
                    z = _mamba_proj(h, lp, l, i, cfg, hi=cfg.mamba_inner)
                    return _mlp_block(_mamba_out(h, y, z, lp, i, cfg), lp,
                                      l, cfg)

                h_p = live_rows(out, n_live, tile, h_p, mixed)
            else:
                q_t, k_t, v_t = _qkv(h_p, lp, l, i, cfg)
                with scope("kv_write"):
                    k_pool, v_pool = paged_kv_write_prefill(
                        k_pool, v_pool, to_grid(k_t), to_grid(v_t),
                        pf_block_tables, pf_grid_pos, pf_lengths,
                        jnp.asarray(i, jnp.int32), enabled=cfg.pallas,
                        multi_ok=cfg.pallas_batched_prefill)
                with scope("attn"):
                    attn = dispatch_prefill_attention(
                        to_grid(q_t), k_pool, v_pool, pf_block_tables,
                        pf_grid_pos, pf_seq_lens, i, enabled=cfg.pallas,
                        multi_ok=cfg.pallas_batched_prefill)
                    # The decode rows' write takes the pool in place:
                    # only once the slices' attention has read it, or
                    # XLA copies the whole pool to keep both.
                    attn, k_pool, v_pool = lax.optimization_barrier(
                        (attn, k_pool, v_pool))
                attn = grid_to_rows(attn, pf_starts, jnp.zeros_like(q_t))

                def out(h, attn):
                    return _mlp_block(_attn_out(h, attn, lp, i, cfg), lp, l,
                                      cfg)

                h_p = live_rows(out, n_live, tile, h_p, attn)
        with scope("decode_rows"):
            h_d, k_pool, v_pool, rs = _decode_layer(
                h_d, lp, l, kind, i, k_pool, v_pool, rs, geom, live, walk,
                cfg)
        return h_p, h_d, k_pool, v_pool, rs

    h_p, h_d, k_pool, v_pool, row_state = _run_layers(
        cfg, layer, (h_p, h_d, kv_cache["k"], kv_cache["v"], row_state),
        False)
    with scope("slices"), scope("head"):
        pf_logits = _head(params, cfg, h_p[pf_starts[:S] + pf_lengths - 1])
    with scope("decode_rows"), scope("head"):
        return (_head(params, cfg, h_d), pf_logits,
                {"k": k_pool, "v": v_pool}, row_state)
