"""The ZAYA1 block (``model_type`` ``zaya``; the ZAYA1 report,
arXiv:2511.17127) in pure JAX: compressed convolutional attention (CCA,
arXiv:2510.04476) and a top-1 routed SwiGLU behind a router NETWORK that
carries its state from layer to layer, in every layer; pre-norm, a
scaled residual merge, tied head.

With N_a, N_f a layer's two RMSNorms and ``merge(x, f) = (a_r x + c_r)
+ (a_h f + c_h)`` (four learned vectors a sublayer)::

    u  = N_a(x);  [q~ ; k~ ; v1 ; v2] = u W_qkv          # COMPRESSED
    q^, k^, v = the CCA mix (ops/cca.py): q-k mean, two causal 2-tap
        convolutions, two L2 norms, the key temperature, v = [v1_t ;
        v2_{t-1}]; RoPE on the first ``rotary_dim`` values of each head
    x' = merge(x, GQA(q^, K^, V) W_o)                    # H : G heads of d
    s  = N_f(x')
    r  = s W_d + b_d  (+ gamma * the layer before's r)   # the CARRY
    p  = softmax(W_3 gelu(W_2 gelu(W_1 N_r(r) + b_1) + b_2))
    e  = argmax(p + beta);  y = p[e] SwiGLU_e(s)         # gate NOT renormed
    x  = merge(x', y)
    logits = N(x_L) E^T

**Pages AND row state, on one layer.** Queries live in ``n_heads *
head_dim`` (half the hidden size as published) and keys and values in
``n_kv_heads * head_dim`` (an eighth): attention runs inside the
compressed space. K and V go into the page pool AFTER the mix
(convolved, normed, tempered, rotated; V shifted), so the pool is a
plain GQA pool (``init_kv_pages``: ``models/llama``'s layout) and decode
and prefill attention are the paged GQA ops every family shares. What
the mix needs of the token BEFORE is not in a page: each layer carries,
a batch row, the TAIL ``[c_t | a_t | v2_t]`` (``ops/cca.py``) —
``init_row_state``: ``tail`` ``(L, rows + 1, 2 C + W)`` float32, the
last row nobody's. Every forward function takes it as ``row_state``
beside the pool and returns it after the pool; position 0 starts from a
tail of zeros inside the program; a decode row that is not ``active``
keeps its tail; a prompt slice's tail ends at its last VALID token.
Pages alone do not hold the tail, so the engine adopts no cached prefix
or pinned conversation (``get_stats()["row_state"]``) — although THIS
state, unlike a recurrence's, could be rebuilt by recomputing two
tokens (ROADMAP.md has it as what the system cannot do yet).

**The carry.** ``r`` (rows, ``router_dim``) float32 goes from layer to
layer beside the residual stream, in all three programs; it starts at
the model's first layer (held layer 0) and is no state of a sequence: a
token's own, over depth. The router network runs in float32 at the
highest matmul precision under ``moe_route``, and the choice is
``ops/moe.choose``'s, as every routed family's.

Every program unrolls its layers, and a layer's expert matrices are a
leaf of their own (``models/deepseek_v3.param_shapes`` has why); the
mixed step puts its slices back onto the (S, T) grid at the door and
runs the routed layer over slices and decode rows together, so the
experts are streamed once for both.

Not written: sliding-window layers (``layer_types`` other than
``hybrid``), a skip route among the experts, int8 weights, an int8
cache, a mesh — each refused by name (``check_serving``).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from llmq_tpu.models.granitemoehybrid import _decode_geometry
from llmq_tpu.models.latent import param_count  # noqa: F401 (surface)
from llmq_tpu.models.latent import prod as _prod
from llmq_tpu.ops.attention import (dispatch_prefill_attention,
                                    kernel_routes, paged_decode_step,
                                    paged_kv_write_prefill)
from llmq_tpu.ops.cca import cca_slices, cca_step, tail_width
from llmq_tpu.ops.moe import choose, pass_extras, routed_ffn
from llmq_tpu.ops.norms import rms_norm
from llmq_tpu.ops.rope import apply_rope, rope_cos_sin
from llmq_tpu.ops.rows import grid_positions, rows_to_grid
from llmq_tpu.ops.ssm import own_rows, rows_read, rows_write
from llmq_tpu.utils.profiling import scope

Params = Dict[str, Any]
KVCache = Dict[str, jnp.ndarray]
RowState = Dict[str, jnp.ndarray]

HYBRID = "hybrid"


@dataclass(frozen=True)
class ZayaConfig:
    FAMILY: ClassVar[str] = "zaya"           # models/__init__.py
    name: str = "zaya-tiny"
    vocab_size: int = 512
    dim: int = 128
    #: The published ``layer_types`` of the layers HELD.
    layer_types: Tuple[str, ...] = (HYBRID,) * 3
    n_heads: int = 4                         # queries: n_heads * head_dim
    n_kv_heads: int = 2                      # keys, values: n_kv_heads * head_dim
    head_dim: int = 32
    rotary_dim: int = 16                     # partial_rotary_factor * head_dim
    n_experts: int = 4
    n_experts_per_tok: int = 1
    moe_ffn_dim: int = 64                    # one expert's SwiGLU
    router_dim: int = 32                     # router_hidden_size
    max_seq_len: int = 2048
    rope_theta: float = 5000000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    pallas: bool = True
    pallas_batched_prefill: bool = False

    def __post_init__(self) -> None:
        if (self.n_heads % self.n_kv_heads or self.n_kv_heads % 2
                or self.rotary_dim % 2 or self.rotary_dim > self.head_dim):
            raise ValueError(
                f"model {self.name!r}: {self.n_heads} query heads over "
                f"{self.n_kv_heads} KV heads (an even number: half carry "
                f"the shifted value), {self.rotary_dim} rotated of "
                f"{self.head_dim}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def conv_width(self) -> int:
        """Channels the two convolutions run over: q~ and k~."""
        return (self.n_heads + self.n_kv_heads) * self.head_dim

    @property
    def shift_width(self) -> int:
        """Each of the two value projections: half the K/V width."""
        return self.n_kv_heads * self.head_dim // 2

    @property
    def tail_width(self) -> int:
        return tail_width(self.n_heads, self.n_kv_heads, self.head_dim,
                          self.shift_width)


def zaya_tiny(**kw) -> ZayaConfig:
    """CPU-test size: three layers, so the carry has a first layer, one
    that receives and hands on, and a last."""
    return replace(ZayaConfig(), **kw)


def zaya1_8b(**kw) -> ZayaConfig:
    """Zyphra/ZAYA1-8B at its published sizes
    (https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json): 40
    layers, all ``hybrid``; hidden 2,048; 8 query heads over 2 KV heads
    of 128 (queries in 1,024, keys and values in 256); two 2-tap
    convolutions; RoPE on 64 of 128, theta 5e6; 16 experts of 2,048,
    one a token, no shared expert; router network of 256; vocabulary
    262,272 tied; context 131,072. 8,840,475,344 parameters, 17.68 GB in
    bf16: one 16 GB chip serves a cut in depth
    (benchmark/configs/zaya1-8b-bf16-pp2.json holds 20 layers of it, one
    pipeline stage of two, with every width and all 16 experts)."""
    return replace(ZayaConfig(
        name="zaya1-8b", vocab_size=262272, dim=2048,
        layer_types=(HYBRID,) * 40, n_heads=8, n_kv_heads=2, head_dim=128,
        rotary_dim=64, n_experts=16, n_experts_per_tok=1, moe_ffn_dim=2048,
        router_dim=256, max_seq_len=131072, rope_theta=5000000.0,
        norm_eps=1e-5), **kw)


MODEL_CONFIGS = {
    "zaya-tiny": zaya_tiny,
    "zaya1-8b": zaya1_8b,
}


# -- the family surface (models/__init__.py) -----------------------------------

def serving_config(cfg: ZayaConfig) -> ZayaConfig:
    return replace(cfg, pallas_batched_prefill=True)


def check_serving(cfg: ZayaConfig, *, quantization: str = "",
                  kv_quantization: str = "", mesh: bool = False) -> None:
    """Refuse what is not written for this family, naming the setting."""
    what = None
    other = sorted(set(cfg.layer_types) - {HYBRID})
    if other:
        what = (f"layer_types {other} (only {HYBRID!r} layers are "
                f"written: no sliding-window attention in this family)")
    elif quantization:
        what = (f"model.quantization={quantization!r} (no int8 form of "
                f"the compressed projections or the experts)")
    elif kv_quantization:
        what = (f"model.kv_quantization={kv_quantization!r} (int8 pages "
                f"beside a float32 row state)")
    elif mesh:
        what = "executor.mesh (no partition rules for the row state)"
    if what:
        raise ValueError(f"model {cfg.name!r} (family zaya) does not "
                         f"support {what}; unset it")


def import_hf(model_dir: str, cfg: ZayaConfig, **kw) -> Params:
    raise NotImplementedError(
        f"model {cfg.name!r} (family zaya): no checkpoint loader is "
        f"written (model.weights_path) — what a published checkpoint's "
        f"tensors are called is not known here; the weights are random")


def step_stats_layout(cfg: ZayaConfig) -> Dict[str, Any]:
    """``models/deepseek_v3.step_stats_layout``'s: the tokens each of
    the experts received, the experts that received any summed over the
    layers, and the routed layers run."""
    E = cfg.n_experts
    return {"load": (0, E), "touched": E, "runs": E + 1}


def step_stats_size(cfg: ZayaConfig) -> int:
    return cfg.n_experts + 2


def mixed_live_rows(tokens: int, batch: int, slices: int, width: int) -> int:
    """Every row of the slices' grid, whatever ``tokens`` is
    (``models/deepseek_v3.mixed_live_rows``)."""
    return slices * width


# -- parameters ---------------------------------------------------------------

def param_shapes(cfg: ZayaConfig) -> Dict[str, Dict[str, tuple]]:
    """Leaf name -> (shape, fan_in) by group: the MATRICES, drawn at
    variance 1 / fan_in (init and the benchmark's builder follow it).
    ``layers``: the CCA sublayers' stacked over the layers — ``wqkv``
    the four compressed projections side by side ``[W_q | W_k | W_v1 |
    W_v2]``, ``conv0_w`` the depthwise taps (older first), ``conv1_w``
    the full convolution within a head ``(heads, 2 d, d)``, the older
    tap's rows first; ``router``: the router networks'; ``experts``: a
    layer's expert matrices, a leaf of their own a layer (gate and up
    side by side); ``top``: the embedding, which is the head."""
    L, D, V = cfg.n_layers, cfg.dim, cfg.vocab_size
    H, G, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    C, W, R = cfg.conv_width, cfg.shift_width, cfg.router_dim
    E, F = cfg.n_experts, cfg.moe_ffn_dim
    return {
        "layers": {"wqkv": ((L, D, C + 2 * W), D),
                   "wo": ((L, H * d, D), H * d),
                   "conv0_w": ((L, C, 2), 2),
                   "conv1_w": ((L, H + G, 2 * d, d), 2 * d)},
        "router": {"w_down": ((L, D, R), D), "w1": ((L, R, R), R),
                   "w2": ((L, R, R), R), "w3": ((L, R, E), R)},
        "experts": {"we_gate_up": ((E, D, 2 * F), D),
                    "we_down": ((E, F, D), F)},
        "top": {"embed": ((V, D), D)},
    }


#: What ``small_init`` draws the leaves that are no matrix from, each
#: uniform in its range (gains are ones): the residual merge's scales
#: ``a`` and biases ``c`` (and every other bias), the carry's ``gamma``,
#: the key temperature ``tau``, the router's selection bias ``beta`` —
#: none at its neutral value, so that a program that drops one is seen.
SMALL_RANGES = {"a": (0.5, 1.5), "c": (-0.02, 0.02), "gamma": (0.5, 1.0),
                "tau": (0.5, 2.0), "beta": (-0.02, 0.02)}


def small_init(key: jax.Array, cfg: ZayaConfig) -> Dict[str, Params]:
    """The leaves that are no matrix, by group: norm gains (ones), the
    two residual merges ``(L, 4, D)`` as ``[a_r, c_r, a_h, c_h]``, the
    convolutions' biases, the temperature ``(L, G)``, the router's
    biases, ``gamma`` and norm, and its selection bias (float32)."""
    L, D, C, R = cfg.n_layers, cfg.dim, cfg.conv_width, cfg.router_dim
    ks = iter(jax.random.split(key, 12))

    def u(shape, kind, dtype=cfg.dtype):
        return jax.random.uniform(next(ks), shape, jnp.float32,
                                  *SMALL_RANGES[kind]).astype(dtype)

    def merge():
        a, c = u((L, 2, D), "a", jnp.float32), u((L, 2, D), "c", jnp.float32)
        return jnp.stack([a[:, 0], c[:, 0], a[:, 1], c[:, 1]],
                         axis=1).astype(cfg.dtype)

    return {
        "layers": {"attn_norm": jnp.ones((L, D), cfg.dtype),
                   "mlp_norm": jnp.ones((L, D), cfg.dtype),
                   "res_attn": merge(), "res_mlp": merge(),
                   "conv0_b": u((L, C), "c"), "conv1_b": u((L, C), "c"),
                   "temp": u((L, cfg.n_kv_heads), "tau")},
        "router": {"b_down": u((L, R), "c"), "gamma": u((L, R), "gamma"),
                   "norm": jnp.ones((L, R), cfg.dtype),
                   "b1": u((L, R), "c"), "b2": u((L, R), "c"),
                   "bias": u((L, cfg.n_experts), "beta", jnp.float32)},
        "final_norm": jnp.ones((D,), cfg.dtype),
    }


def assemble(cfg: ZayaConfig, drawn: Dict[str, Dict[str, Any]],
             small: Dict[str, Params]) -> Params:
    """``param_shapes``-shaped groups of arrays (``experts``: a list of
    one array a layer under each name) + ``small_init``'s -> the
    parameter tree."""
    return {"embed": drawn["top"]["embed"],
            "final_norm": small["final_norm"],
            "layers": {**drawn["layers"], **small["layers"]},
            "router": {**drawn["router"], **small["router"]},
            "moe": {k: tuple(v) for k, v in drawn["experts"].items()}}


def init_params(key: jax.Array, cfg: ZayaConfig) -> Params:
    """Random-init parameter tree: matrices N(0, 1 / fan_in) as the
    Llama block's, the rest ``small_init``'s."""
    from llmq_tpu.models.latent import draw_groups
    k_m, k_s = jax.random.split(key)
    return assemble(cfg, draw_groups(k_m, param_shapes(cfg), cfg.dtype,
                                     cfg.n_layers), small_init(k_s, cfg))


def init_params_quantized(key: jax.Array, cfg: ZayaConfig) -> Params:
    check_serving(cfg, quantization="int8")
    raise AssertionError("unreachable")


def param_count_analytic(cfg: ZayaConfig) -> int:
    """Parameters HELD, from the configuration alone: the matrices and
    what ``small_init`` draws (a layer: two norms and two merges 10 D,
    the convolutions' biases 2 C, G temperatures, the router's 5 R + E;
    the final norm)."""
    n = sum(_prod(shape) * (cfg.n_layers if g == "experts" else 1)
            for g, leaves in param_shapes(cfg).items()
            for shape, _f in leaves.values())
    small = (10 * cfg.dim + 2 * cfg.conv_width + cfg.n_kv_heads
             + 5 * cfg.router_dim + cfg.n_experts)
    return n + cfg.n_layers * small + cfg.dim


def active_param_count(cfg: ZayaConfig) -> int:
    """Parameters one token multiplies with: the held count less the
    experts it is not routed to."""
    idle = cfg.n_experts - cfg.n_experts_per_tok
    return (param_count_analytic(cfg)
            - cfg.n_layers * idle * 3 * cfg.dim * cfg.moe_ffn_dim)


def weight_bytes(cfg: ZayaConfig) -> int:
    return param_count_analytic(cfg) * jnp.dtype(cfg.dtype).itemsize


def kv_bytes_per_token(cfg: ZayaConfig,
                       cache_dtype: Optional[Any] = None) -> int:
    """K and V of the compressed space, every layer: all a token adds
    to the page pool."""
    itemsize = jnp.dtype(cache_dtype or cfg.dtype).itemsize
    return 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * itemsize


def init_kv_pages(cfg: ZayaConfig, num_pages: int, page_size: int,
                  dtype: Optional[Any] = None) -> KVCache:
    """The page pool, ``models/llama``'s layout: ``(L, P, page_size,
    n_kv_heads * head_dim)`` for K and for V, page 0 reserved. What it
    holds is K and V as the attention reads them: AFTER the mix."""
    dt = dtype or cfg.dtype
    if jnp.dtype(dt) == jnp.int8:
        check_serving(cfg, kv_quantization="int8")
    shape = (cfg.n_layers, num_pages, page_size,
             cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def init_row_state(cfg: ZayaConfig, batch: int) -> RowState:
    """The row-state leaf for ``batch`` rows, zero: ``tail`` ``(L,
    batch + 1, 2 C + W)`` float32 — each layer's ``[c | a |
    v2]`` of a row's last token (``ops/cca.py``), one axis
    (``models/granitemoehybrid.init_row_state`` has why). The last row
    is NOBODY'S, as page 0 of the pool is."""
    return {"tail": jnp.zeros((cfg.n_layers, batch + 1, cfg.tail_width),
                              jnp.float32)}


def row_state_bytes_per_row(cfg: ZayaConfig) -> int:
    return cfg.n_layers * cfg.tail_width * 4


def routes(cfg: ZayaConfig, cache: KVCache, *, batch: int, page_size: int,
           max_pages: int, decode: bool = False,
           prefill_rows: int = 0) -> Dict[str, str]:
    """The paged GQA ops' routes (``ops/attention.kernel_routes``: after
    the mix this is a plain ``n_heads : n_kv_heads`` attention) and the
    mix's own: plain JAX."""
    out = kernel_routes(
        batch=batch, page_size=page_size, max_pages=max_pages,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        kv_itemsize=cache["k"].dtype.itemsize, quant_kv=False,
        enabled=cfg.pallas, multi_ok=cfg.pallas_batched_prefill,
        decode=decode, prefill_rows=prefill_rows)
    out["cca_mix"] = "xla"
    return out


# -- forward -------------------------------------------------------------------

def _embed(params: Params, tokens) -> jnp.ndarray:
    with scope("embed"):
        return params["embed"][tokens].astype(jnp.float32)


def _head(params: Params, cfg: ZayaConfig, h) -> jnp.ndarray:
    with scope("head"):
        hn = rms_norm(h, params["final_norm"], cfg.norm_eps).astype(cfg.dtype)
        return jnp.dot(hn, params["embed"].T).astype(jnp.float32)


def _merge(h, f, res) -> jnp.ndarray:
    """``(a_r h + c_r) + (a_h f + c_h)``: ``res`` (4, D), float32."""
    a_r, c_r, a_h, c_h = res.astype(jnp.float32)
    return (a_r * h + c_r) + (a_h * f.astype(jnp.float32) + c_h)


def _project(h, lp: Params, l: int, cfg: ZayaConfig):
    """The norm and the four compressed projections over rows ``h``
    (..., D): ``([q~ ; k~] (..., C), v1, v2 (..., W))`` float32."""
    u = rms_norm(h, lp["attn_norm"][l], cfg.norm_eps).astype(cfg.dtype)
    p = jnp.dot(u, lp["wqkv"][l], preferred_element_type=jnp.float32)
    C, W = cfg.conv_width, cfg.shift_width
    return p[..., :C], p[..., C:C + W], p[..., C + W:]


def _conv(lp: Params, l: int, cfg: ZayaConfig) -> Dict[str, Any]:
    return dict(w0=lp["conv0_w"][l], b0=lp["conv0_b"][l],
                w1=lp["conv1_w"][l], b1=lp["conv1_b"][l],
                temp=lp["temp"][l], n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads)


def _rotate(q, k, v, rope, cfg: ZayaConfig):
    """RoPE on the first ``rotary_dim`` values of each head of q^ and
    k^ (float32); all three in the served type."""
    with scope("qkv"):
        n = cfg.rotary_dim

        def turn(x):
            return jnp.concatenate(
                [apply_rope(x[..., :n], *rope), x[..., n:]], -1)

        return (turn(q).astype(cfg.dtype), turn(k).astype(cfg.dtype),
                v.astype(cfg.dtype))


def _rope(cfg: ZayaConfig, positions):
    with scope("qkv"):
        return rope_cos_sin(positions, cfg.rotary_dim, cfg.rope_theta)


def _attn_out(h, attn, lp: Params, l: int, cfg: ZayaConfig):
    with scope("attn_out"):
        o = jnp.dot(attn.reshape(h.shape[:-1] + (-1,)).astype(cfg.dtype),
                    lp["wo"][l])
        return _merge(h, o, lp["res_attn"][l])


def _fresh(tail, start):
    """A row's tail where its sequence goes on, zeros where it STARTS
    (``start`` (rows,): the row is at position 0): each convolution and
    the value shift pad their own input with zeros."""
    return jnp.where(start[:, None], 0, tail)


def _cca_slices(h, lp: Params, l: int, rs: RowState, rows, first, lengths,
                rope, cfg: ZayaConfig):
    """S slices of T tokens through layer ``l``'s mix: ``h`` (S, T, D);
    ``rows`` (S,) the batch row each slice's sequence owns (one past the
    batch's last: nobody's), ``first`` (S,) whether the slice starts its
    sequence (a tail of zeros), ``lengths`` (S,). Returns ``(q, k, v``
    rotated, in the served type``, row state)``."""
    with scope("cca_mix"):
        qk, v1, v2 = _project(h, lp, l, cfg)
        tail = rows_read(rs["tail"], l, rows)
        q, k, v, tail = cca_slices(
            _fresh(tail, first), qk, v1, v2, lengths,
            **_conv(lp, l, cfg))
        rs = {"tail": rows_write(rs["tail"], l, rows, tail)}
    return _rotate(q, k, v, rope, cfg) + (rs,)


def _decode_layer(h, lp: Params, l: int, k_pool, v_pool, rs: RowState, geom,
                  live, start, rope, cfg: ZayaConfig):
    """One decode token a row through layer ``l``'s CCA sublayer (the
    decode program's and the decode rows' half of the mixed step's):
    ``geom`` the step's ``models/granitemoehybrid._decode_geometry`` (the
    hidden rows stay by batch row — the tail is a row's — and the order
    the attention kernel wants is made once a step), ``live`` (B,) the
    rows whose tail moves on, ``start`` (B,) the rows at position 0."""
    B = h.shape[0]
    with scope("cca_mix"):
        qk, v1, v2 = _project(h, lp, l, cfg)
        old = rs["tail"][l, :B]
        q, k, v, new = cca_step(_fresh(old, start), qk, v1, v2,
                                **_conv(lp, l, cfg))
        rs = {"tail": rs["tail"].at[l, :B].set(
            jnp.where(live[:, None], new, old))}
    q, k, v = _rotate(q, k, v, rope, cfg)
    block_tables, page_of, slot_of, seq_lens, order = geom
    with scope("attn"):
        attn, k_pool, v_pool = paged_decode_step(
            q, k, v, k_pool, v_pool, block_tables, seq_lens, page_of,
            slot_of, jnp.asarray(l, jnp.int32), enabled=cfg.pallas,
            order=order)
    return _attn_out(h, attn, lp, l, cfg), k_pool, v_pool, rs


def _ffn(params: Params, cfg: ZayaConfig, l: int, h, r, live):
    """Layer ``l``'s routed sublayer over the stream's rows ``h`` (N,
    D) float32, ``r`` (N, R) float32 what the layer before's router
    left (ignored by the model's first layer). Returns ``(h', r',
    stats, experts)``: ``ops/moe.routed_ffn``'s counts and the experts
    chosen (N, 1)."""
    lp, rt, hi = params["layers"], params["router"], lax.Precision.HIGHEST
    f32 = jnp.float32
    with scope("mlp"):
        s = rms_norm(h, lp["mlp_norm"][l], cfg.norm_eps)
    with scope("moe_route"):
        def lin(x, w, b=None):
            y = jnp.dot(x, rt[w][l].astype(f32), precision=hi)
            return y if b is None else y + rt[b][l].astype(f32)

        here = lin(s, "w_down", "b_down")
        r = here if l == 0 else here + rt["gamma"][l].astype(f32) * r
        x = rms_norm(r, rt["norm"][l], cfg.norm_eps)
        x = jax.nn.gelu(lin(x, "w1", "b1"), approximate=False)
        x = jax.nn.gelu(lin(x, "w2", "b2"), approximate=False)
        p = jax.nn.softmax(lin(x, "w3"), axis=-1)
        experts, gates = choose(p, rt["bias"][l],
                                top_k=cfg.n_experts_per_tok, scale=1.0,
                                norm_topk=False)
    m = params["moe"]
    y, st = routed_ffn(s.astype(cfg.dtype), experts, gates,
                       m["we_gate_up"][l], m["we_down"][l], live)
    with scope("mlp"):
        return _merge(h, y, lp["res_mlp"][l]), r, st, experts


def _no_carry(cfg: ZayaConfig, n: int) -> jnp.ndarray:
    return jnp.zeros((n, cfg.router_dim), jnp.float32)


@partial(jax.jit, static_argnames=("cfg", "last_only", "stats", "chosen"))
def forward_prefill(params: Params, cfg: ZayaConfig, tokens: jnp.ndarray,
                    positions: jnp.ndarray, lengths: jnp.ndarray,
                    kv_cache: KVCache, block_tables: jnp.ndarray,
                    last_only: bool = False, stats: bool = False,
                    row_state: Optional[RowState] = None,
                    rows: Optional[jnp.ndarray] = None,
                    chosen: bool = False):
    """``models/llama.forward_prefill``'s signature and conventions,
    and beside them ``row_state`` and ``rows`` (B,): the batch row each
    sequence owns. A chunk that starts at position 0 starts from a tail
    of zeros; any other continues what its row holds. Returns ``(logits,
    cache, row_state)``, and after them the routed layers' counts with
    ``stats`` and their choices (rows in (B, T) order) with ``chosen``
    (``ops/moe.pass_extras``)."""
    B, T = tokens.shape
    row_state, rows = own_rows(partial(init_row_state, cfg), B, row_state,
                               rows)
    h = _embed(params, tokens)
    rope = _rope(cfg, positions)
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    seq_lens = jnp.max(jnp.where(valid, positions, -1), axis=1) + 1
    first = positions[:, 0] == 0
    lp, counts = params["layers"], []
    k_pool, v_pool = kv_cache["k"], kv_cache["v"]
    r = _no_carry(cfg, B * T)
    for l in range(cfg.n_layers):
        q, k, v, row_state = _cca_slices(h, lp, l, row_state, rows, first,
                                         lengths, rope, cfg)
        with scope("kv_write"):
            k_pool, v_pool = paged_kv_write_prefill(
                k_pool, v_pool, k, v, block_tables, positions, lengths,
                jnp.asarray(l, jnp.int32), enabled=cfg.pallas,
                multi_ok=cfg.pallas_batched_prefill)
        with scope("attn"):
            attn = dispatch_prefill_attention(
                q, k_pool, v_pool, block_tables, positions, seq_lens, l,
                enabled=cfg.pallas, multi_ok=cfg.pallas_batched_prefill)
        h = _attn_out(h, attn, lp, l, cfg)
        h, r, *took = _ffn(params, cfg, l, h.reshape(B * T, -1), r,
                           valid.reshape(-1))
        h = h.reshape(B, T, -1)
        counts.append(took)
    if last_only:
        with scope("head"):
            h = h[jnp.arange(B), lengths - 1]
    out = (_head(params, cfg, h), {"k": k_pool, "v": v_pool}, row_state)
    return out + pass_extras(counts, cfg.n_experts + 1, stats, chosen)


@partial(jax.jit, static_argnames=("cfg", "stats", "chosen"))
def forward_decode(params: Params, cfg: ZayaConfig, tokens: jnp.ndarray,
                   positions: jnp.ndarray, kv_cache: KVCache,
                   block_tables: jnp.ndarray,
                   active: Optional[jnp.ndarray] = None,
                   stats: bool = False,
                   row_state: Optional[RowState] = None,
                   chosen: bool = False):
    """One decode step for every active row
    (``models/llama.forward_decode``'s contract); batch row ``b``
    updates row ``b`` of ``row_state``. A row that is not active leaves
    its tail as it found it, writes to page 0 and is routed to no
    expert; its carry is computed and thrown away like the rest of it,
    and its logits mean nothing. Returns ``(logits (B, V), cache,
    row_state)``, and ``pass_extras`` after them."""
    B = tokens.shape[0]
    row_state, _ = own_rows(partial(init_row_state, cfg), B, row_state,
                            None)
    live = jnp.ones((B,), bool) if active is None else active
    h = _embed(params, tokens)
    rope = _rope(cfg, positions)
    geom = _decode_geometry(positions, block_tables, kv_cache, active, cfg)
    lp, counts = params["layers"], []
    k_pool, v_pool = kv_cache["k"], kv_cache["v"]
    r = _no_carry(cfg, B)
    for l in range(cfg.n_layers):
        h, k_pool, v_pool, row_state = _decode_layer(
            h, lp, l, k_pool, v_pool, row_state, geom, live, positions == 0,
            rope, cfg)
        h, r, *took = _ffn(params, cfg, l, h, r, active)
        counts.append(took)
    out = (_head(params, cfg, h), {"k": k_pool, "v": v_pool}, row_state)
    return out + pass_extras(counts, cfg.n_experts + 1, stats, chosen)


@partial(jax.jit, static_argnames=("cfg", "stats", "chosen"))
def forward_mixed(params: Params, cfg: ZayaConfig, dec_tokens: jnp.ndarray,
                  dec_positions: jnp.ndarray, kv_cache: KVCache,
                  dec_block_tables: jnp.ndarray, pf_tokens: jnp.ndarray,
                  pf_positions: jnp.ndarray, pf_lengths: jnp.ndarray,
                  pf_starts: jnp.ndarray, pf_block_tables: jnp.ndarray,
                  dec_active: Optional[jnp.ndarray] = None,
                  stats: bool = False,
                  row_state: Optional[RowState] = None,
                  pf_rows: Optional[jnp.ndarray] = None,
                  chosen: bool = False):
    """The fused mixed step (``models/llama.forward_mixed``'s contract,
    the slices' tokens TIGHT and ``pf_starts`` with them), and beside it
    ``row_state`` and ``pf_rows`` (S,): the batch row each slice's
    sequence owns; an unused slice names one past the last row. A slice
    is never one of the step's active decode rows, so the two halves of
    a layer touch different rows of the tail and different pages. The
    slices go back onto the (S, T) grid at the door
    (``mixed_live_rows``); the routed sublayer — its router network and
    its carry with it — runs slices and decode rows together, so the
    experts are streamed once for both. Returns ``(dec_logits (B, V),
    pf_logits (S, V), cache, row_state)`` and ``pass_extras`` after them
    (``chosen``: the slices' S * T grid rows, then the B decode rows)."""
    B = dec_tokens.shape[0]
    S = pf_lengths.shape[0]
    T = pf_tokens.shape[0] // S
    row_state, _ = own_rows(partial(init_row_state, cfg), B, row_state,
                            None)
    if pf_rows is None:
        pf_rows = jnp.full((S,), B, jnp.int32)
    live_d = jnp.ones((B,), bool) if dec_active is None else dec_active
    pf_tokens = rows_to_grid(pf_tokens, pf_starts, T)
    pf_positions, pf_seq_lens = grid_positions(pf_positions, pf_lengths,
                                               pf_starts, T)
    k_pool, v_pool = kv_cache["k"], kv_cache["v"]
    with scope("decode_rows"):
        h_d = _embed(params, dec_tokens)
        rope_d = _rope(cfg, dec_positions)
        geom = _decode_geometry(dec_positions, dec_block_tables, kv_cache,
                                dec_active, cfg)
    with scope("slices"):
        h_p = _embed(params, pf_tokens)
        rope_p = _rope(cfg, pf_positions)
        pf_valid = jnp.arange(T)[None, :] < pf_lengths[:, None]
        first = pf_positions[:, 0] == 0
    live = jnp.concatenate([pf_valid.reshape(-1), live_d])
    lp, counts = params["layers"], []
    r = _no_carry(cfg, S * T + B)
    for l in range(cfg.n_layers):
        with scope("slices"):
            q, k, v, row_state = _cca_slices(h_p, lp, l, row_state, pf_rows,
                                             first, pf_lengths, rope_p, cfg)
            with scope("kv_write"):
                k_pool, v_pool = paged_kv_write_prefill(
                    k_pool, v_pool, k, v, pf_block_tables, pf_positions,
                    pf_lengths, jnp.asarray(l, jnp.int32),
                    enabled=cfg.pallas, multi_ok=cfg.pallas_batched_prefill)
            with scope("attn"):
                attn = dispatch_prefill_attention(
                    q, k_pool, v_pool, pf_block_tables, pf_positions,
                    pf_seq_lens, l, enabled=cfg.pallas,
                    multi_ok=cfg.pallas_batched_prefill)
                # The decode rows' write takes the pool in place: only
                # once the slices' attention has read it, or XLA copies
                # the whole pool to keep both
                # (models/granitemoehybrid.forward_mixed).
                attn, k_pool, v_pool = lax.optimization_barrier(
                    (attn, k_pool, v_pool))
            h_p = _attn_out(h_p, attn, lp, l, cfg)
        with scope("decode_rows"):
            h_d, k_pool, v_pool, row_state = _decode_layer(
                h_d, lp, l, k_pool, v_pool, row_state, geom, live_d,
                dec_positions == 0, rope_d, cfg)
        # The routed sublayer takes both kinds of row side by side (its
        # matrices are streamed once): no row kind on its scopes.
        h, r, *took = _ffn(params, cfg, l,
                           jnp.concatenate([h_p.reshape(S * T, -1), h_d]), r,
                           live)
        h_p, h_d = h[:S * T].reshape(S, T, -1), h[S * T:]
        counts.append(took)
    with scope("slices"):
        with scope("head"):
            h_p = h_p[jnp.arange(S), pf_lengths - 1]
        pf_logits = _head(params, cfg, h_p)
    with scope("decode_rows"):
        dec_logits = _head(params, cfg, h_d)
    out = (dec_logits, pf_logits, {"k": k_pool, "v": v_pool}, row_state)
    return out + pass_extras(counts, cfg.n_experts + 1, stats, chosen)
