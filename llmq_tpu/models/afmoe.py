"""The Arcee ``afmoe`` block (Trinity) in pure JAX: grouped-query
attention whose layers are, in a published order, SLIDING-WINDOW layers
(rotary positions, each query sees its last ``sliding_window`` keys) or
FULL layers (no rotary embedding, every key), each with a per-head
RMSNorm on q and k and an output GATE; a norm before AND after every
sublayer; a dense SwiGLU in the first ``n_dense_layers`` layers and a
sigmoid-routed layer beside one shared expert after.

With ``t = layer_types[l]``::

    h0 = E[token] * sqrt(dim)                                # mup_enabled
    x  = rms(h; g_in)
    q  = rms_head(x Wq; g_q)   k = rms_head(x Wk; g_k)   v = x Wv
    t == sliding:  q, k = rope(q, k)                         # full: none
    key s visible to query p:  s <= p, and sliding: s > p - W
    a  = softmax(q k^T / sqrt(hd)) v  *  sigmoid(x Wg)       # the gate
    h  = h + rms(a Wo; g_post_attn)                          # sandwich
    y  = rms(h; g_pre_mlp)
    l <  n_dense_layers:  f = SwiGLU(y)
    l >= n_dense_layers:  s = sigmoid(y Wr) (float32); top-k of (s + b),
                          b chooses only; g = s / (sum s + 1e-20) * scale;
                          f = shared(y) + sum_e g_e expert_e(y)
    h  = h + rms(f; g_post_mlp)
    logits = rms(h; g_final) W_head

The router is ``ops/moe.route(scoring="sigmoid", norm_topk=True)`` and
the experts ``ops/moe.routed_ffn``, as ``models/deepseek_v3``'s; **a
chip's share** of each routed layer's experts is ``held_experts`` (``lo,
hi``), as ``models/longcat_flash``'s: the router scores all E, a token
chooses among all, the held pairs are multiplied here, the shared expert
is computed here whole, and what the experts held elsewhere would have
added is NOT — nothing stands in for the other chips.

**Two kinds of cache.** A full layer's K and V grow with the context
and live in the page pool (``init_kv_pages``: leaves over the FULL
layers only, ``models/llama``'s layout). A sliding layer needs its last
W keys whatever the context: its K and V are ROW STATE
(``init_row_state``: ``wk`` / ``wv`` ``(L_w, 1 + rows * n, page_size,
H_kv * hd)``), a SLAB of ``n`` pages a batch row a layer, written as a
ring, behind a page 0 that is reserved as the pool's is. The program
makes the slab's block table arithmetically — position page ``j`` of
batch row ``r`` is slab page ``1 + r n + j mod n`` —
so the kernels see an ordinary position-indexed table and the same
pool layout; their ``window`` argument keeps them off the chunks that
lie wholly before the window, and what an aliased entry inside the
first visited chunk shows (a NEWER page, finite) is masked. ``n`` covers
the window and everything ONE program may write for one row before it
reads (``bind_cache``: ``ceil((W + step_tokens) / page_size) + 1``), so
no visible key is overwritten before it is read. Page 0 is NOBODY'S,
as page 0 of the pool is: where a row that does not decode, a slice
that is not used and a slice's padding leave their token. The host
allocates
nothing for a sliding layer, and the pages alone no longer rebuild a
sequence: the engine adopts no pinned conversation, promotion or
hand-over for this family (``get_stats()["row_state"]``); a cached
PREFIX it can, by a TAIL (``row_tail``, at the end of this file).

Float32 residual stream and router, bf16 products, every layer
unrolled. In a mixed step the attention runs a layer's slices and its
decode rows apart and the feed-forward runs them TOGETHER (a routed
layer's experts are streamed once for both), as ``deepseek_v3``'s.
Int8 weights, an int8 cache and a mesh are not written: each is refused
by name (``check_serving``).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from llmq_tpu.models.latent import draw_groups
from llmq_tpu.models.latent import prod as _prod
from llmq_tpu.models.llama import _mlp
from llmq_tpu.ops.attention import (decode_order,
                                    dispatch_prefill_attention,
                                    kernel_routes, paged_decode_step,
                                    paged_kv_write_prefill, rows_by_place)
from llmq_tpu.ops.moe import route, routed_ffn, share_counts
from llmq_tpu.ops.norms import rms_norm
from llmq_tpu.ops.rope import apply_rope, rope_cos_sin
from llmq_tpu.ops.rows import grid_positions, rows_to_grid
from llmq_tpu.ops.ssm import own_rows
from llmq_tpu.utils.profiling import scope

Params = Dict[str, Any]
KVCache = Dict[str, jnp.ndarray]
RowState = Dict[str, jnp.ndarray]

SLIDING, FULL = "sliding_attention", "full_attention"

#: The context a decode step hands the attention kernel for a row that
#: is not active (``_decode_geometry``: it attends to nothing) — what
#: the executor's ``attn_work`` counts an empty seat as.
IDLE_ROW_CONTEXT = 0


@dataclass(frozen=True)
class AfmoeConfig:
    FAMILY: ClassVar[str] = "afmoe"        # models/__init__.py
    name: str = "afmoe-tiny"
    vocab_size: int = 512                  # the rows of the vocabulary HELD
    dim: int = 128
    #: The published ``layer_types``.
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 2
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 64
    sliding_window: int = 24
    ffn_dim: int = 256                     # the dense layers' SwiGLU
    n_dense_layers: int = 1
    moe_ffn_dim: int = 64                  # one expert's SwiGLU
    n_routed_experts: int = 16
    n_experts_per_tok: int = 4
    n_shared_experts: int = 1
    route_scale: float = 2.448
    route_norm: bool = True
    held_experts: Optional[Tuple[int, int]] = None    # None: all E
    mup_enabled: bool = True
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    pallas: bool = True
    pallas_batched_prefill: bool = False
    #: The cache's geometry (``bind_cache``; 0: not bound yet): the page
    #: size of the pool and of the slabs, and the pages a slab holds.
    page_size: int = 0
    slab_pages: int = 0

    def __post_init__(self) -> None:
        lo, hi = self.held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(
                f"model {self.name!r}: held_experts {self.held_experts} of "
                f"{self.n_routed_experts} routed experts")
        if set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"model {self.name!r}: layer_types "
                             f"{sorted(set(self.layer_types))}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_sliding(self) -> int:
        return self.layer_types.count(SLIDING)

    @property
    def n_full(self) -> int:
        return self.layer_types.count(FULL)

    @property
    def n_routed_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def held(self) -> Tuple[int, int]:
        """The router's experts whose matrices this chip holds."""
        return (tuple(self.held_experts) if self.held_experts is not None
                else (0, self.n_routed_experts))

    @property
    def n_held(self) -> int:
        return self.held[1] - self.held[0]

    def kind_index(self, l: int) -> int:
        """Layer ``l``'s index among the layers of its kind: the layer
        of its cache leaf."""
        return self.layer_types[:l].count(self.layer_types[l])


def afmoe_tiny(**kw) -> AfmoeConfig:
    """CPU-test size: two periods of ``s s s f``, layer 0 dense, 16
    experts with 4 a token beside a shared one, a window of 24."""
    return replace(AfmoeConfig(), **kw)


def trinity_large_preview(**kw) -> AfmoeConfig:
    """arcee-ai/Trinity-Large-Preview at its published sizes
    (https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/main/config.json):
    60 layers, 3 sliding (window 4,096) then 1 full, 15 times; hidden
    3,072, 48 query heads over 8 KV heads of 128; the first 6 layers a
    dense SwiGLU of 12,288, the other 54 routed: 256 experts of 3,072
    with 4 a token (sigmoid, renormalised, scaled 2.448) beside 1 shared
    expert; vocabulary 200,192, untied head, RoPE theta 10,000, context
    262,144. About 400 B parameters: one chip holds a share
    (benchmark/configs/trinity-large-preview-bf16-ep16.json: 5 layers,
    16 of the 256 experts, an eighth of the vocabulary)."""
    return replace(AfmoeConfig(
        name="trinity-large-preview", vocab_size=200192, dim=3072,
        layer_types=(SLIDING, SLIDING, SLIDING, FULL) * 15, n_heads=48,
        n_kv_heads=8, head_dim=128, sliding_window=4096, ffn_dim=12288,
        n_dense_layers=6, moe_ffn_dim=3072, n_routed_experts=256,
        n_experts_per_tok=4, n_shared_experts=1, route_scale=2.448,
        route_norm=True, mup_enabled=True, max_seq_len=262144,
        rope_theta=10000.0, norm_eps=1e-5), **kw)


MODEL_CONFIGS = {
    "afmoe-tiny": afmoe_tiny,
    "trinity-large-preview": trinity_large_preview,
}


# -- the family surface (models/__init__.py) -----------------------------------

def serving_config(cfg: AfmoeConfig) -> AfmoeConfig:
    return replace(cfg, pallas_batched_prefill=True)


def bind_cache(cfg: AfmoeConfig, *, page_size: int,
               step_tokens: int) -> AfmoeConfig:
    """``cfg`` with the cache's geometry bound: ``page_size`` of the
    pool (the slabs are cut in the same pages) and ``step_tokens``, the
    most tokens ONE program writes for one sequence before it reads
    (a decode step 1, a prefill program its bucket, a mixed step ONE
    slice — the engine packs one slice a sequence a step,
    ``engine._pack_prefill_slices``; a caller that puts several slices
    of one prompt into a step binds their sum: the executor gives the
    largest of what it runs)."""
    # the window and the step's writes in whole pages, and one more for
    # a window that starts inside a page
    n = -(-(cfg.sliding_window + max(1, step_tokens)) // page_size) + 1
    return replace(cfg, page_size=int(page_size), slab_pages=n)


def attention_window(cfg: AfmoeConfig) -> Dict[str, int]:
    """What the engine counts a window cache by (``get_stats()
    ["window"]``): the window, the layers that have one and the tokens a
    row's slab reserves in each."""
    return {"tokens": cfg.sliding_window, "layers": cfg.n_sliding,
            "slab_tokens": cfg.slab_pages * cfg.page_size}


def check_serving(cfg: AfmoeConfig, *, quantization: str = "",
                  kv_quantization: str = "", mesh: bool = False) -> None:
    """Refuse what is not written for this family, naming the setting."""
    what = None
    if quantization:
        what = f"model.quantization={quantization!r} (int8 experts)"
    elif kv_quantization:
        what = (f"model.kv_quantization={kv_quantization!r} (int8 pages "
                f"beside the sliding layers' slabs)")
    elif mesh:
        what = ("executor.mesh (no partition rules for the slabs or the "
                "experts, no exchange between shares)")
    if what:
        raise ValueError(f"model {cfg.name!r} (family afmoe) does not "
                         f"support {what}; unset it")


def import_hf(model_dir: str, cfg: AfmoeConfig,
              meta_rope_layout: bool = False) -> Params:
    """A local Hugging Face ``afmoe`` checkpoint directory into this
    family's tree (``models/checkpoint.import_hf_afmoe``): the held
    experts and the held rows of the vocabulary alone."""
    if meta_rope_layout:
        raise ValueError("model.meta_rope_layout is the Llama block's "
                         "(Meta's .pth layout)")
    from llmq_tpu.models.checkpoint import import_hf_afmoe
    return import_hf_afmoe(model_dir, cfg)


def step_stats_layout(cfg: AfmoeConfig) -> Dict[str, Any]:
    """``models/longcat_flash.step_stats_layout``'s, without
    zero-compute experts: the tokens each HELD expert received, the held
    experts that received any summed over the routed layers, the slots
    whose expert is held elsewhere, and the routed layers run."""
    n = cfg.n_held
    return {"load": (0, n), "touched": n, "away_slots": n + 1,
            "runs": n + 2}


def step_stats_size(cfg: AfmoeConfig) -> int:
    return cfg.n_held + 3


def mixed_live_rows(tokens: int, batch: int, slices: int, width: int) -> int:
    """Every row of the slices' grid, whatever ``tokens`` is
    (``models/deepseek_v3.mixed_live_rows``)."""
    return slices * width


# -- parameters ---------------------------------------------------------------

def param_shapes(cfg: AfmoeConfig) -> Dict[str, Dict[str, tuple]]:
    """Leaf name -> (shape, fan_in) by group (init, the loader and the
    benchmark's builder follow it). ``layers``: the attention's
    matrices stacked over all L layers; ``dense``: the SwiGLUs of the
    first ``n_dense_layers``; ``moe``: router and shared expert stacked
    over the routed layers; ``experts``: a leaf OF ITS OWN a routed
    layer (``params["moe"]["we_gate_up"]`` is a tuple of them), the
    HELD experts' matrices."""
    L, Ld, Lm = cfg.n_layers, cfg.n_dense_layers, cfg.n_routed_layers
    D, V, F, Fe = cfg.dim, cfg.vocab_size, cfg.ffn_dim, cfg.moe_ffn_dim
    H, G, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Fs = cfg.n_shared_experts * Fe
    return {
        "layers": {"wq": ((L, D, H * hd), D), "wk": ((L, D, G * hd), D),
                   "wv": ((L, D, G * hd), D), "wg": ((L, D, H * hd), D),
                   "wo": ((L, H * hd, D), H * hd)},
        "dense": {"w_gate": ((Ld, D, F), D), "w_up": ((Ld, D, F), D),
                  "w_down": ((Ld, F, D), F)},
        "moe": {"router": ((Lm, D, cfg.n_routed_experts), D),
                "ws_gate": ((Lm, D, Fs), D), "ws_up": ((Lm, D, Fs), D),
                "ws_down": ((Lm, Fs, D), Fs)},
        "experts": {"we_gate_up": ((cfg.n_held, D, 2 * Fe), D),
                    "we_down": ((cfg.n_held, Fe, D), Fe)},
        "top": {"embed": ((V, D), D), "lm_head": ((D, V), D)},
    }


#: A layer's four RMSNorms over the stream and its two over a head.
STREAM_NORMS = ("attn_norm", "post_attn_norm", "mlp_norm", "post_mlp_norm")
HEAD_NORMS = ("q_norm", "k_norm")


def norm_leaves(cfg: AfmoeConfig) -> Params:
    """The tree's RMSNorm weights (ones) and the router's selection
    bias (zeros, float32): what a random init does not draw."""
    L, D = cfg.n_layers, cfg.dim
    layers = {n: jnp.ones((L, D), cfg.dtype) for n in STREAM_NORMS}
    layers.update({n: jnp.ones((L, cfg.head_dim), cfg.dtype)
                   for n in HEAD_NORMS})
    return {"layers": layers,
            "moe": {"router_bias": jnp.zeros(
                (cfg.n_routed_layers, cfg.n_routed_experts), jnp.float32)},
            "final_norm": jnp.ones((D,), cfg.dtype)}


def assemble(cfg: AfmoeConfig, drawn: Dict[str, Dict[str, Any]]) -> Params:
    """``param_shapes``-shaped groups of arrays (``experts``: a list of
    one array a routed layer under each name) + ``norm_leaves`` -> the
    parameter tree."""
    fixed = norm_leaves(cfg)
    return {"embed": drawn["top"]["embed"],
            "lm_head": drawn["top"]["lm_head"],
            "final_norm": fixed["final_norm"],
            "layers": {**drawn["layers"], **fixed["layers"]},
            "dense": dict(drawn["dense"]),
            "moe": {**drawn["moe"], **fixed["moe"],
                    **{k: tuple(v) for k, v in drawn["experts"].items()}}}


def init_params(key: jax.Array, cfg: AfmoeConfig) -> Params:
    """Random-init parameter tree, N(0, 1 / fan_in) as the other
    families'."""
    return assemble(cfg, draw_groups(key, param_shapes(cfg), cfg.dtype,
                                     cfg.n_routed_layers))


def init_params_quantized(key: jax.Array, cfg: AfmoeConfig) -> Params:
    check_serving(cfg, quantization="int8")


def param_count(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


def param_count_analytic(cfg: AfmoeConfig) -> int:
    """Parameters HELD, from the configuration alone."""
    n = sum(_prod(shape) * (cfg.n_routed_layers if g == "experts" else 1)
            for g, leaves in param_shapes(cfg).items()
            for shape, _f in leaves.values())
    fixed = (cfg.n_layers * (len(STREAM_NORMS) * cfg.dim
                             + len(HEAD_NORMS) * cfg.head_dim) + cfg.dim
             + cfg.n_routed_layers * cfg.n_routed_experts)
    return n + fixed


def active_param_count(cfg: AfmoeConfig) -> int:
    """Parameters one token multiplies with HERE, in expectation: the
    held count less the held experts it is not routed to (of its k
    slots, the share n_held / E falls on a held expert under uniform
    routing)."""
    idle = cfg.n_held * (1 - cfg.n_experts_per_tok / cfg.n_routed_experts)
    return int(param_count_analytic(cfg)
               - cfg.n_routed_layers * idle * 3 * cfg.dim * cfg.moe_ffn_dim)


def weight_bytes(cfg: AfmoeConfig) -> int:
    return param_count_analytic(cfg) * jnp.dtype(cfg.dtype).itemsize


def kv_bytes_per_token(cfg: AfmoeConfig,
                       cache_dtype: Optional[Any] = None) -> int:
    """K and V of the FULL layers: all a token adds to the page pool
    (the sliding layers' are bounded a row: ``row_state_bytes_per_row``)."""
    itemsize = jnp.dtype(cache_dtype or cfg.dtype).itemsize
    return 2 * cfg.n_full * cfg.n_kv_heads * cfg.head_dim * itemsize


def init_kv_pages(cfg: AfmoeConfig, num_pages: int, page_size: int,
                  dtype: Optional[Any] = None) -> KVCache:
    """The page pool of the full layers, ``models/llama``'s layout:
    ``(L_f, P, page_size, H_kv * head_dim)`` for K and for V, page 0
    reserved."""
    dt = dtype or cfg.dtype
    if jnp.dtype(dt) == jnp.int8:
        check_serving(cfg, kv_quantization="int8")
    shape = (cfg.n_full, num_pages, page_size, cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _bound(cfg: AfmoeConfig) -> None:
    if cfg.page_size <= 0 or cfg.slab_pages <= 0:
        raise ValueError(f"model {cfg.name!r}: the sliding layers' slabs "
                         f"have no geometry yet (afmoe.bind_cache)")


def init_row_state(cfg: AfmoeConfig, batch: int) -> RowState:
    """The sliding layers' K and V for ``batch`` rows, zero: ``wk`` /
    ``wv`` ``(L_w, 1 + batch * n, page_size, H_kv * head_dim)`` — the
    pool's layout, so the same kernels read and write them — page 0
    reserved, batch row ``r``'s slab the pages ``1 + r n .. r n + n``."""
    _bound(cfg)
    shape = (cfg.n_sliding, 1 + batch * cfg.slab_pages, cfg.page_size,
             cfg.n_kv_heads * cfg.head_dim)
    return {"wk": jnp.zeros(shape, cfg.dtype),
            "wv": jnp.zeros(shape, cfg.dtype)}


def row_state_bytes_per_row(cfg: AfmoeConfig) -> int:
    """What one batch row's slabs hold, whatever its sequence's
    length."""
    _bound(cfg)
    return (2 * cfg.n_sliding * cfg.slab_pages * cfg.page_size
            * cfg.n_kv_heads * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize)


def routes(cfg: AfmoeConfig, cache: KVCache, *, batch: int, page_size: int,
           max_pages: int, decode: bool = False,
           prefill_rows: int = 0) -> Dict[str, str]:
    """Both kinds of layer take the same routes
    (``ops/attention.kernel_routes``: the slabs have the pool's layout
    and the table its width); a route that serves the sliding layers
    says with which window."""
    out = kernel_routes(
        batch=batch, page_size=page_size, max_pages=max_pages,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        kv_itemsize=cache["k"].dtype.itemsize, quant_kv=False,
        enabled=cfg.pallas, multi_ok=cfg.pallas_batched_prefill,
        decode=decode, prefill_rows=prefill_rows)
    for op in ("decode_attention", "prefill_attention"):
        if op in out:
            out[op + "_window"] = f"{out[op]}[window={cfg.sliding_window}]"
    return out


# -- the layer -----------------------------------------------------------------

def _normed(h, w, cfg: AfmoeConfig) -> jnp.ndarray:
    return rms_norm(h, w, cfg.norm_eps).astype(cfg.dtype)


def _embed(params: Params, cfg: AfmoeConfig, tokens) -> jnp.ndarray:
    with scope("embed"):
        h = params["embed"][tokens].astype(jnp.float32)
        return h * cfg.dim ** 0.5 if cfg.mup_enabled else h


def _head(params: Params, cfg: AfmoeConfig, h) -> jnp.ndarray:
    with scope("head"):
        return jnp.dot(_normed(h, params["final_norm"], cfg),
                       params["lm_head"]).astype(jnp.float32)


def _rotates(cfg: AfmoeConfig, l: int) -> bool:
    """Whether layer ``l`` rotates q and k: the sliding layers do, the
    full layers have no position embedding."""
    return cfg.layer_types[l] == SLIDING


def _head_norm(x, w, cfg: AfmoeConfig) -> jnp.ndarray:
    """RMSNorm over each head's ``head_dim`` values."""
    return rms_norm(x, w, cfg.norm_eps)


def _post_norm(f, lp: Params, name: str, l: int, cfg: AfmoeConfig):
    """The norm AFTER a sublayer (``post_attn_norm``,
    ``post_mlp_norm``): what goes onto the stream, float32."""
    return rms_norm(f.astype(jnp.float32), lp[name][l], cfg.norm_eps)


def _qkvg(x, lp: Params, l: int, cos, sin, cfg: AfmoeConfig):
    """Layer ``l``'s q, k, v and gate of the normalised rows ``x``
    (..., D): q (..., H, hd), k, v (..., G, hd), gate (..., H * hd).
    q and k are normalised per head and, in a sliding layer, rotated
    (``cos``, ``sin`` (..., hd / 2))."""
    with scope("qkv"):
        q, k, v = (jnp.dot(x, lp[w][l]).reshape(x.shape[:-1]
                                                + (-1, cfg.head_dim))
                   for w in ("wq", "wk", "wv"))
        q = _head_norm(q, lp["q_norm"][l], cfg)
        k = _head_norm(k, lp["k_norm"][l], cfg)
        if _rotates(cfg, l):
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    with scope("attn_gate"):
        gate = jnp.dot(x, lp["wg"][l])
    return q, k, v, gate


def _attn_close(h, attn, gate, lp: Params, l: int, cfg: AfmoeConfig):
    """The gate, the output projection and the norm AFTER it."""
    with scope("attn_gate"):
        a = (attn.reshape(gate.shape).astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(cfg.dtype)
    with scope("attn_out"):
        return h + _post_norm(jnp.dot(a, lp["wo"][l]), lp,
                              "post_attn_norm", l, cfg)


def _ffn(params: Params, cfg: AfmoeConfig, l: int, h, live):
    """Layer ``l``'s feed-forward over the stream's rows h (N, D),
    between its two norms. Returns (h', stats or None):
    ``ops/moe.routed_ffn``'s counts of a routed layer as
    ``step_stats_layout`` has them (without its ``runs``)."""
    lp = params["layers"]
    with scope("mlp"):
        yf = rms_norm(h, lp["mlp_norm"][l], cfg.norm_eps)
        y = yf.astype(cfg.dtype)
    if l < cfg.n_dense_layers:
        with scope("mlp"):
            d = params["dense"]
            f, st = _mlp(y, d["w_gate"][l], d["w_up"][l], d["w_down"][l]), None
    else:
        m, i = params["moe"], l - cfg.n_dense_layers
        experts, gates = route(
            yf, m["router"][i], m["router_bias"][i],
            top_k=cfg.n_experts_per_tok, scale=cfg.route_scale,
            norm_topk=cfg.route_norm, scoring="sigmoid")
        # (``n_routed``: the router's width, so that share 0 — experts
        # 0 .. n_held - 1 — is known for a share and not taken for all)
        f, st = routed_ffn(y, experts, gates, m["we_gate_up"][i],
                           m["we_down"][i], live, held=cfg.held,
                           n_routed=cfg.n_routed_experts)
        st = share_counts(st, cfg.n_held)
        with scope("mlp"):    # the shared expert, beside the routed ones
            f = f + _mlp(y, m["ws_gate"][i], m["ws_up"][i], m["ws_down"][i])
    with scope("mlp"):
        return h + _post_norm(f, lp, "post_mlp_norm", l, cfg), st


def _sum_stats(cfg: AfmoeConfig, per_layer) -> jnp.ndarray:
    """One forward pass's counters (``step_stats_size``): the routed
    layers' counts summed, then how many routed layers ran."""
    got = [st for st in per_layer if st is not None]
    total = sum(got, jnp.zeros((cfg.n_held + 2,), jnp.int32))
    return jnp.concatenate([total, jnp.full((1,), len(got), jnp.int32)])


def _slab_table(rows, cfg: AfmoeConfig, rs: RowState,
                width: int) -> jnp.ndarray:
    """The sliding layers' block table of the batch rows ``rows`` (R,):
    position page ``j`` is slab page ``1 + r n + j mod n``; a row past
    the last (an unused slice's) has page 0 throughout."""
    n = cfg.slab_pages
    rows = rows.astype(jnp.int32)[:, None]
    table = 1 + rows * n + (jnp.arange(width, dtype=jnp.int32) % n)[None]
    return jnp.where(rows < (rs["wk"].shape[1] - 1) // n, table, 0)


def _own_rows(cfg: AfmoeConfig, batch: int, kv_cache: KVCache, row_state,
              rows):
    """A caller without row state (a test, a plain prefill) gets a zero
    one of its batch's size, row ``b`` for sequence ``b``; the slabs and
    the pool are cut in the same pages."""
    row_state, rows = own_rows(partial(init_row_state, cfg), batch,
                               row_state, rows)
    if kv_cache["k"].shape[2] != row_state["wk"].shape[2]:
        raise ValueError(
            f"model {cfg.name!r}: pages of {kv_cache['k'].shape[2]} tokens "
            f"beside slabs of {row_state['wk'].shape[2]}-token pages")
    return row_state, rows


def _pools(kind: str, kv_cache: KVCache, rs: RowState):
    return ((rs["wk"], rs["wv"]) if kind == SLIDING
            else (kv_cache["k"], kv_cache["v"]))


def _put(kind: str, kv_cache: KVCache, rs: RowState, k_pool, v_pool):
    if kind == SLIDING:
        return kv_cache, {"wk": k_pool, "wv": v_pool}
    return {"k": k_pool, "v": v_pool}, rs


def _attn_scope(kind: str):
    """The scope of the attention call of a layer of ``kind``."""
    return scope("attn_window") if kind == SLIDING else scope("attn_full")


def _window(cfg: AfmoeConfig, kind: str) -> Optional[int]:
    return cfg.sliding_window if kind == SLIDING else None


def _prefill_attn(cfg: AfmoeConfig, l: int, q, k, v, kv_cache, rs, tables,
                  positions, lengths, seq_lens):
    """Layer ``l``'s KV write and attention of prompt rows on the
    (rows, T) grid: q (S, T, H, hd); ``tables``: {kind: block table}."""
    kind = cfg.layer_types[l]
    i = jnp.asarray(cfg.kind_index(l), jnp.int32)
    k_pool, v_pool = _pools(kind, kv_cache, rs)
    with scope("kv_write"):
        k_pool, v_pool = paged_kv_write_prefill(
            k_pool, v_pool, k, v, tables[kind], positions, lengths, i,
            enabled=cfg.pallas, multi_ok=cfg.pallas_batched_prefill)
    with scope("attn"), _attn_scope(kind):
        attn = dispatch_prefill_attention(
            q, k_pool, v_pool, tables[kind], positions, seq_lens, i,
            enabled=cfg.pallas, multi_ok=cfg.pallas_batched_prefill,
            window=_window(cfg, kind))
    return (attn,) + _put(kind, kv_cache, rs, k_pool, v_pool)


def _decode_geometry(cfg: AfmoeConfig, positions, block_tables, active,
                     kv_cache: KVCache, rs: RowState):
    """What both kinds of layer need of a decode step's rows: ``tables``
    and ``page_of`` by kind (a row that is not active writes to page 0
    of the pool and of the slabs), ``slot_of``, ``seq_lens`` with 0 for
    a row that is not active (it attends to nothing), ``live``, and the
    ``order`` the attention kernel wants its rows in
    (``ops/attention.decode_order``: made here ONCE for the step's
    layers of both kinds — one geometry, so one kernel route, for the
    pool and the slabs — with the tables, ``page_of`` and ``seq_lens``
    laid out by it; None where the kernel does not serve)."""
    B = positions.shape[0]
    ps = cfg.page_size
    live = jnp.ones((B,), bool) if active is None else active
    rows = jnp.arange(B, dtype=jnp.int32)
    kinds = (FULL, SLIDING)
    tables = (block_tables,
              _slab_table(rows, cfg, rs, block_tables.shape[1]))
    page_of = tuple(jnp.where(live, table[rows, positions // ps], 0)
                    for table in tables)
    seq_lens = jnp.where(live, positions + 1, 0)
    order = decode_order(seq_lens, (kv_cache["k"], kv_cache["v"]),
                         block_tables.shape[1], cfg.head_dim,
                         enabled=cfg.pallas)
    seq_lens, *placed = rows_by_place(order, seq_lens, *tables, *page_of)
    return (dict(zip(kinds, placed[:2])), dict(zip(kinds, placed[2:])),
            positions % ps, seq_lens, live, order)


def _decode_attn(cfg: AfmoeConfig, l: int, q, k, v, kv_cache, rs, geom):
    kind = cfg.layer_types[l]
    tables, page_of, slot_of, seq_lens, _, order = geom
    k_pool, v_pool = _pools(kind, kv_cache, rs)
    with scope("attn"), _attn_scope(kind):
        attn, k_pool, v_pool = paged_decode_step(
            q, k, v, k_pool, v_pool, tables[kind], seq_lens, page_of[kind],
            slot_of, jnp.asarray(cfg.kind_index(l), jnp.int32),
            enabled=cfg.pallas, window=_window(cfg, kind), order=order)
    return (attn,) + _put(kind, kv_cache, rs, k_pool, v_pool)


# -- forward ------------------------------------------------------------------

def _rope_tables(cfg: AfmoeConfig, positions):
    with scope("qkv"):
        return rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)


@partial(jax.jit, static_argnames=("cfg", "last_only", "stats"))
def forward_prefill(params: Params, cfg: AfmoeConfig, tokens: jnp.ndarray,
                    positions: jnp.ndarray, lengths: jnp.ndarray,
                    kv_cache: KVCache, block_tables: jnp.ndarray,
                    last_only: bool = False, stats: bool = False,
                    row_state: Optional[RowState] = None,
                    rows: Optional[jnp.ndarray] = None):
    """``models/llama.forward_prefill``'s signature and conventions, and
    beside them ``row_state`` and ``rows`` (B,): the batch row whose
    slabs each sequence writes. Returns ``(logits, cache, row_state)``,
    and the routed layers' counts after them with ``stats``."""
    B, T = tokens.shape
    row_state, rows = _own_rows(cfg, B, kv_cache, row_state, rows)
    h = _embed(params, cfg, tokens)
    cos, sin = _rope_tables(cfg, positions)
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    seq_lens = jnp.max(jnp.where(valid, positions, -1), axis=1) + 1
    tables = {FULL: block_tables,
              SLIDING: _slab_table(rows, cfg, row_state,
                                   block_tables.shape[1])}
    lp, counts = params["layers"], []
    for l in range(cfg.n_layers):
        with scope("qkv"):
            x = _normed(h, lp["attn_norm"][l], cfg)
        q, k, v, gate = _qkvg(x, lp, l, cos, sin, cfg)
        attn, kv_cache, row_state = _prefill_attn(
            cfg, l, q, k, v, kv_cache, row_state, tables, positions, lengths,
            seq_lens)
        h = _attn_close(h, attn, gate, lp, l, cfg)
        h, st = _ffn(params, cfg, l, h.reshape(B * T, -1), valid.reshape(-1))
        h = h.reshape(B, T, -1)
        counts.append(st)
    if last_only:
        with scope("head"):
            h = h[jnp.arange(B), lengths - 1]
    out = (_head(params, cfg, h), kv_cache, row_state)
    return out + (_sum_stats(cfg, counts),) if stats else out


@partial(jax.jit, static_argnames=("cfg", "stats"))
def forward_decode(params: Params, cfg: AfmoeConfig, tokens: jnp.ndarray,
                   positions: jnp.ndarray, kv_cache: KVCache,
                   block_tables: jnp.ndarray,
                   active: Optional[jnp.ndarray] = None,
                   stats: bool = False,
                   row_state: Optional[RowState] = None):
    """One decode step for every active row
    (``models/llama.forward_decode``'s contract); batch row ``b`` writes
    slab ``b`` of every sliding layer. A row that is not active writes
    to page 0 of the pool and of the slabs, attends to nothing and is routed to
    no expert; its logits mean nothing. Returns ``(logits (B, V), cache,
    row_state)``, and the counts after them with ``stats``."""
    B = tokens.shape[0]
    row_state, _ = _own_rows(cfg, B, kv_cache, row_state, None)
    h = _embed(params, cfg, tokens)
    cos, sin = _rope_tables(cfg, positions)
    geom = _decode_geometry(cfg, positions, block_tables, active, kv_cache,
                            row_state)
    lp, counts = params["layers"], []
    for l in range(cfg.n_layers):
        with scope("qkv"):
            x = _normed(h, lp["attn_norm"][l], cfg)
        q, k, v, gate = _qkvg(x, lp, l, cos, sin, cfg)
        attn, kv_cache, row_state = _decode_attn(cfg, l, q, k, v, kv_cache,
                                                 row_state, geom)
        h = _attn_close(h, attn, gate, lp, l, cfg)
        h, st = _ffn(params, cfg, l, h, geom[4])
        counts.append(st)
    out = (_head(params, cfg, h), kv_cache, row_state)
    return out + (_sum_stats(cfg, counts),) if stats else out


@partial(jax.jit, static_argnames=("cfg", "stats"))
def forward_mixed(params: Params, cfg: AfmoeConfig, dec_tokens: jnp.ndarray,
                  dec_positions: jnp.ndarray, kv_cache: KVCache,
                  dec_block_tables: jnp.ndarray, pf_tokens: jnp.ndarray,
                  pf_positions: jnp.ndarray, pf_lengths: jnp.ndarray,
                  pf_starts: jnp.ndarray, pf_block_tables: jnp.ndarray,
                  dec_active: Optional[jnp.ndarray] = None,
                  stats: bool = False,
                  row_state: Optional[RowState] = None,
                  pf_rows: Optional[jnp.ndarray] = None):
    """The fused mixed step (``models/llama.forward_mixed``'s contract,
    the slices' tokens TIGHT and ``pf_starts`` with them), and beside it
    ``row_state`` and ``pf_rows`` (S,): the batch row whose slabs each
    slice writes; an unused slice names one past the last row (page 0).
    The
    slices go back onto the (S, T) grid at the door
    (``mixed_live_rows``). A layer's slices WRITE and attend before its
    decode rows do; every slice of the step is written before any
    attends, so the slabs' slack covers what the step writes for ONE
    sequence (``bind_cache``). The feed-forward runs slices and decode rows
    together. Returns ``(dec_logits (B, V), pf_logits (S, V), cache,
    row_state [, counts])``."""
    B = dec_tokens.shape[0]
    S = pf_lengths.shape[0]
    T = pf_tokens.shape[0] // S
    row_state, _ = _own_rows(cfg, B, kv_cache, row_state, None)
    if pf_rows is None:
        pf_rows = jnp.full((S,), B, jnp.int32)
    pf_tokens = rows_to_grid(pf_tokens, pf_starts, T)
    pf_positions, pf_seq_lens = grid_positions(pf_positions, pf_lengths,
                                               pf_starts, T)
    with scope("decode_rows"):
        h_d = _embed(params, cfg, dec_tokens)
        cos_d, sin_d = _rope_tables(cfg, dec_positions)
        geom = _decode_geometry(cfg, dec_positions, dec_block_tables,
                                dec_active, kv_cache, row_state)
    with scope("slices"):
        h_p = _embed(params, cfg, pf_tokens)
        cos_p, sin_p = _rope_tables(cfg, pf_positions)
        pf_valid = jnp.arange(T)[None, :] < pf_lengths[:, None]
        pf_tables = {FULL: pf_block_tables,
                     SLIDING: _slab_table(pf_rows, cfg, row_state,
                                          pf_block_tables.shape[1])}
    live = jnp.concatenate([pf_valid.reshape(-1), geom[4]])
    lp, counts = params["layers"], []
    for l in range(cfg.n_layers):
        with scope("slices"):
            with scope("qkv"):
                x = _normed(h_p, lp["attn_norm"][l], cfg)
            q, k, v, gate = _qkvg(x, lp, l, cos_p, sin_p, cfg)
            attn, kv_cache, row_state = _prefill_attn(
                cfg, l, q, k, v, kv_cache, row_state, pf_tables,
                pf_positions, pf_lengths, pf_seq_lens)
            # The decode rows' write takes the pools in place: only
            # once the slices' attention has read them, or XLA copies a
            # whole pool to keep both (models/granitemoehybrid).
            attn, kv_cache, row_state = jax.lax.optimization_barrier(
                (attn, kv_cache, row_state))
            h_p = _attn_close(h_p, attn, gate, lp, l, cfg)
        with scope("decode_rows"):
            with scope("qkv"):
                x = _normed(h_d, lp["attn_norm"][l], cfg)
            q, k, v, gate = _qkvg(x, lp, l, cos_d, sin_d, cfg)
            attn, kv_cache, row_state = _decode_attn(
                cfg, l, q, k, v, kv_cache, row_state, geom)
            h_d = _attn_close(h_d, attn, gate, lp, l, cfg)
        # The feed-forward takes both kinds of row side by side (its
        # matrices are streamed once): no row kind on its scopes.
        h, st = _ffn(params, cfg, l,
                     jnp.concatenate([h_p.reshape(S * T, -1), h_d]), live)
        h_p, h_d = h[:S * T].reshape(S, T, -1), h[S * T:]
        counts.append(st)
    with scope("slices"):
        with scope("head"):
            h_p = h_p[jnp.arange(S), pf_lengths - 1]
        pf_logits = _head(params, cfg, h_p)
    with scope("decode_rows"):
        dec_logits = _head(params, cfg, h_d)
    out = (dec_logits, pf_logits, kv_cache, row_state)
    return out + (_sum_stats(cfg, counts),) if stats else out


# -- tails: what rebuilds a row at a page boundary -----------------------------
#
# W tokens of K and V rebuild a row: the last ``ceil(W / page_size)`` slab
# pages before a page boundary E are post-rotary at absolute positions,
# so copied into another row's ring at the same positions they are what
# that row would have written had it prefilled the same prefix. That is
# how the prefix cache adopts a hit for a window family
# (``docs/prefix_cache.md`` "Tails"; ``models/mellum.py`` imports these).
# (At the END of the file: the lines above keep their numbers, and with
# them the serving programs their place in XLA's cache.)

#: A tail is taken at every multiple of the stride on the way through
#: a prefill: the largest power-of-two multiple of the page size that
#: divides this many tokens and is at most two windows.
TAIL_STRIDE_DIVIDES = 2048


def row_tail(cfg) -> Dict[str, int]:
    """What rebuilds a row's state at a page boundary E, for the engine
    and the prefix cache (``models/__init__.py``): ``pages`` slab pages
    a sliding layer (the window in whole pages), ``stride`` (the prompt
    positions a tail is taken at on the way through a prefill),
    ``slack_tokens`` (how far past E a row may have written and its
    slab still hold the tail: the ring's pages beyond the tail's) and
    ``bytes`` of one tail."""
    _bound(cfg)
    ps = cfg.page_size
    W = cfg.sliding_window
    pages, stride = -(-W // ps), ps
    while TAIL_STRIDE_DIVIDES % (2 * stride) == 0 and stride <= W:
        stride *= 2
    return {"pages": pages, "stride": stride,
            "slack_tokens": (cfg.slab_pages - pages) * ps,
            "bytes": (2 * cfg.n_sliding * pages * ps * cfg.n_kv_heads
                      * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize)}


def init_row_tails(cfg, slots: int) -> RowState:
    """The pool of ``slots`` tails, the slabs' layout: ``wk`` / ``wv``
    ``(L_w, slots * pages, page_size, H_kv * head_dim)``, tail ``s`` the
    pages ``s * pages .. s * pages + pages - 1``."""
    shape = (cfg.n_sliding, slots * row_tail(cfg)["pages"], cfg.page_size,
             cfg.n_kv_heads * cfg.head_dim)
    return {"wk": jnp.zeros(shape, cfg.dtype),
            "wv": jnp.zeros(shape, cfg.dtype)}


def _tail_slab_pages(cfg, row, end_page):
    """The slab pages of batch row ``row`` that hold the position pages
    ``end_page - pages .. end_page - 1``; page 0 (nobody's) for one
    before the sequence's start."""
    n, t = cfg.slab_pages, row_tail(cfg)["pages"]
    j = end_page - t + jnp.arange(t, dtype=jnp.int32)
    return jnp.where(j >= 0, 1 + row * n + j % n, 0)


def _move_pages(dst, src, dst_pages, src_pages):
    """``dst`` (L, P, page, width) with its pages ``dst_pages[t]`` set
    to ``src``'s pages ``src_pages[t]``, a page at a time in place (a
    gather of a few pages out of a pool-sized leaf makes XLA copy the
    leaf in halves: 0.5 GB where 19 MB move)."""
    for d, s in zip(dst_pages, src_pages):
        page = jax.lax.dynamic_slice_in_dim(src, s, 1, axis=1)
        dst = jax.lax.dynamic_update_slice_in_dim(dst, page, d, axis=1)
    return dst


def export_row_tail(cfg, row_state: RowState, tails: RowState, row,
                    end_page, slot) -> RowState:
    """``tails`` with tail ``slot`` holding batch row ``row``'s K and V
    of the ``pages`` position pages before ``end_page`` in every sliding
    layer (``row``, ``end_page``, ``slot``: int32 scalars)."""
    t = row_tail(cfg)["pages"]
    src = _tail_slab_pages(cfg, row, end_page)
    return {k: _move_pages(tails[k], row_state[k],
                           [slot * t + i for i in range(t)],
                           [src[i] for i in range(t)]) for k in tails}


def import_row_tail(cfg, row_state: RowState, tails: RowState, slot, row,
                    end_page) -> RowState:
    """``row_state`` with tail ``slot`` in batch row ``row``'s ring at
    the position pages before ``end_page``: the row then continues at
    ``end_page * page_size`` as if it had prefilled the prefix."""
    t = row_tail(cfg)["pages"]
    dst = _tail_slab_pages(cfg, row, end_page)
    return {k: _move_pages(row_state[k], tails[k],
                           [dst[i] for i in range(t)],
                           [slot * t + i for i in range(t)])
            for k in row_state}
