"""The Solar-Open2 block in pure JAX (upstage/Solar-Open2-250B,
``model_type`` ``solar_open2``): Kimi Delta Attention — delta-rule
LINEAR attention with a decay a channel, arXiv:2510.26692, in its
published form: low-rank gates, an unbounded decay, beta in (0, 2) — in
three layers of four, softmax grouped-query attention WITHOUT rotary
positions and with an output gate in every fourth (``gqa_layers``), and
in EVERY layer a sigmoid-routed SwiGLU beside one shared expert.
Pre-norm, plain residual, untied head.

With N, N' a layer's two RMSNorms::

    h = x + Mixer_l(N(x))      # GQA if l in gqa_layers, else KDA
    y = h + FFN_l(N'(h))       # routed, every layer

    KDA (H heads of d = kda_head_dim keys and values; x = N(x)):
        [q ; k ; v] = silu(conv4(x W_qkv))          # depthwise, causal
        q = q / |q| / sqrt(d);  k = k / |k|         # a head, no gain
        g = -exp(A_log)_h * softplus(W_f^up W_f^down x + dt_bias)
                                # log-decay a CHANNEL, rank kda_rank
        b = 2 sigmoid(x W_b)                        # a head, in (0, 2)
        S_t = (I - b k k^T) Diag(exp(g)) S_{t-1} + b k v^T   # (d, d) float32
        o = S_t^T q
        out = W_o [sigmoid(W_g^up W_g^down x) * RMSNorm_head(o)]
    GQA (H_q query heads over H_kv key/value heads of head_dim, no
        position embedding, no q/k norm):
        a = softmax(q k^T / sqrt(head_dim)) v;  out = W_o [sigmoid(x W_g) * a]
        — ``models/afmoe.py``'s full layer without its sandwich norms.
    Routed: s = sigmoid(x W_r) in float32; the top k of s + bias (the
        bias chooses only), gates the chosen s, normalised, times
        ``routed_scaling_factor`` (``ops/moe.route``; no groups);
        + SwiGLU_shared(x).

**Two kinds of cache.** A GQA layer's K and V grow with the context and
live in the page pool (``init_kv_pages``: leaves ``k`` / ``v`` over the
GQA layers only, ``models/llama``'s layout, so the shared paged kernels
read and write them). A KDA layer carries ROW STATE, ``models/
ling_hybrid.py``'s leaves to the letter: its matrix ``S`` ``(d, H d)``
float32 (``ops/kda.py`` has the layout) and the last ``conv - 1`` inputs
of its convolution (``init_row_state``: ``kda`` ``(L_k, rows, d, H d)``
and ``conv`` ``(L_k, rows, (conv - 1) * 3 H d)``; each leaf holds one
row more than the batch, nobody's). Every forward function takes it as
``row_state`` beside the pool and returns it after the pool; position 0
starts from a zero state inside the program; a decode row that is not
``active`` keeps its state; a prompt slice's state ends at its last
VALID token. Pages alone do not rebuild a sequence, so the engine adopts
no cached prefix, pinned conversation, tiering promotion or hand-over
(``get_stats()["row_state"]``).

**A chip's share** (``held_experts``, ``models/afmoe.py``'s): the
router scores all ``n_routed_experts`` and a token chooses among them
all; the pairs whose expert lies in ``lo .. hi - 1`` are multiplied
here, the shared expert is computed here whole, and nothing stands in
for the chips that hold the others.

Not written: leading dense layers (``first_k_dense_replace`` is 0 in the
published model; ``intermediate_size`` is carried and unused), a
checkpoint loader. Int8 weights, an int8 cache and a mesh are refused by
name.

The residual stream is float32 (the router reads the float32 normed
activations, ``models/deepseek_v3.py`` has why), products take bf16, the
recurrence and what feeds its decay are float32. One period of four
layers is held where this is served, so every program unrolls its
layers. The mixed step's slice rows lie TIGHT (``ops/rows.py``): what is
a row's own runs over the tiles that hold a token, and what needs a
slice a row takes the (S, T) grid (``forward_mixed``).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from llmq_tpu.models.latent import draw_groups
from llmq_tpu.models.latent import prod as _prod
from llmq_tpu.models.latent import swiglu as _mlp
from llmq_tpu.ops.attention import (decode_geometry,
                                    dispatch_prefill_attention,
                                    kernel_routes, paged_decode_step,
                                    paged_kv_write_prefill)
from llmq_tpu.ops.kda import (L2_EPS, conv_step, kda_scan_slices,
                              kda_update_layer, kimi_decay, low_rank,
                              scan_route, update_route)
from llmq_tpu.ops.moe import (pass_extras, route, routed_ffn,
                              share_counts)
from llmq_tpu.ops.norms import rms_norm
from llmq_tpu.ops.rows import (grid_positions, grid_to_rows, live_rows,
                               row_tile, rows_to_grid, tile_rows)
from llmq_tpu.ops.ssm import (conv_slices, decode_walk, own_rows,
                              rows_read, rows_write)
from llmq_tpu.utils.profiling import scope

Params = Dict[str, Any]
KVCache = Dict[str, jnp.ndarray]
RowState = Dict[str, jnp.ndarray]

KDA, GQA = "kda", "gqa"

#: The context a decode step hands the attention kernel for a row that
#: is not active (``ops/attention.decode_geometry``: it attends to
#: nothing) — what the executor's ``attn_work`` counts an empty seat as.
IDLE_ROW_CONTEXT = 0


@dataclass(frozen=True)
class SolarOpen2Config:
    FAMILY: ClassVar[str] = "solar_open2"      # models/__init__.py
    name: str = "solar-open2-tiny"
    vocab_size: int = 512                  # the rows of the vocabulary HELD
    dim: int = 128
    n_layers: int = 8
    #: The published ``gqa_layers``: the layers with softmax attention.
    gqa_layers: Tuple[int, ...] = (0, 4)
    n_heads: int = 4                       # GQA query heads
    n_kv_heads: int = 2
    head_dim: int = 32
    kda_heads: int = 4                     # linear_attn_config.num_heads
    kda_head_dim: int = 32                 # d_k = d_v
    kda_conv: int = 4
    kda_rank: int = 16                     # the low-rank pairs' (= head_dim)
    kda_chunk: int = 8
    moe_ffn_dim: int = 64                  # one expert's SwiGLU
    n_routed_experts: int = 16
    n_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    held_experts: Optional[Tuple[int, int]] = None    # None: all E
    max_seq_len: int = 2048
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    pallas: bool = True
    pallas_batched_prefill: bool = False

    def __post_init__(self) -> None:
        lo, hi = self.held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(
                f"model {self.name!r}: held_experts {self.held_experts} of "
                f"{self.n_routed_experts} routed experts")
        if any(not 0 <= l < self.n_layers for l in self.gqa_layers):
            raise ValueError(f"model {self.name!r}: gqa_layers "
                             f"{self.gqa_layers} of {self.n_layers} layers")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(GQA if l in self.gqa_layers else KDA
                     for l in range(self.n_layers))

    @property
    def n_kda(self) -> int:
        return self.layer_types.count(KDA)

    @property
    def n_gqa(self) -> int:
        return self.layer_types.count(GQA)

    @property
    def n_routed_layers(self) -> int:
        return self.n_layers

    @property
    def kda_width(self) -> int:
        """Lanes of a head's keys (or values) over all KDA heads."""
        return self.kda_heads * self.kda_head_dim

    @property
    def held(self) -> Tuple[int, int]:
        """The router's experts whose matrices this chip holds."""
        return (tuple(self.held_experts) if self.held_experts is not None
                else (0, self.n_routed_experts))

    @property
    def n_held(self) -> int:
        return self.held[1] - self.held[0]

    def kind_index(self, l: int) -> int:
        """Layer ``l``'s index among the layers of its kind: its slice
        of the mixer's stacked leaves and of its cache leaf."""
        return self.layer_types[:l].count(self.layer_types[l])


def solar_open2_tiny(**kw) -> SolarOpen2Config:
    """CPU-test size: two periods of ``G K K K``, 16 experts with 4 a
    token beside a shared one, low-rank pairs of 16."""
    return replace(SolarOpen2Config(), **kw)


def solar_open2_250b(**kw) -> SolarOpen2Config:
    """upstage/Solar-Open2-250B at its published sizes
    (https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json):
    48 layers, softmax GQA at 0, 4, ..., 44 (64 query heads over 8 KV
    heads of 128, no rotary, gated) and KDA elsewhere (64 heads of 128,
    conv 4, low-rank gates of rank 128, beta in (0, 2)); hidden 4,096;
    every layer routed: 320 experts of 1,280 with 8 a token (sigmoid,
    renormalised, scaled 1) beside 1 shared expert; vocabulary 196,608,
    untied head, context 1,048,576. About 250 B parameters: one chip
    holds a share (benchmark/configs/solar-open2-250b-bf16-ep8.json: 4
    layers, 40 of the 320 experts, an eighth of the vocabulary)."""
    return replace(SolarOpen2Config(
        name="solar-open2-250b", vocab_size=196608, dim=4096, n_layers=48,
        gqa_layers=tuple(range(0, 48, 4)), n_heads=64, n_kv_heads=8,
        head_dim=128, kda_heads=64, kda_head_dim=128, kda_conv=4,
        kda_rank=128, kda_chunk=16, moe_ffn_dim=1280, n_routed_experts=320,
        n_experts_per_tok=8, n_shared_experts=1, routed_scaling_factor=1.0,
        norm_topk_prob=True, max_seq_len=1048576, norm_eps=1e-5), **kw)


MODEL_CONFIGS = {
    "solar-open2-tiny": solar_open2_tiny,
    "solar-open2-250b": solar_open2_250b,
}


# -- the family surface (models/__init__.py) ----------------------------------

def serving_config(cfg: SolarOpen2Config) -> SolarOpen2Config:
    return replace(cfg, pallas_batched_prefill=True)


def check_serving(cfg: SolarOpen2Config, *, quantization: str = "",
                  kv_quantization: str = "", mesh: bool = False) -> None:
    """Refuse what is not written for this family, naming the setting."""
    what = None
    if quantization:
        what = (f"model.quantization={quantization!r} (no int8 form of "
                f"the KDA mixer's projections or the experts)")
    elif kv_quantization:
        what = (f"model.kv_quantization={kv_quantization!r} (int8 pages "
                f"beside a float32 row state)")
    elif mesh:
        what = ("executor.mesh (no partition rules for the row state or "
                "the experts, no exchange between shares)")
    if what:
        raise ValueError(f"model {cfg.name!r} (family solar_open2) does "
                         f"not support {what}; unset it")


def import_hf(model_dir: str, cfg: SolarOpen2Config, **kw) -> Params:
    raise ValueError(f"model {cfg.name!r} (family solar_open2): no "
                     f"checkpoint loader is written (model.weights_path); "
                     f"the weights are random")


def step_stats_layout(cfg: SolarOpen2Config) -> Dict[str, Any]:
    """``models/afmoe.step_stats_layout``'s: the tokens each HELD
    expert received, the held experts that received any summed over the
    routed layers, the slots whose expert is held elsewhere, and the
    routed layers run."""
    n = cfg.n_held
    return {"load": (0, n), "touched": n, "away_slots": n + 1,
            "runs": n + 2}


def step_stats_size(cfg: SolarOpen2Config) -> int:
    return cfg.n_held + 3


def mixed_live_rows(tokens: int, batch: int, slices: int, width: int) -> int:
    """As ``models/granitemoehybrid.mixed_live_rows``: the slice rows'
    live tiles (the decode rows go through products of their own)."""
    return tile_rows(tokens, row_tile(width), slices * width)


def mixed_key_blocks(seq_lens, T: int, page_size: int, max_pages: int):
    """ONE GQA layer's prefill attention over a mixed step's slices, in
    key blocks of a slice's width (``T`` tokens, whole pages): ``(the
    blocks that hold a key some query of the step sees — each slice's
    context, the kernel follows it by itself —, the blocks the slices'
    block tables hold)``. Deep in a document the first is what a mixed
    step's attention costs (34 k keys are 68 blocks of 512 where a fresh
    prompt's slice is 1)."""
    block = max(1, min(-(-T // page_size), max_pages)) * page_size
    visited = sum(-(-int(n) // block) for n in seq_lens)
    return visited, len(seq_lens) * -(-max_pages * page_size // block)


# -- parameters ---------------------------------------------------------------

def param_shapes(cfg: SolarOpen2Config) -> Dict[str, Dict[str, tuple]]:
    """Leaf name -> (shape, fan_in) by group (init and the benchmark's
    builder follow it). ``kda``: the KDA mixers' matrices stacked over
    the KDA layers (``wqkv`` the three projections side by side, as the
    one convolution runs over them; ``wf_a`` / ``wf_b`` the decay's
    low-rank pair, ``wg_a`` / ``wg_b`` the output gate's); ``gqa``: the
    softmax attentions' over the GQA layers (``wg`` the gate, an element
    of the heads' result each); ``moe``, ``experts``, ``top`` as
    ``models/afmoe.param_shapes``, every layer routed."""
    Lk, Lg, L = cfg.n_kda, cfg.n_gqa, cfg.n_layers
    D, V, Fe = cfg.dim, cfg.vocab_size, cfg.moe_ffn_dim
    W, Hk, r = cfg.kda_width, cfg.kda_heads, cfg.kda_rank
    H, G, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Fs = cfg.n_shared_experts * Fe
    return {
        "kda": {"wqkv": ((Lk, D, 3 * W), D),
                "conv_w": ((Lk, 3 * W, cfg.kda_conv), cfg.kda_conv),
                "wf_a": ((Lk, D, r), D), "wf_b": ((Lk, r, W), r),
                "wb": ((Lk, D, Hk), D),
                "wg_a": ((Lk, D, r), D), "wg_b": ((Lk, r, W), r),
                "wo": ((Lk, W, D), W)},
        "gqa": {"wq": ((Lg, D, H * hd), D), "wk": ((Lg, D, G * hd), D),
                "wv": ((Lg, D, G * hd), D), "wg": ((Lg, D, H * hd), D),
                "wo": ((Lg, H * hd, D), H * hd)},
        "moe": {"router": ((L, D, cfg.n_routed_experts), D),
                "ws_gate": ((L, D, Fs), D), "ws_up": ((L, D, Fs), D),
                "ws_down": ((L, Fs, D), Fs)},
        "experts": {"we_gate_up": ((cfg.n_held, D, 2 * Fe), D),
                    "we_down": ((cfg.n_held, Fe, D), Fe)},
        "top": {"embed": ((V, D), D), "lm_head": ((D, V), D)},
    }


#: The ranges ``decay_init`` draws the decay's own parameters from:
#: ``exp(A_log)`` a head uniform in (0.5, 4), ``dt_bias`` a channel
#: uniform in (-8, 2). With the low-rank pair's product of unit variance
#: the softplus's argument lies between -11 and 5, so a channel's decay
#: ``exp(g)`` spans its range: from 0.99999 (a memory of 10^5 tokens,
#: what a 30k-token document needs a state to be held for) past a median
#: of 0.9 to exp(-20) = 2e-9 (forgets at once; the bounded form stops at
#: exp(-5)): a decay pinned at either end would hide a missing
#: ``A_log``, a dropped half of the pair or a state held too narrow.
DECAY_A_RANGE, DECAY_BIAS_RANGE = (0.5, 4.0), (-8.0, 2.0)


def decay_init(key: jax.Array, cfg: SolarOpen2Config
               ) -> Dict[str, jnp.ndarray]:
    ka, kb = jax.random.split(key)
    return {"a_log": jnp.log(jax.random.uniform(
                ka, (cfg.n_kda, cfg.kda_heads), jnp.float32, *DECAY_A_RANGE)),
            "dt_bias": jax.random.uniform(kb, (cfg.n_kda, cfg.kda_width),
                                          jnp.float32, *DECAY_BIAS_RANGE)}


def norm_leaves(cfg: SolarOpen2Config) -> Params:
    """The tree's RMSNorm weights (ones) and the router's selection
    bias (zeros, float32): what a random init does not draw (the decay's
    parameters are ``decay_init``'s)."""
    L, D = cfg.n_layers, cfg.dim
    return {"layers": {"attn_norm": jnp.ones((L, D), cfg.dtype),
                       "mlp_norm": jnp.ones((L, D), cfg.dtype)},
            "kda": {"o_norm": jnp.ones((cfg.n_kda, cfg.kda_head_dim),
                                       cfg.dtype)},
            "moe": {"router_bias": jnp.zeros((L, cfg.n_routed_experts),
                                             jnp.float32)},
            "final_norm": jnp.ones((D,), cfg.dtype)}


def assemble(cfg: SolarOpen2Config, drawn: Dict[str, Dict[str, Any]],
             decay: Dict[str, jnp.ndarray]) -> Params:
    """``param_shapes``-shaped groups of arrays (``experts``: a list of
    one array a layer under each name) + ``decay_init``'s leaves +
    ``norm_leaves`` -> the parameter tree."""
    fixed = norm_leaves(cfg)
    return {"embed": drawn["top"]["embed"],
            "lm_head": drawn["top"]["lm_head"],
            "final_norm": fixed["final_norm"],
            "layers": fixed["layers"],
            "kda": {**drawn["kda"], **fixed["kda"], **decay},
            "gqa": dict(drawn["gqa"]),
            "moe": {**drawn["moe"], **fixed["moe"],
                    **{k: tuple(v) for k, v in drawn["experts"].items()}}}


def init_params(key: jax.Array, cfg: SolarOpen2Config) -> Params:
    """Random-init parameter tree, N(0, 1 / fan_in) as the other
    families', the decay's parameters by ``decay_init``."""
    return assemble(cfg, draw_groups(key, param_shapes(cfg), cfg.dtype,
                                     cfg.n_layers),
                    decay_init(jax.random.fold_in(key, 1), cfg))


def init_params_quantized(key: jax.Array, cfg: SolarOpen2Config) -> Params:
    check_serving(cfg, quantization="int8")


def param_count(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


def param_count_analytic(cfg: SolarOpen2Config) -> int:
    """Parameters HELD, from the configuration alone."""
    n = sum(_prod(shape) * (cfg.n_layers if g == "experts" else 1)
            for g, leaves in param_shapes(cfg).items()
            for shape, _f in leaves.values())
    fixed = (cfg.n_layers * 2 * cfg.dim + cfg.dim
             + cfg.n_kda * (cfg.kda_head_dim + cfg.kda_heads + cfg.kda_width)
             + cfg.n_layers * cfg.n_routed_experts)
    return n + fixed


def active_param_count(cfg: SolarOpen2Config) -> int:
    """``models/afmoe.active_param_count``: the held count less the
    held experts a token is not routed to, in expectation."""
    idle = cfg.n_held * (1 - cfg.n_experts_per_tok / cfg.n_routed_experts)
    return int(param_count_analytic(cfg)
               - cfg.n_layers * idle * 3 * cfg.dim * cfg.moe_ffn_dim)


def weight_bytes(cfg: SolarOpen2Config) -> int:
    return param_count_analytic(cfg) * jnp.dtype(cfg.dtype).itemsize


def kv_bytes_per_token(cfg: SolarOpen2Config,
                       cache_dtype: Optional[Any] = None) -> int:
    """K and V of the GQA layers: all a token adds to the cache (a KDA
    layer's state is a row's: ``row_state_bytes_per_row``)."""
    itemsize = jnp.dtype(cache_dtype or cfg.dtype).itemsize
    return 2 * cfg.n_gqa * cfg.n_kv_heads * cfg.head_dim * itemsize


def init_kv_pages(cfg: SolarOpen2Config, num_pages: int, page_size: int,
                  dtype: Optional[Any] = None) -> KVCache:
    """The page pool of the GQA layers alone, ``models/llama``'s
    layout: ``(L_g, P, page_size, H_kv * head_dim)`` for K and for V,
    page 0 reserved."""
    dt = dtype or cfg.dtype
    if jnp.dtype(dt) == jnp.int8:
        check_serving(cfg, kv_quantization="int8")
    shape = (cfg.n_gqa, num_pages, page_size, cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def init_row_state(cfg: SolarOpen2Config, batch: int) -> RowState:
    """The row-state leaves for ``batch`` rows, zero
    (``models/ling_hybrid.init_row_state``'s): ``kda`` each KDA layer's
    state ``(L_k, batch + 1, d, H d)`` float32 and ``conv`` its
    convolution's last ``conv - 1`` inputs laid end to end, ``(L_k,
    batch + 1, (conv - 1) * 3 H d)`` in the activations' type. The last
    row is NOBODY'S."""
    return {
        "kda": jnp.zeros((cfg.n_kda, batch + 1, cfg.kda_head_dim,
                          cfg.kda_width), jnp.float32),
        "conv": jnp.zeros((cfg.n_kda, batch + 1,
                           (cfg.kda_conv - 1) * 3 * cfg.kda_width),
                          cfg.dtype),
    }


def row_state_bytes_per_row(cfg: SolarOpen2Config) -> int:
    """What one batch row holds in ``init_row_state``'s leaves, whatever
    its sequence's length."""
    return cfg.n_kda * (
        cfg.kda_head_dim * cfg.kda_width * 4
        + (cfg.kda_conv - 1) * 3 * cfg.kda_width
        * jnp.dtype(cfg.dtype).itemsize)


def routes(cfg: SolarOpen2Config, cache: KVCache, *, batch: int,
           page_size: int, max_pages: int, decode: bool = False,
           prefill_rows: int = 0) -> Dict[str, str]:
    """The GQA layers' routes (``ops/attention.kernel_routes``) and the
    KDA layers': ``ssm_update`` of a program that decodes — the kernel's
    line names its walk's plan, the head blocks a live row takes —,
    ``ssm_scan`` of one that runs prompt tokens (the kernel takes slices
    of whole 64-token steps; this function is not told a program's)."""
    out = kernel_routes(
        batch=batch, page_size=page_size, max_pages=max_pages,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        kv_itemsize=cache["k"].dtype.itemsize, quant_kv=False,
        enabled=cfg.pallas, multi_ok=cfg.pallas_batched_prefill,
        decode=decode, prefill_rows=prefill_rows)
    d, H = cfg.kda_head_dim, cfg.kda_heads

    def named(route, kernel):
        use, interp = route
        return (f"pallas{'-interpret' if interp else ''}:{kernel}"
                if use else "xla")

    if prefill_rows:
        step, route = _scan_route(cfg)
        out["ssm_scan"] = named(route, f"kda_scan_pallas(slice%{step}==0)")
    if decode:
        from llmq_tpu.ops.pallas.kda_update import head_blocks
        out["ssm_update"] = named(
            update_route(d, H, d, enabled=cfg.pallas),
            f"kda_update_pallas(head_blocks={head_blocks(H)})")
    return out


def _scan_route(cfg: SolarOpen2Config, T: Optional[int] = None):
    """``(the scan kernel's step in tokens, ops/kda.scan_route of slices
    of T tokens)``; ``T`` None: of slices of whole steps."""
    from llmq_tpu.ops.pallas.kda_scan import CHUNK
    d = cfg.kda_head_dim
    return CHUNK, scan_route(d, cfg.kda_heads, d, T or CHUNK, cfg.kda_chunk,
                             enabled=cfg.pallas)


def scan_step_tokens(cfg: SolarOpen2Config, T: int) -> Optional[int]:
    """Tokens a grid step of the KDA layers' scan kernel takes of a
    slice of ``T`` tokens (``ops/pallas/kda_scan.CHUNK``) — None where
    such slices go to XLA's scan (:func:`llmq_tpu.ops.kda.scan_route`).
    What the executor's ``scan_work`` counts a program's chunks by."""
    step, (use, _) = _scan_route(cfg, T)
    return step if use else None


# -- the layer ----------------------------------------------------------------

def _normed(h, w, cfg: SolarOpen2Config) -> jnp.ndarray:
    return rms_norm(h, w, cfg.norm_eps).astype(cfg.dtype)


def _embed(params: Params, tokens) -> jnp.ndarray:
    with scope("embed"):
        return params["embed"][tokens].astype(jnp.float32)


def _head(params: Params, cfg: SolarOpen2Config, h) -> jnp.ndarray:
    with scope("head"):
        return jnp.dot(_normed(h, params["final_norm"], cfg),
                       params["lm_head"]).astype(jnp.float32)


def _head_sums(x, cfg: SolarOpen2Config) -> jnp.ndarray:
    """``x`` (M, H d) float32, heads side by side on the lanes -> each
    head's sum over its d lanes (M, H): a product with the heads'
    indicator at the highest precision. NOT a reshape to (M, H, d) and a
    sum: on the TPU that reshape takes the rows off the sublanes, and
    XLA copied every (2,560, 64, 128) float32 operand of the scan whole,
    84 MB each way, nine times a mixed step (1.9 ms, all of it outside
    every scope: PERF.md section 6, PR 52)."""
    H, d = cfg.kda_heads, cfg.kda_head_dim
    ind = (jnp.arange(H * d)[:, None] // d
           == jnp.arange(H)[None, :]).astype(jnp.float32)
    return jnp.dot(x, ind, precision=lax.Precision.HIGHEST)


def _over_lanes(s, cfg: SolarOpen2Config) -> jnp.ndarray:
    """``s`` (M, H), a value a head -> (M, H d), each head's value on
    its d lanes (:func:`_head_sums`' way back)."""
    H, d = cfg.kda_heads, cfg.kda_head_dim
    ind = (jnp.arange(H)[:, None]
           == jnp.arange(H * d)[None, :] // d).astype(jnp.float32)
    return jnp.dot(s, ind, precision=lax.Precision.HIGHEST)


def _unit(x, cfg: SolarOpen2Config) -> jnp.ndarray:
    """``x`` (M, H d) float32 at unit length a head (``ops/kda.l2_norm``
    on the flat rows): ``x / sqrt(sum_head x^2 + L2_EPS)``, no gain."""
    return x * _over_lanes(lax.rsqrt(_head_sums(x * x, cfg) + L2_EPS), cfg)


def _kda_proj(x, kp: Params, i: int, cfg: SolarOpen2Config):
    """KDA layer ``i``'s products over the normed rows ``x`` (M, D) that
    the convolution and the scan read: ``(qkv (M, 3 H d) before the
    convolution, g (M, H d) the log-decay, b (M, H) in (0, 2))``, heads
    side by side on the lanes."""
    with scope("qkv"):
        qkv = jnp.dot(x, kp["wqkv"][i])
    with scope("kda_gates"):
        # (the decay's and beta's products come out in float32, the
        # pair's second product exactly: ``ops/kda.low_rank``)
        g = kimi_decay(low_rank(x, kp["wf_a"][i], kp["wf_b"][i], exact=True),
                       kp["a_log"][i], kp["dt_bias"][i])
        b = 2.0 * jax.nn.sigmoid(jnp.dot(
            x, kp["wb"][i], preferred_element_type=jnp.float32))
        return qkv, g, b


def _kda_gate(x, kp: Params, i: int, cfg: SolarOpen2Config):
    """The output gate's logits ``z`` (M, H d) float32 of the normed
    rows ``x``: :func:`_kda_out` alone reads them."""
    with scope("kda_gates"):
        return low_rank(x, kp["wg_a"][i], kp["wg_b"][i])


def _kda_in(x, kp: Params, i: int, cfg: SolarOpen2Config):
    """``(qkv, g, b, z)``: :func:`_kda_proj` and :func:`_kda_gate` of
    the same rows."""
    return _kda_proj(x, kp, i, cfg) + (_kda_gate(x, kp, i, cfg),)


def _kda_unit(y, cfg: SolarOpen2Config):
    """The convolved channels ``y`` (..., 3 H d) float32 as ``(q, k, v)``
    (..., H d) each: q and k at unit length a head, q times 1/sqrt(d).
    Computed on the FLAT rows, heads side by side on the lanes
    (:func:`_head_sums` has why)."""
    W, d = cfg.kda_width, cfg.kda_head_dim
    lead, y = y.shape[:-1], y.reshape(-1, 3 * W)
    return tuple(x.reshape(lead + (W,)) for x in (
        _unit(y[:, :W], cfg) * d ** -0.5, _unit(y[:, W:2 * W], cfg),
        y[:, 2 * W:]))


def _kda_heads(y, g, cfg: SolarOpen2Config):
    """:func:`_kda_unit` of ``y`` (..., 3 H d) and the log-decay ``g``
    (M, H d) as ``(q, k, v, g (..., H, d))``: the heads' axis is split
    last, where the update takes it."""
    return tuple(x.reshape(y.shape[:-1] + (cfg.kda_heads, cfg.kda_head_dim))
                 for x in _kda_unit(y, cfg) + (g,))


def _kda_out(h, o, z, kp: Params, i: int, cfg: SolarOpen2Config):
    """The norm over each head's values, the gate and the output
    projection: ``o`` (M, H d) float32 (flat: :func:`_head_sums`), ``z``
    (M, H d) float32."""
    with scope("attn_out"):
        H, d = cfg.kda_heads, cfg.kda_head_dim
        ms = _head_sums(o * o, cfg) / d
        gain = jnp.tile(kp["o_norm"][i].astype(jnp.float32), H)
        o = o * _over_lanes(lax.rsqrt(ms + cfg.norm_eps), cfg) * gain
        y = (o * jax.nn.sigmoid(z)).astype(cfg.dtype)
        return h + jnp.dot(y, kp["wo"][i]).astype(jnp.float32)


def _conv_bias(cfg: SolarOpen2Config) -> jnp.ndarray:
    """``ops/ssm.conv_slices``'s bias: this convolution has none."""
    return jnp.zeros((3 * cfg.kda_width,), jnp.float32)


def _kda_decode(h, x, kp: Params, i: int, rs: RowState, active, walk,
                cfg: SolarOpen2Config):
    """One token a row through KDA layer ``i``; rows that are not
    ``active`` keep their window and their state."""
    qkv, g, b, z = _kda_in(x, kp, i, cfg)
    kda, conv = rs["kda"], rs["conv"]
    with scope("ssm_conv"):
        y, conv = conv_step(conv, i, qkv, kp["conv_w"][i], active)
    with scope("ssm_update"):
        q, k, v, g = _kda_heads(y, g, cfg)
        o, kda = kda_update_layer(kda, i, q, k, v, g, b, active, walk=walk,
                                  enabled=cfg.pallas)
    return (_kda_out(h, o.reshape(z.shape), z, kp, i, cfg),
            {"kda": kda, "conv": conv})


def _kda_scan(qkv, g, b, kp: Params, i: int, rs: RowState, rows, first,
              lengths, cfg: SolarOpen2Config, tight=None):
    """S slices of T tokens through KDA layer ``i``'s convolution and
    scan: ``qkv`` (S, T, 3 H d), ``g`` (S, T, H d), ``b`` (S, T, H) on
    the grid (:func:`_kda_proj`'s); ``rows`` (S,) the batch row each
    slice's sequence owns (one past the batch's last: nobody's),
    ``first`` (S,) whether the slice starts its sequence (a zero state),
    ``lengths`` (S,). Returns ``(o (S, T, H d) float32, row state)``.

    ``tight=(starts, used)``: the three lie TIGHT, (S T, ...), slice
    ``s`` from row ``starts[s]`` (``ops/rows.py``), and the first
    ``used`` slices are in use (a traced scalar). The convolution and
    the unit norms then run over those, a slice at a time
    (``ops/rows.live_rows`` with a slice as its tile): each trip cuts
    its slice's T rows out of the tight buffers where they lie — that
    IS the move onto the grid, and an unused slice is neither moved nor
    computed (its q, k, v, g and b are zero) —, and one slice's
    (T, 3 H d) float32 stands at a time where all S stood (805 MB at
    16 x 512). The scan kernel skips what lies past a slice's length by
    itself."""
    S, H = lengths.shape[0], cfg.kda_heads
    T = qkv.shape[0] // S if tight else qkv.shape[1]
    kda, conv = rs["kda"], rs["conv"]
    keep = ~first[:, None, None]

    def conv_unit(win, x, n):
        with scope("ssm_conv"):
            y, win = conv_slices(win, x, n, kp["conv_w"][i], _conv_bias(cfg))
        with scope("ssm_scan"):
            return _kda_unit(y, cfg) + (win,)

    with scope("ssm_conv"):
        win = jnp.where(keep, rows_read(conv, i, rows).reshape(
            S, cfg.kda_conv - 1, -1), 0)
    if tight:
        starts, used = tight

        def some_slices(win, n, at):    # (one a trip; all S where S <= 2)
            qkv_s, g_s, b_s = (jnp.stack([
                lax.dynamic_slice_in_dim(x, at[j], T)
                for j in range(at.shape[0])]) for x in (qkv, g, b))
            return conv_unit(win, qkv_s, n) + (g_s, b_s)

        q, k, v, win, g, b = live_rows(some_slices, used, 1, win, lengths,
                                       starts[:S])
    else:
        q, k, v, win = conv_unit(win, qkv, lengths)
    with scope("ssm_conv"):
        conv = rows_write(conv, i, rows, win.reshape(S, -1))
    with scope("ssm_scan"):
        before = rows_read(kda, i, rows, enabled=cfg.pallas)
        o, st = kda_scan_slices(
            jnp.where(keep, before, 0),
            *(x.reshape(S, T, H, -1) for x in (q, k, v, g)), b, lengths,
            cfg.kda_chunk, enabled=cfg.pallas)
        kda = rows_write(kda, i, rows, st, enabled=cfg.pallas)
    return o.reshape(S, T, -1), {"kda": kda, "conv": conv}


def _kda_slices(h, x, kp: Params, i: int, rs: RowState, rows, first,
                lengths, cfg: SolarOpen2Config):
    """S slices of T tokens through KDA layer ``i``, all of them on the
    grid: ``h``, ``x`` (S, T, D); the rest as :func:`_kda_scan`."""
    S, T = x.shape[:2]
    qkv, g, b, z = _kda_in(x.reshape(S * T, -1), kp, i, cfg)
    o, rs = _kda_scan(qkv.reshape(S, T, -1), g.reshape(S, T, -1),
                      b.reshape(S, T, -1), kp, i, rs, rows, first, lengths,
                      cfg)
    h = _kda_out(h.reshape(S * T, -1), o.reshape(z.shape), z, kp, i, cfg)
    return h.reshape(S, T, -1), rs


def _qkv(x, gp: Params, i: int, cfg: SolarOpen2Config):
    """GQA layer ``i``'s q (..., H * hd), k and v (..., G * hd) of the
    normalised rows ``x`` (..., D), FLAT. No rotary embedding and no
    q/k norm: the keys go to the pages as the product left them."""
    with scope("qkv"):
        return tuple(jnp.dot(x, gp[w][i]) for w in ("wq", "wk", "wv"))


def _gate(x, gp: Params, i: int, cfg: SolarOpen2Config):
    """GQA layer ``i``'s gate (..., H * hd) of the same rows:
    :func:`_attn_close` alone reads it."""
    with scope("attn_gate"):
        return jnp.dot(x, gp["wg"][i])


def _qkvg(x, gp: Params, i: int, cfg: SolarOpen2Config):
    """:func:`_qkv` split into heads — q (..., H, hd), k, v (..., G,
    hd) — and :func:`_gate`."""
    q, k, v = (y.reshape(x.shape[:-1] + (-1, cfg.head_dim))
               for y in _qkv(x, gp, i, cfg))
    return q, k, v, _gate(x, gp, i, cfg)


def _attn_close(h, attn, gate, gp: Params, i: int, cfg: SolarOpen2Config):
    """The gate, an element of the heads' result each, and the output
    projection."""
    with scope("attn_gate"):
        a = (attn.reshape(gate.shape).astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(cfg.dtype)
    with scope("attn_out"):
        return h + jnp.dot(a, gp["wo"][i]).astype(jnp.float32)


def _gqa_attend(q, k, v, i: int, kv_cache: KVCache, tables, positions,
                lengths, seq_lens, cfg: SolarOpen2Config):
    """S slices through GQA layer ``i``'s layer of the pool: k, v (S, T,
    G, hd) written, then q (S, T, H, hd) attended."""
    layer = jnp.asarray(i, jnp.int32)
    with scope("kv_write"):
        k_pool, v_pool = paged_kv_write_prefill(
            kv_cache["k"], kv_cache["v"], k, v, tables, positions, lengths,
            layer, enabled=cfg.pallas, multi_ok=cfg.pallas_batched_prefill)
    with scope("attn"), scope("attn_full"):
        attn = dispatch_prefill_attention(
            q, k_pool, v_pool, tables, positions, seq_lens, layer,
            enabled=cfg.pallas, multi_ok=cfg.pallas_batched_prefill)
    return attn, {"k": k_pool, "v": v_pool}


def _gqa_decode(h, x, gp: Params, i: int, kv_cache: KVCache, geom,
                cfg: SolarOpen2Config):
    tables, page_of, slot_of, seq_lens, order = geom
    q, k, v, gate = _qkvg(x, gp, i, cfg)
    with scope("attn"), scope("attn_full"):
        attn, k_pool, v_pool = paged_decode_step(
            q, k, v, kv_cache["k"], kv_cache["v"], tables, seq_lens,
            page_of, slot_of, jnp.asarray(i, jnp.int32), enabled=cfg.pallas,
            order=order)
    return (_attn_close(h, attn, gate, gp, i, cfg),
            {"k": k_pool, "v": v_pool})


def _ffn_in(params: Params, cfg: SolarOpen2Config, l: int, h):
    """What layer ``l``'s feed-forward reads of the stream's rows h
    (M, D), each row its own: ``(x the normed rows in the products'
    type, experts, gates (M, k) — ``ops/moe.route``'s of the float32
    normed rows)``."""
    with scope("mlp"):
        xf = rms_norm(h, params["layers"]["mlp_norm"][l], cfg.norm_eps)
        x = xf.astype(cfg.dtype)
    m = params["moe"]
    experts, gates = route(
        xf, m["router"][l], m["router_bias"][l],
        top_k=cfg.n_experts_per_tok, scale=cfg.routed_scaling_factor,
        norm_topk=cfg.norm_topk_prob, scoring="sigmoid")
    return x, experts, gates


def _shared(params: Params, cfg: SolarOpen2Config, l: int, x):
    """Layer ``l``'s shared expert over the normed rows ``x``."""
    m = params["moe"]
    with scope("mlp"):
        return _mlp(x, m["ws_gate"][l], m["ws_up"][l], m["ws_down"][l])


def _routed(params: Params, cfg: SolarOpen2Config, l: int, x, experts, gates,
            live):
    """Layer ``l``'s held experts over :func:`_ffn_in`'s rows: ``(y,
    ops/moe.routed_ffn's counts as step_stats_layout has them, without
    runs)``."""
    m = params["moe"]
    y, st = routed_ffn(x, experts, gates, m["we_gate_up"][l],
                       m["we_down"][l], live, held=cfg.held,
                       n_routed=cfg.n_routed_experts)
    return y, share_counts(st, cfg.n_held)


def _ffn(params: Params, cfg: SolarOpen2Config, l: int, h, live):
    """Layer ``l``'s routed feed-forward over the stream's rows h
    (N, D). Returns (h', stats, experts): :func:`_routed`'s counts and
    the experts ``ops/moe.route`` chose for each row (N, k)."""
    x, experts, gates = _ffn_in(params, cfg, l, h)
    y, st = _routed(params, cfg, l, x, experts, gates, live)
    shared = _shared(params, cfg, l, x)       # beside the routed ones
    with scope("mlp"):
        return h + y + shared, st, experts


# -- forward ------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "last_only", "stats", "chosen"))
def forward_prefill(params: Params, cfg: SolarOpen2Config,
                    tokens: jnp.ndarray, positions: jnp.ndarray,
                    lengths: jnp.ndarray, kv_cache: KVCache,
                    block_tables: jnp.ndarray, last_only: bool = False,
                    stats: bool = False,
                    row_state: Optional[RowState] = None,
                    rows: Optional[jnp.ndarray] = None,
                    chosen: bool = False):
    """``models/llama.forward_prefill``'s signature and conventions,
    and beside them ``row_state`` and ``rows`` (B,): the batch row each
    sequence owns. A chunk that starts at position 0 starts from a zero
    state; any other continues what its row holds. Returns ``(logits,
    cache, row_state)``, and after them the routed layers' counts with
    ``stats`` and their choices (rows in (B, T) order) with ``chosen``
    (``ops/moe.pass_extras``)."""
    B, T = tokens.shape
    row_state, rows = own_rows(partial(init_row_state, cfg), B, row_state,
                               rows)
    h = _embed(params, tokens)
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    seq_lens = jnp.max(jnp.where(valid, positions, -1), axis=1) + 1
    first = positions[:, 0] == 0
    lp, counts = params["layers"], []
    for l, kind in enumerate(cfg.layer_types):
        i = cfg.kind_index(l)
        with scope("qkv"):
            x = _normed(h, lp["attn_norm"][l], cfg)
        if kind == KDA:
            h, row_state = _kda_slices(h, x, params["kda"], i, row_state,
                                       rows, first, lengths, cfg)
        else:
            q, k, v, gate = _qkvg(x, params["gqa"], i, cfg)
            attn, kv_cache = _gqa_attend(q, k, v, i, kv_cache, block_tables,
                                         positions, lengths, seq_lens, cfg)
            h = _attn_close(h, attn, gate, params["gqa"], i, cfg)
        h, *took = _ffn(params, cfg, l, h.reshape(B * T, -1),
                        valid.reshape(-1))
        h = h.reshape(B, T, -1)
        counts.append(took)
    if last_only:
        with scope("head"):
            h = h[jnp.arange(B), lengths - 1]
    out = (_head(params, cfg, h), kv_cache, row_state)
    return out + pass_extras(counts, cfg.n_held + 2, stats, chosen)


@partial(jax.jit, static_argnames=("cfg", "stats", "chosen"))
def forward_decode(params: Params, cfg: SolarOpen2Config,
                   tokens: jnp.ndarray, positions: jnp.ndarray,
                   kv_cache: KVCache, block_tables: jnp.ndarray,
                   active: Optional[jnp.ndarray] = None,
                   stats: bool = False,
                   row_state: Optional[RowState] = None,
                   chosen: bool = False):
    """One decode step for every active row
    (``models/llama.forward_decode``'s contract); batch row ``b``
    updates row ``b`` of ``row_state``. A row that is not active leaves
    its state as it found it, writes to page 0, attends to nothing and
    is routed to no expert; its logits mean nothing. Returns ``(logits
    (B, V), cache, row_state)``, and ``pass_extras`` after them."""
    B = tokens.shape[0]
    row_state, _ = own_rows(partial(init_row_state, cfg), B, row_state,
                            None)
    live = jnp.ones((B,), bool) if active is None else active
    h = _embed(params, tokens)
    geom = decode_geometry(positions, block_tables, active,
                           (kv_cache["k"], kv_cache["v"]), cfg.head_dim,
                           enabled=cfg.pallas)
    walk = decode_walk(live)
    lp, counts = params["layers"], []
    for l, kind in enumerate(cfg.layer_types):
        i = cfg.kind_index(l)
        with scope("qkv"):
            x = _normed(h, lp["attn_norm"][l], cfg)
        if kind == KDA:
            h, row_state = _kda_decode(h, x, params["kda"], i, row_state,
                                       live, walk, cfg)
        else:
            h, kv_cache = _gqa_decode(h, x, params["gqa"], i, kv_cache,
                                      geom, cfg)
        h, *took = _ffn(params, cfg, l, h, live)
        counts.append(took)
    out = (_head(params, cfg, h), kv_cache, row_state)
    return out + pass_extras(counts, cfg.n_held + 2, stats, chosen)


@partial(jax.jit, static_argnames=("cfg", "stats", "chosen"))
def forward_mixed(params: Params, cfg: SolarOpen2Config,
                  dec_tokens: jnp.ndarray, dec_positions: jnp.ndarray,
                  kv_cache: KVCache, dec_block_tables: jnp.ndarray,
                  pf_tokens: jnp.ndarray, pf_positions: jnp.ndarray,
                  pf_lengths: jnp.ndarray, pf_starts: jnp.ndarray,
                  pf_block_tables: jnp.ndarray,
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
    a layer touch different rows of the state and different pages.

    What is a row's own runs over the tight rows a live tile at a time
    (``ops/rows.live_rows``, ``mixed_live_rows``), in two tiles a
    layer: the norm and the products the mixer reads (``_kda_proj``,
    ``_qkv``) in front of it; behind it the output gate — made beside
    its only reader, of the tile's norm made again, so that nothing of
    a gate's size outlives the convolution and the scan
    (``models/granitemoehybrid.forward_mixed``) —, the mixer's close
    and what the feed-forward reads of a row (its norm, the router,
    the shared expert). The convolution, the scan, the K/V write and
    the attention take the (S, T) grid, a slice a row (``_kda_scan``
    over the slices in use). The routed experts take slices and decode
    rows together, so a layer's experts are streamed once for both; the
    decode rows' shared expert is a product of their own. A row past
    ``pf_starts[S]`` holds no token and is routed nowhere (an unused
    slice's position, the first dead row, is not counted).
    Returns ``(dec_logits (B, V), pf_logits (S, V), cache, row_state)``
    and ``pass_extras`` after them (``chosen``: the slices' S * T GRID
    rows, then the B decode rows)."""
    B = dec_tokens.shape[0]
    S = pf_lengths.shape[0]
    N = pf_tokens.shape[0]
    T = N // S
    row_state, _ = own_rows(partial(init_row_state, cfg), B, row_state,
                            None)
    if pf_rows is None:
        pf_rows = jnp.full((S,), B, jnp.int32)
    live_d = jnp.ones((B,), bool) if dec_active is None else dec_active
    n_live = pf_starts[S]
    used = jnp.sum(pf_starts[:S] < n_live)      # they come first
    with scope("decode_rows"):
        h_d = _embed(params, dec_tokens)
        geom = decode_geometry(
            dec_positions, dec_block_tables, dec_active,
            (kv_cache["k"], kv_cache["v"]), cfg.head_dim, enabled=cfg.pallas)
        walk = decode_walk(live_d)
    with scope("slices"):
        h_p = _embed(params, pf_tokens)
        grid_pos, pf_seq_lens = grid_positions(pf_positions, pf_lengths,
                                               pf_starts, T)
        first = grid_pos[:, 0] == 0
    live = jnp.concatenate([jnp.arange(N) < n_live, live_d])
    lp, counts = params["layers"], []

    def tiles(fn, *rows):
        return live_rows(fn, n_live, row_tile(T), *rows)

    def to_grid(x):
        return rows_to_grid(x, pf_starts, T)

    def to_rows(x):
        return grid_to_rows(x, pf_starts,
                            jnp.zeros((N,) + x.shape[2:], x.dtype))

    for l, kind in enumerate(cfg.layer_types):
        i = cfg.kind_index(l)
        mp = params[kind]          # (a kind is named as its leaves' group)

        def normed(h):
            with scope("qkv"):
                return _normed(h, lp["attn_norm"][l], cfg)

        with scope("slices"):
            if kind == KDA:
                qkv, g, b = tiles(
                    lambda h: _kda_proj(normed(h), mp, i, cfg), h_p)
                mixed, row_state = _kda_scan(
                    qkv, g, b, mp, i, row_state, pf_rows, first, pf_lengths,
                    cfg, tight=(pf_starts, used))

                def close(h, o):
                    z = _kda_gate(normed(h), mp, i, cfg)
                    return _kda_out(h, o, z, mp, i, cfg)
            else:
                q, k, v = (to_grid(x).reshape(S, T, -1, cfg.head_dim)
                           for x in tiles(
                               lambda h: _qkv(normed(h), mp, i, cfg), h_p))
                mixed, kv_cache = _gqa_attend(
                    q, k, v, i, kv_cache, pf_block_tables, grid_pos,
                    pf_lengths, pf_seq_lens, cfg)
                # The decode rows' write takes the pools in place: only
                # once the slices' attention has read them, or XLA copies
                # a whole pool to keep both (models/granitemoehybrid).
                mixed, kv_cache = jax.lax.optimization_barrier(
                    (mixed, kv_cache))
                mixed = mixed.reshape(S, T, -1)

                def close(h, attn):
                    gate = _gate(normed(h), mp, i, cfg)
                    return _attn_close(h, attn, gate, mp, i, cfg)

            def behind(h, mixed):
                h = close(h, mixed)
                x, experts, gates = _ffn_in(params, cfg, l, h)
                return h, x, experts, gates, _shared(params, cfg, l, x)

            h_p, x_p, took_p, gates_p, shared_p = tiles(behind, h_p,
                                                        to_rows(mixed))
        with scope("decode_rows"):
            x = normed(h_d)
            if kind == KDA:
                h_d, row_state = _kda_decode(h_d, x, mp, i, row_state,
                                             live_d, walk, cfg)
            else:
                h_d, kv_cache = _gqa_decode(h_d, x, mp, i, kv_cache, geom,
                                            cfg)
            x_d, took_d, gates_d = _ffn_in(params, cfg, l, h_d)
            shared_d = _shared(params, cfg, l, x_d)
        # The held experts take both kinds of row side by side (their
        # matrices are streamed once): no row kind on their scopes.
        y, st = _routed(params, cfg, l, jnp.concatenate([x_p, x_d]),
                        jnp.concatenate([took_p, took_d]),
                        jnp.concatenate([gates_p, gates_d]), live)
        with scope("mlp"):
            h_p, h_d = h_p + y[:N] + shared_p, h_d + y[N:] + shared_d
        if chosen:                 # the choices leave in grid order
            with scope("slices"):
                took_p = to_grid(took_p).reshape(N, -1)
            took_p = jnp.concatenate([took_p, took_d])
        counts.append((st, took_p))
    with scope("slices"):
        with scope("head"):
            h_p = h_p[pf_starts[:S] + pf_lengths - 1]
        pf_logits = _head(params, cfg, h_p)
    with scope("decode_rows"):
        dec_logits = _head(params, cfg, h_d)
    out = (dec_logits, pf_logits, kv_cache, row_state)
    return out + pass_extras(counts, cfg.n_held + 2, stats, chosen)
