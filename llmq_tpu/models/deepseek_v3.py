"""The DeepSeek-V3 block in pure JAX (``model_type: deepseek_v3``):
multi-head LATENT attention over a latent page pool, and a routed
feed-forward with shared experts. Kanana-2-30B-A3B is this block at
hidden 2,048 with 128 experts of 768, 6 a token, and 2 shared.

The same conventions as ``models/llama.py`` (stacked layer parameters,
unrolled layer loops around pool-aliasing kernels, a paged pool indexed
by block tables, page 0 reserved, bf16 weights and matmul operands with
float32 norms and softmax) and the same serving surface
(``forward_prefill`` / ``forward_decode`` / ``forward_mixed``,
``init_kv_pages``), so the executor's programs serve either family
(``models/__init__.py`` has the registry and the surface).

Layer equations (x: the layer's input after its RMSNorm; h: a head):

- q_h = x W_q split [q_h^nope (128) ; q_h^rope (64)], RoPE on the rope
  part. [c' ; k'] = x W_kva; c = RMSNorm(c') and k^rope = RoPE(k'), ONE
  for all heads. **The cache holds (c, k^rope)**: ``kv_lora_rank +
  qk_rope_head_dim`` = 576 values a token a layer, 1,152 B in bf16.
  [k_h^nope ; v_h] = c W_kvb,h; score_h(t, s) = (q_h^nope . k_h^nope(s)
  + q_h^rope . k^rope(s)) / sqrt(192); o_h = sum_s p_h(t, s) v_h(s).
- **Decode is ABSORBED**: q~_h = q_h^nope (W_kvb,h^K)^T (512 wide),
  score = (q~_h . c(s) + q_h^rope . k^rope(s)) / sqrt(192), o~_h =
  sum_s p c(s), o_h = o~_h W_kvb,h^V — all heads read the same 576
  values of a token and K/V are never expanded
  (``ops/pallas/latent_decode.py``). Prefill EXPANDS K/V from the
  cached latents and runs under XLA (a kernel for it is later work).
- **The pool's one leaf** ``"ckv"`` is ``(L, P, page_size, W)`` with W
  = 576 rounded up to 128 lanes = 640: ``[c | k^rope | zeros]``. One
  leaf, because a score is then one contraction of a row with
  ``[q~ | q^rope | 0]`` and a page one DMA; padded, because a 576-lane
  row is tiled to 640 lanes in HBM and VMEM whatever its shape says,
  so the 64 lanes cost what they cost and a 640-lane leaf says so. The
  64 lanes of zeros are not counted in the published 1,152 B a token
  a layer (``kv_bytes_per_token``); in HBM a row is ``latent_width``
  values.
- RoPE in the split-half layout (``ops/rope.py``), as everywhere in
  this repo: the published checkpoints interleave the pairs
  (``rope_interleave``), and the loader (``models/checkpoint.py``)
  permutes W_q's and W_kva's rope columns once, which leaves every
  score unchanged.
- Routed layer (``ops/moe.py``): s = sigmoid(x W_r) in float32, the 6
  experts the top 6 of s + b (b chooses only), g = 2.448 s_i / sum of
  the chosen s, y = sum g_i SwiGLU_i(x) + SwiGLU_shared(x). The first
  ``first_k_dense`` layers have a dense SwiGLU instead.

The residual stream is float32 here, and the router reads the float32
normalised activations: a routed layer's top-6 is a discontinuity, and
each rounding of the stream to bf16 (0.2-0.4 % of it) is as wide as a
tenth of the mean gap between a token's 6th and 7th score. The stream
is a few kilobytes a token; the matmuls' operands stay bf16.

The attention itself — the cache's row, the absorbed decode, the
expanded prefill, the kernels' routes, and ``q_lora_rank`` (a low-rank
query) — is ``models/latent.py``'s, which ``models/longcat_flash.py``
shares. Int8 weights, an int8 cache and a mesh are not written for this
family: each is refused with an error that names the setting
(``check_serving``).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, ClassVar, Dict, Optional

import jax
import jax.numpy as jnp

from llmq_tpu.models.latent import (  # noqa: F401
    LatentDims, attn_norm_count, attn_norm_leaves, attn_param_shapes,
    draw_groups, init_latent_pool, latent_decode_attention,
    latent_prefill_attention, latent_write_prefill, param_count,
    prefill_key_blocks, routes)
from llmq_tpu.models.latent import prod as _prod
from llmq_tpu.models.latent import swiglu as _mlp
from llmq_tpu.models.latent import decode_geometry as _decode_geometry
from llmq_tpu.models.latent import qkv as _qkv
from llmq_tpu.ops.moe import route, routed_ffn
from llmq_tpu.ops.norms import rms_norm
from llmq_tpu.ops.quant import embed_lookup
from llmq_tpu.ops.rope import rope_cos_sin
from llmq_tpu.ops.rows import grid_positions, rows_to_grid
from llmq_tpu.utils.profiling import scope

Params = Dict[str, Any]
KVCache = Dict[str, jnp.ndarray]


@dataclass(frozen=True)
class DeepseekV3Config(LatentDims):
    FAMILY: ClassVar[str] = "deepseek_v3"
    name: str = "deepseek-v3-tiny"
    vocab_size: int = 512
    dim: int = 128
    n_layers: int = 3
    n_heads: int = 4
    kv_lora_rank: int = 128
    qk_nope_head_dim: int = 32
    qk_rope_head_dim: int = 16
    v_head_dim: int = 32
    q_lora_rank: Optional[int] = None   # a low-rank query (models/latent.py)
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    ffn_dim: int = 256                 # the dense layers' SwiGLU
    moe_ffn_dim: int = 64              # one expert's SwiGLU
    n_routed_experts: int = 16
    n_shared_experts: int = 1
    n_experts_per_tok: int = 4
    first_k_dense: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    max_seq_len: int = 2048
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        if not 0 <= self.first_k_dense <= self.n_layers:
            raise ValueError(f"model {self.name!r}: first_k_dense "
                             f"{self.first_k_dense} of {self.n_layers}")

    @property
    def n_routed_layers(self) -> int:
        return self.n_layers - self.first_k_dense


def deepseek_v3_tiny(**kw) -> DeepseekV3Config:
    return replace(DeepseekV3Config(), **kw)


def kanana_2_30b_a3b(**kw) -> DeepseekV3Config:
    """kakaocorp/kanana-2-30b-a3b-instruct-2601 at its published sizes
    (https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/
    blob/main/config.json): 48 layers (the first dense, SwiGLU 6,144),
    hidden 2,048, 32 heads of 128 + 64 over a latent of 512, values of
    128, 128 routed experts of 768 with 6 a token and 2 shared, sigmoid
    scores scaled 2.448, vocabulary 128,256 untied, RoPE theta 1e6,
    context 32,768. 30.67 B parameters, 61 GB in bf16: one 16 GB chip
    serves a cut in depth (benchmark/configs/kanana-2-30b-a3b-bf16.json
    holds 8 layers of it with every width and all 128 experts)."""
    return replace(DeepseekV3Config(
        name="kanana-2-30b-a3b", vocab_size=128256, dim=2048, n_layers=48,
        n_heads=32, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, ffn_dim=6144, moe_ffn_dim=768,
        n_routed_experts=128, n_shared_experts=2, n_experts_per_tok=6,
        first_k_dense=1, routed_scaling_factor=2.448, norm_topk_prob=True,
        max_seq_len=32768, rope_theta=1000000.0, norm_eps=1e-6), **kw)


MODEL_CONFIGS = {
    "deepseek-v3-tiny": deepseek_v3_tiny,
    "kanana-2-30b-a3b": kanana_2_30b_a3b,
}


def serving_config(cfg: DeepseekV3Config) -> DeepseekV3Config:
    """``cfg`` for the forward-only serving programs: as it is."""
    return cfg


def import_hf(model_dir: str, cfg: DeepseekV3Config,
              meta_rope_layout: bool = False) -> Params:
    """A local Hugging Face checkpoint directory into this family's
    tree (``models/checkpoint.import_hf_deepseek_v3``)."""
    if meta_rope_layout:
        raise ValueError("model.meta_rope_layout is the Llama block's "
                         "(Meta's .pth layout); the family deepseek_v3 "
                         "has its own rotary permutation")
    from llmq_tpu.models.checkpoint import import_hf_deepseek_v3
    return import_hf_deepseek_v3(model_dir, cfg)


def step_stats_layout(cfg: DeepseekV3Config) -> Dict[str, Any]:
    """Where each int32 counter of a forward pass with ``stats=True``
    lies: the tokens each expert received (E), the experts that
    received any summed over the routed layers, and the routed layers
    run."""
    E = cfg.n_routed_experts
    return {"load": (0, E), "touched": E, "runs": E + 1}


def step_stats_size(cfg: DeepseekV3Config) -> int:
    return cfg.n_routed_experts + 2


def mixed_key_blocks(seq_lens, T: int, page_size: int, max_pages: int):
    """(visited, the table holds): the key blocks ONE prefill attention
    of a mixed step runs over slices of these contexts (a NumPy array;
    the executor's empty slot is one trash token: 1), and those their
    block tables hold (``models/__init__.py``). The slices attend side
    by side: every one runs the longest's blocks."""
    _, visited, table = prefill_key_blocks(seq_lens, T, page_size, max_pages)
    return len(seq_lens) * int(visited), len(seq_lens) * table


def init_row_state(cfg, batch: int) -> None:
    """No row state: the pages are this family's whole cache
    (``models/__init__.py``)."""
    return None


def row_state_bytes_per_row(cfg) -> int:
    return 0


def check_serving(cfg: DeepseekV3Config, *, quantization: str = "",
                  kv_quantization: str = "", mesh: bool = False) -> None:
    """Refuse what is not written for this family, naming the setting."""
    what = None
    if quantization:
        what = f"model.quantization={quantization!r} (int8 experts)"
    elif kv_quantization:
        what = f"model.kv_quantization={kv_quantization!r} (an int8 latent)"
    elif mesh:
        what = "executor.mesh (no partition rules for latents or experts)"
    if what:
        raise ValueError(f"model {cfg.name!r} (family deepseek_v3) does "
                         f"not support {what}; unset it")


# -- parameters ---------------------------------------------------------------

def param_shapes(cfg: DeepseekV3Config) -> Dict[str, Dict[str, tuple]]:
    """Leaf name -> (shape, fan_in) by group: the tree's layout in one
    place (init, the loader and the benchmark's builder follow it).
    Every leaf is stacked over its layers, except the group
    ``experts``: a routed layer's expert matrices are a leaf OF THEIR
    OWN, one a routed layer (``params["moe"]["we_gate_up"]`` is a tuple
    of them) — a slice of a stacked 5.6 GB leaf handed to the grouped
    product was copied, 0.8 GB a layer of temporaries."""
    L, D, V = cfg.n_layers, cfg.dim, cfg.vocab_size
    Ld, Lm = cfg.first_k_dense, cfg.n_routed_layers
    E, Fe, F = cfg.n_routed_experts, cfg.moe_ffn_dim, cfg.ffn_dim
    Fs = cfg.n_shared_experts * Fe
    return {
        "layers": attn_param_shapes(cfg, L),
        "dense": {"w_gate": ((Ld, D, F), D), "w_up": ((Ld, D, F), D),
                  "w_down": ((Ld, F, D), F)},
        "moe": {"router": ((Lm, D, E), D),
                "ws_gate": ((Lm, D, Fs), D), "ws_up": ((Lm, D, Fs), D),
                "ws_down": ((Lm, Fs, D), Fs)},
        "experts": {"we_gate_up": ((E, D, 2 * Fe), D),
                    "we_down": ((E, Fe, D), Fe)},
        "top": {"embed": ((V, D), D), "lm_head": ((D, V), D)},
    }


def norm_leaves(cfg: DeepseekV3Config) -> Params:
    """The tree's RMSNorm weights (ones) and the router's selection
    bias (zeros, float32): what a random init does not draw."""
    L, D, Lm = cfg.n_layers, cfg.dim, cfg.n_routed_layers
    return {"layers": {"attn_norm": jnp.ones((L, D), cfg.dtype),
                       "mlp_norm": jnp.ones((L, D), cfg.dtype),
                       **attn_norm_leaves(cfg, L)},
            "moe": {"router_bias": jnp.zeros((Lm, cfg.n_routed_experts),
                                             jnp.float32)},
            "final_norm": jnp.ones((D,), cfg.dtype)}


def assemble(cfg: DeepseekV3Config, drawn: Dict[str, Dict[str, Any]]
             ) -> Params:
    """``param_shapes``-shaped groups of arrays (``experts``: a list
    of one array a routed layer under each name) + ``norm_leaves`` ->
    the parameter tree."""
    fixed = norm_leaves(cfg)
    return {"embed": drawn["top"]["embed"],
            "lm_head": drawn["top"]["lm_head"],
            "final_norm": fixed["final_norm"],
            "layers": {**drawn["layers"], **fixed["layers"]},
            "dense": dict(drawn["dense"]),
            "moe": {**drawn["moe"], **fixed["moe"],
                    **{k: tuple(v) for k, v in drawn["experts"].items()}}}


def init_params(key: jax.Array, cfg: DeepseekV3Config) -> Params:
    """Random-init parameter tree, N(0, 1 / fan_in) as the Llama
    block's."""
    drawn = draw_groups(key, param_shapes(cfg), cfg.dtype,
                        cfg.n_routed_layers)
    return assemble(cfg, drawn)


def init_params_quantized(key: jax.Array, cfg: DeepseekV3Config) -> Params:
    check_serving(cfg, quantization="int8")


def param_count_analytic(cfg: DeepseekV3Config) -> int:
    """Parameters HELD, from the configuration alone."""
    n = sum(_prod(shape) * (cfg.n_routed_layers if g == "experts" else 1)
            for g, leaves in param_shapes(cfg).items()
            for shape, _f in leaves.values())
    fixed = (cfg.n_layers * (2 * cfg.dim + attn_norm_count(cfg)) + cfg.dim
             + cfg.n_routed_layers * cfg.n_routed_experts)
    return n + fixed


def active_param_count(cfg: DeepseekV3Config) -> int:
    """Parameters one token multiplies with: the held count less the
    experts it is not routed to (the MFU estimate's numerator)."""
    idle = cfg.n_routed_experts - cfg.n_experts_per_tok
    return (param_count_analytic(cfg)
            - cfg.n_routed_layers * idle * 3 * cfg.dim * cfg.moe_ffn_dim)


def weight_bytes(cfg: DeepseekV3Config) -> int:
    return param_count_analytic(cfg) * jnp.dtype(cfg.dtype).itemsize


def kv_bytes_per_token(cfg: DeepseekV3Config,
                       cache_dtype: Optional[Any] = None) -> int:
    """The published cost of one cached token across the layers held:
    the latent and the RoPE key, without the pool's lane padding."""
    itemsize = jnp.dtype(cache_dtype or cfg.dtype).itemsize
    return (cfg.n_layers * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            * itemsize)


def init_kv_pages(cfg: DeepseekV3Config, num_pages: int, page_size: int,
                  dtype: Optional[Any] = None) -> KVCache:
    """The latent page pool: ONE leaf ``"ckv"`` ``(L, P, page_size,
    latent_width)`` (the module's docstring has the layout), page 0
    reserved as in every pool of this repo."""
    if dtype is not None and jnp.dtype(dtype) == jnp.int8:
        check_serving(cfg, kv_quantization="int8")
    return init_latent_pool(cfg, cfg.n_layers, num_pages, page_size, dtype)


def mixed_live_rows(tokens: int, batch: int, slices: int, width: int) -> int:
    """Rows ``forward_mixed``'s row-wise products run for the prompt
    tokens of a chunk (``models/__init__.py``): every row of the grid,
    whatever ``tokens`` is."""
    return slices * width


# -- feed-forward -------------------------------------------------------------

def _ffn(params: Params, cfg: DeepseekV3Config, l: int, x, live):
    """Layer ``l``'s feed-forward over tokens x (N, D), normalised and
    float32 (what the router reads; the products take it in
    ``cfg.dtype``): dense for the first ``first_k_dense`` layers,
    routed + shared after. Returns
    (y, stats or None): ``ops/moe.routed_ffn``'s counts."""
    with scope("mlp"):
        xf, x = x, x.astype(cfg.dtype)
        if l < cfg.first_k_dense:
            d = params["dense"]
            return _mlp(x, d["w_gate"][l], d["w_up"][l],
                        d["w_down"][l]), None
    m, i = params["moe"], l - cfg.first_k_dense
    experts, gates = route(
        xf, m["router"][i], m["router_bias"][i],
        top_k=cfg.n_experts_per_tok, scale=cfg.routed_scaling_factor,
        norm_topk=cfg.norm_topk_prob)
    y, st = routed_ffn(x, experts, gates, m["we_gate_up"][i],
                       m["we_down"][i], live)
    with scope("mlp"):       # the shared experts, beside the routed ones
        return y + _mlp(x, m["ws_gate"][i], m["ws_up"][i],
                        m["ws_down"][i]), st


def _sum_stats(cfg: DeepseekV3Config, per_layer) -> jnp.ndarray:
    """One forward pass's counters (``step_stats_size``): the routed
    layers' counts summed, then how many routed layers ran."""
    got = [st for st in per_layer if st is not None]
    total = sum(got, jnp.zeros((cfg.n_routed_experts + 1,), jnp.int32))
    return jnp.concatenate([total, jnp.full((1,), len(got), jnp.int32)])


def _finish(params, h, cfg):
    with scope("head"):
        h = rms_norm(h, params["final_norm"],
                     cfg.norm_eps).astype(cfg.dtype)
        return jnp.dot(h, params["lm_head"]).astype(jnp.float32)


# -- forward ------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "last_only", "stats"))
def forward_prefill(params: Params, cfg: DeepseekV3Config, tokens,
                    positions, lengths, kv_cache: KVCache, block_tables,
                    last_only: bool = False, stats: bool = False):
    """``models/llama.forward_prefill``'s contract (right-padded rows,
    contiguous absolute ``positions``, continuation over cached pages
    through the block tables) over the latent pool. Returns (logits,
    cache), and the routed layers' counts after them with ``stats``."""
    B, T = tokens.shape
    with scope("embed"):
        h = embed_lookup(params["embed"], tokens, jnp.float32)
    with scope("qkv"):
        cos, sin = rope_cos_sin(positions, cfg.qk_rope_head_dim,
                                cfg.rope_theta)
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    seq_lens = jnp.max(jnp.where(valid, positions, -1), axis=1) + 1
    lp, pool, counts = params["layers"], kv_cache["ckv"], []
    for l in range(cfg.n_layers):
        with scope("qkv"):
            x = rms_norm(h, lp["attn_norm"][l],
                         cfg.norm_eps).astype(cfg.dtype)
        q_nope, q_rope, row = _qkv(cfg, lp, l, x, cos, sin)
        pool = latent_write_prefill(pool, row, block_tables, positions,
                                    lengths, l)
        attn = latent_prefill_attention(cfg, lp, l, q_nope, q_rope, pool,
                                        block_tables, positions, seq_lens)
        with scope("attn_out"):
            h = h + jnp.dot(attn, lp["wo"][l])
        with scope("mlp"):
            x = rms_norm(h, lp["mlp_norm"][l], cfg.norm_eps)
        y, st = _ffn(params, cfg, l, x.reshape(B * T, -1),
                     valid.reshape(-1))
        counts.append(st)
        with scope("mlp"):
            h = h + y.reshape(B, T, -1)
    if last_only:
        with scope("head"):
            h = h[jnp.arange(B), lengths - 1]
    out = (_finish(params, h, cfg), {"ckv": pool})
    return out + (_sum_stats(cfg, counts),) if stats else out


@partial(jax.jit, static_argnames=("cfg", "stats"))
def forward_decode(params: Params, cfg: DeepseekV3Config, tokens, positions,
                   kv_cache: KVCache, block_tables, active=None,
                   stats: bool = False):
    """One decode step for every active row
    (``models/llama.forward_decode``'s contract). A row that is not
    active writes to page 0, attends to nothing and is routed to no
    expert; its logits mean nothing."""
    pool = kv_cache["ckv"]
    with scope("embed"):
        h = embed_lookup(params["embed"], tokens, jnp.float32)  # (B, D)
    with scope("qkv"):
        cos, sin = rope_cos_sin(positions[:, None], cfg.qk_rope_head_dim,
                                cfg.rope_theta)
    page_of, slot_of, seq_lens = _decode_geometry(
        positions, block_tables, pool.shape[2], active)
    lp, counts = params["layers"], []
    for l in range(cfg.n_layers):
        with scope("qkv"):
            x = rms_norm(h, lp["attn_norm"][l],
                         cfg.norm_eps).astype(cfg.dtype)
        q_nope, q_rope, row = _qkv(cfg, lp, l, x[:, None], cos, sin)
        attn, pool = latent_decode_attention(
            cfg, lp, l, q_nope[:, 0], q_rope[:, 0], row[:, 0], pool,
            block_tables, seq_lens, page_of, slot_of)
        with scope("attn_out"):
            h = h + jnp.dot(attn, lp["wo"][l])
        with scope("mlp"):
            x = rms_norm(h, lp["mlp_norm"][l], cfg.norm_eps)
        y, st = _ffn(params, cfg, l, x, active)
        counts.append(st)
        with scope("mlp"):
            h = h + y
    out = (_finish(params, h, cfg), {"ckv": pool})
    return out + (_sum_stats(cfg, counts),) if stats else out


@partial(jax.jit, static_argnames=("cfg", "stats"))
def forward_mixed(params: Params, cfg: DeepseekV3Config, dec_tokens,
                  dec_positions, kv_cache: KVCache, dec_block_tables,
                  pf_tokens, pf_positions, pf_lengths, pf_starts,
                  pf_block_tables, dec_active=None, stats: bool = False):
    """The fused mixed step (``models/llama.forward_mixed``'s
    contract, the slices' tokens TIGHT and ``pf_starts`` with them): B
    decode rows one token and S prefill slices of up to T tokens in ONE
    traversal of the layers. The slices go back onto the (S, T) grid at
    the door and every product runs all S T of their rows
    (``mixed_live_rows``). Attention runs a layer's slices and its
    decode rows apart (disjoint pages); the feed-forward runs them
    TOGETHER, so a routed layer's experts are streamed once for both
    (and skip a dead row by themselves: ``live``). Returns
    (dec_logits (B, V), pf_logits (S, V), cache
    [, counts]): of a slice only its LAST valid position is projected
    — serving samples nothing else, and 1,024
    slice tokens through a 128k-row head are 0.5 GB of float32 and
    half a teraflop a mixed step."""
    B = dec_tokens.shape[0]
    S = pf_lengths.shape[0]
    T = pf_tokens.shape[0] // S
    # Back onto the (S, T) grid at the door, and no row tiles after it:
    # this family's dense blocks are a fifth of its mixed step beside
    # routed experts that skip a dead row by themselves, and measured
    # slower as loops than whole (PERF.md, PR 38).
    pf_tokens = rows_to_grid(pf_tokens, pf_starts, T)
    pf_positions, _ = grid_positions(pf_positions, pf_lengths, pf_starts, T)
    pool = kv_cache["ckv"]
    with scope("decode_rows"):
        with scope("embed"):
            h_d = embed_lookup(params["embed"], dec_tokens, jnp.float32)
        with scope("qkv"):
            cos_d, sin_d = rope_cos_sin(dec_positions[:, None],
                                        cfg.qk_rope_head_dim,
                                        cfg.rope_theta)
        page_of, slot_of, dec_seq_lens = _decode_geometry(
            dec_positions, dec_block_tables, pool.shape[2], dec_active)
    with scope("slices"):
        with scope("embed"):
            h_p = embed_lookup(params["embed"], pf_tokens, jnp.float32)
        with scope("qkv"):
            cos_p, sin_p = rope_cos_sin(pf_positions, cfg.qk_rope_head_dim,
                                        cfg.rope_theta)
        pf_valid = jnp.arange(T)[None, :] < pf_lengths[:, None]
        pf_seq_lens = jnp.max(jnp.where(pf_valid, pf_positions, -1),
                              axis=1) + 1
    live = jnp.concatenate(
        [pf_valid.reshape(-1), (dec_active if dec_active is not None
                                else jnp.ones((B,), jnp.bool_))])
    lp, counts = params["layers"], []
    for l in range(cfg.n_layers):
        with scope("slices"):
            with scope("qkv"):
                x_p = rms_norm(h_p, lp["attn_norm"][l],
                               cfg.norm_eps).astype(cfg.dtype)
            qn_p, qr_p, row_p = _qkv(cfg, lp, l, x_p, cos_p, sin_p)
            pool = latent_write_prefill(pool, row_p, pf_block_tables,
                                        pf_positions, pf_lengths, l)
            attn_p = latent_prefill_attention(
                cfg, lp, l, qn_p, qr_p, pool, pf_block_tables,
                pf_positions, pf_seq_lens)
            with scope("attn_out"):
                h_p = h_p + jnp.dot(attn_p, lp["wo"][l])
        with scope("decode_rows"):
            with scope("qkv"):
                x_d = rms_norm(h_d, lp["attn_norm"][l],
                               cfg.norm_eps).astype(cfg.dtype)
            qn_d, qr_d, row_d = _qkv(cfg, lp, l, x_d[:, None], cos_d,
                                     sin_d)
            attn_d, pool = latent_decode_attention(
                cfg, lp, l, qn_d[:, 0], qr_d[:, 0], row_d[:, 0], pool,
                dec_block_tables, dec_seq_lens, page_of, slot_of)
            with scope("attn_out"):
                h_d = h_d + jnp.dot(attn_d, lp["wo"][l])
        # The feed-forward takes both kinds of row side by side (its
        # matrices are streamed once): no row kind on its scopes.
        with scope("mlp"):
            x = jnp.concatenate(
                [rms_norm(h_p, lp["mlp_norm"][l], cfg.norm_eps).reshape(
                    S * T, -1),
                 rms_norm(h_d, lp["mlp_norm"][l], cfg.norm_eps)])
        y, st = _ffn(params, cfg, l, x, live)
        counts.append(st)
        with scope("mlp"):
            h_p = h_p + y[:S * T].reshape(S, T, -1)
            h_d = h_d + y[S * T:]
    with scope("slices"), scope("head"):
        h_p = h_p[jnp.arange(S), pf_lengths - 1]
    with scope("decode_rows"):
        dec_logits = _finish(params, h_d, cfg)
    with scope("slices"):
        pf_logits = _finish(params, h_p, cfg)
    out = (dec_logits, pf_logits, {"ckv": pool})
    return out + (_sum_stats(cfg, counts),) if stats else out
