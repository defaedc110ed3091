"""The LongCat-Flash block in pure JAX (``model_type: longcat_flash``):
a SHORTCUT-CONNECTED DOUBLE LAYER — two latent attentions, two dense
feed-forwards and, beside them, one routed layer whose input is read
after the first attention and whose result is added at the layer's end
— with a softmax router over the routed experts AND a set of
zero-compute (identity) experts. LongCat-Flash-Chat is this block at
hidden 6,144, 64 heads, dense SwiGLUs of 12,288 and 512 experts of
2,048 + 256 zero-compute ones, 12 a token.

One layer (N, N' its four RMSNorms; x the float32 residual stream):

    h1 = x  + MLA_0(N_0(x))            u = N'_0(h1)
    m  = Routed(u)                     # the shortcut
    h2 = h1 + FFN_0(u)
    h3 = h2 + MLA_1(N_1(h2))
    y  = h3 + FFN_1(N'_1(h3)) + m

    Routed(u): p = softmax(u W_r) over ALL E + Z outputs, float32;
               S = top-k of (p + b), b chooses only; g_e = scale * p_e
               for e in S, NOT renormalised;
               m = sum_{e in S, e < E} g_e SwiGLU_e(u)
                   + sum_{e in S, e >= E} g_e u

MLA is ``models/latent.py``'s (the equations are in
``models/deepseek_v3.py``'s docstring) with a low-rank query and both
scale factors: q = s_q RMSNorm(x W_qa) W_qb with s_q = sqrt(hidden /
q_lora_rank), [k^nope ; v] = s_kv c W_kvb with s_kv = sqrt(hidden /
kv_lora_rank). **The cache** holds ``(c, k^rope)`` for EACH of a
layer's two attentions: the pool's one leaf ``"ckv"`` is ``(2 L, P,
page_size, 640)``, attention ``i`` of layer ``l`` at index ``2 l + i``
— of the attention leaves (``params["layers"]``, stacked over the 2 L
attentions) and of the pool alike. Everything that treats a cache as a
pytree of ``(L, P, ...)`` leaves goes on doing so.

**A chip's share.** A deployment of this model divides each routed
layer's experts over many chips and keeps everything else
data-parallel. ``held_experts = (lo, hi)`` says which of the router's
experts THIS chip holds (``params["moe"]["we_*"]`` are those alone):
the router scores all E + Z, a token chooses its k among all, the held
pairs are multiplied here (``ops/moe.routed_ffn(held=...)``), the
zero-compute experts are added here (they are a token's home chip's),
and what the experts held elsewhere would have added is NOT: no code
stands in for the other chips or the exchange with them, so ``m`` is
this chip's partial sum and it is what goes on to the next layer.
``vocab_size`` is likewise what this chip holds of the vocabulary
(embedding rows and head columns); sampling is over that slice.

Conventions as ``models/deepseek_v3.py``: float32 residual stream and
router, bf16 matmul operands, absorbed decode through the latent
kernel, expanded prefill under XLA, each routed layer's expert matrices
a leaf of their own. In a mixed step every projection and feed-forward
runs ONCE over the slices' tokens and the decode rows together; only
the attention itself runs them apart. Int8 weights, an int8 cache and a
mesh are not written: each is refused by name (``check_serving``).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from llmq_tpu.models.latent import (  # noqa: F401
    LatentDims, attn_norm_count, attn_norm_leaves, attn_param_shapes,
    decode_geometry, draw_groups, init_latent_pool,
    latent_decode_attention, latent_prefill_attention, latent_write_prefill,
    key_blocks_each, param_count, prefill_key_blocks, qkv, routes)
from llmq_tpu.models.latent import prod as _prod
from llmq_tpu.models.latent import swiglu as _mlp
from llmq_tpu.ops.moe import identity_gate, route, routed_ffn
from llmq_tpu.ops.norms import rms_norm
from llmq_tpu.ops.quant import embed_lookup
from llmq_tpu.ops.rope import rope_cos_sin
from llmq_tpu.ops.rows import (grid_positions, grid_to_rows, live_rows,
                               row_tile, rows_to_grid, tile_rows)
from llmq_tpu.utils.profiling import scope

Params = Dict[str, Any]
KVCache = Dict[str, jnp.ndarray]


@dataclass(frozen=True)
class LongcatFlashConfig(LatentDims):
    FAMILY: ClassVar[str] = "longcat_flash"
    name: str = "longcat-flash-tiny"
    vocab_size: int = 512              # the rows of the vocabulary HELD
    dim: int = 256
    n_layers: int = 2                  # double layers
    n_heads: int = 4
    kv_lora_rank: int = 128
    q_lora_rank: Optional[int] = 64
    qk_nope_head_dim: int = 32
    qk_rope_head_dim: int = 16
    v_head_dim: int = 32
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    ffn_dim: int = 256                 # each of a layer's two dense SwiGLUs
    moe_ffn_dim: int = 64              # one expert's SwiGLU
    n_routed_experts: int = 16         # E: the router's real experts
    zero_expert_num: int = 8           # Z: its zero-compute (identity) ones
    n_experts_per_tok: int = 4
    routed_scaling_factor: float = 6.0
    held_experts: Optional[Tuple[int, int]] = None   # None: all E
    max_seq_len: int = 2048
    rope_theta: float = 10000000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self) -> None:
        lo, hi = self.held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(
                f"model {self.name!r}: held_experts {self.held_experts} of "
                f"{self.n_routed_experts} routed experts")

    @property
    def held(self) -> Tuple[int, int]:
        """The router's experts whose matrices this chip holds."""
        return (tuple(self.held_experts) if self.held_experts is not None
                else (0, self.n_routed_experts))

    @property
    def n_held(self) -> int:
        return self.held[1] - self.held[0]

    @property
    def n_attn(self) -> int:
        """Attentions, and with them cache layers: two a layer."""
        return 2 * self.n_layers


def longcat_flash_tiny(**kw) -> LongcatFlashConfig:
    return replace(LongcatFlashConfig(), **kw)


def longcat_flash_chat(**kw) -> LongcatFlashConfig:
    """meituan-longcat/LongCat-Flash-Chat at its published sizes
    (https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/
    main/config.json): 28 double layers, hidden 6,144, 64 heads of 128 +
    64 over a latent of 512 with a query of rank 1,536, values of 128,
    dense SwiGLUs of 12,288, 512 experts of 2,048 and 256 zero-compute
    ones with 12 a token scaled 6, vocabulary 131,072, RoPE theta 1e7,
    context 131,072. 560.7 B parameters: one chip holds a share
    (benchmark/configs/longcat-flash-chat-bf16-ep32.json: 4 layers, 16
    of the 512 experts, an eighth of the vocabulary)."""
    return replace(LongcatFlashConfig(
        name="longcat-flash-chat", vocab_size=131072, dim=6144, n_layers=28,
        n_heads=64, kv_lora_rank=512, q_lora_rank=1536, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, mla_scale_q_lora=True,
        mla_scale_kv_lora=True, ffn_dim=12288, moe_ffn_dim=2048,
        n_routed_experts=512, zero_expert_num=256, n_experts_per_tok=12,
        routed_scaling_factor=6.0, max_seq_len=131072, rope_theta=1e7,
        norm_eps=1e-5), **kw)


MODEL_CONFIGS = {
    "longcat-flash-tiny": longcat_flash_tiny,
    "longcat-flash-chat": longcat_flash_chat,
}


def serving_config(cfg: LongcatFlashConfig) -> LongcatFlashConfig:
    """``cfg`` for the forward-only serving programs: as it is."""
    return cfg


def import_hf(model_dir: str, cfg: LongcatFlashConfig,
              meta_rope_layout: bool = False) -> Params:
    """A local Hugging Face checkpoint directory into this family's
    tree (``models/checkpoint.import_hf_longcat_flash``): the held
    experts and the held rows of the vocabulary alone."""
    if meta_rope_layout:
        raise ValueError("model.meta_rope_layout is the Llama block's "
                         "(Meta's .pth layout); the family longcat_flash "
                         "has its own rotary permutation")
    from llmq_tpu.models.checkpoint import import_hf_longcat_flash
    return import_hf_longcat_flash(model_dir, cfg)


def step_stats_layout(cfg: LongcatFlashConfig) -> Dict[str, Any]:
    """Where each counter of a forward pass with ``stats=True`` lies:
    the tokens each HELD expert received, the held experts that
    received any summed over the routed layers, the slots that chose a
    zero-compute expert, the slots whose expert is held elsewhere, and
    the routed layers run."""
    n = cfg.n_held
    return {"load": (0, n), "touched": n, "zero_slots": n + 1,
            "away_slots": n + 2, "runs": n + 3}


def step_stats_size(cfg: LongcatFlashConfig) -> int:
    return cfg.n_held + 4


def mixed_key_blocks(seq_lens, T: int, page_size: int, max_pages: int):
    """(visited, the table holds): the key blocks ONE prefill attention
    of a mixed step runs over slices of these contexts (a NumPy array;
    the executor's empty slot is one trash token: 1), and those their
    block tables hold (``models/__init__.py``). The slices attend one
    at a time (``_prefill_attend``): each runs its own blocks."""
    return key_blocks_each(seq_lens, T, page_size, max_pages)


def init_row_state(cfg, batch: int) -> None:
    """No row state: the pages are this family's whole cache
    (``models/__init__.py``)."""
    return None


def row_state_bytes_per_row(cfg) -> int:
    return 0


def check_serving(cfg: LongcatFlashConfig, *, quantization: str = "",
                  kv_quantization: str = "", mesh: bool = False) -> None:
    """Refuse what is not written for this family, naming the setting."""
    what = None
    if quantization:
        what = f"model.quantization={quantization!r} (int8 experts)"
    elif kv_quantization:
        what = f"model.kv_quantization={kv_quantization!r} (an int8 latent)"
    elif mesh:
        what = ("executor.mesh (no partition rules for latents or experts, "
                "no exchange between shares)")
    if what:
        raise ValueError(f"model {cfg.name!r} (family longcat_flash) does "
                         f"not support {what}; unset it")


# -- parameters ---------------------------------------------------------------

def param_shapes(cfg: LongcatFlashConfig) -> Dict[str, Dict[str, tuple]]:
    """Leaf name -> (shape, fan_in) by group: the tree's layout in one
    place (init, the loader and the benchmark's builder follow it).
    ``layers`` (the attentions) and ``ffn`` (the dense SwiGLUs) are
    stacked over the 2 L of them, attention / SwiGLU ``i`` of layer
    ``l`` at ``2 l + i``; ``moe`` over the L routed layers; the group
    ``experts`` is a leaf OF ITS OWN a routed layer
    (``params["moe"]["we_gate_up"]`` is a tuple of them), holding the
    HELD experts' matrices."""
    L, A, D, V = cfg.n_layers, cfg.n_attn, cfg.dim, cfg.vocab_size
    F, Fe = cfg.ffn_dim, cfg.moe_ffn_dim
    R = cfg.n_routed_experts + cfg.zero_expert_num
    return {
        "layers": attn_param_shapes(cfg, A),
        "ffn": {"w_gate": ((A, D, F), D), "w_up": ((A, D, F), D),
                "w_down": ((A, F, D), F)},
        "moe": {"router": ((L, D, R), D)},
        "experts": {"we_gate_up": ((cfg.n_held, D, 2 * Fe), D),
                    "we_down": ((cfg.n_held, Fe, D), Fe)},
        "top": {"embed": ((V, D), D), "lm_head": ((D, V), D)},
    }


def norm_leaves(cfg: LongcatFlashConfig) -> Params:
    """The tree's RMSNorm weights (ones) and the router's selection
    bias (zeros, float32): what a random init does not draw."""
    L, A, D = cfg.n_layers, cfg.n_attn, cfg.dim
    R = cfg.n_routed_experts + cfg.zero_expert_num
    return {"layers": {"attn_norm": jnp.ones((A, D), cfg.dtype),
                       **attn_norm_leaves(cfg, A)},
            "ffn": {"mlp_norm": jnp.ones((A, D), cfg.dtype)},
            "moe": {"router_bias": jnp.zeros((L, R), jnp.float32)},
            "final_norm": jnp.ones((D,), cfg.dtype)}


def assemble(cfg: LongcatFlashConfig, drawn: Dict[str, Dict[str, Any]]
             ) -> Params:
    """``param_shapes``-shaped groups of arrays (``experts``: a list
    of one array a routed layer under each name) + ``norm_leaves`` ->
    the parameter tree."""
    fixed = norm_leaves(cfg)
    return {"embed": drawn["top"]["embed"],
            "lm_head": drawn["top"]["lm_head"],
            "final_norm": fixed["final_norm"],
            "layers": {**drawn["layers"], **fixed["layers"]},
            "ffn": {**drawn["ffn"], **fixed["ffn"]},
            "moe": {**drawn["moe"], **fixed["moe"],
                    **{k: tuple(v) for k, v in drawn["experts"].items()}}}


def init_params(key: jax.Array, cfg: LongcatFlashConfig) -> Params:
    """Random-init parameter tree, N(0, 1 / fan_in) as the other
    families'."""
    drawn = draw_groups(key, param_shapes(cfg), cfg.dtype, cfg.n_layers)
    return assemble(cfg, drawn)


def init_params_quantized(key: jax.Array, cfg: LongcatFlashConfig) -> Params:
    check_serving(cfg, quantization="int8")


def param_count_analytic(cfg: LongcatFlashConfig) -> int:
    """Parameters HELD, from the configuration alone."""
    n = sum(_prod(shape) * (cfg.n_layers if g == "experts" else 1)
            for g, leaves in param_shapes(cfg).items()
            for shape, _f in leaves.values())
    fixed = (cfg.n_attn * (2 * cfg.dim + attn_norm_count(cfg)) + cfg.dim
             + cfg.n_layers * (cfg.n_routed_experts + cfg.zero_expert_num))
    return n + fixed


def active_param_count(cfg: LongcatFlashConfig) -> int:
    """Parameters one token multiplies with HERE, in expectation: the
    held count less the held experts it is not routed to (of its k
    slots, the share n_held / (E + Z) falls on a held expert under
    uniform routing)."""
    slots = (cfg.n_experts_per_tok * cfg.n_held
             / (cfg.n_routed_experts + cfg.zero_expert_num))
    idle = cfg.n_held - slots
    return int(param_count_analytic(cfg)
               - cfg.n_layers * idle * 3 * cfg.dim * cfg.moe_ffn_dim)


def weight_bytes(cfg: LongcatFlashConfig) -> int:
    return param_count_analytic(cfg) * jnp.dtype(cfg.dtype).itemsize


def kv_bytes_per_token(cfg: LongcatFlashConfig,
                       cache_dtype: Optional[Any] = None) -> int:
    """The published cost of one cached token across the layers held:
    the latent and the RoPE key of BOTH attentions of each, without the
    pool's lane padding."""
    itemsize = jnp.dtype(cache_dtype or cfg.dtype).itemsize
    return (cfg.n_attn * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            * itemsize)


def init_kv_pages(cfg: LongcatFlashConfig, num_pages: int, page_size: int,
                  dtype: Optional[Any] = None) -> KVCache:
    """The latent page pool, one layer of it an ATTENTION: ``"ckv"``
    ``(2 L, P, page_size, latent_width)``."""
    if dtype is not None and jnp.dtype(dtype) == jnp.int8:
        check_serving(cfg, kv_quantization="int8")
    return init_latent_pool(cfg, cfg.n_attn, num_pages, page_size, dtype)


# -- the double layer ---------------------------------------------------------

def _routed(params: Params, cfg: LongcatFlashConfig, l: int, u, live):
    """Layer ``l``'s routed part of tokens u (N, D), normalised and
    float32 (what the router and the identity experts read; the held
    experts' products take it in ``cfg.dtype``). Returns (m float32,
    stats): this chip's PARTIAL sum and ``ops/moe.routed_ffn``'s
    counts."""
    m = params["moe"]
    experts, gates = route(
        u, m["router"][l], m["router_bias"][l], top_k=cfg.n_experts_per_tok,
        scale=cfg.routed_scaling_factor, norm_topk=False, scoring="softmax")
    y, st = routed_ffn(u.astype(cfg.dtype), experts, gates,
                       m["we_gate_up"][l], m["we_down"][l], live,
                       held=cfg.held, n_routed=cfg.n_routed_experts)
    if st.shape[0] == cfg.n_held + 1:      # all held, none zero-compute
        st = jnp.concatenate([st, jnp.zeros((2,), jnp.int32)])
    zero = identity_gate(experts, gates, cfg.n_routed_experts, live)
    with scope("moe_combine"):
        return y.astype(jnp.float32) + zero[:, None] * u, st


def _layer(params: Params, cfg: LongcatFlashConfig, l: int, h, cos, sin,
           attend, live):
    """Double layer ``l`` over tokens h (N, D) float32, all of a
    program's tokens side by side. ``attend(a, q_nope (N, H, dn),
    q_rope (N, H, dr), row (N, W)) -> (N, H dv)`` is the program's
    attention ``a`` (it writes the rows to the pool and attends).
    Returns (h', the routed layer's stats)."""
    at, ff = params["layers"], params["ffn"]
    a0, a1 = 2 * l, 2 * l + 1

    def attention(a, h):
        with scope("qkv"):
            x = rms_norm(h, at["attn_norm"][a],
                         cfg.norm_eps).astype(cfg.dtype)
        q_nope, q_rope, row = qkv(cfg, at, a, x[None], cos, sin)
        o = attend(a, q_nope[0], q_rope[0], row[0])
        with scope("attn_out"):
            return h + jnp.dot(o, at["wo"][a])

    def dense(a, x):          # called under ``mlp``, with its norm
        return _mlp(x.astype(cfg.dtype), ff["w_gate"][a], ff["w_up"][a],
                    ff["w_down"][a])

    h = attention(a0, h)
    with scope("mlp"):
        u = rms_norm(h, ff["mlp_norm"][a0], cfg.norm_eps)
    m, st = _routed(params, cfg, l, u, live)
    with scope("mlp"):
        h = h + dense(a0, u)
    h = attention(a1, h)
    with scope("mlp"):
        y = dense(a1, rms_norm(h, ff["mlp_norm"][a1], cfg.norm_eps))
    with scope("moe_combine"):
        return h + y + m, st


def mixed_live_rows(tokens: int, batch: int, slices: int, width: int) -> int:
    """Rows ``forward_mixed``'s row-wise products run for ``tokens``
    prompt tokens in ``slices`` slices ``width`` wide
    (``models/__init__.py``): the live tiles' rows, less the ``batch``
    decode rows that lead them."""
    return tile_rows(tokens, row_tile(width), slices * width, lead=batch)


def _mixed_layer(params: Params, cfg: LongcatFlashConfig, l: int, h, cos,
                 sin, attend, live, rows):
    """``_layer`` as ``forward_mixed`` runs it: the same sums, cut where
    the attentions and the routed layer stand, so that what lies
    between two of them and is a token's own is ONE ``fn`` of
    ``rows(fn, *arrays)`` — the live tiles of the step's rows
    (``ops/rows.live_rows``). h, cos, sin are flat (N, ...);
    ``attend`` takes and returns flat rows."""
    at, ff = params["layers"], params["ffn"]
    a0, a1 = 2 * l, 2 * l + 1

    def attention(a, h):
        def project(h, cos, sin):
            with scope("qkv"):
                x = rms_norm(h, at["attn_norm"][a],
                             cfg.norm_eps).astype(cfg.dtype)
            return qkv(cfg, at, a, x, cos, sin)
        return attend(a, *rows(project, h, cos, sin))

    def out_dense(a, h, o):       # -> (what the SwiGLU read, h', its result)
        with scope("attn_out"):
            h = h + jnp.dot(o, at["wo"][a])
        with scope("mlp"):
            u = rms_norm(h, ff["mlp_norm"][a], cfg.norm_eps)
            return u, h, _mlp(u.astype(cfg.dtype), ff["w_gate"][a],
                              ff["w_up"][a], ff["w_down"][a])

    def first(h, o):
        u, h, y = out_dense(a0, h, o)
        with scope("mlp"):
            return u, h + y

    def second(h, o, m):
        _, h, y = out_dense(a1, h, o)
        with scope("moe_combine"):
            return h + y + m

    u, h = rows(first, h, attention(a0, h))
    m, st = _routed(params, cfg, l, u, live)
    return rows(second, h, attention(a1, h), m), st


def _sum_stats(per_layer) -> jnp.ndarray:
    """One forward pass's counters (``step_stats_layout``): the routed
    layers' counts summed, then how many routed layers ran."""
    return jnp.concatenate([sum(per_layer),
                            jnp.full((1,), len(per_layer), jnp.int32)])


def _finish(params, h, cfg):
    with scope("head"):
        h = rms_norm(h, params["final_norm"],
                     cfg.norm_eps).astype(cfg.dtype)
        return jnp.dot(h, params["lm_head"]).astype(jnp.float32)


def _run(params, cfg, h, positions, attend, live):
    """All layers over the flat tokens h (N, D) at ``positions`` (N,).
    Returns (h', counters)."""
    with scope("qkv"):
        cos, sin = rope_cos_sin(positions[None], cfg.qk_rope_head_dim,
                                cfg.rope_theta)
    counts = []
    for l in range(cfg.n_layers):
        h, st = _layer(params, cfg, l, h, cos, sin, attend, live)
        counts.append(st)
    return h, _sum_stats(counts)


def _prefill_attend(cfg, lp, pool, block_tables, positions, lengths,
                    seq_lens):
    """``attend`` of B slices of T tokens: write the rows, then the
    expanded attention over each slice's live key blocks
    (``latent_prefill_attention``), ONE SLICE AT A TIME: each slice
    then runs exactly its own blocks, and at 64 heads a slice's float32
    scores over one 512-token block are 67 MB where four slices side by
    side hold 268. ``pool`` is a
    one-element list: the pool as it stands."""
    B, T = positions.shape

    def attend(a, q_nope, q_rope, row):
        pool[0] = latent_write_prefill(
            pool[0], row.reshape(B, T, -1), block_tables, positions,
            lengths, a)

        def one(s):
            q_n, q_r, bt, pos, n = s
            return latent_prefill_attention(
                cfg, lp, a, q_n[None], q_r[None], pool[0], bt[None],
                pos[None], n[None])[0]

        o = jax.lax.map(one, (q_nope.reshape((B, T) + q_nope.shape[1:]),
                              q_rope.reshape((B, T) + q_rope.shape[1:]),
                              block_tables, positions, seq_lens))
        return o.reshape(B * T, -1)
    return attend


def _decode_attend(cfg, lp, pool, block_tables, seq_lens, page_of, slot_of):
    def attend(a, q_nope, q_rope, row):
        o, pool[0] = latent_decode_attention(
            cfg, lp, a, q_nope, q_rope, row, pool[0], block_tables,
            seq_lens, page_of, slot_of)
        return o
    return attend


# -- forward ------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "last_only", "stats"))
def forward_prefill(params: Params, cfg: LongcatFlashConfig, tokens,
                    positions, lengths, kv_cache: KVCache, block_tables,
                    last_only: bool = False, stats: bool = False):
    """``models/llama.forward_prefill``'s contract (right-padded rows,
    contiguous absolute ``positions``, continuation over cached pages
    through the block tables) over the latent pool. Returns (logits,
    cache), and the routed layers' counts after them with ``stats``."""
    B, T = tokens.shape
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    seq_lens = jnp.max(jnp.where(valid, positions, -1), axis=1) + 1
    pool = [kv_cache["ckv"]]
    with scope("embed"):
        h = embed_lookup(params["embed"], tokens.reshape(-1), jnp.float32)
    h, counts = _run(
        params, cfg, h, positions.reshape(-1),
        _prefill_attend(cfg, params["layers"], pool, block_tables,
                        positions, lengths, seq_lens),
        valid.reshape(-1))
    h = h.reshape(B, T, -1)
    if last_only:
        with scope("head"):
            h = h[jnp.arange(B), lengths - 1]
    out = (_finish(params, h, cfg), {"ckv": pool[0]})
    return out + (counts,) if stats else out


@partial(jax.jit, static_argnames=("cfg", "stats"))
def forward_decode(params: Params, cfg: LongcatFlashConfig, tokens,
                   positions, kv_cache: KVCache, block_tables, active=None,
                   stats: bool = False):
    """One decode step for every active row
    (``models/llama.forward_decode``'s contract). A row that is not
    active writes to page 0, attends to nothing and is routed to no
    expert; its logits mean nothing."""
    pool = [kv_cache["ckv"]]
    page_of, slot_of, seq_lens = decode_geometry(
        positions, block_tables, pool[0].shape[2], active)
    with scope("embed"):
        h = embed_lookup(params["embed"], tokens, jnp.float32)
    h, counts = _run(
        params, cfg, h, positions,
        _decode_attend(cfg, params["layers"], pool, block_tables, seq_lens,
                       page_of, slot_of),
        active)
    out = (_finish(params, h, cfg), {"ckv": pool[0]})
    return out + (counts,) if stats else out


@partial(jax.jit, static_argnames=("cfg", "stats"))
def forward_mixed(params: Params, cfg: LongcatFlashConfig, dec_tokens,
                  dec_positions, kv_cache: KVCache, dec_block_tables,
                  pf_tokens, pf_positions, pf_lengths, pf_starts,
                  pf_block_tables, dec_active=None, stats: bool = False):
    """The fused mixed step (``models/llama.forward_mixed``'s
    contract, the slices' tokens TIGHT and ``pf_starts`` with them): B
    decode rows one token and S prefill slices of up to T tokens in ONE
    traversal of the layers, the B rows FIRST and the slices' tight
    rows behind them, so what holds a token is one prefix: every
    projection and dense feed-forward runs over that prefix a tile of
    rows at a time (``ops/rows.live_rows``) and streams its matrices
    once a tile for both kinds of row; the routed layer skips a dead
    row by itself. The attention runs a layer's slices (cut out onto
    the (S, T) grid, laid back after) and its decode rows apart
    (disjoint pages). Returns (dec_logits (B, V), pf_logits (S, V),
    cache [, counts]): of a slice only its LAST valid position is
    projected."""
    B = dec_tokens.shape[0]
    S = pf_lengths.shape[0]
    N = pf_tokens.shape[0]
    T = N // S
    n_live, tile = pf_starts[S], row_tile(T)
    pool = [kv_cache["ckv"]]
    lp = params["layers"]
    page_of, slot_of, dec_seq_lens = decode_geometry(
        dec_positions, dec_block_tables, pool[0].shape[2], dec_active)
    pf_grid_pos, pf_seq_lens = grid_positions(pf_positions, pf_lengths,
                                              pf_starts, T)
    attend_p = _prefill_attend(cfg, lp, pool, pf_block_tables, pf_grid_pos,
                               pf_lengths, pf_seq_lens)
    attend_d = _decode_attend(cfg, lp, pool, dec_block_tables, dec_seq_lens,
                              page_of, slot_of)

    def attend(a, *qkv_rows):
        # The one part of a layer that takes the two kinds of row
        # apart: everything else runs them side by side, under the
        # module's name alone.
        with scope("slices"):
            o_p = attend_p(a, *(
                rows_to_grid(x, pf_starts, T, lead=B).reshape(
                    (N,) + x.shape[1:]) for x in qkv_rows)).reshape(S, T, -1)
            # The decode rows' write takes the pool in place: only
            # once the slices' attention has read it, or XLA copies
            # the whole pool to keep both (2 GB, twice a mixed step).
            o_p, pool[0] = jax.lax.optimization_barrier((o_p, pool[0]))
        with scope("decode_rows"):
            o_d = attend_d(a, *(x[:B] for x in qkv_rows))
        with scope("slices"):
            return grid_to_rows(
                o_p, pf_starts, jnp.concatenate(
                    [o_d, jnp.zeros((N, o_d.shape[1]), o_d.dtype)]), lead=B)

    live = jnp.concatenate(
        [(dec_active if dec_active is not None
          else jnp.ones((B,), jnp.bool_)), jnp.arange(N) < n_live])
    with scope("embed"):
        h = embed_lookup(params["embed"],
                         jnp.concatenate([dec_tokens, pf_tokens]),
                         jnp.float32)
    with scope("qkv"):
        cos, sin = rope_cos_sin(
            jnp.concatenate([dec_positions, pf_positions]),
            cfg.qk_rope_head_dim, cfg.rope_theta)
    counts = []
    for l in range(cfg.n_layers):
        h, st = _mixed_layer(
            params, cfg, l, h, cos, sin, attend, live,
            lambda fn, *rows: live_rows(fn, B + n_live, tile, *rows,
                                       lead=B))
        counts.append(st)
    counts = _sum_stats(counts)
    with scope("slices"), scope("head"):
        h_p = h[B + pf_starts[:S] + pf_lengths - 1]
    with scope("decode_rows"):
        dec_logits = _finish(params, h[:B], cfg)
    with scope("slices"):
        pf_logits = _finish(params, h_p, cfg)
    out = (dec_logits, pf_logits, {"ckv": pool[0]})
    return out + (counts,) if stats else out
