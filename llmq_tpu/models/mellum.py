"""The JetBrains ``mellum`` block (Mellum 2) in pure JAX: grouped-query
attention whose layers are, in a published order, SLIDING-WINDOW layers
(each query sees its last ``sliding_window`` keys) or FULL layers, every
layer rotated — but BY A TABLE OF ITS KIND: the sliding layers by the
plain frequencies, the full layers by YaRN's (``ops/rope.yarn_inv_freq``)
with cos and sin multiplied by the published attention factor — a
per-head RMSNorm on q and k, one norm before each sublayer and nothing
after, and a softmax-routed feed-forward in EVERY layer (no dense layer,
no shared expert, no selection bias).

With ``t = layer_types[l]``::

    x  = rms(h; g_in)
    q  = rms_head(x Wq; g_q)   k = rms_head(x Wk; g_k)   v = x Wv
    q, k = rope_t(q, k)        # sliding: f_i; full: YaRN f'_i, * factor
    key s visible to query p:  s <= p, and sliding: s > p - W
    h  = h + softmax(q k^T / sqrt(hd)) v Wo
    y  = rms(h; g_mlp)
    s  = softmax(y Wr) over all E (float32); S = top-k of s;
    g  = s[S] / sum s[S]                                   # norm_topk_prob
    h  = h + sum_{e in S} g_e W_down_e(silu(W_gate_e y) * W_up_e y)
    logits = rms(h; g_final) W_head

The router is ``ops/moe.route(scoring="softmax")`` with a zero bias and
the experts ``ops/moe.routed_ffn`` over ALL experts held
(``models/deepseek_v3``'s form: one chip holds every expert of the
layers it holds).

**The cache is ``models/afmoe``'s**, imported: the full layers' K and V
in the page pool, a sliding layer's in a ring SLAB of pool-shaped pages
a batch row (``afmoe.bind_cache``, ``init_row_state``, ``_slab_table``,
the ``window`` argument of the two attention kernels), and a row's state
is rebuilt at a page boundary by the last window's pages before it
(``afmoe.row_tail`` and the two tail programs): what lets the prefix
cache adopt a hit for this family (``docs/prefix_cache.md`` "Tails").
The K and V in both caches are post-rotary at absolute positions, each
layer by its own table, so a page or a tail is valid exactly where its
tokens stood.

Float32 residual stream and router, bf16 products, every layer
unrolled. **The mixed step runs the rows that hold a token**
(``forward_mixed``, ``ops/rows.py``): ONE stream of B + S T rows, the
B decode rows leading and the slices' tokens TIGHT behind them, from
the door to the head. What is a row's own — the two norms, q / k / v
with the per-head norms and the rotation, ``wo`` and the residual, the
router — runs over the live prefix a tile of rows at a time
(``live_rows``: as many tiles as hold a token, read on the device), and
the routed experts multiply the live (token, expert) pairs alone, a
block of sorted pairs at a time (``ops/moe.routed_ffn(n_live=...)``: ONE
array of N k rows, in bfloat16, written and gathered only where pairs are
live). Only the K/V write and the two attentions see the (S, T) grid.
``mixed_live_rows`` says what ran. Int8 weights, an int8 cache and a
mesh are not written: each is refused by name (``check_serving``).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from llmq_tpu.models import afmoe
from llmq_tpu.models.afmoe import (  # noqa: F401 — the family surface
    FULL, IDLE_ROW_CONTEXT, SLIDING, attention_window, bind_cache,
    export_row_tail, import_row_tail, init_kv_pages, init_row_state,
    init_row_tails, kv_bytes_per_token, routes, row_state_bytes_per_row,
    row_tail)
from llmq_tpu.models.latent import draw_groups
from llmq_tpu.models.latent import prod as _prod
from llmq_tpu.ops.moe import pass_extras, route, routed_ffn
from llmq_tpu.ops.norms import rms_norm
from llmq_tpu.ops.rope import (apply_rope, rope_cos_sin,
                               rope_cos_sin_scaled, yarn_inv_freq)
from llmq_tpu.ops.rows import (grid_positions, grid_to_rows, live_rows,
                               row_tile, rows_to_grid, tile_rows)
from llmq_tpu.utils.profiling import scope

Params = Dict[str, Any]
KVCache = Dict[str, jnp.ndarray]
RowState = Dict[str, jnp.ndarray]


@dataclass(frozen=True)
class Yarn:
    """``rope_parameters.full_attention`` as published."""
    factor: float = 16.0
    original_max_position: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.2772588722239782      # 0.1 ln 16 + 1


@dataclass(frozen=True)
class MellumConfig:
    FAMILY: ClassVar[str] = "mellum"       # models/__init__.py
    name: str = "mellum-tiny"
    vocab_size: int = 512
    dim: int = 128
    #: The published ``layer_types``.
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 2
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 64
    sliding_window: int = 24
    moe_ffn_dim: int = 64                  # one expert's SwiGLU
    n_routed_experts: int = 16
    n_experts_per_tok: int = 4
    route_norm: bool = True                # norm_topk_prob
    #: ASSUMED (no key in the published config; the Qwen3-MoE lineage
    #: whose keys it carries normalises always): q and k RMS-normalised
    #: per head, each with a gain, before the rotary embedding.
    qk_norm: bool = True
    max_seq_len: int = 2048
    rope_theta: float = 500000.0
    #: The full layers' YaRN; None: they rotate plainly too.
    rope_full: Optional[Yarn] = Yarn(original_max_position=64)
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    pallas: bool = True
    pallas_batched_prefill: bool = False
    #: The cache's geometry (``afmoe.bind_cache``; 0: not bound yet).
    page_size: int = 0
    slab_pages: int = 0

    def __post_init__(self) -> None:
        if set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"model {self.name!r}: layer_types "
                             f"{sorted(set(self.layer_types))}")
        if not 0 < self.n_experts_per_tok <= self.n_routed_experts:
            raise ValueError(
                f"model {self.name!r}: {self.n_experts_per_tok} of "
                f"{self.n_routed_experts} experts a token")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_sliding(self) -> int:
        return self.layer_types.count(SLIDING)

    @property
    def n_full(self) -> int:
        return self.layer_types.count(FULL)

    def kind_index(self, l: int) -> int:
        """Layer ``l``'s index among the layers of its kind: the layer
        of its cache leaf."""
        return self.layer_types[:l].count(self.layer_types[l])


def mellum_tiny(**kw) -> MellumConfig:
    """CPU-test size: two periods of ``s s s f``, 16 experts with 4 a
    token, a window of 24, YaRN from an original length of 64."""
    return replace(MellumConfig(), **kw)


def mellum2_12b_a2_5b(**kw) -> MellumConfig:
    """JetBrains/Mellum2-12B-A2.5B-Instruct at its published sizes
    (https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json):
    28 layers, 3 sliding (window 1,024) then 1 full, 7 times; hidden
    2,304, 32 query heads over 4 KV heads of 128; every layer routed:
    64 experts of 896 with 8 a token (softmax over all, renormalised);
    vocabulary 98,304, untied head, RoPE theta 500,000 — YaRN (factor
    16 from 8,192) on the full layers — context 131,072. About 12 B
    parameters: one chip holds a pipeline stage
    (benchmark/configs/mellum2-12b-a2.5b-bf16.json: 12 layers)."""
    return replace(MellumConfig(
        name="mellum2-12b-a2.5b", vocab_size=98304, dim=2304,
        layer_types=(SLIDING, SLIDING, SLIDING, FULL) * 7, n_heads=32,
        n_kv_heads=4, head_dim=128, sliding_window=1024, moe_ffn_dim=896,
        n_routed_experts=64, n_experts_per_tok=8, route_norm=True,
        qk_norm=True, max_seq_len=131072, rope_theta=500000.0,
        rope_full=Yarn(), norm_eps=1e-6), **kw)


MODEL_CONFIGS = {
    "mellum-tiny": mellum_tiny,
    "mellum2-12b-a2.5b": mellum2_12b_a2_5b,
}


# -- the family surface (models/__init__.py) -----------------------------------

def serving_config(cfg: MellumConfig) -> MellumConfig:
    return replace(cfg, pallas_batched_prefill=True)


def check_serving(cfg: MellumConfig, *, quantization: str = "",
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
                "experts)")
    if what:
        raise ValueError(f"model {cfg.name!r} (family mellum) does not "
                         f"support {what}; unset it")


def import_hf(model_dir: str, cfg: MellumConfig, **kw) -> Params:
    raise ValueError(f"model {cfg.name!r} (family mellum): no checkpoint "
                     f"importer is written; the weights are random")


def step_stats_layout(cfg: MellumConfig) -> Dict[str, Any]:
    """``models/deepseek_v3.step_stats_layout``'s: the tokens each
    expert received, the experts that received any summed over the
    layers, and the routed layers run (every layer)."""
    E = cfg.n_routed_experts
    return {"load": (0, E), "touched": E, "runs": E + 1}


def step_stats_size(cfg: MellumConfig) -> int:
    return cfg.n_routed_experts + 2


def mixed_live_rows(tokens: int, batch: int, slices: int, width: int) -> int:
    """Rows ``forward_mixed``'s row-wise work runs for ``tokens`` prompt
    tokens (``models/__init__.py``): the live tiles' rows, less the
    ``batch`` decode rows that lead them
    (``models/llama.mixed_live_rows``)."""
    return tile_rows(tokens, row_tile(width), slices * width, lead=batch)


# -- parameters ---------------------------------------------------------------

def param_shapes(cfg: MellumConfig) -> Dict[str, Dict[str, tuple]]:
    """Leaf name -> (shape, fan_in) by group (init and the benchmark's
    builder follow it). ``layers``: the attention's matrices and the
    router stacked over the layers; ``experts``: a leaf OF ITS OWN a
    layer (``params["moe"]["we_gate_up"]`` is a tuple of them), gate
    and up side by side."""
    L, D, V, Fe = cfg.n_layers, cfg.dim, cfg.vocab_size, cfg.moe_ffn_dim
    H, G, hd, E = (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                   cfg.n_routed_experts)
    return {
        "layers": {"wq": ((L, D, H * hd), D), "wk": ((L, D, G * hd), D),
                   "wv": ((L, D, G * hd), D),
                   "wo": ((L, H * hd, D), H * hd),
                   "router": ((L, D, E), D)},
        "experts": {"we_gate_up": ((E, D, 2 * Fe), D),
                    "we_down": ((E, Fe, D), Fe)},
        "top": {"embed": ((V, D), D), "lm_head": ((D, V), D)},
    }


STREAM_NORMS = ("attn_norm", "mlp_norm")
HEAD_NORMS = ("q_norm", "k_norm")


def norm_leaves(cfg: MellumConfig) -> Params:
    """The tree's RMSNorm weights (ones): what a random init does not
    draw. ``q_norm`` / ``k_norm`` only with ``qk_norm``."""
    L = cfg.n_layers
    layers = {n: jnp.ones((L, cfg.dim), cfg.dtype) for n in STREAM_NORMS}
    if cfg.qk_norm:
        layers.update({n: jnp.ones((L, cfg.head_dim), cfg.dtype)
                       for n in HEAD_NORMS})
    return {"layers": layers, "final_norm": jnp.ones((cfg.dim,), cfg.dtype)}


def assemble(cfg: MellumConfig, drawn: Dict[str, Dict[str, Any]]) -> Params:
    """``param_shapes``-shaped groups of arrays (``experts``: a list of
    one array a layer under each name) + ``norm_leaves`` -> the tree."""
    fixed = norm_leaves(cfg)
    return {"embed": drawn["top"]["embed"],
            "lm_head": drawn["top"]["lm_head"],
            "final_norm": fixed["final_norm"],
            "layers": {**drawn["layers"], **fixed["layers"]},
            "moe": {k: tuple(v) for k, v in drawn["experts"].items()}}


def init_params(key: jax.Array, cfg: MellumConfig) -> Params:
    """Random-init parameter tree, N(0, 1 / fan_in) as the other
    families'."""
    return assemble(cfg, draw_groups(key, param_shapes(cfg), cfg.dtype,
                                     cfg.n_layers))


def init_params_quantized(key: jax.Array, cfg: MellumConfig) -> Params:
    check_serving(cfg, quantization="int8")


def param_count(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


def param_count_analytic(cfg: MellumConfig) -> int:
    n = sum(_prod(shape) * (cfg.n_layers if g == "experts" else 1)
            for g, leaves in param_shapes(cfg).items()
            for shape, _f in leaves.values())
    heads = len(HEAD_NORMS) * cfg.head_dim if cfg.qk_norm else 0
    return n + cfg.n_layers * (len(STREAM_NORMS) * cfg.dim + heads) + cfg.dim


def active_param_count(cfg: MellumConfig) -> int:
    """Parameters one token multiplies with: all but the experts it is
    not routed to."""
    idle = cfg.n_routed_experts - cfg.n_experts_per_tok
    return (param_count_analytic(cfg)
            - cfg.n_layers * idle * 3 * cfg.dim * cfg.moe_ffn_dim)


def weight_bytes(cfg: MellumConfig) -> int:
    return param_count_analytic(cfg) * jnp.dtype(cfg.dtype).itemsize


# -- the layer -----------------------------------------------------------------

def _normed(h, w, cfg: MellumConfig) -> jnp.ndarray:
    return rms_norm(h, w, cfg.norm_eps).astype(cfg.dtype)


def _embed(params: Params, cfg: MellumConfig, tokens) -> jnp.ndarray:
    with scope("embed"):
        return params["embed"][tokens].astype(jnp.float32)


def _head(params: Params, cfg: MellumConfig, h) -> jnp.ndarray:
    with scope("head"):
        return jnp.dot(_normed(h, params["final_norm"], cfg),
                       params["lm_head"]).astype(jnp.float32)


def rope_tables(cfg: MellumConfig, positions) -> Dict[str, tuple]:
    """``{kind: (cos, sin)}`` of ``positions``: the sliding layers' plain
    table and the full layers' (YaRN's frequencies, cos and sin times
    the attention factor; the plain one again without ``rope_full``).
    Made once a forward pass."""
    with scope("qkv"):
        plain = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
        y = cfg.rope_full
        if y is None:
            return {SLIDING: plain, FULL: plain}
        inv = yarn_inv_freq(
            cfg.head_dim, cfg.rope_theta, factor=y.factor,
            original_max_position=y.original_max_position,
            beta_fast=y.beta_fast, beta_slow=y.beta_slow)
        return {SLIDING: plain,
                FULL: rope_cos_sin_scaled(positions, inv,
                                          y.attention_factor)}


def _qkv(x, lp: Params, l: int, rope, cfg: MellumConfig):
    """Layer ``l``'s q (..., H, hd), k and v (..., G, hd) of the
    normalised rows ``x`` (..., D): q and k normalised per head
    (``qk_norm``) and rotated by the table of the layer's kind."""
    with scope("qkv"):
        q, k, v = (jnp.dot(x, lp[w][l]).reshape(x.shape[:-1]
                                                + (-1, cfg.head_dim))
                   for w in ("wq", "wk", "wv"))
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"][l], cfg.norm_eps)
            k = rms_norm(k, lp["k_norm"][l], cfg.norm_eps)
        cos, sin = rope[cfg.layer_types[l]]
        return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def _attn_close(h, attn, lp: Params, l: int, cfg: MellumConfig):
    with scope("attn_out"):
        a = attn.reshape(attn.shape[:-2] + (-1,)).astype(cfg.dtype)
        return h + jnp.dot(a, lp["wo"][l]).astype(jnp.float32)


def _ffn_in(lp: Params, cfg: MellumConfig, l: int, h):
    """What layer ``l``'s experts read of the rows h (N, D): the normed
    rows (N, D) in ``cfg.dtype``, and the router's choice of the
    float32 ones, experts (N, k) and gates (N, k)."""
    with scope("mlp"):
        yf = rms_norm(h, lp["mlp_norm"][l], cfg.norm_eps)
        y = yf.astype(cfg.dtype)
    return (y,) + route(
        yf, lp["router"][l],
        jnp.zeros((cfg.n_routed_experts,), jnp.float32),
        top_k=cfg.n_experts_per_tok, scale=1.0, norm_topk=cfg.route_norm,
        scoring="softmax")


def _ffn_out(params: Params, l: int, h, y, experts, gates, live,
             n_live=None):
    """h plus layer ``l``'s experts' weighted results for the normed
    rows y (``n_live``: ``ops/moe.routed_ffn``'s). Returns (h',
    stats)."""
    m = params["moe"]
    f, st = routed_ffn(y, experts, gates, m["we_gate_up"][l],
                       m["we_down"][l], live, n_live=n_live)
    with scope("mlp"):
        return h + f.astype(jnp.float32), st


def _ffn(params: Params, cfg: MellumConfig, l: int, h, live):
    """Layer ``l``'s routed feed-forward over the stream's rows h
    (N, D). Returns (h', stats, experts (N, k))."""
    y, experts, gates = _ffn_in(params["layers"], cfg, l, h)
    h, st = _ffn_out(params, l, h, y, experts, gates, live)
    return h, st, experts


def _extras(cfg: MellumConfig, per_layer, stats: bool) -> tuple:
    return pass_extras(per_layer, cfg.n_routed_experts + 1, stats, False)


# -- forward ------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "last_only", "stats"))
def forward_prefill(params: Params, cfg: MellumConfig, tokens: jnp.ndarray,
                    positions: jnp.ndarray, lengths: jnp.ndarray,
                    kv_cache: KVCache, block_tables: jnp.ndarray,
                    last_only: bool = False, stats: bool = False,
                    row_state: Optional[RowState] = None,
                    rows: Optional[jnp.ndarray] = None):
    """``models/afmoe.forward_prefill``'s signature, conventions and
    returns: ``(logits, cache, row_state [, counts])``."""
    B, T = tokens.shape
    row_state, rows = afmoe._own_rows(cfg, B, kv_cache, row_state, rows)
    h = _embed(params, cfg, tokens)
    rope = rope_tables(cfg, positions)
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    seq_lens = jnp.max(jnp.where(valid, positions, -1), axis=1) + 1
    tables = {FULL: block_tables,
              SLIDING: afmoe._slab_table(rows, cfg, row_state,
                                         block_tables.shape[1])}
    lp, per_layer = params["layers"], []
    for l in range(cfg.n_layers):
        with scope("qkv"):
            x = _normed(h, lp["attn_norm"][l], cfg)
        q, k, v = _qkv(x, lp, l, rope, cfg)
        attn, kv_cache, row_state = afmoe._prefill_attn(
            cfg, l, q, k, v, kv_cache, row_state, tables, positions, lengths,
            seq_lens)
        h = _attn_close(h, attn, lp, l, cfg)
        h, st, ex = _ffn(params, cfg, l, h.reshape(B * T, -1),
                         valid.reshape(-1))
        h = h.reshape(B, T, -1)
        per_layer.append((st, ex))
    if last_only:
        with scope("head"):
            h = h[jnp.arange(B), lengths - 1]
    return ((_head(params, cfg, h), kv_cache, row_state)
            + _extras(cfg, per_layer, stats))


@partial(jax.jit, static_argnames=("cfg", "stats"))
def forward_decode(params: Params, cfg: MellumConfig, tokens: jnp.ndarray,
                   positions: jnp.ndarray, kv_cache: KVCache,
                   block_tables: jnp.ndarray,
                   active: Optional[jnp.ndarray] = None,
                   stats: bool = False,
                   row_state: Optional[RowState] = None):
    """One decode step for every active row
    (``models/afmoe.forward_decode``'s contract). Returns ``(logits
    (B, V), cache, row_state [, counts])``."""
    B = tokens.shape[0]
    row_state, _ = afmoe._own_rows(cfg, B, kv_cache, row_state, None)
    h = _embed(params, cfg, tokens)
    rope = rope_tables(cfg, positions)
    geom = afmoe._decode_geometry(cfg, positions, block_tables, active,
                                  kv_cache, row_state)
    lp, per_layer = params["layers"], []
    for l in range(cfg.n_layers):
        with scope("qkv"):
            x = _normed(h, lp["attn_norm"][l], cfg)
        q, k, v = _qkv(x, lp, l, rope, cfg)
        attn, kv_cache, row_state = afmoe._decode_attn(
            cfg, l, q, k, v, kv_cache, row_state, geom)
        h = _attn_close(h, attn, lp, l, cfg)
        h, st, ex = _ffn(params, cfg, l, h, geom[4])
        per_layer.append((st, ex))
    return ((_head(params, cfg, h), kv_cache, row_state)
            + _extras(cfg, per_layer, stats))


@partial(jax.jit, static_argnames=("cfg", "stats"))
def forward_mixed(params: Params, cfg: MellumConfig, dec_tokens: jnp.ndarray,
                  dec_positions: jnp.ndarray, kv_cache: KVCache,
                  dec_block_tables: jnp.ndarray, pf_tokens: jnp.ndarray,
                  pf_positions: jnp.ndarray, pf_lengths: jnp.ndarray,
                  pf_starts: jnp.ndarray, pf_block_tables: jnp.ndarray,
                  dec_active: Optional[jnp.ndarray] = None,
                  stats: bool = False,
                  row_state: Optional[RowState] = None,
                  pf_rows: Optional[jnp.ndarray] = None):
    """The fused mixed step (``models/llama.forward_mixed``'s contract
    and layout of the rows, ``models/afmoe.forward_mixed``'s caches and
    order). ONE stream h (B + S T, D): the B decode rows LEAD, the
    slices' tokens lie TIGHT behind them as they were handed over
    (slice ``s`` the ``pf_lengths[s]`` rows from ``B + pf_starts[s]``),
    and the first ``B + pf_starts[S]`` rows are all that hold a token.
    A layer's FRONT (the attention's norm, q / k / v, the per-head
    norms, the rotation by the layer kind's table, made once for the
    joined positions) and its CLOSE (``wo``, the residual, the
    feed-forward's norm, the router's float32 scores and choice) run
    over that prefix a tile of rows at a time (``ops/rows.live_rows``),
    and the experts over the live (token, expert) pairs
    (``routed_ffn(n_live=...)``). Between front and close the slices'
    q, k, v go to the (S, T) grid, are written and attend there; the
    barrier; then the decode rows' — the first B — write in place and
    attend; the two outputs are laid back into one buffer. A row past
    the live prefix is never read by anyone: of a slice only its last
    valid row goes through the head, and a dead row is routed nowhere.
    Returns ``(dec_logits (B, V), pf_logits (S, V), cache, row_state
    [, counts])``."""
    B = dec_tokens.shape[0]
    S = pf_lengths.shape[0]
    N = pf_tokens.shape[0]
    T = N // S
    tile = row_tile(T)
    row_state, _ = afmoe._own_rows(cfg, B, kv_cache, row_state, None)
    if pf_rows is None:
        pf_rows = jnp.full((S,), B, jnp.int32)
    n_live = B + pf_starts[S]
    grid_pos, pf_seq_lens = grid_positions(pf_positions, pf_lengths,
                                           pf_starts, T)
    with scope("decode_rows"):
        h_d = _embed(params, cfg, dec_tokens)
        geom = afmoe._decode_geometry(cfg, dec_positions, dec_block_tables,
                                      dec_active, kv_cache, row_state)
    with scope("slices"):
        h_p = _embed(params, cfg, pf_tokens)
        pf_tables = {FULL: pf_block_tables,
                     SLIDING: afmoe._slab_table(pf_rows, cfg, row_state,
                                                pf_block_tables.shape[1])}
    h = jnp.concatenate([h_d, h_p])                         # (B + N, D)
    rope = rope_tables(cfg, jnp.concatenate([dec_positions, pf_positions]))
    live = jnp.concatenate([geom[4], jnp.ones((N,), bool)])
    lp, per_layer = params["layers"], []
    for l, kind in enumerate(cfg.layer_types):
        def front(h, cos, sin, l=l, kind=kind):
            with scope("qkv"):
                x = _normed(h, lp["attn_norm"][l], cfg)
            return _qkv(x, lp, l, {kind: (cos, sin)}, cfg)

        def close(h, attn, l=l):
            h = _attn_close(h, attn, lp, l, cfg)
            return (h,) + _ffn_in(lp, cfg, l, h)

        q, k, v = live_rows(front, n_live, tile, h, *rope[kind], lead=B)
        with scope("slices"):
            q_p, k_p, v_p = (rows_to_grid(x, pf_starts, T, lead=B)
                             for x in (q, k, v))
            attn_p, kv_cache, row_state = afmoe._prefill_attn(
                cfg, l, q_p, k_p, v_p, kv_cache, row_state, pf_tables,
                grid_pos, pf_lengths, pf_seq_lens)
            # The decode rows' write takes the pools in place: only
            # once the slices' attention has read them (models/afmoe).
            attn_p, kv_cache, row_state = jax.lax.optimization_barrier(
                (attn_p, kv_cache, row_state))
        with scope("decode_rows"):
            attn_d, kv_cache, row_state = afmoe._decode_attn(
                cfg, l, q[:B], k[:B], v[:B], kv_cache, row_state, geom)
        with scope("slices"):
            attn = grid_to_rows(
                attn_p, pf_starts,
                jnp.concatenate([attn_d, jnp.zeros((N,) + attn_d.shape[1:],
                                                   attn_d.dtype)]), lead=B)
        h, y, experts, gates = live_rows(close, n_live, tile, h, attn,
                                         lead=B)
        h, st = _ffn_out(params, l, h, y, experts, gates, live,
                         n_live=n_live)
        per_layer.append((st, experts))
    with scope("slices"):
        with scope("head"):
            h_p = h[B + pf_starts[:S] + pf_lengths - 1]
        pf_logits = _head(params, cfg, h_p)
    with scope("decode_rows"):
        dec_logits = _head(params, cfg, h[:B])
    return ((dec_logits, pf_logits, kv_cache, row_state)
            + _extras(cfg, per_layer, stats))
