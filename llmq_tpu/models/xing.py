"""The ``xing4_0`` block (Xing4.0-29B-A4B) in pure JAX: DeepSeek-V3's
layer — multi-head LATENT attention over a latent page pool
(``models/latent.py``, with a low-rank query) and a routed feed-forward
with a shared expert (``ops/moe.py``; the leading layers dense) — around
a residual of ``hc_mult`` STREAMS mixed at every sub-layer by
manifold-constrained hyper-connections (``ops/hyper.py`` has the
equations; arXiv 2512.24880), and rotary positions scaled by YaRN in
DeepSeek-V3's reading (``ops/rope.YarnScaling``: the 64 rotary lanes by
``yarn_inv_freq``, the softmax scale times the temperature squared,
``models/latent.LatentDims.softmax_scale``).

A token's state between layers is ``X`` (hc_mult, hidden), float32. The
embedding is COPIED into every stream; a layer has two SITES, attention
and feed-forward, each ``u = H_pre X``, ``X' = H_res X + H_post^T
F(u)`` with ``F`` the sub-layer behind its own RMSNorm; after the last
layer held the streams are SUMMED, then the final norm and the head — a
pipeline stage run alone collapses its own output so. ``hc_mult`` 1 and
no ``rope_scaling`` is ``models/deepseek_v3.py``'s program to the bit
(``tests/test_xing.py``): the sites are then the plain residual, a
Python branch at trace time.

Why a module beside ``deepseek_v3.py`` and not a branch in it: that
family's mixed step keeps its slices on the (S, T) grid, and the sites'
float32 streams are 57 KB a token, read twice and written once a site —
they have to run over the rows that hold a token. ``forward_mixed``
here is ``models/mellum.py``'s shape: ONE tight stream of B decode rows
and the slices' tokens behind them, a layer's row-wise work in two
``ops/rows.live_rows`` passes (FRONT: the feed-forward site of the
layer before closes, the attention site opens, norm, q and the cache's
row; CLOSE: ``wo``, the attention site closes, the feed-forward site
opens, norm, router and the shared expert), the routed experts over the
live pairs (``ops/moe.routed_ffn(n_live=...)``: held as sorted, a token
gathering its k), and only the cache's write and the two attentions on
the grid — the slices' attention a slice at a time, each over its own key
blocks (``latent_prefill_attention_each``); the PREFILL attention's call
stands under the scope ``attn_full``, the name the families with two
kinds of layer give the attention that sees its whole context, so what
reads theirs deep in a document reads this one. ``mixed_live_rows``
counts by that rule.
Everything else — the parameter tree (plus the group ``hc``), the
latent pool, the counters (plus one: the worst ``|row sum - 1|`` of
``H_res`` x 1e6 of a pass, ``hc_row_sum_err``) — is ``deepseek_v3``'s,
imported. The multi-token-prediction layer of the published model is
neither held nor served (``n_nextn_served`` refuses). Int8 weights, an
int8 cache and a mesh are refused by name (``check_serving``).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from llmq_tpu.models import deepseek_v3 as ds
from llmq_tpu.models.deepseek_v3 import (  # noqa: F401 — the family surface
    init_row_state, kv_bytes_per_token, param_shapes,
    row_state_bytes_per_row, serving_config)
from llmq_tpu.models.latent import (  # noqa: F401
    decode_geometry, key_blocks_each, latent_decode_attention,
    latent_prefill_attention, latent_prefill_attention_each,
    latent_write_prefill, param_count, qkv, rope_table, routes, swiglu)
from llmq_tpu.ops import hyper
from llmq_tpu.ops.hyper import ATTN, FFN
from llmq_tpu.ops.moe import route, routed_ffn
from llmq_tpu.ops.norms import rms_norm
from llmq_tpu.ops.quant import embed_lookup
from llmq_tpu.ops.rope import YarnScaling
from llmq_tpu.ops.rows import (grid_positions, grid_to_rows, live_rows,
                               row_tile, rows_to_grid, tile_rows)
from llmq_tpu.utils.profiling import scope

Params = Dict[str, Any]
KVCache = Dict[str, jnp.ndarray]


@dataclass(frozen=True)
class XingConfig(ds.DeepseekV3Config):
    FAMILY: ClassVar[str] = "xing"
    name: str = "xing-tiny"
    q_lora_rank: Optional[int] = 48
    rope_theta: float = 10000.0
    #: ``rope_scaling`` (type yarn) as published; None: plain RoPE.
    rope_scaling: Optional[YarnScaling] = YarnScaling(
        factor=8.0, original_max_position=32, mscale=1.0, mscale_all_dim=1.0)
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    #: Multi-token-prediction layers asked to be SERVED: none can be.
    n_nextn_served: int = 0

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.hc_mult < 1:
            raise ValueError(f"model {self.name!r}: hc_mult {self.hc_mult}")
        if self.n_nextn_served:
            raise ValueError(
                f"model {self.name!r} (family xing): "
                f"num_nextn_predict_layers={self.n_nextn_served} asked to "
                f"be served, and no step of this program yields more than "
                f"one token a row; leave the layer out")


def xing_tiny(**kw) -> XingConfig:
    return replace(XingConfig(), **kw)


def xing4_0_29b_a4b(**kw) -> XingConfig:
    """XingChen-AGI/Xing4.0-29B-A4B at its published sizes
    (https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/
    config.json): 40 layers (the first two dense, SwiGLU 9,216), hidden
    3,584 in 4 streams, 32 heads of 128 + 64 over a latent of 512 with
    a query of rank 768, 64 routed experts of 1,024 with 4 a token and
    1 shared, sigmoid scores scaled 2, vocabulary 131,072 untied, RoPE
    theta 10,000 under YaRN x 64 from 4,096 (262,144 positions). 29.5 B
    parameters without its multi-token-prediction layer, 59 GB in bf16:
    one 16 GB chip serves a cut in depth (benchmark/configs/
    xing4.0-29b-a4b-bf16-pp7.json holds 6 layers with every width)."""
    return replace(XingConfig(
        name="xing4.0-29b-a4b", vocab_size=131072, dim=3584, n_layers=40,
        n_heads=32, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, q_lora_rank=768, ffn_dim=9216,
        moe_ffn_dim=1024, n_routed_experts=64, n_shared_experts=1,
        n_experts_per_tok=4, first_k_dense=2, routed_scaling_factor=2.0,
        norm_topk_prob=True, max_seq_len=262144, rope_theta=10000.0,
        rope_scaling=YarnScaling(factor=64.0, original_max_position=4096,
                                 beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                                 mscale_all_dim=1.0),
        norm_eps=1e-6, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
        hc_clamp=(-30.0, 30.0)), **kw)


MODEL_CONFIGS = {
    "xing-tiny": xing_tiny,
    "xing4.0-29b-a4b": xing4_0_29b_a4b,
}


# -- the family surface (models/__init__.py) -----------------------------------

def import_hf(model_dir: str, cfg: XingConfig, **kw) -> Params:
    raise ValueError(f"model {cfg.name!r} (family xing): no checkpoint "
                     f"importer is written; the weights are random")


def step_stats_layout(cfg: XingConfig) -> Dict[str, Any]:
    """``models/deepseek_v3.step_stats_layout``'s and, last, the worst
    ``|row sum - 1|`` of any site's ``H_res`` over the pass's live rows
    x 1e6 (``ops/hyper.row_sum_error``; the programs SUM a chunk's
    steps, so a reader divides by them)."""
    E = cfg.n_routed_experts
    return {**ds.step_stats_layout(cfg), "hc_row_sum_err": E + 2}


def step_stats_size(cfg: XingConfig) -> int:
    return cfg.n_routed_experts + 3


def check_serving(cfg: XingConfig, *, quantization: str = "",
                  kv_quantization: str = "", mesh: bool = False) -> None:
    """Refuse what is not written for this family, naming the setting."""
    what = None
    if quantization:
        what = f"model.quantization={quantization!r} (int8 experts)"
    elif kv_quantization:
        what = f"model.kv_quantization={kv_quantization!r} (an int8 latent)"
    elif mesh:
        what = ("executor.mesh (no partition rules for latents, experts or "
                "streams)")
    if what:
        raise ValueError(f"model {cfg.name!r} (family xing) does not "
                         f"support {what}; unset it")


def mixed_live_rows(tokens: int, batch: int, slices: int, width: int) -> int:
    """Rows ``forward_mixed``'s row-wise work — the sites among it —
    runs for ``tokens`` prompt tokens (``models/__init__.py``): the
    live tiles' rows, less the ``batch`` decode rows that lead them
    (``models/llama.mixed_live_rows``)."""
    return tile_rows(tokens, row_tile(width), slices * width, lead=batch)


def hc_rows_live(tokens: int, batch: int, slices: int, width: int) -> int:
    """Rows the sites of a mixed STEP run: its decode rows and the live
    tiles behind them (``engine.dispatch``'s ``hc_rows_live``)."""
    return batch + mixed_live_rows(tokens, batch, slices, width)


# -- parameters ---------------------------------------------------------------

def hc_shapes(cfg: XingConfig) -> Dict[str, tuple]:
    """The group ``hc``, apart from ``param_shapes``' (``deepseek_v3``'s
    own: bf16, drawn by fan in): ``ops/hyper.site_shapes`` stacked over
    (layer, site), float32; empty at ``hc_mult`` 1."""
    if cfg.hc_mult == 1:
        return {}
    return {k: (cfg.n_layers, 2) + shape
            for k, shape in hyper.site_shapes(cfg.hc_mult, cfg.dim).items()}


def assemble(cfg: XingConfig, drawn: Dict[str, Dict[str, Any]],
             hc: Optional[Dict[str, Any]] = None) -> Params:
    """``models/deepseek_v3.assemble`` plus ``hc``: ``hc_shapes``-shaped
    float32 arrays (None: the sites of :func:`hc_init`)."""
    params = ds.assemble(cfg, drawn)
    if cfg.hc_mult > 1:
        params["hc"] = hc_init(cfg) if hc is None else dict(hc)
    return params


def hc_init(cfg: XingConfig, key: Optional[jax.Array] = None) -> Params:
    """Sites as training starts them (the paper's): H_pre 1 / n, H_post
    1 and H_res the projection of a diagonal lead by their biases, with
    ``phi`` zero and ``alpha`` 0.01 where ``key`` is None — the three H
    are then constants; with a key ``phi`` is drawn N(0, 1 / fan_in)
    and ``alpha`` is 0.1, so every H depends on its token."""
    n, shapes = cfg.hc_mult, hc_shapes(cfg)
    lead = shapes["phi"][:2]
    bias = jnp.concatenate([
        jnp.full((n,), -jnp.log(n - 1.0)), jnp.zeros((n,)),
        (2.0 * jnp.eye(n)).reshape(-1)]).astype(jnp.float32)
    phi = (jnp.zeros(shapes["phi"], jnp.float32) if key is None else
           jax.random.normal(key, shapes["phi"], jnp.float32)
           * (n * cfg.dim) ** -0.5)
    return {"phi": phi,
            "alpha": jnp.full(shapes["alpha"],
                              0.01 if key is None else 0.1, jnp.float32),
            "bias": jnp.broadcast_to(bias, lead + bias.shape)}


def init_params(key: jax.Array, cfg: XingConfig) -> Params:
    """Random-init parameter tree: ``deepseek_v3``'s draws and sites
    that depend on the token (``hc_init`` with a key)."""
    k_hc, key = jax.random.split(key)
    drawn = ds.draw_groups(key, param_shapes(cfg), cfg.dtype,
                           cfg.n_routed_layers)
    return assemble(cfg, drawn, hc_init(cfg, k_hc) if cfg.hc_mult > 1
                    else None)


def init_params_quantized(key: jax.Array, cfg: XingConfig) -> Params:
    check_serving(cfg, quantization="int8")


def _hc_count(cfg: XingConfig) -> int:
    if cfg.hc_mult == 1:
        return 0
    return cfg.n_layers * 2 * hyper.site_param_count(cfg.hc_mult, cfg.dim)


def param_count_analytic(cfg: XingConfig) -> int:
    """Parameters HELD, from the configuration alone."""
    return ds.param_count_analytic(cfg) + _hc_count(cfg)


def active_param_count(cfg: XingConfig) -> int:
    return ds.active_param_count(cfg) + _hc_count(cfg)


def weight_bytes(cfg: XingConfig) -> int:
    return ds.weight_bytes(cfg) + 4 * _hc_count(cfg)


def init_kv_pages(cfg: XingConfig, num_pages: int, page_size: int,
                  dtype: Optional[Any] = None) -> KVCache:
    if dtype is not None and jnp.dtype(dtype) == jnp.int8:
        check_serving(cfg, kv_quantization="int8")
    return ds.init_latent_pool(cfg, cfg.n_layers, num_pages, page_size,
                               dtype)


# -- the sites ------------------------------------------------------------------

def _fan_out(h, cfg: XingConfig):
    """The embedding (R, C) into every stream: (R, n, C)."""
    return jnp.broadcast_to(h[:, None], (h.shape[0], cfg.hc_mult,
                                         h.shape[1]))


def _collapse(x):
    """The streams (R, n, C) summed: what the final norm reads."""
    return x[:, 0] if x.shape[1] == 1 else jnp.sum(x, axis=1)


def _open(params: Params, cfg: XingConfig, l: int, s: int, x):
    """Site ``s`` of layer ``l`` over the streams x (R, n, C): ``(u
    (R, C) the sub-layer's input, mix: what :func:`_shut` needs as a
    tuple of arrays with R leading rows (``live_rows`` carries it), err
    (R,): each row's |row sum - 1| of H_res)``. ``hc_mult`` 1: the
    stream itself and nothing to carry."""
    if cfg.hc_mult == 1:
        return x[:, 0], (), jnp.zeros((x.shape[0],), jnp.float32)
    hc = params["hc"]
    h_pre, h_post, h_res = hyper.project(
        x, hc["phi"][l, s], hc["alpha"][l, s], hc["bias"][l, s],
        iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps, clamp=cfg.hc_clamp,
        norm_eps=cfg.norm_eps)
    mix = (h_post, h_res.reshape(-1, h_res.shape[-1]).T)
    return hyper.read(x, h_pre), mix, hyper.row_sum_error(h_res)


def _shut(x, mix, y):
    """The site closes over the sub-layer's output y (R, C): X'."""
    if not mix:
        return x + y[:, None]
    h_post, h_res = mix
    n = x.shape[1]
    return hyper.write(x, h_res.T.reshape(n, n, -1), h_post, y)


def _err_count(errs, live) -> jnp.ndarray:
    """A pass's ``hc_row_sum_err``: the sites' per-row errors (each
    (R,)) over the live rows, the worst x 1e6 as (1,) int32."""
    worst = jnp.max(jnp.stack(errs), axis=0)
    if live is not None:
        worst = jnp.where(live, worst, 0.0)
    return jnp.minimum(jnp.max(worst) * 1e6, 2.0 ** 30).astype(
        jnp.int32)[None]


def _stats(cfg: XingConfig, counts, errs, live) -> jnp.ndarray:
    return jnp.concatenate([ds._sum_stats(cfg, counts),
                            _err_count(errs, live)])


def mixed_key_blocks(seq_lens, T: int, page_size: int, max_pages: int):
    """(visited, the table holds): the slices of a mixed step attend one
    at a time, each over its own context's key blocks
    (``models/latent.key_blocks_each``; ``models/__init__.py``)."""
    return key_blocks_each(seq_lens, T, page_size, max_pages)


# -- forward ------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "last_only", "stats"))
def forward_prefill(params: Params, cfg: XingConfig, tokens, positions,
                    lengths, kv_cache: KVCache, block_tables,
                    last_only: bool = False, stats: bool = False):
    """``models/deepseek_v3.forward_prefill``'s contract and returns,
    the residual in streams."""
    B, T = tokens.shape
    with scope("embed"):
        h = embed_lookup(params["embed"], tokens, jnp.float32)
    x = _fan_out(h.reshape(B * T, -1), cfg)
    with scope("qkv"):
        cos, sin = rope_table(cfg, positions)
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    seq_lens = jnp.max(jnp.where(valid, positions, -1), axis=1) + 1
    live = valid.reshape(-1)
    lp, pool, counts, errs = params["layers"], kv_cache["ckv"], [], []
    for l in range(cfg.n_layers):
        u, mix, err = _open(params, cfg, l, ATTN, x)
        errs.append(err)
        with scope("qkv"):
            xn = rms_norm(u.reshape(B, T, -1), lp["attn_norm"][l],
                          cfg.norm_eps).astype(cfg.dtype)
        q_nope, q_rope, row = qkv(cfg, lp, l, xn, cos, sin)
        pool = latent_write_prefill(pool, row, block_tables, positions,
                                    lengths, l)
        with scope("attn_full"):
            attn = latent_prefill_attention(
                cfg, lp, l, q_nope, q_rope, pool, block_tables, positions,
                seq_lens)
        with scope("attn_out"):
            y = jnp.dot(attn, lp["wo"][l]).reshape(B * T, -1)
        x = _shut(x, mix, y)
        u, mix, err = _open(params, cfg, l, FFN, x)
        errs.append(err)
        with scope("mlp"):
            xn = rms_norm(u, lp["mlp_norm"][l], cfg.norm_eps)
        y, st = ds._ffn(params, cfg, l, xn, live)
        counts.append(st)
        x = _shut(x, mix, y)
    h = _collapse(x).reshape(B, T, -1)
    if last_only:
        with scope("head"):
            h = h[jnp.arange(B), lengths - 1]
    out = (ds._finish(params, h, cfg), {"ckv": pool})
    return out + (_stats(cfg, counts, errs, live),) if stats else out


@partial(jax.jit, static_argnames=("cfg", "stats"))
def forward_decode(params: Params, cfg: XingConfig, tokens, positions,
                   kv_cache: KVCache, block_tables, active=None,
                   stats: bool = False):
    """One decode step for every active row
    (``models/deepseek_v3.forward_decode``'s contract)."""
    pool = kv_cache["ckv"]
    with scope("embed"):
        h = embed_lookup(params["embed"], tokens, jnp.float32)  # (B, D)
    x = _fan_out(h, cfg)
    with scope("qkv"):
        cos, sin = rope_table(cfg, positions[:, None])
    page_of, slot_of, seq_lens = decode_geometry(
        positions, block_tables, pool.shape[2], active)
    lp, counts, errs = params["layers"], [], []
    for l in range(cfg.n_layers):
        u, mix, err = _open(params, cfg, l, ATTN, x)
        errs.append(err)
        with scope("qkv"):
            xn = rms_norm(u, lp["attn_norm"][l],
                          cfg.norm_eps).astype(cfg.dtype)
        q_nope, q_rope, row = qkv(cfg, lp, l, xn[:, None], cos, sin)
        attn, pool = latent_decode_attention(
            cfg, lp, l, q_nope[:, 0], q_rope[:, 0], row[:, 0], pool,
            block_tables, seq_lens, page_of, slot_of)
        with scope("attn_out"):
            y = jnp.dot(attn, lp["wo"][l])
        x = _shut(x, mix, y)
        u, mix, err = _open(params, cfg, l, FFN, x)
        errs.append(err)
        with scope("mlp"):
            xn = rms_norm(u, lp["mlp_norm"][l], cfg.norm_eps)
        y, st = ds._ffn(params, cfg, l, xn, active)
        counts.append(st)
        x = _shut(x, mix, y)
    out = (ds._finish(params, _collapse(x), cfg), {"ckv": pool})
    return out + (_stats(cfg, counts, errs, active),) if stats else out


@partial(jax.jit, static_argnames=("cfg", "stats"))
def forward_mixed(params: Params, cfg: XingConfig, dec_tokens,
                  dec_positions, kv_cache: KVCache, dec_block_tables,
                  pf_tokens, pf_positions, pf_lengths, pf_starts,
                  pf_block_tables, dec_active=None, stats: bool = False):
    """The fused mixed step (``models/llama.forward_mixed``'s contract
    and layout of the rows): ONE tight stream of B + S T rows, the B
    decode rows leading, of which the first ``B + pf_starts[S]`` hold a
    token; the module's docstring has the two passes a layer. A row
    past the live prefix is never read by anyone: of a slice only its
    last valid row goes through the head, and a dead row is routed
    nowhere. Returns ``(dec_logits (B, V), pf_logits (S, V), cache
    [, counts])``."""
    B = dec_tokens.shape[0]
    S = pf_lengths.shape[0]
    N = pf_tokens.shape[0]
    T = N // S
    M, tile = B + N, row_tile(T)
    n_live = B + pf_starts[S]
    grid_pos, pf_seq_lens = grid_positions(pf_positions, pf_lengths,
                                           pf_starts, T)
    pool = kv_cache["ckv"]
    with scope("decode_rows"):
        with scope("embed"):
            h_d = embed_lookup(params["embed"], dec_tokens, jnp.float32)
        page_of, slot_of, dec_seq_lens = decode_geometry(
            dec_positions, dec_block_tables, pool.shape[2], dec_active)
    with scope("slices"), scope("embed"):
        h_p = embed_lookup(params["embed"], pf_tokens, jnp.float32)
    x = _fan_out(jnp.concatenate([h_d, h_p]), cfg)           # (M, n, C)
    with scope("qkv"):
        cos, sin = rope_table(
            cfg, jnp.concatenate([dec_positions, pf_positions])[:, None])
    live = jnp.concatenate(
        [dec_active if dec_active is not None else jnp.ones((B,), bool),
         jnp.ones((N,), bool)])
    alive = live & (jnp.arange(M) < n_live)
    lp, counts, errs = params["layers"], [], []
    pending: tuple = ()      # the feed-forward site still open: (y, *mix)

    def front(x, cos, sin, *pending, l):
        if pending:
            x = _shut(x, pending[1:], pending[0])
        u, mix, err = _open(params, cfg, l, ATTN, x)
        with scope("qkv"):
            xn = rms_norm(u, lp["attn_norm"][l],
                          cfg.norm_eps).astype(cfg.dtype)
        q_nope, q_rope, row = qkv(cfg, lp, l, xn[:, None], cos, sin)
        return (x, err, q_nope[:, 0], q_rope[:, 0], row[:, 0]) + mix

    def close(x, attn, *mix, l):
        with scope("attn_out"):
            y = jnp.dot(attn, lp["wo"][l])
        x = _shut(x, mix, y)
        u, mix, err = _open(params, cfg, l, FFN, x)
        with scope("mlp"):
            xf = rms_norm(u, lp["mlp_norm"][l], cfg.norm_eps)
            xn = xf.astype(cfg.dtype)
            if l < cfg.first_k_dense:
                d = params["dense"]
                return (x, err, swiglu(xn, d["w_gate"][l], d["w_up"][l],
                                       d["w_down"][l])) + mix
        m, i = params["moe"], l - cfg.first_k_dense
        experts, gates = route(
            xf, m["router"][i], m["router_bias"][i],
            top_k=cfg.n_experts_per_tok, scale=cfg.routed_scaling_factor,
            norm_topk=cfg.norm_topk_prob)
        with scope("mlp"):   # the shared expert, beside the routed ones
            shared = swiglu(xn, m["ws_gate"][i], m["ws_up"][i],
                            m["ws_down"][i])
        return (x, err, xn, experts, gates, shared) + mix

    for l in range(cfg.n_layers):
        x, err, q_nope, q_rope, row, *mix = live_rows(
            partial(front, l=l), n_live, tile, x, cos, sin, *pending, lead=B)
        errs.append(err)
        with scope("slices"):
            qn_p, qr_p, row_p = (rows_to_grid(a, pf_starts, T, lead=B)
                                 for a in (q_nope, q_rope, row))
            pool = latent_write_prefill(pool, row_p, pf_block_tables,
                                        grid_pos, pf_lengths, l)
            with scope("attn_full"):
                # one slice at a time: side by side sixteen slices each
                # ran the LONGEST context's key blocks, 800 (slice,
                # block) pairs a layer where their own are 150, over
                # 537 MB of float32 scores a block: the mixed step took
                # 1.2 s (PERF.md section 6, PR 58)
                attn_p = latent_prefill_attention_each(
                    cfg, lp, l, qn_p, qr_p, pool, pf_block_tables, grid_pos,
                    pf_seq_lens)
        with scope("decode_rows"):
            attn_d, pool = latent_decode_attention(
                cfg, lp, l, q_nope[:B], q_rope[:B], row[:B], pool,
                dec_block_tables, dec_seq_lens, page_of, slot_of)
        with scope("slices"):
            attn = grid_to_rows(
                attn_p, pf_starts,
                jnp.concatenate([attn_d, jnp.zeros((N,) + attn_d.shape[1:],
                                                   attn_d.dtype)]), lead=B)
        x, err, *rest = live_rows(partial(close, l=l), n_live, tile, x,
                                  attn, *mix, lead=B)
        errs.append(err)
        if l < cfg.first_k_dense:
            counts.append(None)
            pending = tuple(rest)
            continue
        xn, experts, gates, shared, *mix = rest
        i = l - cfg.first_k_dense
        y, st = routed_ffn(xn, experts, gates, params["moe"]["we_gate_up"][i],
                           params["moe"]["we_down"][i], live, n_live=n_live)
        counts.append(st)
        with scope("mlp"):
            pending = (y + shared,) + tuple(mix)
    # The last feed-forward site closes over the rows the head reads:
    # the decode rows, and of each slice its last valid token.
    with scope("head"):
        at = jnp.concatenate([jnp.arange(B),
                              B + pf_starts[:S] + pf_lengths - 1])
        x, *pending = (a[at] for a in (x,) + pending)
    h = _collapse(_shut(x, tuple(pending[1:]), pending[0]))
    with scope("decode_rows"):
        dec_logits = ds._finish(params, h[:B], cfg)
    with scope("slices"):
        pf_logits = ds._finish(params, h[B:], cfg)
    out = (dec_logits, pf_logits, {"ckv": pool})
    return out + (_stats(cfg, counts, errs, alive),) if stats else out
