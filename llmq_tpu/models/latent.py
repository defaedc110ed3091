"""Multi-head LATENT attention over a latent page pool: what the
families that have it share (``models/deepseek_v3.py``,
``models/longcat_flash.py``, ``models/ling_hybrid.py``,
``models/xing.py``). The equations and the pool's layout are
in ``models/deepseek_v3.py``'s docstring; here they are written once.

An attention is addressed by ONE index ``l``: its slice of the stacked
attention leaves ``lp`` AND its layer of the pool. A family with one
attention a layer passes the layer; one with two a layer stacks both
and its pool's leading axis is twice its layers.

Three things a configuration may add to DeepSeek-V3's attention, all
written here and nowhere else:

- ``q_lora_rank``: a low-rank query, c_q = RMSNorm(x W_qa), q = c_q
  W_qb (leaves ``wq_a``, ``q_norm``, ``wq_b`` in place of ``wq``);
- ``mla_scale_q_lora`` / ``mla_scale_kv_lora``: q is multiplied by s_q
  = sqrt(hidden / q_lora_rank) and [k^nope ; v] by s_kv = sqrt(hidden /
  kv_lora_rank) (the RoPE key is not). s_kv never touches the cached
  row: it is folded into the query's latent part and into the output,
  in float32, so the pool holds the same ``[c | k^rope | 0]`` row
  whatever the scales;
- ``rope_scaling`` (``ops/rope.YarnScaling``, DeepSeek-V3's reading of
  a ``yarn`` block): the rotary lanes turn by YaRN's frequencies
  (``rope_table``) and a score's scale is no longer ``qk_head_dim **
  -0.5`` alone: ``LatentDims.softmax_scale`` is the ONE place that
  computes it, read by the absorbed decode (folded into its query; the
  kernel takes the query pre-scaled) and by the expanded prefill alike,
  so the temperature cannot reach one and miss the other.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from llmq_tpu.ops.norms import rms_norm
from llmq_tpu.ops.rope import (apply_rope, rope_cos_sin, rope_cos_sin_scaled,
                               yarn_inv_freq, yarn_mscale)
from llmq_tpu.utils.profiling import scope

Params = Dict[str, Any]
NEG = -1e30


class LatentDims:
    """What the functions below read of a config beside its fields
    (``dim``, ``n_heads``, ``kv_lora_rank``, ``q_lora_rank``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
    ``mla_scale_q_lora``, ``mla_scale_kv_lora``, ``norm_eps``,
    ``dtype``, and where it has them ``rope_theta`` and
    ``rope_scaling``, an ``ops/rope.YarnScaling``): a mixin for the
    families' frozen dataclasses."""

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Lanes of one cached row: the latent and the RoPE key, rounded
        up to whole 128-lane tiles."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def softmax_scale(self) -> float:
        """What a score is multiplied by before the softmax, computed
        HERE and nowhere else (the absorbed decode folds it into its
        query, the expanded prefill multiplies its scores):
        ``qk_head_dim ** -0.5``, times YaRN's temperature squared where
        the configuration scales its positions (DeepSeek-V3's reading
        of ``rope_scaling``: 2.00474 at factor 64)."""
        y = getattr(self, "rope_scaling", None)
        m = 1.0 if y is None else yarn_mscale(y.factor, y.mscale_all_dim)
        return self.qk_head_dim ** -0.5 * m * m

    @property
    def q_scale(self) -> float:
        if self.mla_scale_q_lora and self.q_lora_rank:
            return (self.dim / self.q_lora_rank) ** 0.5
        return 1.0

    @property
    def kv_scale(self) -> float:
        if self.mla_scale_kv_lora:
            return (self.dim / self.kv_lora_rank) ** 0.5
        return 1.0


def attn_param_shapes(cfg, n: int) -> Dict[str, tuple]:
    """Leaf name -> (shape, fan_in) of ``n`` stacked attentions'
    matrices."""
    D, H, r = cfg.dim, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rq = cfg.q_lora_rank
    q = ({"wq": ((n, D, H * (dn + dr)), D)} if not rq else
         {"wq_a": ((n, D, rq), D), "wq_b": ((n, rq, H * (dn + dr)), rq)})
    return {**q, "wkv_a": ((n, D, r + dr), D),
            "wkv_b": ((n, r, H * (dn + dv)), r),
            "wo": ((n, H * dv, D), H * dv)}


def attn_norm_leaves(cfg, n: int) -> Params:
    """The RMSNorm weights (ones) inside ``n`` stacked attentions."""
    out = {"kv_norm": jnp.ones((n, cfg.kv_lora_rank), cfg.dtype)}
    if cfg.q_lora_rank:
        out["q_norm"] = jnp.ones((n, cfg.q_lora_rank), cfg.dtype)
    return out


def attn_norm_count(cfg) -> int:
    """Parameters of ``attn_norm_leaves`` for one attention."""
    return cfg.kv_lora_rank + (cfg.q_lora_rank or 0)


def init_latent_pool(cfg, n_attn: int, num_pages: int, page_size: int,
                     dtype: Optional[Any] = None) -> Dict[str, jnp.ndarray]:
    """The latent page pool: ONE leaf ``"ckv"`` ``(n_attn, P,
    page_size, latent_width)``, page 0 reserved as in every pool of
    this repo."""
    return {"ckv": jnp.zeros((n_attn, num_pages, page_size,
                              cfg.latent_width), dtype or cfg.dtype)}


# -- what the families' parameter trees share ---------------------------------

def swiglu(x, w_gate, w_up, w_down):
    g = jnp.dot(x, w_gate)
    return jnp.dot(jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)
                   * jnp.dot(x, w_up), w_down)


def prod(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def param_count(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


def draw_groups(key: jax.Array, shapes: Dict[str, Dict[str, tuple]], dtype,
                n_expert_leaves: int) -> Dict[str, Dict[str, Any]]:
    """A family's ``param_shapes`` drawn N(0, 1 / fan_in) as the Llama
    block's: group -> leaf -> array, and of the group ``experts`` a
    LIST of ``n_expert_leaves`` arrays under each name (a leaf a routed
    layer). What the family's ``assemble`` takes."""
    keys = iter(jax.random.split(key, sum(len(g) for g in shapes.values())))

    def draw(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    drawn = {g: {name: draw(next(keys), shape, fan_in)
                 for name, (shape, fan_in) in leaves.items()}
             for g, leaves in shapes.items() if g != "experts"}
    drawn["experts"] = {
        name: [draw(k, shape, fan_in)
               for k in jax.random.split(next(keys), n_expert_leaves)]
        for name, (shape, fan_in) in shapes["experts"].items()}
    return drawn


# -- kernel routes ------------------------------------------------------------

def _route(cfg, page_size: int):
    """(use the latent kernels, interpret) at this geometry: the
    shared LLMQ_PALLAS policy, plus what the kernels need of the
    shapes."""
    from llmq_tpu.ops.attention import _kernel_route
    ok = cfg.kv_lora_rank % 128 == 0 and page_size % 8 == 0
    return _kernel_route(cfg.latent_width, extra_ok=ok)


def routes(cfg, cache, *, batch: int, page_size: int, max_pages: int,
           decode: bool = False, prefill_rows: int = 0) -> Dict[str, str]:
    """Which implementation each attention op of one serving program
    takes (``ops/attention.kernel_routes``'s form): the latent decode
    kernels with their plans (the rows of its page a decode row's write
    moves: its sublane tile, or the page where that is no whole tiles),
    and XLA for prefill."""
    from llmq_tpu.ops.pallas.latent_decode import (
        pages_per_chunk, write_rows)
    out: Dict[str, str] = {}
    if prefill_rows:
        out["prefill_write"] = out["prefill_attention"] = "xla"
    if decode:
        use, interp = _route(cfg, page_size)
        tag = f"pallas{'-interpret' if interp else ''}:"
        chunk = pages_per_chunk(page_size, max_pages) * page_size
        out["decode_write"] = "xla"
        if use:
            rows = write_rows(cache["ckv"])
            out["decode_write"] = (
                f"{tag}_latent_write_kernel("
                f"{'tile' if rows < page_size else 'page'}_rows={rows})")
        out["decode_attention"] = (
            f"{tag}_latent_decode_kernel(rows=1,chunk_tokens={chunk})"
            if use else "xla")
    return out


def _jit_latent(name: str):
    from llmq_tpu.ops.attention import _kernel_jit

    def make():
        from llmq_tpu.ops.pallas import latent_decode
        if name == "latent_write":
            return jax.jit(latent_decode.latent_write_pallas,
                           static_argnames=("interpret",))
        return jax.jit(latent_decode.latent_decode_attention_pallas,
                       static_argnames=("rank", "interpret"))
    return _kernel_jit(name, make)


# -- attention ----------------------------------------------------------------

def rope_table(cfg, positions):
    """(cos, sin) of the ``qk_rope_head_dim`` rotary lanes at
    ``positions`` (..., T): the plain table, or YaRN's frequencies
    where the configuration has a ``rope_scaling`` (cos and sin times
    its attention factor, 1 where ``mscale`` equals ``mscale_all_dim``:
    the temperature is then all in ``softmax_scale``)."""
    y = getattr(cfg, "rope_scaling", None)
    if y is None:
        return rope_cos_sin(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
    inv = yarn_inv_freq(
        cfg.qk_rope_head_dim, cfg.rope_theta, factor=y.factor,
        original_max_position=y.original_max_position,
        beta_fast=y.beta_fast, beta_slow=y.beta_slow)
    return rope_cos_sin_scaled(positions, inv, y.attention_factor)


def qkv(cfg, lp: Params, l: int, x, cos, sin):
    """x (..., T, D) normed -> q_nope (..., T, H, dn), q_rope
    (..., T, H, dr) rotated, row (..., T, W): the cache's row. Both
    halves of q carry s_q; the row carries no scale."""
    r, dn, dr = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with scope("qkv"):
        if cfg.q_lora_rank:
            c_q = rms_norm(jnp.dot(x, lp["wq_a"][l]), lp["q_norm"][l],
                           cfg.norm_eps)
            q = jnp.dot(c_q, lp["wq_b"][l])
        else:
            q = jnp.dot(x, lp["wq"][l])
        if cfg.q_scale != 1.0:
            q = (q.astype(jnp.float32) * cfg.q_scale).astype(q.dtype)
        q = q.reshape(x.shape[:-1] + (cfg.n_heads, dn + dr))
        q_rope = apply_rope(q[..., dn:], cos, sin)
        kva = jnp.dot(x, lp["wkv_a"][l])
        c = rms_norm(kva[..., :r], lp["kv_norm"][l], cfg.norm_eps)
        k_rope = apply_rope(kva[..., None, r:], cos, sin)[..., 0, :]
        pad = jnp.zeros(x.shape[:-1] + (cfg.latent_width - r - dr,),
                        c.dtype)
        return (q[..., :dn], q_rope,
                jnp.concatenate([c, k_rope, pad], axis=-1))


def wkv_b(cfg, lp: Params, l: int):
    w = lp["wkv_b"][l].reshape(cfg.kv_lora_rank, cfg.n_heads,
                               cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def decode_geometry(positions, block_tables, page_size, active):
    """(page, slot, context length) of each decode row's new token; a
    row that is not active writes to page 0 and attends to nothing."""
    B = positions.shape[0]
    page_of = block_tables[jnp.arange(B), positions // page_size]
    seq_lens = positions + 1
    if active is not None:
        page_of = jnp.where(active, page_of, 0)
        seq_lens = jnp.where(active, seq_lens, 0)
    return page_of, positions % page_size, seq_lens


def latent_decode_attention(cfg, lp: Params, l: int, q_nope, q_rope, row,
                            pool, block_tables, seq_lens, page_of, slot_of):
    """One decode step's attention ``l`` in the ABSORBED form: write
    each row's new cache row, then attend over the cached rows.
    q_nope (B, H, dn), q_rope (B, H, dr), row (B, W); ``seq_lens`` 0
    marks a row that is not live (its output is 0, its write went to
    page 0). Returns (o (B, H * dv), pool)."""
    B, r = q_nope.shape[0], cfg.kv_lora_rank
    with scope("qkv"):       # the query through wkv_b's key half
        wk, wv = wkv_b(cfg, lp, l)
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, wk)
        pad = jnp.zeros((B, cfg.n_heads,
                         cfg.latent_width - r - cfg.qk_rope_head_dim),
                        q_lat.dtype)
        scale, s_kv = cfg.softmax_scale, cfg.kv_scale
        if s_kv != 1.0:
            q_lat = q_lat.astype(jnp.float32) * s_kv
            q_rope, pad = (q_rope.astype(jnp.float32),
                           pad.astype(jnp.float32))
        q_cat = (jnp.concatenate([q_lat, q_rope, pad], axis=-1)
                 .astype(jnp.float32) * scale).astype(pool.dtype)
    use, interp = _route(cfg, pool.shape[2])
    if use:
        with scope("kv_write"):
            pool = _jit_latent("latent_write")(
                pool, row, page_of, slot_of, jnp.int32(l),
                interpret=interp)
        with scope("attn"):
            o_lat = _jit_latent("latent_decode")(
                q_cat, pool, block_tables, seq_lens, jnp.int32(l), rank=r,
                interpret=interp)
    else:
        with scope("kv_write"):
            pool = pool.at[l, page_of, slot_of].set(row.astype(pool.dtype))
        with scope("attn"):
            rows = pool[l][block_tables].reshape(B, -1, pool.shape[-1])
            s = jnp.einsum("bhw,bsw->bhs", q_cat, rows,
                           preferred_element_type=jnp.float32)
            live = (jnp.arange(rows.shape[1])[None, :]
                    < seq_lens[:, None])[:, None, :]
            p = jnp.where(live,
                          jax.nn.softmax(jnp.where(live, s, NEG), -1), 0.0)
            o_lat = jnp.einsum("bhs,bsr->bhr", p.astype(rows.dtype),
                               rows[..., :r],
                               preferred_element_type=jnp.float32)
    with scope("attn_out"):  # the values out of wkv_b's value half
        if s_kv != 1.0:
            o_lat = o_lat.astype(jnp.float32) * s_kv
        o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(cfg.dtype), wv)
        return o.reshape(B, -1), pool


def latent_write_prefill(pool, rows, block_tables, positions, lengths,
                         l: int):
    """Write the rows of B contiguous slices (rows (B, T, W), the first
    ``lengths`` of each valid, starting at ``positions[:, 0]``) into
    layer ``l`` of the pool, a PAGE at a time: every page a slice
    touches is read, merged and written once (an XLA scatter pays by
    the index, so by the page here and not by the token). Pages no
    valid token touches go to reserved page 0."""
    with scope("kv_write"):
        B, T, W = rows.shape
        ps, mp = pool.shape[2], block_tables.shape[1]
        n_pages = -(-T // ps) + 1
        p0 = positions[:, 0]
        src = jnp.arange(n_pages * ps)[None, :] - (p0 % ps)[:, None]
        valid = (src >= 0) & (src < lengths[:, None])          # (B, NP*ps)
        buf = jnp.take_along_axis(rows, jnp.clip(src, 0, T - 1)[..., None],
                                  axis=1)
        idx = (p0 // ps)[:, None] + jnp.arange(n_pages)[None, :]
        pages = jnp.take_along_axis(block_tables, jnp.clip(idx, 0, mp - 1),
                                    axis=1)
        valid = valid.reshape(B, n_pages, ps)
        pages = jnp.where(valid.any(-1) & (idx < mp), pages, 0)
        merged = jnp.where(valid[..., None],
                           buf.reshape(B, n_pages, ps, W).astype(pool.dtype),
                           pool[l, pages])
        return pool.at[l, pages.reshape(-1)].set(
            merged.reshape(B * n_pages, ps, W))


def prefill_key_blocks(seq_lens, T: int, page_size: int, max_pages: int):
    """The prefill attention's key blocks for rows attending SIDE BY
    SIDE: (pages a block, blocks the loop visits, blocks the table
    holds). A block is the whole pages that hold a slice of T tokens
    (512 tokens = 4 pages of 128 where a slice is 512 wide), so a fresh
    prompt's context is ONE block, and never more than the table; the
    loop runs to the end of the longest row's context. ``seq_lens`` a
    device array inside a program or a NumPy one on the host: the
    executor counts by the rule the program runs by."""
    pages = max(1, min(-(-T // page_size), max_pages))
    tokens = pages * page_size
    return (pages, (seq_lens.max() + tokens - 1) // tokens,
            -(-max_pages // pages))


def latent_prefill_attention(cfg, lp: Params, l: int, q_nope, q_rope, pool,
                             block_tables, positions, seq_lens):
    """Prefill attention ``l``, UNABSORBED, under XLA, over the LIVE
    key blocks of its rows' block-table windows (the new tokens already
    written), causal by absolute position. q_* (B, T, H, .).
    Returns (B, T, H * dv).

    One loop body, its trip count read from ``seq_lens``
    (``prefill_key_blocks``): block ``j`` gathers its pages of every
    row, expands K and V from their latents, scores them in float32
    (the nope part times s_kv, plus the RoPE part, times the head
    scale), masks by position and ``seq_lens`` and folds them into a
    running maximum, sum and float32 accumulator (the online softmax);
    one division at the end. The scores that exist at a time are
    (B, H, T, block); a block the loop does not visit is past every
    context: all mask. Block 0 holds key 0, which every position of a
    live row sees, so from there on the running maximum is a real score
    and a wholly masked (row, block) adds exp(-1e30 - m) = 0. A row
    with ``seq_lens`` 0 (an empty slot of a mixed step) returns zeros;
    alone it runs no block."""
    B, T, H = q_nope.shape[:3]
    r, dr, dv = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.v_head_dim
    ps, mp = pool.shape[2], block_tables.shape[1]
    bp, visited, _ = prefill_key_blocks(seq_lens, T, ps, mp)
    kb = bp * ps
    block_tables = jnp.pad(block_tables, ((0, 0), (0, -mp % bp)))
    wk, wv = wkv_b(cfg, lp, l)
    scale, s_kv = cfg.softmax_scale, cfg.kv_scale

    def block(j, carry):
        m, z, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(block_tables, j * bp, bp, 1)
        rows = pool[l, pages].reshape(B, kb, pool.shape[-1])
        k_nope = jnp.einsum("bsr,rhn->bshn", rows[..., :r], wk)
        v = jnp.einsum("bsr,rhv->bshv", rows[..., :r], wv)
        s = jnp.einsum("bthn,bshn->bhts", q_nope, k_nope,
                       preferred_element_type=jnp.float32)
        if s_kv != 1.0:
            s = s * s_kv
        s = s + jnp.einsum("bthr,bsr->bhts", q_rope, rows[..., r:r + dr],
                           preferred_element_type=jnp.float32)
        key_pos = j * kb + jnp.arange(kb)
        mask = ((key_pos[None, None, :] <= positions[:, :, None])
                & (key_pos[None, None, :] < seq_lens[:, None, None]))
        s = jnp.where(mask[:, None], s * scale, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        fade = jnp.exp(m - m_new)
        z = z * fade + jnp.sum(p, axis=-1)
        acc = acc * fade.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhts,bshv->bthv", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, z, acc

    with scope("attn"), scope("latent_prefill_attention"):
        _, z, acc = jax.lax.fori_loop(
            0, visited, block,
            (jnp.full((B, H, T), NEG, jnp.float32),
             jnp.zeros((B, H, T), jnp.float32),
             jnp.zeros((B, T, H, dv), jnp.float32)))
        # A row that is not live: z is 0 (no block ran) or counts masked
        # keys (a live row beside it ran some), and its output is 0.
        live = (seq_lens > 0)[:, None, None]
        z = jnp.where(live, z, 1.0).transpose(0, 2, 1)[..., None]
        o = jnp.where(live[..., None], acc / z, 0.0)
        if s_kv != 1.0:
            o = o * s_kv
        return o.astype(jnp.result_type(pool.dtype, wv.dtype)).reshape(
            B, T, -1)


def latent_prefill_attention_each(cfg, lp: Params, l: int, q_nope, q_rope,
                                  pool, block_tables, positions, seq_lens):
    """``latent_prefill_attention`` ONE ROW AT A TIME (a ``lax.map`` over
    the B rows): each row then runs exactly its own key blocks, where
    side by side every row runs the LONGEST context's, and a block's
    float32 scores are a B-th. What a family whose mixed step holds many
    slices calls (``models/xing.py``; ``models/longcat_flash.py`` has
    the same map in ``_prefill_attend``, bound to its module's own name
    of the attention, which ``tests/test_latent_prefill.py`` swaps);
    ``key_blocks_each`` counts by this rule."""
    def one(s):
        q_n, q_r, bt, pos, n = s
        return latent_prefill_attention(cfg, lp, l, q_n[None], q_r[None],
                                        pool, bt[None], pos[None],
                                        n[None])[0]
    return jax.lax.map(one, (q_nope, q_rope, block_tables, positions,
                             seq_lens))


def key_blocks_each(seq_lens, T: int, page_size: int, max_pages: int):
    """A family's ``mixed_key_blocks`` (``models/__init__.py``) where the
    slices attend one at a time: (the blocks visited, those the tables
    hold), each slice its own."""
    each = [prefill_key_blocks(seq_lens[i:i + 1], T, page_size, max_pages)
            for i in range(len(seq_lens))]
    return sum(int(v) for _, v, _ in each), sum(t for _, _, t in each)


# -- a head-wise output gate (an optional leaf) ---------------------------------

def head_gate(cfg, lp: Params, l: int, x, o):
    """What a configuration may add to the attention's result before
    ``wo``: each head's ``v_head_dim`` values times ``sigmoid(x
    W_gate)``, one value a head (``gated_attention_proj_granularity_type``
    "head_wise"; leaf ``w_head_gate`` (n, D, H)). ``x`` (..., D) the
    attention's normed input, ``o`` (..., H * dv). A tree without the
    leaf: ``o`` as it came — the program there was."""
    if "w_head_gate" not in lp:
        return o
    with scope("attn_gate"):
        gate = jax.nn.sigmoid(jnp.dot(x, lp["w_head_gate"][l])
                              .astype(jnp.float32))
        shape = o.shape
        o = o.reshape(shape[:-1] + (cfg.n_heads, cfg.v_head_dim))
        return (o.astype(jnp.float32) * gate[..., None]).astype(
            o.dtype).reshape(shape)
