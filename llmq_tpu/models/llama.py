"""The Llama block in pure JAX (functional, stacked layers, paged KV):
the Llama-3 family and Mistral-7B-v0.3, which is the same block.

New scope: the reference serves models behind external HTTP endpoints and
has no model code (SURVEY.md §2.2); this is the in-tree TPU model layer
for BASELINE configs #2/#3/#5 (8B single chip, KV reuse, 70B TP).

Design notes (TPU-first):

- **Stacked layer parameters** (leading dim L), and a layer loop that
  is UNROLLED wherever a layer calls a Pallas kernel that aliases the
  KV pool — decode, mixed and verify steps, and prefill over bf16
  pools: around such a call a pool carried through ``lax.scan`` was
  copied whole once a layer (2-8x slower decode steps on the v5e), so
  those programs pay for their depth at compile time instead. Prefill
  over INT8 pools has no such call — its write is XLA's, page by page,
  and its attention kernel only READS the pools — and is ROLLED
  (``lax.fori_loop``, the pools as its carry): 6 s to compile against
  220 s at 32 layers.
  The table is in docs/performance.md, "Unrolled decode layers".
- **Paged KV cache**: global page pools ``(L, P, page_size, H_kv, D)``
  indexed by per-sequence block tables. Static shapes everywhere: one
  compiled program per (batch, max_pages) bucket, regardless of actual
  sequence lengths.
- **bf16 weights/activations, f32 softmax/norms** — MXU-friendly without
  logit drift.
- Sharding is NOT baked in here: ``parallel/sharding.py`` assigns
  PartitionSpecs to this pytree by path (TP over heads/ffn), so the same
  model code runs single-chip or pjit-sharded.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from llmq_tpu.ops.attention import (decode_order,
                                    dispatch_prefill_attention,
                                    dispatch_prefill_attention_q8,
                                    paged_decode_step,
                                    paged_decode_step_q8,
                                    paged_kv_write_prefill,
                                    paged_kv_write_prefill_q8,
                                    rows_by_place)
from llmq_tpu.ops.norms import rms_norm
from llmq_tpu.ops.quant import (embed_lookup, is_quantized, layer_slice,
                                linear, tied_head_logits)
from llmq_tpu.ops.rope import apply_rope, rope_cos_sin
from llmq_tpu.ops.rows import (grid_positions, grid_to_rows, live_rows,
                               row_tile, rows_to_grid, tile_rows,
                               worth_a_loop)
from llmq_tpu.utils.profiling import scope

Params = Dict[str, Any]
KVCache = Dict[str, jnp.ndarray]


@dataclass(frozen=True)
class LlamaConfig:
    FAMILY: ClassVar[str] = "llama"        # models/__init__.py registry
    name: str = "llama3-tiny"
    vocab_size: int = 512
    dim: int = 128
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 2
    ffn_dim: int = 256
    max_seq_len: int = 2048
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False
    #: Allow the single-chip Pallas kernels (env LLMQ_PALLAS still
    #: applies). Mesh-sharded executors set False: GSPMD cannot
    #: partition a Pallas call, so sharded programs must trace the
    #: pure-JAX paths it CAN partition (static — part of the jit key).
    pallas: bool = True
    #: Allow the PREFILL kernels for B > 1 (row-looped inside the
    #: program). Only the serving executor sets this: the kernels have
    #: no VJP, and the training/loss path runs forward_prefill with
    #: B > 1 under jax.grad — it must keep the differentiable pure-JAX
    #: route (B == 1 serving prefill is kernel-eligible either way).
    pallas_batched_prefill: bool = False

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads


def llama3_tiny(**kw) -> LlamaConfig:
    return replace(LlamaConfig(), **kw)


def llama3_1b(**kw) -> LlamaConfig:
    # Public Llama-3.2-1B architecture constants. The largest family
    # member whose bf16 weights + KV pool fit one 16 GB v5e chip —
    # the single-chip benchmark model (BASELINE config #2 scaled to the
    # available chip; 8B bf16 weights alone are 16 GB).
    return replace(LlamaConfig(
        name="llama3-1b", vocab_size=128256, dim=2048, n_layers=16,
        n_heads=32, n_kv_heads=8, ffn_dim=8192, max_seq_len=8192,
        rope_theta=500000.0, tie_embeddings=True), **kw)


def llama3_8b(**kw) -> LlamaConfig:
    # Public Llama-3-8B architecture constants.
    return replace(LlamaConfig(
        name="llama3-8b", vocab_size=128256, dim=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, ffn_dim=14336, max_seq_len=8192,
        rope_theta=500000.0), **kw)


def llama3_70b(**kw) -> LlamaConfig:
    # Public Llama-3-70B architecture constants.
    return replace(LlamaConfig(
        name="llama3-70b", vocab_size=128256, dim=8192, n_layers=80,
        n_heads=64, n_kv_heads=8, ffn_dim=28672, max_seq_len=8192,
        rope_theta=500000.0), **kw)


def mistral_7b_v03(**kw) -> LlamaConfig:
    """mistralai/Mistral-7B-v0.3 at its published sizes
    (https://huggingface.co/mistralai/Mistral-7B-v0.3/blob/main/config.json):
    hidden 4096, 32 layers, 32 query heads over 8 KV heads of 128,
    FFN 14,336, vocabulary 32,768, RoPE theta 1e6, context 32,768,
    untied head, no bias; v0.3 has no sliding window, so the block is
    this file's Llama block unchanged. 7.25 B parameters: on one 16 GB
    v5e chip it is served as ``quantization: int8`` over
    ``kv_quantization: int8`` with a shorter ``max_seq_len``
    (benchmark/configs/mistral-7b-v0.3-w8kv8.json)."""
    return replace(LlamaConfig(
        name="mistral-7b-v0.3", vocab_size=32768, dim=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, ffn_dim=14336, max_seq_len=32768,
        rope_theta=1000000.0), **kw)


MODEL_CONFIGS = {
    "llama3-tiny": llama3_tiny,
    "llama3-1b": llama3_1b,
    "llama3-8b": llama3_8b,
    "llama3-70b": llama3_70b,
    "mistral-7b-v0.3": mistral_7b_v03,
}


def get_config(name: str, **kw) -> LlamaConfig:
    try:
        return MODEL_CONFIGS[name](**kw)
    except KeyError:
        raise ValueError(
            f"unknown model {name!r}; known: {sorted(MODEL_CONFIGS)}")


# -- parameters ---------------------------------------------------------------

def init_params(key: jax.Array, cfg: LlamaConfig) -> Params:
    """Random-init parameter pytree (stacked layers: leading dim L)."""
    L, D, H, HKV, F, V = (cfg.n_layers, cfg.dim, cfg.n_heads,
                          cfg.n_kv_heads, cfg.ffn_dim, cfg.vocab_size)
    hd = cfg.head_dim
    keys = jax.random.split(key, 10)

    def norm_init(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(cfg.dtype)

    params: Params = {
        "embed": norm_init(keys[0], (V, D), D),
        "layers": {
            "wq": norm_init(keys[1], (L, D, H * hd), D),
            "wk": norm_init(keys[2], (L, D, HKV * hd), D),
            "wv": norm_init(keys[3], (L, D, HKV * hd), D),
            "wo": norm_init(keys[4], (L, H * hd, D), H * hd),
            "w_gate": norm_init(keys[5], (L, D, F), D),
            "w_up": norm_init(keys[6], (L, D, F), D),
            "w_down": norm_init(keys[7], (L, F, D), F),
            "attn_norm": jnp.ones((L, D), cfg.dtype),
            "mlp_norm": jnp.ones((L, D), cfg.dtype),
        },
        "final_norm": jnp.ones((D,), cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm_init(keys[8], (D, V), D)
    return params


def init_params_quantized(key: jax.Array, cfg: LlamaConfig) -> Params:
    """Random-init directly into int8 quant leaves (ops/quant layout).

    Generates and quantizes ONE weight per jitted call so the bf16
    transient never exceeds a single leaf — materializing the full bf16
    tree for llama3-8B (16 GB) before quantizing would OOM the very chip
    int8 exists to fit. Matches ``quantize_params(init_params(...))``
    numerically leaf-by-leaf (same keys, same init)."""
    from llmq_tpu.ops.quant import quantize_embedding, quantize_weight

    L, D, H, HKV, F, V = (cfg.n_layers, cfg.dim, cfg.n_heads,
                          cfg.n_kv_heads, cfg.ffn_dim, cfg.vocab_size)
    hd = cfg.head_dim
    keys = jax.random.split(key, 10)

    def _gen(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(cfg.dtype)

    @partial(jax.jit, static_argnames=("shape", "fan_in"))
    def qinit(k, shape, fan_in):
        return quantize_weight(_gen(k, shape, fan_in), axis=-2)

    @partial(jax.jit, static_argnames=("shape", "fan_in"))
    def einit(k, shape, fan_in):
        return quantize_embedding(_gen(k, shape, fan_in))

    params: Params = {
        "embed": einit(keys[0], shape=(V, D), fan_in=D),
        "layers": {
            "wq": qinit(keys[1], shape=(L, D, H * hd), fan_in=D),
            "wk": qinit(keys[2], shape=(L, D, HKV * hd), fan_in=D),
            "wv": qinit(keys[3], shape=(L, D, HKV * hd), fan_in=D),
            "wo": qinit(keys[4], shape=(L, H * hd, D), fan_in=H * hd),
            "w_gate": qinit(keys[5], shape=(L, D, F), fan_in=D),
            "w_up": qinit(keys[6], shape=(L, D, F), fan_in=D),
            "w_down": qinit(keys[7], shape=(L, F, D), fan_in=F),
            "attn_norm": jnp.ones((L, D), cfg.dtype),
            "mlp_norm": jnp.ones((L, D), cfg.dtype),
        },
        "final_norm": jnp.ones((D,), cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = qinit(keys[8], shape=(D, V), fan_in=D)
    return params


def param_count(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


def param_count_analytic(cfg: LlamaConfig) -> int:
    """Parameter count from the config alone (no materialization — 70B
    is 141 GB of bf16; sizing math must not allocate it)."""
    D, H, HKV, F, V, L = (cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                          cfg.ffn_dim, cfg.vocab_size, cfg.n_layers)
    hd = cfg.head_dim
    per_layer = (D * H * hd          # wq
                 + 2 * D * HKV * hd  # wk, wv
                 + H * hd * D        # wo
                 + 3 * D * F         # gate, up, down
                 + 2 * D)            # attn_norm, mlp_norm
    total = V * D + L * per_layer + D
    if not cfg.tie_embeddings:
        total += D * V
    return total


#: A dense block: every parameter multiplies with every token.
active_param_count = param_count_analytic


def serving_config(cfg: LlamaConfig) -> LlamaConfig:
    """``cfg`` for the forward-only serving programs: the batched-
    prefill kernels are safe there (the flag keeps them away from the
    differentiated training path, which shares ``forward_prefill``)."""
    return replace(cfg, pallas_batched_prefill=True)


def import_hf(model_dir: str, cfg: LlamaConfig, **kw) -> Params:
    """A local Hugging Face checkpoint directory into this family's
    tree (``models/checkpoint.import_hf_llama``)."""
    from llmq_tpu.models.checkpoint import import_hf_llama
    return import_hf_llama(model_dir, cfg, **kw)


def step_stats_layout(cfg: LlamaConfig) -> Dict[str, Any]:
    """A dense block counts nothing (``models/__init__.py``)."""
    return {}


def step_stats_size(cfg: LlamaConfig) -> int:
    """This family's forward passes count nothing
    (``models/__init__.py``)."""
    return 0


def mixed_live_rows(tokens: int, batch: int, slices: int, width: int) -> int:
    """Rows ``forward_mixed``'s row-wise products run for ``tokens``
    prompt tokens in ``slices`` slices ``width`` wide
    (``models/__init__.py``): the live tiles' rows, less the ``batch``
    decode rows that lead them."""
    return tile_rows(tokens, row_tile(width), slices * width, lead=batch)


#: Stacked leaves of ``params["layers"]`` -> how their matrices want to
#: lie on the device (``models/__init__.py``). TRANSPOSED, for the
#: decode step's products: a 32-row product whose result is split into
#: 64-wide heads is given its matrix with the contracted axis minor;
#: inside the chunk
#: programs' decode loop that would be a transposition a layer a step,
#: so XLA lifts it out of the loop — a copy of the whole stack at the
#: start of every run (three 201 MB copies, 1.89 ms a run of SmolLM2's
#: ``decode_chunk`` and ``mixed_chunk``: PERF.md, PR 47). An int8 leaf
#: with its scales asks for none and is never laid
#: (``engine/executor.lay_params``).
DEVICE_LAYOUT = {"wq": "transposed", "wk": "transposed", "wv": "transposed"}


def init_row_state(cfg, batch: int) -> None:
    """No row state: the pages are this family's whole cache
    (``models/__init__.py``)."""
    return None


def row_state_bytes_per_row(cfg) -> int:
    return 0


def check_serving(cfg: LlamaConfig, *, quantization: str = "",
                  kv_quantization: str = "", mesh: bool = False) -> None:
    """Everything the serving settings can ask for is written for this
    family."""


def routes(cfg: LlamaConfig, cache: KVCache, *, batch: int, page_size: int,
           max_pages: int, decode: bool = False,
           prefill_rows: int = 0) -> Dict[str, str]:
    """``ops/attention.kernel_routes`` at this config and pool."""
    from llmq_tpu.ops.attention import kernel_routes
    return kernel_routes(
        batch=batch, page_size=page_size, max_pages=max_pages,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        kv_itemsize=cache["k"].dtype.itemsize,
        quant_kv="k_scale" in cache, enabled=cfg.pallas,
        multi_ok=cfg.pallas_batched_prefill, decode=decode,
        prefill_rows=prefill_rows)


def weight_bytes(cfg: LlamaConfig) -> int:
    """Weight footprint in bytes at the config dtype (bf16 = 2 B/param)."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return param_count_analytic(cfg) * itemsize


def kv_bytes_per_token(cfg: LlamaConfig,
                       cache_dtype: Optional[Any] = None) -> int:
    """HBM cost of one cached token across all layers (K and V)."""
    itemsize = jnp.dtype(cache_dtype or cfg.dtype).itemsize
    return 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * itemsize


def init_kv_pages(cfg: LlamaConfig, num_pages: int, page_size: int,
                  dtype: Optional[Any] = None) -> KVCache:
    """Global paged KV pool: (L, P, page_size, H_kv·head_dim) per K/V.
    Page 0 is reserved as the null/padding page.

    The KV-head and head-dim axes are stored FLAT as one trailing axis.
    This is deliberate and load-bearing: the Pallas kernels DMA pages as
    (page_size, H_kv·D) tiles (lane dim 128-aligned), and any 5-D⇄4-D
    reshape between the per-layer aliased kernel calls makes XLA's
    layout assignment materialize full-pool copies — measured at
    ~0.65 ms per pool per layer call on v5e, which dominated the entire
    r2 decode step. Helpers needing heads unflatten VALUES (gathers),
    never the pool buffer itself.

    ``dtype=jnp.int8``: quantized KV cache — halves pool bytes AND the
    decode step's KV read traffic (docs/performance.md roofline: the
    next lever after int8 weights). Adds per-(token, kv-head) bf16
    scale pools shaped (L, P, H_kv, page_size) — see ops/quant.py for
    why that layout (sublane-tile fit + transpose-free kernels).
    """
    shape = (cfg.n_layers, num_pages, page_size,
             cfg.n_kv_heads * cfg.head_dim)
    dt = dtype or cfg.dtype
    cache: KVCache = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    if jnp.dtype(dt) == jnp.int8:
        sshape = (cfg.n_layers, num_pages, cfg.n_kv_heads, page_size)
        cache["k_scale"] = jnp.zeros(sshape, jnp.bfloat16)
        cache["v_scale"] = jnp.zeros(sshape, jnp.bfloat16)
    return cache


# -- forward ------------------------------------------------------------------

def _mlp(h: jnp.ndarray, w_gate, w_up, w_down) -> jnp.ndarray:
    """SwiGLU. Weights may be bf16 arrays or int8 quant leaves (ops/quant)."""
    g = linear(h, w_gate)
    u = linear(h, w_up)
    return linear(jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * u, w_down)


def _logits(params: Params, h: jnp.ndarray) -> jnp.ndarray:
    """Final projection → f32 logits, for bf16 or int8-quantized heads."""
    head = params.get("lm_head")
    if head is not None:
        return linear(h, head).astype(jnp.float32)
    embed = params["embed"]
    if is_quantized(embed):
        return tied_head_logits(embed, h)
    return jnp.dot(h, embed.T).astype(jnp.float32)


@partial(jax.jit, static_argnames=("cfg", "last_only"))
def forward_prefill(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,        # (B, T) int32, right-padded
    positions: jnp.ndarray,     # (B, T) int32 absolute positions
    lengths: jnp.ndarray,       # (B,) int32 — valid tokens per row
    kv_cache: KVCache,          # paged pools (written in place via .at)
    block_tables: jnp.ndarray,  # (B, max_pages) int32; pad with page 0
    last_only: bool = False,
) -> Tuple[jnp.ndarray, KVCache]:
    """Prefill: run up to T tokens per sequence, writing their KV into the
    paged pool. Returns (logits (B, T, V) f32, updated cache).

    ``last_only`` (serving): project only each row's LAST valid token —
    logits (B, V). Serving samples nothing else, and the head is the
    widest matmul of the pass: at B=4, T=2048, V=128k the full logits
    are 3.9 GB of f32 plus gather temporaries, which is what kept
    llama3-8b's batched-prefill program from fitting a 16 GB chip.

    Conventions (shared with the engine's KV allocator):
    - **page 0 of the pool is reserved** — never allocated to a sequence;
      padded tokens scatter their garbage KV there and padded block-table
      entries point at it (masked out of attention by ``seq_lens``).
    - supports continuation prefill (conversation turn 2+): ``positions``
      carry absolute offsets; new tokens attend to the previously cached
      pages through the same block tables.
    - each row of ``positions`` must be CONTIGUOUS (``positions[b, 0] +
      arange(T)``): the TPU attention kernel derives q positions from
      ``positions[b, 0]`` only (see dispatch_prefill_attention); padding
      rows past ``lengths`` are discarded so their values don't matter.
    """
    B, T = tokens.shape

    with scope("embed"):
        h = embed_lookup(params["embed"], tokens, cfg.dtype)  # (B, T, D)
    with scope("qkv"):
        cos, sin = rope_cos_sin(positions, cfg.head_dim,
                                cfg.rope_theta)            # (B,T,half)

    # Absolute visible history per row: last valid position + 1.
    valid = (jnp.arange(T)[None, :] < lengths[:, None])    # (B, T)
    last_pos = jnp.max(jnp.where(valid, positions, -1), axis=1)
    seq_lens = last_pos + 1                                # (B,)

    lp = params["layers"]

    def qkv(h, l):
        with scope("qkv"):
            hn = rms_norm(h, lp["attn_norm"][l], cfg.norm_eps)
            q = linear(hn, layer_slice(lp["wq"], l)).reshape(
                B, T, cfg.n_heads, cfg.head_dim)
            k = linear(hn, layer_slice(lp["wk"], l)).reshape(
                B, T, cfg.n_kv_heads, cfg.head_dim)
            v = linear(hn, layer_slice(lp["wv"], l)).reshape(
                B, T, cfg.n_kv_heads, cfg.head_dim)
            return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v

    def out_mlp(h, attn, l):
        with scope("attn_out"):
            h = h + linear(attn.reshape(B, T, -1), layer_slice(lp["wo"], l))
        with scope("mlp"):
            hn2 = rms_norm(h, lp["mlp_norm"][l], cfg.norm_eps)
            return h + _mlp(hn2, layer_slice(lp["w_gate"], l),
                            layer_slice(lp["w_up"], l),
                            layer_slice(lp["w_down"], l))

    if "k_scale" in kv_cache:
        # int8 pools: quantized write + dequantizing attention
        # (ops/attention.py int8 section), layers ROLLED: one
        # ``fori_loop`` body over the stacked parameters with the four
        # pools as its carry. The one serving program with no ALIASED
        # Pallas call in it (the writes are ``.at[].set`` of whole
        # pages, which XLA updates in place inside a loop body; the
        # attention kernel reads the carried pools and returns none of
        # them: tests/test_tpu_compile.py holds the temporaries under
        # one pool), so the reason the others unroll does not hold —
        # and unrolled it compiled longest of all (docs/performance.md
        # "Unrolled decode layers").
        def layer(l, carry):
            h, pools = carry
            q, k, v = qkv(h, l)
            with scope("kv_write"):
                pools = paged_kv_write_prefill_q8(
                    pools, k, v, block_tables, positions, lengths, l)
            with scope("attn"):
                attn = dispatch_prefill_attention_q8(
                    q, pools, block_tables, positions, seq_lens, l,
                    enabled=cfg.pallas,
                    multi_ok=cfg.pallas_batched_prefill)
            return out_mlp(h, attn, l), pools

        h, pools = lax.fori_loop(
            0, cfg.n_layers, layer,
            (h, (kv_cache["k"], kv_cache["v"], kv_cache["k_scale"],
                 kv_cache["v_scale"])))
        out_cache = {"k": pools[0], "v": pools[1],
                     "k_scale": pools[2], "v_scale": pools[3]}
    else:
        # bf16 pools: layers UNROLLED, one stacked pool threaded
        # through per-layer aliased Pallas writes (B==1 serving
        # prefill) — same structure and rationale as forward_decode
        # below: any scan formulation makes XLA materialize pool
        # copies around an aliased kernel call (ys restack per call;
        # carried pools degrade to per-layer full copies), and XLA
        # scatter costs ~13µs per row. The pure-JAX fallback (general
        # B / CPU) scatters into the threaded pool instead.
        k_pool, v_pool = kv_cache["k"], kv_cache["v"]
        for l in range(cfg.n_layers):
            q, k, v = qkv(h, l)
            # Write this layer's KV into its slice of the pool.
            with scope("kv_write"):
                k_pool, v_pool = paged_kv_write_prefill(
                    k_pool, v_pool, k, v, block_tables, positions,
                    lengths, jnp.int32(l), enabled=cfg.pallas,
                    multi_ok=cfg.pallas_batched_prefill)
            # Attend over the full paged history (covers continuation
            # turns); causality enforced via absolute positions.
            with scope("attn"):
                attn = dispatch_prefill_attention(
                    q, k_pool, v_pool, block_tables, positions, seq_lens,
                    l, enabled=cfg.pallas,
                    multi_ok=cfg.pallas_batched_prefill)
            h = out_mlp(h, attn, l)
        out_cache = {"k": k_pool, "v": v_pool}
    with scope("head"):
        if last_only:
            h = h[jnp.arange(B), lengths - 1]              # (B, D)
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        return _logits(params, h), out_cache


def _decode_geometry(cfg, kv_cache: KVCache, positions, block_tables,
                     active):
    """What the layers' attention needs of a decode step's rows:
    ``(block_tables, seq_lens, page_of, slot_of, order)`` — a row that
    is not active writes to page 0. The first three are laid out by the
    ``order`` the attention kernel wants its rows in
    (``ops/attention.decode_order``, made here ONCE for all the step's
    layers; None, and nothing moved, where the kernel does not serve):
    every layer's ``paged_decode_step*`` is handed them with it."""
    page_sz = kv_cache["k"].shape[2]
    page_of = block_tables[jnp.arange(positions.shape[0]),
                           positions // page_sz]
    if active is not None:
        page_of = jnp.where(active, page_of, 0)
    seq_lens = positions + 1
    pools = (kv_cache["k"], kv_cache["v"])
    if "k_scale" in kv_cache:
        pools += (kv_cache["k_scale"], kv_cache["v_scale"])
    order = decode_order(seq_lens, pools, block_tables.shape[1],
                         cfg.head_dim, enabled=cfg.pallas)
    return rows_by_place(order, block_tables, seq_lens, page_of) + (
        positions % page_sz, order)


@partial(jax.jit, static_argnames=("cfg",))
def forward_decode(
    params: Params,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,        # (B,) int32 — last generated token per seq
    positions: jnp.ndarray,     # (B,) int32 — absolute position of `tokens`
    kv_cache: KVCache,
    block_tables: jnp.ndarray,  # (B, max_pages)
    active: Optional[jnp.ndarray] = None,  # (B,) bool — inactive rows write to page 0
) -> Tuple[jnp.ndarray, KVCache]:
    """One decode step for every active sequence. Returns
    (logits (B, V) f32, updated cache).

    ``active`` supports multi-step on-device decoding (executor
    ``decode_chunk``): rows whose sequence already finished inside the
    chunk scatter their KV to reserved page 0 instead of the real pages.
    """
    B = tokens.shape[0]

    with scope("embed"):
        h = embed_lookup(params["embed"], tokens, cfg.dtype)   # (B, D)
    with scope("qkv"):
        cos, sin = rope_cos_sin(positions[:, None], cfg.head_dim,
                                cfg.rope_theta)            # (B,1,half)
    block_tables, seq_lens, page_of, slot_of, order = _decode_geometry(
        cfg, kv_cache, positions, block_tables, active)

    # Layers are UNROLLED (no scan) and the stacked pool threads through
    # one aliased Pallas write + one attention read per layer. This is
    # what makes the decode step in-place: the write kernel aliases its
    # pool operand (input_output_aliases), so 16 sequential calls update
    # one buffer. Any scan formulation forces XLA to materialize pool
    # copies (ys stacking rewrites it once per call; a carried pool
    # degrades to per-layer full copies) — measured 2-8x slower on v5e.
    # Unrolling costs compile time (once, at warmup) instead.
    lp = params["layers"]
    quant_kv = "k_scale" in kv_cache
    k_pool, v_pool = kv_cache["k"], kv_cache["v"]
    if quant_kv:
        pools = (k_pool, v_pool, kv_cache["k_scale"], kv_cache["v_scale"])
    for l in range(cfg.n_layers):
        with scope("qkv"):
            hn = rms_norm(h, lp["attn_norm"][l], cfg.norm_eps)
            q = linear(hn, layer_slice(lp["wq"], l)).reshape(
                B, 1, cfg.n_heads, cfg.head_dim)
            k = linear(hn, layer_slice(lp["wk"], l)).reshape(
                B, 1, cfg.n_kv_heads, cfg.head_dim)
            v = linear(hn, layer_slice(lp["wv"], l)).reshape(
                B, 1, cfg.n_kv_heads, cfg.head_dim)
            q = apply_rope(q, cos, sin)[:, 0]              # (B, H, D)
            k = apply_rope(k, cos, sin)[:, 0]              # (B, H_kv, D)
            v = v[:, 0]
        # Fused write + attention (every live sequence owns its page
        # this step; inactive rows redirect to reserved page 0): the
        # write is the attention kernel's, so no ``kv_write`` here.
        with scope("attn"):
            if quant_kv:
                attn, pools = paged_decode_step_q8(
                    q, k, v, pools, block_tables, seq_lens,
                    page_of, slot_of, jnp.int32(l), enabled=cfg.pallas,
                    order=order)
            else:
                attn, k_pool, v_pool = paged_decode_step(
                    q, k, v, k_pool, v_pool, block_tables, seq_lens,
                    page_of, slot_of, jnp.int32(l),
                    enabled=cfg.pallas, order=order)       # (B, H, D)
        with scope("attn_out"):
            h = h + linear(attn.reshape(B, -1), layer_slice(lp["wo"], l))
        with scope("mlp"):
            hn2 = rms_norm(h, lp["mlp_norm"][l], cfg.norm_eps)
            h = h + _mlp(hn2, layer_slice(lp["w_gate"], l),
                         layer_slice(lp["w_up"], l),
                         layer_slice(lp["w_down"], l))
    if quant_kv:
        out_cache = {"k": pools[0], "v": pools[1],
                     "k_scale": pools[2], "v_scale": pools[3]}
    else:
        out_cache = {"k": k_pool, "v": v_pool}
    with scope("head"):
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        return _logits(params, h), out_cache


@partial(jax.jit, static_argnames=("cfg",))
def forward_mixed(
    params: Params,
    cfg: LlamaConfig,
    dec_tokens: jnp.ndarray,        # (B,) int32 — decode rows' last tokens
    dec_positions: jnp.ndarray,     # (B,) int32
    kv_cache: KVCache,
    dec_block_tables: jnp.ndarray,  # (B, max_pages)
    pf_tokens: jnp.ndarray,         # (S*T,) int32, the slices TIGHT
    pf_positions: jnp.ndarray,      # (S*T,) int32 absolute
    pf_lengths: jnp.ndarray,        # (S,) int32 — valid tokens per slice
    pf_starts: jnp.ndarray,         # (S+1,) int32 — first row per slice; live rows
    pf_block_tables: jnp.ndarray,   # (S, max_pages)
    dec_active: Optional[jnp.ndarray] = None,  # (B,) bool
) -> Tuple[jnp.ndarray, jnp.ndarray, KVCache]:
    """Fused mixed step (token-budget mixed batching): advance B decode
    rows one token AND write S prefill slices (up to T tokens each) into
    the shared paged pool in ONE program, layer by layer.

    This is the device program behind ``executor.mixed_batch``: the
    decode rows' stall behind prefill work is bounded by T·S (the
    engine's ``prefill_token_budget``) instead of the longest admitted
    prompt.

    **The rows' layout** (``ops/rows.py``). ONE activation buffer of
    B + S·T rows: the B decode rows LEAD, and behind them the slices'
    tokens lie TIGHT: slice ``s`` is the ``pf_lengths[s]`` rows from
    ``B + pf_starts[s]``, slice after slice with no gap;
    ``pf_starts[S]`` is how many slice rows hold a token, the rest is
    zeros. An unused slice starts there, holds no tight row and has
    length 1 (one trash token against reserved page 0, as in
    :func:`forward_prefill`). What holds a token is so one prefix of
    ``B + pf_starts[S]`` rows, and a layer's ``qkv``, ``attn_out`` and
    feed-forward (each row's norm, residual and ``act_quant`` with
    them) are each ONE product over both kinds of row: the layer's
    matrices are read once a mixed step, not once for the slices and
    once more for the decode rows. ``attn_out`` and the feed-forward
    (nine tenths of the products) run over that prefix a tile at a
    time (``live_rows``: as many tiles as hold a token, read on the
    device, the matrices streamed once a tile); the q, k, v
    projections run all B + S·T rows (a head split inside a loop makes
    XLA transpose the stacked matrices). Where S·T is two tiles or
    fewer nothing loops, all B + S·T rows run whole, and the slices go
    back to T rows each at the door (``ops/rows.worth_a_loop``: the
    slice rows alone decide, the rows that lead never tip it).

    Only the KV write and the attention take the two kinds of row
    apart. The slices' q, k, v are cut out of the tight rows onto the
    (S, T) grid they always took, a slice a row, written and attended
    there; the decode rows' are the first B, written and attended by
    the fused decode step; the two outputs are laid back into one
    buffer. Decode rows and slice rows are separate sequences over the
    same pool, so their KV writes are disjoint; the decode rows' write
    takes the pool in place, and is held behind the slices' attention
    so that XLA keeps one pool. Of a slice only its last valid row
    goes through the head.

    On the grid, row conventions are exactly :func:`forward_prefill`'s
    (positions contiguous per slice and held at the last valid one past
    ``pf_lengths``, unused slices against reserved page 0), and the
    decode rows' are :func:`forward_decode`'s (``dec_active`` redirects
    finished rows' writes to page 0; such a row is computed all the
    same). Returns ``(dec_logits (B, V), pf_logits (S, V), cache)``: of
    a slice the logits of its last valid position, the one serving
    samples.
    """
    B = dec_tokens.shape[0]
    S = pf_lengths.shape[0]
    N = pf_tokens.shape[0]
    T = N // S
    tile = row_tile(T)
    if not worth_a_loop(N, tile):
        # Rows that run whole: every slice back at its own T rows, so
        # the moves to the grid and back are reshapes and the step is
        # the grid's program (``ops/rows.worth_a_loop`` has why).
        pf_tokens = rows_to_grid(pf_tokens, pf_starts, T).reshape(-1)
        pf_positions = grid_positions(pf_positions, pf_lengths, pf_starts,
                                      T)[0].reshape(-1)
        pf_starts = jnp.arange(S + 1, dtype=jnp.int32) * T
    n_live = pf_starts[S]

    # Decode-row geometry (forward_decode) and the grid's
    # (forward_prefill).
    dec_block_tables, dec_seq_lens, page_of, slot_of, order = (
        _decode_geometry(cfg, kv_cache, dec_positions, dec_block_tables,
                         dec_active))
    pf_grid_pos, pf_seq_lens = grid_positions(pf_positions, pf_lengths,
                                              pf_starts, T)

    # The decode rows lead the tight slice rows.
    with scope("decode_rows"), scope("embed"):
        h_d = embed_lookup(params["embed"], dec_tokens, cfg.dtype)  # (B, D)
    with scope("slices"), scope("embed"):
        h_p = embed_lookup(params["embed"], pf_tokens, cfg.dtype)   # (N, D)
    h = jnp.concatenate([h_d, h_p])                             # (B + N, D)
    with scope("qkv"):
        cos, sin = rope_cos_sin(
            jnp.concatenate([dec_positions, pf_positions]), cfg.head_dim,
            cfg.rope_theta)

    lp = params["layers"]
    quant_kv = "k_scale" in kv_cache
    k_pool, v_pool = kv_cache["k"], kv_cache["v"]
    if quant_kv:
        pools = (k_pool, v_pool, kv_cache["k_scale"], kv_cache["v_scale"])
    for l in range(cfg.n_layers):
        def out_mlp(h, attn, l=l):
            with scope("attn_out"):
                h = h + linear(attn.reshape(h.shape[0], -1),
                               layer_slice(lp["wo"], l))
            with scope("mlp"):
                hn = rms_norm(h, lp["mlp_norm"][l], cfg.norm_eps)
                return h + _mlp(hn, layer_slice(lp["w_gate"], l),
                                layer_slice(lp["w_up"], l),
                                layer_slice(lp["w_down"], l))

        # All B + S·T rows at once: the three projections are a tenth
        # of the products and measured no faster a tile at a time
        # (PERF.md, PR 38).
        with scope("qkv"):
            hn = rms_norm(h, lp["attn_norm"][l], cfg.norm_eps)
            q, k, v = (linear(hn, layer_slice(lp[w], l)).reshape(
                B + N, -1, cfg.head_dim) for w in ("wq", "wk", "wv"))
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)

        # Slice rows first (disjoint pages, but the decode rows' write
        # is the one that takes the pool in place): write their KV,
        # attend over their history.
        with scope("slices"):
            q_p, k_p, v_p = (rows_to_grid(x, pf_starts, T, lead=B)
                             for x in (q, k, v))
            if quant_kv:
                with scope("kv_write"):
                    pools = paged_kv_write_prefill_q8(
                        pools, k_p, v_p, pf_block_tables, pf_grid_pos,
                        pf_lengths, jnp.int32(l))
                with scope("attn"):
                    attn_p = dispatch_prefill_attention_q8(
                        q_p, pools, pf_block_tables, pf_grid_pos,
                        pf_seq_lens, l, enabled=cfg.pallas,
                        multi_ok=cfg.pallas_batched_prefill)
                # The decode rows' write takes the pool in place: only
                # once the slices' attention has read it, or XLA copies
                # the whole pool to keep both.
                attn_p, pools = lax.optimization_barrier((attn_p, pools))
            else:
                with scope("kv_write"):
                    k_pool, v_pool = paged_kv_write_prefill(
                        k_pool, v_pool, k_p, v_p, pf_block_tables,
                        pf_grid_pos, pf_lengths, jnp.int32(l),
                        enabled=cfg.pallas,
                        multi_ok=cfg.pallas_batched_prefill)
                with scope("attn"):
                    attn_p = dispatch_prefill_attention(
                        q_p, k_pool, v_pool, pf_block_tables, pf_grid_pos,
                        pf_seq_lens, l, enabled=cfg.pallas,
                        multi_ok=cfg.pallas_batched_prefill)
                attn_p, k_pool, v_pool = lax.optimization_barrier(
                    (attn_p, k_pool, v_pool))

        with scope("decode_rows"), scope("attn"):
            if quant_kv:
                attn_d, pools = paged_decode_step_q8(
                    q[:B], k[:B], v[:B], pools, dec_block_tables,
                    dec_seq_lens, page_of, slot_of, jnp.int32(l),
                    enabled=cfg.pallas, order=order)
            else:
                attn_d, k_pool, v_pool = paged_decode_step(
                    q[:B], k[:B], v[:B], k_pool, v_pool, dec_block_tables,
                    dec_seq_lens, page_of, slot_of, jnp.int32(l),
                    enabled=cfg.pallas, order=order)

        with scope("slices"):
            attn = grid_to_rows(
                attn_p, pf_starts,
                jnp.concatenate([attn_d, jnp.zeros((N,) + attn_d.shape[1:],
                                                   attn_d.dtype)]), lead=B)
        h = live_rows(out_mlp, B + n_live, tile, h, attn, lead=B)

    if quant_kv:
        out_cache = {"k": pools[0], "v": pools[1],
                     "k_scale": pools[2], "v_scale": pools[3]}
    else:
        out_cache = {"k": k_pool, "v": v_pool}
    with scope("slices"), scope("head"):
        h_p = rms_norm(h[B + pf_starts[:S] + pf_lengths - 1],
                       params["final_norm"], cfg.norm_eps)
        pf_logits = _logits(params, h_p)
    with scope("decode_rows"), scope("head"):
        h_d = rms_norm(h[:B], params["final_norm"], cfg.norm_eps)
        return _logits(params, h_d), pf_logits, out_cache


def _sp_forward_local(params: Params, tokens_local: jnp.ndarray,
                      cfg: LlamaConfig, axis_name: str) -> jnp.ndarray:
    """Per-device body of the sequence-parallel long-context forward
    (runs inside ``shard_map``): this device holds a contiguous
    sequence chunk; attention is exact over the GLOBAL sequence via the
    ring rotation (ops/ring_attention.py), everything else is local."""
    from llmq_tpu.ops.ring_attention import ring_attention

    B, Tl = tokens_local.shape
    my = lax.axis_index(axis_name)
    pos = my * Tl + jnp.arange(Tl)                       # global positions
    positions = jnp.broadcast_to(pos[None, :], (B, Tl))
    h = embed_lookup(params["embed"], tokens_local, cfg.dtype)
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    lp = params["layers"]
    for l in range(cfg.n_layers):
        hn = rms_norm(h, lp["attn_norm"][l], cfg.norm_eps)
        q = linear(hn, layer_slice(lp["wq"], l)).reshape(
            B, Tl, cfg.n_heads, cfg.head_dim)
        k = linear(hn, layer_slice(lp["wk"], l)).reshape(
            B, Tl, cfg.n_kv_heads, cfg.head_dim)
        v = linear(hn, layer_slice(lp["wv"], l)).reshape(
            B, Tl, cfg.n_kv_heads, cfg.head_dim)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        attn = ring_attention(q, k, v, axis_name=axis_name, causal=True)
        h = h + linear(attn.reshape(B, Tl, -1), layer_slice(lp["wo"], l))
        hn2 = rms_norm(h, lp["mlp_norm"][l], cfg.norm_eps)
        h = h + _mlp(hn2, layer_slice(lp["w_gate"], l),
                     layer_slice(lp["w_up"], l),
                     layer_slice(lp["w_down"], l))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return _logits(params, h)


def forward_prefill_sp(params: Params, cfg: LlamaConfig,
                       tokens: jnp.ndarray, mesh,
                       axis_name: str = "sp") -> jnp.ndarray:
    """Long-context prefill/scoring over a sequence-parallel mesh axis.

    The sequence dim of ``tokens`` (B, T) is sharded over ``axis_name``
    (T must divide by the axis size); each device computes its chunk's
    full transformer stack locally and exact global causal attention
    via ring rotation over ICI — peak activation memory O(T/n) per
    device, which is how a context longer than one chip's HBM prefills
    at all. Returns (B, T, V) f32 logits sharded the same way.

    Status: model-level long-context path (tested equivalent to the
    dense ``forward_prefill``); the serving executor does not yet route
    oversized prompts here — see docs/architecture.md "Long context".
    No reference counterpart (SURVEY §5: long-context absent there).
    """
    from functools import partial as _partial

    from jax.sharding import NamedSharding, PartitionSpec as P

    spec_t = P(None, axis_name)
    fn = jax.jit(jax.shard_map(
        _partial(_sp_forward_local, cfg=cfg, axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(), spec_t),
        out_specs=P(None, axis_name, None),
        check_vma=False,
    ))
    tokens = jax.device_put(tokens, NamedSharding(mesh, spec_t))
    return fn(params, tokens)


def loss_fn(params: Params, cfg: LlamaConfig, tokens: jnp.ndarray,
            kv_cache: KVCache, block_tables: jnp.ndarray) -> jnp.ndarray:
    """Next-token cross-entropy (used by the training step that
    __graft_entry__.dryrun_multichip exercises over the device mesh)."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    lengths = jnp.full((B,), T, jnp.int32)
    logits, _ = forward_prefill(params, cfg, tokens, positions, lengths,
                                kv_cache, block_tables)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    tgt = tokens[:, 1:]
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return nll.mean()
