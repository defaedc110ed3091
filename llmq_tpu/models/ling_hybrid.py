"""The Ling-3.0-flash block in pure JAX (the language model of
inclusionAI/Ling-3.0-flash-VL): delta-rule LINEAR attention with a
decay a channel (KDA, arXiv:2510.26692) in most layers, multi-head
LATENT attention with a head-wise output gate in every
``layer_group_size``-th, a dense SwiGLU in the leading layers and after
them a routed SwiGLU whose router is limited to groups, beside a shared
expert. Pre-norm, plain residual, untied head.

With N, N' a layer's two RMSNorms::

    h = x + Mixer_l(N(x))      # latent if (l + 1) % layer_group_size == 0
    y = h + FFN_l(N'(h))       # dense for l < first_k_dense, else routed

    KDA (H heads of d = kda_head_dim keys and values; x = N(x)):
        [q ; k ; v] = silu(conv4(x W_qkv))          # depthwise, causal
        q = q / |q| / sqrt(d);  k = k / |k|         # a head
        g = kda_lower_bound * sigmoid(exp(A_log)_h * (x W_f + b_f))
                                                    # log-decay a CHANNEL
        b = sigmoid(x W_b)                          # a head
        S_t = (I - b k k^T) Diag(exp(g)) S_{t-1} + b k v^T   # (d, d) float32
        o = S_t^T q
        out = W_o [sigmoid(x W_g) * RMSNorm_head(o)]
    Latent: ``models/latent.py``'s (``q_lora_rank`` null, RoPE on the
        rope part), each head's result times sigmoid(x W_gate)_h before
        W_o (``latent.head_gate``).
    Routed: s = sigmoid(x W_r) in float32; the experts lie in
        ``n_group`` groups, a group's score the sum of its top 2 of
        s + bias, the best ``topk_group`` groups kept, the top k among
        their experts (``ops/moe.route``); gates the chosen s,
        normalised, times ``routed_scaling_factor``; + SwiGLU_shared(x).

**Two kinds of cache, both of the latent form's.** The latent layers
write ``[c | k^rope | 0]`` rows into a latent page pool
(``init_kv_pages``: leaf ``ckv`` over the LATENT layers only). A KDA
layer carries ROW STATE: its matrix ``S`` ``(d, H d)`` float32
(``ops/kda.py`` has the layout) and the last ``conv - 1`` inputs of its
convolution (``init_row_state``: ``kda`` ``(L_k, rows, d, H d)`` and
``conv`` ``(L_k, rows, (conv - 1) * 3 H d)``; each leaf holds one row
more than the batch, nobody's). Every forward function takes it as
``row_state`` beside the pool and returns it after the pool; position 0
starts from a zero state inside the program; a decode row that is not
``active`` keeps its state; a prompt slice's state ends at its last
VALID token. Pages alone do not rebuild a sequence, so the engine
adopts no cached prefix, pinned conversation, tiering promotion or
hand-over (``get_stats()["row_state"]``).

**A chip's share** (``held_experts``, ``models/afmoe.py``'s): the
router scores all ``n_routed_experts`` and a token chooses among them
all; the pairs whose expert lies in ``lo .. hi - 1`` are multiplied
here, the shared expert is computed here whole, and nothing stands in
for the chips that hold the others.

Not written: the SwiGLU clamp of the published deep layers
(``expert_swiglu_limit`` / ``shared_swiglu_limit`` must be 0 for every
held layer: ``check_serving``), multi-token prediction, the vision
tower. Int8 weights, an int8 cache and a mesh are refused by name.

The residual stream is float32 (the router reads the float32 normed
activations, ``models/deepseek_v3.py`` has why), products take bf16, the
recurrence is float32. Seven layers are held where this is served, so
every program unrolls its layers, and the mixed step puts its slices
back onto the (S, T) grid at the door (``models/deepseek_v3.py``).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from llmq_tpu.models import latent
from llmq_tpu.models.latent import (  # noqa: F401 (param_count: surface)
    LatentDims, attn_norm_count, attn_norm_leaves, attn_param_shapes,
    draw_groups, init_latent_pool,
    param_count, prefill_key_blocks)
from llmq_tpu.models.latent import prod as _prod
from llmq_tpu.models.latent import swiglu as _mlp
from llmq_tpu.ops.kda import (conv_step, kda_scan_slices, kda_update_layer,
                              l2_norm, scan_route, update_route)
from llmq_tpu.ops.moe import (pass_extras, route, routed_ffn,
                              share_counts)
from llmq_tpu.ops.norms import rms_norm
from llmq_tpu.ops.rope import rope_cos_sin
from llmq_tpu.ops.rows import grid_positions, rows_to_grid
from llmq_tpu.ops.ssm import (conv_slices, decode_walk, own_rows, rows_read,
                              rows_write)
from llmq_tpu.utils.profiling import scope

Params = Dict[str, Any]
KVCache = Dict[str, jnp.ndarray]
RowState = Dict[str, jnp.ndarray]

KDA, LATENT = "kda", "latent"


@dataclass(frozen=True)
class LingHybridConfig(LatentDims):
    FAMILY: ClassVar[str] = "ling_hybrid"      # models/__init__.py
    name: str = "ling-hybrid-tiny"
    vocab_size: int = 512                  # the rows of the vocabulary HELD
    dim: int = 128
    n_layers: int = 6
    layer_group_size: int = 3              # K K L
    first_k_dense: int = 1                 # the dense layers HELD
    n_heads: int = 4                       # of both mixers
    kda_head_dim: int = 32                 # d_k = d_v
    kda_conv: int = 4
    kda_lower_bound: float = -5.0
    kda_chunk: int = 8
    kv_lora_rank: int = 128
    qk_nope_head_dim: int = 32
    qk_rope_head_dim: int = 16
    v_head_dim: int = 32
    q_lora_rank: Optional[int] = None
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    ffn_dim: int = 256                     # the dense layers' SwiGLU
    moe_ffn_dim: int = 64                  # one expert's SwiGLU
    n_routed_experts: int = 16
    n_experts_per_tok: int = 4
    n_group: int = 4
    topk_group: int = 2
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    held_experts: Optional[Tuple[int, int]] = None    # None: all E
    #: The published SwiGLU clamps of the held layers (0: none). A
    #: non-zero one is refused: the clamp is not written.
    expert_swiglu_limit: Tuple[float, ...] = ()
    shared_swiglu_limit: Tuple[float, ...] = ()
    max_seq_len: int = 2048
    rope_theta: float = 6000000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    pallas: bool = True

    def __post_init__(self) -> None:
        lo, hi = self.held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(
                f"model {self.name!r}: held_experts {self.held_experts} of "
                f"{self.n_routed_experts} routed experts")
        if not 0 <= self.first_k_dense <= self.n_layers:
            raise ValueError(f"model {self.name!r}: first_k_dense "
                             f"{self.first_k_dense} of {self.n_layers}")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(LATENT if (l + 1) % self.layer_group_size == 0 else KDA
                     for l in range(self.n_layers))

    @property
    def n_kda(self) -> int:
        return self.layer_types.count(KDA)

    @property
    def n_latent(self) -> int:
        return self.layer_types.count(LATENT)

    @property
    def n_routed_layers(self) -> int:
        return self.n_layers - self.first_k_dense

    @property
    def kda_width(self) -> int:
        """Lanes of a head's keys (or values) over all heads."""
        return self.n_heads * self.kda_head_dim

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


def ling_hybrid_tiny(**kw) -> LingHybridConfig:
    """CPU-test size: two periods of ``K K L``, layer 0 dense, 16
    experts in 4 groups with the top 4 of the best 2 groups beside a
    shared one."""
    return replace(LingHybridConfig(), **kw)


def ling_3_0_flash(**kw) -> LingHybridConfig:
    """The language model of inclusionAI/Ling-3.0-flash-VL at its
    published sizes
    (https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/config.json):
    42 layers, latent attention at 5, 11, ..., 41 and KDA elsewhere;
    hidden 2,560, 32 heads of 128 (KDA) and of 128 + 64 over a latent
    of 512 (latent attention); the first 2 layers a dense SwiGLU of
    6,144, the other 40 routed: 512 experts of 768 in 8 groups, the top
    8 of the best 4 groups (sigmoid, renormalised, scaled 2.5) beside 1
    shared expert; vocabulary 157,184, untied head, RoPE theta 6e6,
    context 131,072. About 125 B parameters: one chip holds a share
    (benchmark/configs/ling-3.0-flash-bf16-ep4.json: 7 layers, 128 of
    the 512 experts, a quarter of the vocabulary). The published layers
    34-41 clamp their SwiGLUs, which is not written: ``check_serving``
    refuses this configuration as it stands, and serves its cuts that
    hold none of them."""
    return replace(LingHybridConfig(
        name="ling-3.0-flash", vocab_size=157184, dim=2560, n_layers=42,
        layer_group_size=6, first_k_dense=2, n_heads=32, kda_head_dim=128,
        kda_conv=4, kda_lower_bound=-5.0, kda_chunk=16, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        ffn_dim=6144, moe_ffn_dim=768, n_routed_experts=512,
        n_experts_per_tok=8, n_group=8, topk_group=4, n_shared_experts=1,
        routed_scaling_factor=2.5, norm_topk_prob=True,
        expert_swiglu_limit=(0.0,) * 35 + (4.0,) * 7,
        shared_swiglu_limit=(0.0,) * 34 + (5.0,) * 6 + (7.0,) * 2,
        max_seq_len=131072, rope_theta=6000000.0, norm_eps=1e-6), **kw)


MODEL_CONFIGS = {
    "ling-hybrid-tiny": ling_hybrid_tiny,
    "ling-3.0-flash": ling_3_0_flash,
}


# -- the family surface (models/__init__.py) -----------------------------------

def serving_config(cfg: LingHybridConfig) -> LingHybridConfig:
    """``cfg`` for the forward-only serving programs: as it is."""
    return cfg


def check_serving(cfg: LingHybridConfig, *, quantization: str = "",
                  kv_quantization: str = "", mesh: bool = False) -> None:
    """Refuse what is not written for this family, naming the setting."""
    what = None
    clamps = (tuple(cfg.expert_swiglu_limit[:cfg.n_layers])
              + tuple(cfg.shared_swiglu_limit[:cfg.n_layers]))
    if any(clamps):
        what = (f"expert_swiglu_limit / shared_swiglu_limit {clamps} "
                f"(the SwiGLU clamp of the deep layers is not written: "
                f"hold layers whose limits are 0)")
    elif quantization:
        what = (f"model.quantization={quantization!r} (no int8 form of "
                f"the KDA mixer's projections or the experts)")
    elif kv_quantization:
        what = (f"model.kv_quantization={kv_quantization!r} (an int8 "
                f"latent beside a float32 row state)")
    elif mesh:
        what = "executor.mesh (no partition rules for the row state)"
    if what:
        raise ValueError(f"model {cfg.name!r} (family ling_hybrid) does "
                         f"not support {what}; unset it")


def import_hf(model_dir: str, cfg: LingHybridConfig, **kw) -> Params:
    raise ValueError(f"model {cfg.name!r} (family ling_hybrid): no "
                     f"checkpoint loader is written (model.weights_path); "
                     f"the weights are random")


def step_stats_layout(cfg: LingHybridConfig) -> Dict[str, Any]:
    """``models/afmoe.step_stats_layout``'s: the tokens each HELD
    expert received, the held experts that received any summed over the
    routed layers, the slots whose expert is held elsewhere, and the
    routed layers run."""
    n = cfg.n_held
    return {"load": (0, n), "touched": n, "away_slots": n + 1,
            "runs": n + 2}


def step_stats_size(cfg: LingHybridConfig) -> int:
    return cfg.n_held + 3


def mixed_live_rows(tokens: int, batch: int, slices: int, width: int) -> int:
    """Every row of the slices' grid, whatever ``tokens`` is
    (``models/deepseek_v3.mixed_live_rows``)."""
    return slices * width


def mixed_key_blocks(seq_lens, T: int, page_size: int, max_pages: int):
    """``models/deepseek_v3.mixed_key_blocks``: ONE latent attention's
    key blocks over a mixed step's slices."""
    _, visited, table = prefill_key_blocks(seq_lens, T, page_size, max_pages)
    return len(seq_lens) * int(visited), len(seq_lens) * table


# -- parameters ---------------------------------------------------------------

def param_shapes(cfg: LingHybridConfig) -> Dict[str, Dict[str, tuple]]:
    """Leaf name -> (shape, fan_in) by group (init and the benchmark's
    builder follow it). ``kda``: the KDA mixers' matrices stacked over
    the KDA layers (``wqkv`` the three projections side by side, as the
    one convolution runs over them); ``latent``: the latent attentions'
    (``models/latent.attn_param_shapes`` and the head-wise gate) over
    the latent layers; ``dense``, ``moe``, ``experts``, ``top`` as
    ``models/afmoe.param_shapes``."""
    Lk, Ll, Ld, Lm = (cfg.n_kda, cfg.n_latent, cfg.first_k_dense,
                      cfg.n_routed_layers)
    D, V, F, Fe = cfg.dim, cfg.vocab_size, cfg.ffn_dim, cfg.moe_ffn_dim
    W, H = cfg.kda_width, cfg.n_heads
    Fs = cfg.n_shared_experts * Fe
    return {
        "kda": {"wqkv": ((Lk, D, 3 * W), D),
                "conv_w": ((Lk, 3 * W, cfg.kda_conv), cfg.kda_conv),
                "wf": ((Lk, D, W), D), "wb": ((Lk, D, H), D),
                "wg": ((Lk, D, W), D), "wo": ((Lk, W, D), W)},
        "latent": {**attn_param_shapes(cfg, Ll),
                   "w_head_gate": ((Ll, D, H), D)},
        "dense": {"w_gate": ((Ld, D, F), D), "w_up": ((Ld, D, F), D),
                  "w_down": ((Ld, F, D), F)},
        "moe": {"router": ((Lm, D, cfg.n_routed_experts), D),
                "ws_gate": ((Lm, D, Fs), D), "ws_up": ((Lm, D, Fs), D),
                "ws_down": ((Lm, Fs, D), Fs)},
        "experts": {"we_gate_up": ((cfg.n_held, D, 2 * Fe), D),
                    "we_down": ((cfg.n_held, Fe, D), Fe)},
        "top": {"embed": ((V, D), D), "lm_head": ((D, V), D)},
    }


#: The ranges ``decay_init`` draws the decay's own parameters from:
#: ``exp(A_log)`` a head uniform in (0.5, 2), ``b_f`` a channel uniform
#: in (-6, 2). With x W_f of unit variance the sigmoid's argument then
#: lies between -14 and 6, so a channel's decay ``exp(g)`` spans its
#: whole range, from exp(-5) = 0.0067 (forgets at once) to 0.99999 (a
#: memory of 10^5 tokens), median 0.7: a decay pinned at either end
#: would hide a wrong decay or a state held too narrow.
DECAY_A_RANGE, DECAY_BIAS_RANGE = (0.5, 2.0), (-6.0, 2.0)


def decay_init(key: jax.Array, cfg: LingHybridConfig
               ) -> Dict[str, jnp.ndarray]:
    ka, kb = jax.random.split(key)
    return {"a_log": jnp.log(jax.random.uniform(
                ka, (cfg.n_kda, cfg.n_heads), jnp.float32, *DECAY_A_RANGE)),
            "b_f": jax.random.uniform(kb, (cfg.n_kda, cfg.kda_width),
                                      jnp.float32, *DECAY_BIAS_RANGE)}


def norm_leaves(cfg: LingHybridConfig) -> Params:
    """The tree's RMSNorm weights (ones) and the router's selection
    bias (zeros, float32): what a random init does not draw (the decay's
    parameters are ``decay_init``'s)."""
    L, D = cfg.n_layers, cfg.dim
    return {"layers": {"attn_norm": jnp.ones((L, D), cfg.dtype),
                       "mlp_norm": jnp.ones((L, D), cfg.dtype)},
            "kda": {"o_norm": jnp.ones((cfg.n_kda, cfg.kda_head_dim),
                                       cfg.dtype)},
            "latent": attn_norm_leaves(cfg, cfg.n_latent),
            "moe": {"router_bias": jnp.zeros(
                (cfg.n_routed_layers, cfg.n_routed_experts), jnp.float32)},
            "final_norm": jnp.ones((D,), cfg.dtype)}


def assemble(cfg: LingHybridConfig, drawn: Dict[str, Dict[str, Any]],
             decay: Dict[str, jnp.ndarray]) -> Params:
    """``param_shapes``-shaped groups of arrays (``experts``: a list of
    one array a routed layer under each name) + ``decay_init``'s leaves
    + ``norm_leaves`` -> the parameter tree."""
    fixed = norm_leaves(cfg)
    return {"embed": drawn["top"]["embed"],
            "lm_head": drawn["top"]["lm_head"],
            "final_norm": fixed["final_norm"],
            "layers": fixed["layers"],
            "kda": {**drawn["kda"], **fixed["kda"], **decay},
            "latent": {**drawn["latent"], **fixed["latent"]},
            "dense": dict(drawn["dense"]),
            "moe": {**drawn["moe"], **fixed["moe"],
                    **{k: tuple(v) for k, v in drawn["experts"].items()}}}


def init_params(key: jax.Array, cfg: LingHybridConfig) -> Params:
    """Random-init parameter tree, N(0, 1 / fan_in) as the other
    families', the decay's parameters by ``decay_init``."""
    return assemble(cfg, draw_groups(key, param_shapes(cfg), cfg.dtype,
                                     cfg.n_routed_layers),
                    decay_init(jax.random.fold_in(key, 1), cfg))


def init_params_quantized(key: jax.Array, cfg: LingHybridConfig) -> Params:
    check_serving(cfg, quantization="int8")


def param_count_analytic(cfg: LingHybridConfig) -> int:
    """Parameters HELD, from the configuration alone."""
    n = sum(_prod(shape) * (cfg.n_routed_layers if g == "experts" else 1)
            for g, leaves in param_shapes(cfg).items()
            for shape, _f in leaves.values())
    fixed = (cfg.n_layers * 2 * cfg.dim + cfg.dim
             + cfg.n_kda * (cfg.kda_head_dim + cfg.n_heads + cfg.kda_width)
             + cfg.n_latent * attn_norm_count(cfg)
             + cfg.n_routed_layers * cfg.n_routed_experts)
    return n + fixed


def active_param_count(cfg: LingHybridConfig) -> int:
    """``models/afmoe.active_param_count``: the held count less the
    held experts a token is not routed to, in expectation."""
    idle = cfg.n_held * (1 - cfg.n_experts_per_tok / cfg.n_routed_experts)
    return int(param_count_analytic(cfg)
               - cfg.n_routed_layers * idle * 3 * cfg.dim * cfg.moe_ffn_dim)


def weight_bytes(cfg: LingHybridConfig) -> int:
    return param_count_analytic(cfg) * jnp.dtype(cfg.dtype).itemsize


def kv_bytes_per_token(cfg: LingHybridConfig,
                       cache_dtype: Optional[Any] = None) -> int:
    """The latent and the RoPE key in the LATENT layers: all a token
    adds to the cache."""
    itemsize = jnp.dtype(cache_dtype or cfg.dtype).itemsize
    return (cfg.n_latent * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            * itemsize)


def init_kv_pages(cfg: LingHybridConfig, num_pages: int, page_size: int,
                  dtype: Optional[Any] = None) -> KVCache:
    """The latent page pool of the latent layers alone: ``"ckv"``
    ``(L_l, P, page_size, latent_width)``, page 0 reserved."""
    if dtype is not None and jnp.dtype(dtype) == jnp.int8:
        check_serving(cfg, kv_quantization="int8")
    return init_latent_pool(cfg, cfg.n_latent, num_pages, page_size, dtype)


def init_row_state(cfg: LingHybridConfig, batch: int) -> RowState:
    """The row-state leaves for ``batch`` rows, zero: ``kda`` each KDA
    layer's state ``(L_k, batch + 1, d, H d)`` float32 and ``conv`` its convolution's last ``conv - 1`` inputs laid end to
    end, ``(L_k, batch + 1, (conv - 1) * 3 H d)`` in the activations'
    type (``models/granitemoehybrid.init_row_state`` has why one axis).
    The last row is NOBODY'S."""
    return {
        "kda": jnp.zeros((cfg.n_kda, batch + 1, cfg.kda_head_dim,
                          cfg.kda_width), jnp.float32),
        "conv": jnp.zeros((cfg.n_kda, batch + 1,
                           (cfg.kda_conv - 1) * 3 * cfg.kda_width),
                          cfg.dtype),
    }


def row_state_bytes_per_row(cfg: LingHybridConfig) -> int:
    """What one batch row holds in ``init_row_state``'s leaves, whatever
    its sequence's length."""
    return cfg.n_kda * (
        cfg.kda_head_dim * cfg.kda_width * 4
        + (cfg.kda_conv - 1) * 3 * cfg.kda_width
        * jnp.dtype(cfg.dtype).itemsize)


def routes(cfg: LingHybridConfig, cache: KVCache, *, batch: int,
           page_size: int, max_pages: int, decode: bool = False,
           prefill_rows: int = 0) -> Dict[str, str]:
    """The latent layers' routes (``models/latent.routes``) and the KDA
    layers': ``ssm_update`` of a program that decodes, ``ssm_scan`` of
    one that runs prompt tokens (the kernel takes slices of whole
    64-token steps; this function is not told a program's)."""
    out = latent.routes(cfg, cache, batch=batch, page_size=page_size,
                        max_pages=max_pages, decode=decode,
                        prefill_rows=prefill_rows)
    d, H = cfg.kda_head_dim, cfg.n_heads

    def named(route, kernel):
        use, interp = route
        return (f"pallas{'-interpret' if interp else ''}:{kernel}"
                if use else "xla")

    if prefill_rows:
        step, route = _scan_route(cfg)
        out["ssm_scan"] = named(route, f"kda_scan_pallas(slice%{step}==0)")
    if decode:
        out["ssm_update"] = named(update_route(d, H, d, enabled=cfg.pallas),
                                  "kda_update_pallas")
    return out


def _scan_route(cfg: LingHybridConfig, T: Optional[int] = None):
    """``(the scan kernel's step in tokens, ops/kda.scan_route of slices
    of T tokens)``; ``T`` None: of slices of whole steps."""
    from llmq_tpu.ops.pallas.kda_scan import CHUNK
    d = cfg.kda_head_dim
    return CHUNK, scan_route(d, cfg.n_heads, d, T or CHUNK, cfg.kda_chunk,
                             enabled=cfg.pallas)


def scan_step_tokens(cfg: LingHybridConfig, T: int) -> Optional[int]:
    """Tokens a grid step of the KDA layers' scan kernel takes of a
    slice of ``T`` tokens (``ops/pallas/kda_scan.CHUNK``) — None where
    such slices go to XLA's scan (:func:`llmq_tpu.ops.kda.scan_route`).
    What the executor's ``scan_work`` counts a program's chunks by."""
    step, (use, _) = _scan_route(cfg, T)
    return step if use else None


# -- the layer ------------------------------------------------------------------

def _normed(h, w, cfg: LingHybridConfig) -> jnp.ndarray:
    return rms_norm(h, w, cfg.norm_eps).astype(cfg.dtype)


def _embed(params: Params, tokens) -> jnp.ndarray:
    with scope("embed"):
        return params["embed"][tokens].astype(jnp.float32)


def _head(params: Params, cfg: LingHybridConfig, h) -> jnp.ndarray:
    with scope("head"):
        return jnp.dot(_normed(h, params["final_norm"], cfg),
                       params["lm_head"]).astype(jnp.float32)


def _kda_in(x, kp: Params, i: int, cfg: LingHybridConfig):
    """KDA layer ``i``'s products over the normed rows ``x`` (M, D):
    ``(qkv (M, 3 H d) before the convolution, g (M, H, d) the log-decay,
    b (M, H), z (M, H d) the output gate's logits)``."""
    M, H, d = x.shape[0], cfg.n_heads, cfg.kda_head_dim
    with scope("qkv"):
        qkv = jnp.dot(x, kp["wqkv"][i])
    with scope("kda_gates"):
        # (the decay's and beta's products come out in float32: rounded
        # to bfloat16, a logit of 8 is off by 0.03, the log-decay by up
        # to 0.07 a token, and the state carries that on — the served
        # path read three times the lower-precision control's distance
        # from the reference, PERF.md section 6, PR 45)
        f32 = jnp.float32
        f = jnp.dot(x, kp["wf"][i], preferred_element_type=f32) + kp["b_f"][i]
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            f.reshape(M, H, d) * jnp.exp(kp["a_log"][i].astype(f32))[:, None])
        b = jax.nn.sigmoid(jnp.dot(x, kp["wb"][i], preferred_element_type=f32))
        return qkv, g, b, jnp.dot(x, kp["wg"][i])


def _kda_heads(y, cfg: LingHybridConfig):
    """The convolved channels ``y`` (..., 3 H d) float32 as ``(q, k, v
    (..., H, d))``: q and k at unit length a head, q times 1/sqrt(d)."""
    W = cfg.kda_width
    q, k, v = (y[..., j * W:(j + 1) * W].reshape(
        y.shape[:-1] + (cfg.n_heads, cfg.kda_head_dim)) for j in range(3))
    return l2_norm(q) * cfg.kda_head_dim ** -0.5, l2_norm(k), v


def _kda_out(h, o, z, kp: Params, i: int, cfg: LingHybridConfig):
    """The norm over each head's values, the gate and the output
    projection: ``o`` (M, H, d) float32, ``z`` (M, H d)."""
    with scope("attn_out"):
        o = rms_norm(o, kp["o_norm"][i], cfg.norm_eps).reshape(z.shape)
        y = (o * jax.nn.sigmoid(z.astype(jnp.float32))).astype(cfg.dtype)
        return h + jnp.dot(y, kp["wo"][i]).astype(jnp.float32)


def _conv_bias(cfg: LingHybridConfig) -> jnp.ndarray:
    """``ops/ssm.conv_slices``'s bias: this convolution has none."""
    return jnp.zeros((3 * cfg.kda_width,), jnp.float32)


def _kda_decode(h, x, kp: Params, i: int, rs: RowState, active, walk,
                cfg: LingHybridConfig):
    """One token a row through KDA layer ``i``; rows that are not
    ``active`` keep their window and their state."""
    qkv, g, b, z = _kda_in(x, kp, i, cfg)
    kda, conv = rs["kda"], rs["conv"]
    with scope("ssm_conv"):
        y, conv = conv_step(conv, i, qkv, kp["conv_w"][i], active)
    with scope("ssm_update"):
        q, k, v = _kda_heads(y, cfg)
        o, kda = kda_update_layer(kda, i, q, k, v, g, b, active, walk=walk,
                                  enabled=cfg.pallas)
    return _kda_out(h, o, z, kp, i, cfg), {"kda": kda, "conv": conv}


def _kda_slices(h, x, kp: Params, i: int, rs: RowState, rows, first,
                lengths, cfg: LingHybridConfig):
    """S slices of T tokens through KDA layer ``i``: ``h``, ``x``
    (S, T, D); ``rows`` (S,) the batch row each slice's sequence owns
    (one past the batch's last: nobody's), ``first`` (S,) whether the
    slice starts its sequence (a zero state), ``lengths`` (S,)."""
    S, T, H = x.shape[0], x.shape[1], cfg.n_heads
    qkv, g, b, z = _kda_in(x.reshape(S * T, -1), kp, i, cfg)
    kda, conv = rs["kda"], rs["conv"]
    keep = ~first[:, None, None]
    with scope("ssm_conv"):
        win = rows_read(conv, i, rows).reshape(S, cfg.kda_conv - 1, -1)
        y, win = conv_slices(jnp.where(keep, win, 0), qkv.reshape(S, T, -1),
                             lengths, kp["conv_w"][i], _conv_bias(cfg))
        conv = rows_write(conv, i, rows, win.reshape(S, -1))
    with scope("ssm_scan"):
        q, k, v = _kda_heads(y, cfg)
        before = rows_read(kda, i, rows, enabled=cfg.pallas)
        o, st = kda_scan_slices(jnp.where(keep, before, 0), q, k, v,
                                g.reshape(S, T, H, -1), b.reshape(S, T, H),
                                lengths, cfg.kda_chunk, enabled=cfg.pallas)
        kda = rows_write(kda, i, rows, st, enabled=cfg.pallas)
    h = _kda_out(h.reshape(S * T, -1), o.reshape(S * T, H, -1), z, kp, i,
                 cfg)
    return h.reshape(S, T, -1), {"kda": kda, "conv": conv}


def _latent_slices(h, x, lat: Params, i: int, pool, tables, positions,
                   lengths, seq_lens, rope, cfg: LingHybridConfig):
    """S slices through latent attention ``i`` (its slice of the
    stacked leaves and its layer of the pool): written, then attended."""
    q_nope, q_rope, row = latent.qkv(cfg, lat, i, x, *rope)
    pool = latent.latent_write_prefill(pool, row, tables, positions,
                                       lengths, i)
    attn = latent.latent_prefill_attention(cfg, lat, i, q_nope, q_rope, pool,
                                           tables, positions, seq_lens)
    attn = latent.head_gate(cfg, lat, i, x, attn)
    with scope("attn_out"):
        return h + jnp.dot(attn, lat["wo"][i]), pool


def _latent_decode(h, x, lat: Params, i: int, pool, tables, geom, rope,
                   cfg: LingHybridConfig):
    page_of, slot_of, seq_lens = geom
    q_nope, q_rope, row = latent.qkv(cfg, lat, i, x[:, None], *rope)
    attn, pool = latent.latent_decode_attention(
        cfg, lat, i, q_nope[:, 0], q_rope[:, 0], row[:, 0], pool, tables,
        seq_lens, page_of, slot_of)
    attn = latent.head_gate(cfg, lat, i, x, attn)
    with scope("attn_out"):
        return h + jnp.dot(attn, lat["wo"][i]), pool


def _ffn(params: Params, cfg: LingHybridConfig, l: int, h, live):
    """Layer ``l``'s feed-forward over the stream's rows h (N, D).
    Returns (h', stats, experts), the last two ``None`` for a dense
    layer: ``ops/moe.routed_ffn``'s counts of a routed layer as
    ``step_stats_layout`` has them (without ``runs``), and the experts
    ``ops/moe.route`` chose for each row (N, k)."""
    with scope("mlp"):
        xf = rms_norm(h, params["layers"]["mlp_norm"][l], cfg.norm_eps)
        x = xf.astype(cfg.dtype)
        if l < cfg.first_k_dense:
            d = params["dense"]
            return h + _mlp(x, d["w_gate"][l], d["w_up"][l],
                            d["w_down"][l]), None, None
    m, i = params["moe"], l - cfg.first_k_dense
    experts, gates = route(
        xf, m["router"][i], m["router_bias"][i],
        top_k=cfg.n_experts_per_tok, scale=cfg.routed_scaling_factor,
        norm_topk=cfg.norm_topk_prob, n_group=cfg.n_group,
        topk_group=cfg.topk_group)
    y, st = routed_ffn(x, experts, gates, m["we_gate_up"][i],
                       m["we_down"][i], live, held=cfg.held,
                       n_routed=cfg.n_routed_experts)
    st = share_counts(st, cfg.n_held)
    with scope("mlp"):        # the shared expert, beside the routed ones
        return h + y + _mlp(x, m["ws_gate"][i], m["ws_up"][i],
                            m["ws_down"][i]), st, experts


def _rope(cfg: LingHybridConfig, positions):
    with scope("qkv"):
        return rope_cos_sin(positions, cfg.qk_rope_head_dim, cfg.rope_theta)


# -- forward ------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "last_only", "stats", "chosen"))
def forward_prefill(params: Params, cfg: LingHybridConfig,
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
    rope = _rope(cfg, positions)
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    seq_lens = jnp.max(jnp.where(valid, positions, -1), axis=1) + 1
    first = positions[:, 0] == 0
    lp, pool, counts = params["layers"], kv_cache["ckv"], []
    for l, kind in enumerate(cfg.layer_types):
        i = cfg.kind_index(l)
        with scope("qkv"):
            x = _normed(h, lp["attn_norm"][l], cfg)
        if kind == KDA:
            h, row_state = _kda_slices(h, x, params["kda"], i, row_state,
                                       rows, first, lengths, cfg)
        else:
            h, pool = _latent_slices(h, x, params["latent"], i, pool,
                                     block_tables, positions, lengths,
                                     seq_lens, rope, cfg)
        h, *took = _ffn(params, cfg, l, h.reshape(B * T, -1),
                        valid.reshape(-1))
        h = h.reshape(B, T, -1)
        counts.append(took)
    if last_only:
        with scope("head"):
            h = h[jnp.arange(B), lengths - 1]
    out = (_head(params, cfg, h), {"ckv": pool}, row_state)
    return out + pass_extras(counts, cfg.n_held + 2, stats, chosen)


@partial(jax.jit, static_argnames=("cfg", "stats", "chosen"))
def forward_decode(params: Params, cfg: LingHybridConfig,
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
    pool = kv_cache["ckv"]
    h = _embed(params, tokens)
    rope = _rope(cfg, positions[:, None])
    geom = latent.decode_geometry(positions, block_tables, pool.shape[2],
                                  active)
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
            h, pool = _latent_decode(h, x, params["latent"], i, pool,
                                     block_tables, geom, rope, cfg)
        h, *took = _ffn(params, cfg, l, h, active)
        counts.append(took)
    out = (_head(params, cfg, h), {"ckv": pool}, row_state)
    return out + pass_extras(counts, cfg.n_held + 2, stats, chosen)


@partial(jax.jit, static_argnames=("cfg", "stats", "chosen"))
def forward_mixed(params: Params, cfg: LingHybridConfig,
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
    a layer touch different rows of the state and different pages. The
    slices go back onto the (S, T) grid at the door
    (``mixed_live_rows``); the feed-forward runs slices and decode rows
    together, so a routed layer's experts are streamed once for both.
    Returns ``(dec_logits (B, V), pf_logits (S, V), cache, row_state)``
    and ``pass_extras`` after them (``chosen``: the slices' S * T grid rows,
    then the B decode rows)."""
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
    pool = kv_cache["ckv"]
    with scope("decode_rows"):
        h_d = _embed(params, dec_tokens)
        rope_d = _rope(cfg, dec_positions[:, None])
        geom = latent.decode_geometry(dec_positions, dec_block_tables,
                                      pool.shape[2], dec_active)
        walk = decode_walk(live_d)
    with scope("slices"):
        h_p = _embed(params, pf_tokens)
        rope_p = _rope(cfg, pf_positions)
        pf_valid = jnp.arange(T)[None, :] < pf_lengths[:, None]
        first = pf_positions[:, 0] == 0
    live = jnp.concatenate([pf_valid.reshape(-1), live_d])
    lp, counts = params["layers"], []
    for l, kind in enumerate(cfg.layer_types):
        i = cfg.kind_index(l)
        with scope("slices"):
            with scope("qkv"):
                x = _normed(h_p, lp["attn_norm"][l], cfg)
            if kind == KDA:
                h_p, row_state = _kda_slices(
                    h_p, x, params["kda"], i, row_state, pf_rows, first,
                    pf_lengths, cfg)
            else:
                h_p, pool = _latent_slices(
                    h_p, x, params["latent"], i, pool, pf_block_tables,
                    pf_positions, pf_lengths, pf_seq_lens, rope_p, cfg)
                # The decode rows' write takes the pool in place: only
                # once the slices' attention has read it, or XLA copies
                # the whole pool to keep both (twice a chunk program,
                # 6.5 ms: models/granitemoehybrid has the same).
                h_p, pool = jax.lax.optimization_barrier((h_p, pool))
        with scope("decode_rows"):
            with scope("qkv"):
                x = _normed(h_d, lp["attn_norm"][l], cfg)
            if kind == KDA:
                h_d, row_state = _kda_decode(h_d, x, params["kda"], i,
                                             row_state, live_d, walk, cfg)
            else:
                h_d, pool = _latent_decode(h_d, x, params["latent"], i, pool,
                                           dec_block_tables, geom, rope_d,
                                           cfg)
        # The feed-forward takes both kinds of row side by side (its
        # matrices are streamed once): no row kind on its scopes.
        h, *took = _ffn(params, cfg, l,
                        jnp.concatenate([h_p.reshape(S * T, -1), h_d]), live)
        h_p, h_d = h[:S * T].reshape(S, T, -1), h[S * T:]
        counts.append(took)
    with scope("slices"):
        with scope("head"):
            h_p = h_p[jnp.arange(S), pf_lengths - 1]
        pf_logits = _head(params, cfg, h_p)
    with scope("decode_rows"):
        dec_logits = _head(params, cfg, h_d)
    out = (dec_logits, pf_logits, {"ckv": pool}, row_state)
    return out + pass_extras(counts, cfg.n_held + 2, stats, chosen)
