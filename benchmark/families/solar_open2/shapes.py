"""The Solar-Open2 block's shapes (Kimi Delta Attention, KDA, as ROW
STATE in three layers of four beside gated no-rotary GQA over a K/V page
pool in every fourth; a sigmoid-routed layer beside a shared expert in
EVERY layer), for a chip that holds a SHARE of each layer's experts and
of the vocabulary: what a step or a kernel call MUST move and compute
here, from shapes alone, and what the harness has to know of the family
to read a trace. The surface is ``families/llama/shapes.py``'s, with
``families/afmoe``'s share and ``families/ling_hybrid``'s row state:

- ``num_hidden_layers`` layers are HELD, layer ``l`` a GQA layer if it is
  in ``gqa_layers``, else KDA; every one is routed
  (``first_k_dense_replace`` 0: ``intermediate_size`` is carried and
  counts nothing);
- ``n_routed_experts`` is what this chip HOLDS of the router's
  ``router_experts``; a token draws ``num_experts_per_tok`` of the
  router's outputs, so of a step's ``rows * k`` slots the share held /
  outputs falls on a held expert (40 of 320 here);
- both mixers, the shared SwiGLU and the router are whole
  (data-parallel in the deployment); the head is this chip's slice of
  the vocabulary;
- the GQA layers alone cache by token (``kv_bytes_per_token``); a KDA
  layer keeps ROW STATE, as large for a row of 10 tokens as for one of
  30,000 (``state_bytes_per_row``), and a decode step reads and writes
  every live row's state once (``ssm_update_bytes``: the name the
  accepted ``ssm_update_roofline`` asks a family's shapes for);
- a mixed step's prompt slices go through the KDA layers' chunked scan,
  ``kda_scan_flops`` / ``kda_scan_bytes`` a live 64-token chunk of one
  layer (``kda_scan_roofline``).

Standard library only."""

from __future__ import annotations

from typing import Dict, Tuple

MODEL_KEYS = ("model_type", "partial_rotary_factor", "linear_attn_config",
              "hidden_size", "num_hidden_layers", "num_attention_heads",
              "head_dim", "num_key_value_heads", "vocab_size",
              "intermediate_size", "moe_intermediate_size", "rms_norm_eps",
              "rope_theta", "tie_word_embeddings", "max_position_embeddings",
              "first_k_dense_replace", "use_rope", "gqa_interval",
              "gqa_layers", "use_gqa_gate", "kda_use_full_proj",
              "kda_allow_neg_eigval", "n_routed_experts", "n_shared_experts",
              "norm_topk_prob", "routed_scaling_factor",
              "num_experts_per_tok", "kda_gate_rank", "router_experts",
              "expert_share")
#: The program's kernels by their names in a trace (patterns): the GQA
#: layers call the shared paged kernels.
DECODE_ATTN = r"fused_decode_attention"
PREFILL_ATTN = r"paged_prefill_attention"
#: ... and the routed layers the shared grouped product. (The cell is
#: NOT listed under ``moe_ffn_roofline``: that reader takes two calls
#: for a layer run, and a held share multiplies a block of 256 sorted
#: pairs at a time - two calls a BLOCK, sixteen blocks a mixed step - so
#: it read 128 % here where the scope's time over the layer runs gives
#: 63 %: PERF.md section 6, PR 52.)
MOE_FFN = r"^gmm$"
#: The KDA state is held in float32, the convolution's window in bf16.
STATE_ITEMSIZE, WINDOW_ITEMSIZE = 4, 2
#: Tokens of a grid step of the scan kernel: what the program counts a
#: slice's ``scan_chunks`` in (``llmq_tpu/ops/pallas/kda_scan.CHUNK``).
SCAN_CHUNK = 64


def held_experts(model: Dict) -> Tuple[int, int]:
    """(first, end) of the router's experts this chip holds: share
    ``index`` of ``chips`` equal shares of ``router_experts``."""
    share, n = model["expert_share"], model["n_routed_experts"]
    if share["chips"] * n != model["router_experts"]:
        raise ValueError(f"{share['chips']} shares of {n} experts are not "
                         f"the router's {model['router_experts']}")
    return share["index"] * n, (share["index"] + 1) * n


def layer_kinds(model: Dict) -> Tuple[int, int]:
    """(KDA, GQA) layers among the layers held."""
    L = model["num_hidden_layers"]
    gqa = sum(1 for l in model["gqa_layers"] if 0 <= l < L)
    return L - gqa, gqa


def attn_calls_per_step(model: Dict) -> int:
    """Decode attention calls of one decode step: one a GQA layer."""
    return layer_kinds(model)[1]


def _dims(model: Dict) -> Dict[str, int]:
    lin = model["linear_attn_config"]
    Lk, Lg = layer_kinds(model)
    Hk, d = lin["num_heads"], lin["head_dim"]
    Fe = model["moe_intermediate_size"]
    return {"D": model["hidden_size"], "L": model["num_hidden_layers"],
            "Lk": Lk, "Lg": Lg, "Hk": Hk, "d": d, "W": Hk * d,
            "K": lin["short_conv_kernel_size"],
            "r": model.get("kda_gate_rank", d),
            "H": model["num_attention_heads"],
            "G": model["num_key_value_heads"], "hd": model["head_dim"],
            "Fe": Fe, "Fs": model["n_shared_experts"] * Fe,
            "Eh": model["n_routed_experts"], "R": model["router_experts"],
            "k": model["num_experts_per_tok"], "V": model["vocab_size"]}


def kda_params(model: Dict) -> int:
    """One KDA layer's matrices: W_q, W_k, W_v, the decay's and the
    output gate's low-rank pairs, W_beta, W_o, the convolution's taps."""
    d = _dims(model)
    return (d["D"] * (4 * d["W"] + d["Hk"]) + 3 * d["W"] * d["K"]
            + 2 * d["r"] * (d["D"] + d["W"]))


def gqa_params(model: Dict) -> int:
    """One GQA layer's matrices: W_q, W_k, W_v, the gate (an element of
    the heads' result each), W_o."""
    d = _dims(model)
    return d["D"] * d["hd"] * (3 * d["H"] + 2 * d["G"])


def expert_params(model: Dict) -> int:
    d = _dims(model)
    return 3 * d["D"] * d["Fe"]


def held_slot_share(model: Dict) -> float:
    """The share of a token's slots that falls on an expert held here,
    under uniform routing."""
    d = _dims(model)
    return d["Eh"] / d["R"]


def experts_touched(model: Dict, rows: float) -> float:
    """Distinct HELD experts of one layer that ``rows`` tokens touch, in
    expectation under uniform routing: a token draws k distinct of the
    router's outputs, so it misses a given one with 1 - k / outputs (22
    of 40 at 32 rows)."""
    d = _dims(model)
    return d["Eh"] * (1.0 - (1.0 - d["k"] / d["R"]) ** max(rows, 0.0))


def _once_params(model: Dict) -> int:
    """Matrices a decode step reads once whatever its rows: every
    layer's mixer, router and shared expert, and the head's slice."""
    d = _dims(model)
    return (d["Lk"] * kda_params(model) + d["Lg"] * gqa_params(model)
            + d["L"] * (d["D"] * d["R"] + 3 * d["D"] * d["Fs"])
            + d["V"] * d["D"])


def matmul_params(model: Dict) -> int:
    """Parameters of every matrix a decode step can read here: all
    layers with all their HELD experts, and the head's slice."""
    d = _dims(model)
    return _once_params(model) + d["L"] * d["Eh"] * expert_params(model)


def param_count(model: Dict) -> int:
    """Parameters held: every matrix, the embedding's slice, the norms
    (two a layer, the final one, a KDA layer's over a head), the
    decay's ``A_log`` and ``dt_bias`` and the routers' selection
    biases."""
    d = _dims(model)
    small = (d["L"] * 2 * d["D"] + d["D"]
             + d["Lk"] * (d["d"] + d["Hk"] + d["W"]) + d["L"] * d["R"])
    return matmul_params(model) + d["V"] * d["D"] + small


def published_param_count(model: Dict, published: Dict) -> int:
    """The uncut model's count: ``param_count`` at the ``published``
    depth, layers, experts and vocabulary (a check on the reading of the
    configuration: the name says 250 B)."""
    whole = dict(model, **{k: published[k] for k in (
        "num_hidden_layers", "gqa_layers", "vocab_size")})
    whole["n_routed_experts"] = whole["router_experts"] = published[
        "n_routed_experts"]
    whole["expert_share"] = {"chips": 1, "index": 0}
    return param_count(whole)


def active_param_count(model: Dict) -> int:
    """Parameters one token multiplies with here, in expectation: of
    the held experts the k * held / outputs its slots fall on."""
    d = _dims(model)
    idle = d["Eh"] - d["k"] * held_slot_share(model)
    return int(param_count(model) - d["L"] * idle * expert_params(model))


def kv_bytes_per_token(model: Dict, kv_itemsize: int) -> int:
    """What one cached token adds to the page pool: K and V in the GQA
    layers (a KDA layer's state is a row's)."""
    d = _dims(model)
    return 2 * d["Lg"] * d["G"] * d["hd"] * kv_itemsize


def state_bytes_per_row(model: Dict) -> int:
    """What one batch row's KDA layers hold, whatever its context: the
    float32 state and the convolution's window."""
    d = _dims(model)
    return d["Lk"] * (d["d"] * d["W"] * STATE_ITEMSIZE
                      + (d["K"] - 1) * 3 * d["W"] * WINDOW_ITEMSIZE)


def ssm_update_bytes(model: Dict, rows: float) -> float:
    """One decode step's state update: every live row's state of every
    KDA layer read once and written once (the delta rule reads the
    state it writes; both visits are of one copy in fast memory)."""
    d = _dims(model)
    return rows * d["Lk"] * 2 * d["d"] * d["W"] * STATE_ITEMSIZE


def kda_scan_flops(model: Dict, chunks: float) -> float:
    """The chunked delta rule's products over ``chunks`` live
    ``SCAN_CHUNK``-token steps of ONE KDA layer, all its heads: a head's
    chunk of C tokens needs the keys' and the queries' products with the
    keys under the causal mask (C^2 / 2 each over d_k), the unit-lower
    solve and the output's product over it (C^2 / 2 each over d_v), and
    three products with the carried state (what it answers the keys and
    the queries, and what the chunk adds to it: C d_k d_v each) — 2
    operations a multiply-add. What a kernel spends beyond that (exact
    differences inside a block, an inverse by squaring) is its own."""
    d = _dims(model)
    C = SCAN_CHUNK
    macs = C * C * (d["d"] + d["d"]) + 3 * C * d["d"] * d["d"]
    return 2.0 * macs * d["Hk"] * chunks


def kda_scan_bytes(model: Dict, chunks: float) -> float:
    """... and what they must move: a live chunk's q, k, v and log-decay
    in and its output out, float32 as the recurrence takes them (the
    convolution's result and the decay are float32 values; beta is a
    value a head), all heads. The slices' states in and out (2 x 4 MiB a
    LIVE slice a layer) are left out: the count is of chunks, and a
    share that leaves work out reads low, never over."""
    d = _dims(model)
    return chunks * SCAN_CHUNK * (5 * d["W"] + d["Hk"]) * STATE_ITEMSIZE


def moe_ffn_bytes(model: Dict, weight_itemsize: int,
                  touched: float) -> float:
    """One routed layer's grouped products: the touched held experts'
    three matrices, read once."""
    return touched * expert_params(model) * weight_itemsize


def moe_ffn_flops(model: Dict, pairs: float) -> float:
    """... and their operations for ``pairs`` (token, held expert)
    pairs."""
    return 2.0 * expert_params(model) * pairs


def decode_attn_bytes(model: Dict, kv_itemsize: int, rows: float,
                      context_tokens: float) -> float:
    """One decode step's attention: the GQA layers' cached K and V of
    every context token, read once."""
    return kv_bytes_per_token(model, kv_itemsize) * context_tokens


def decode_attn_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    """QK^T and PV over the same tokens: 2 operations a query head a
    value each."""
    d = _dims(model)
    return 4.0 * d["Lg"] * d["H"] * d["hd"] * context_tokens


def decode_step_bytes(model: Dict, weight_itemsize: int, kv_itemsize: int,
                      rows: float, context_tokens: float) -> float:
    """Bytes one decode step must move: what is read once, each layer's
    TOUCHED held experts (in expectation at ``rows``), the batch's
    cached K and V once, and the live rows' KDA state in and out."""
    d = _dims(model)
    routed = d["L"] * moe_ffn_bytes(model, weight_itemsize,
                                    experts_touched(model, rows))
    return (_once_params(model) * weight_itemsize + routed
            + decode_attn_bytes(model, kv_itemsize, rows, context_tokens)
            + ssm_update_bytes(model, rows))


def decode_step_flops(model: Dict, rows: float,
                      context_tokens: float) -> float:
    """The products, the GQA layers' scores and values, and the delta
    rule's four multiply-adds a state value."""
    d = _dims(model)
    pairs = rows * d["k"] * held_slot_share(model)
    return (2.0 * _once_params(model) * rows
            + d["L"] * moe_ffn_flops(model, pairs)
            + decode_attn_flops(model, rows, context_tokens)
            + 8.0 * d["Lk"] * d["d"] * d["W"] * rows)


def prefill_attn_flops(model: Dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query, visible key) pairs in the GQA
    layers."""
    d = _dims(model)
    return 4.0 * d["Lg"] * d["H"] * d["hd"] * pairs


def prefill_attn_bytes(model: Dict, kv_itemsize: int, new_tokens: float,
                       context_tokens: float) -> float:
    """Least traffic of the GQA layers' prefill attention: each call
    reads its sequence's cached K and V once and its q, and writes its
    output."""
    d = _dims(model)
    qo = 2 * d["Lg"] * d["H"] * d["hd"] * new_tokens * kv_itemsize
    return kv_bytes_per_token(model, kv_itemsize) * context_tokens + qo
