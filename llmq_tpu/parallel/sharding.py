"""Sharding rules for the Llama pytree (GSPMD tensor parallelism).

The megatron-style TP layout, expressed as a **regex partition-rule
table** (the ``match_partition_rules`` shape from the pjit serving
stacks) resolved into PartitionSpecs and left to XLA
to lower into ICI collectives:

- qkv projections shard the HEAD (output) dim → each chip computes its
  heads' attention locally;
- wo shards the input dim → the residual add needs one all-reduce
  (inserted by GSPMD);
- SwiGLU shards ffn_dim on w_gate/w_up (output) and w_down (input) → one
  all-reduce after w_down;
- embedding shards the vocab dim; lm_head shards vocab on the output →
  logits all-gather only at the final projection;
- paged KV pools shard the KV-head dim, so each chip holds only its
  heads' cache (HBM capacity scales with TP degree — how 70B's cache
  fits a v5e-16, BASELINE config #5). With a ``dp`` axis the pool's
  PAGE axis is additionally split, so each dp replica owns its own
  page universe (the host allocator partitions the id space to match —
  engine/kv_allocator.py).

Axes that don't divide evenly fall back to replication (e.g. the tiny
test model's 2 KV heads on an 8-way mesh) — correctness first, the real
model shapes all divide. Quantized ``{"q", "s"}`` leaves ride the same
rules: a scale's contraction axis has size 1, so the divisibility clamp
replicates exactly that axis and the named sharding of the quantized
weight is preserved everywhere else.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llmq_tpu.models.llama import LlamaConfig, Params
from llmq_tpu.utils.logging import get_logger

log = get_logger("sharding")

#: One rule per line: (regex over the '/'-joined tree path,
#: PartitionSpec with NAMED mesh axes). First match wins; the
#: catch-all replicates. Quantized leaves match through their parent
#: name (paths are e.g. "layers/wq/q", "layers/wq/s") — scales keep
#: the weight's spec and the size-1 contraction axis is clamped to
#: replication by the divisibility check in :func:`resolve_rules`.
LLAMA_PARTITION_RULES: List[Tuple[str, P]] = [
    (r"(^|/)embed(/|$)", P("tp", None)),          # vocab rows
    (r"(^|/)lm_head(/|$)", P(None, "tp")),        # vocab cols
    (r"(^|/)(wq|wk|wv)(/|$)", P(None, None, "tp")),   # head (out) dim
    (r"(^|/)wo(/|$)", P(None, "tp", None)),           # head (in) dim
    (r"(^|/)(w_gate|w_up)(/|$)", P(None, None, "tp")),  # ffn out
    (r"(^|/)w_down(/|$)", P(None, "tp", None)),         # ffn in
    (r"norm", P()),                                # tiny, replicate
    (r".", P()),                                   # default: replicate
]


def tree_path_str(path: Sequence) -> str:
    """'/'-joined readable key path for a pytree leaf."""
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "name",
                                                   getattr(k, "idx", k)))))
    return "/".join(parts)


def match_partition_rules(rules: Sequence[Tuple[str, P]], tree):
    """PartitionSpec pytree for ``tree``: each leaf gets the spec of
    the FIRST rule whose regex searches its '/'-joined path. Scalar leaves replicate
    unconditionally. Raises if no rule matches — a partition table
    must be total over the model it claims to cover."""

    def spec_for(path, leaf):
        name = tree_path_str(path)
        shape = getattr(leaf, "shape", ())
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return P()
        for pat, spec in rules:
            if re.search(pat, name) is not None:
                return spec
        raise ValueError(f"no partition rule matches param {name!r}")

    return jax.tree_util.tree_map_with_path(spec_for, tree)


def resolve_rules(rules: Sequence[Tuple[str, P]], tree,
                  mesh: Mesh) -> Params:
    """Rule table → NamedSharding pytree, clamped to what ``mesh`` can
    actually partition: a named axis is kept only where it exists in
    the mesh AND divides the leaf dimension (otherwise that axis of
    that leaf replicates — the tiny-model fallback)."""
    specs = match_partition_rules(rules, tree)

    def clamp(leaf, spec):
        shape = tuple(getattr(leaf, "shape", ()))
        ax = []
        for i, name in enumerate(tuple(spec) + (None,) * (len(shape)
                                                          - len(spec))):
            if (name is not None and name in mesh.axis_names
                    and i < len(shape)
                    and shape[i] % mesh.shape[name] == 0):
                ax.append(name)
            else:
                ax.append(None)
        return NamedSharding(mesh, P(*ax))

    return jax.tree.map(clamp, tree, specs)


def _axis(mesh: Mesh, name: str, dim_size: int):
    """Use mesh axis ``name`` iff it exists and divides ``dim_size``."""
    if name in mesh.axis_names and dim_size % mesh.shape[name] == 0:
        return name
    return None


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def param_shardings(cfg: LlamaConfig, mesh: Mesh,
                    quantized: bool = False,
                    params: Optional[Params] = None) -> Params:
    """NamedSharding pytree congruent with ``init_params``'s layout,
    resolved from :data:`LLAMA_PARTITION_RULES`.

    ``params`` may be the real tree or any shape-carrying pytree; when
    omitted, the layout is traced abstractly from the initializer
    (``jax.eval_shape`` — zero bytes materialized, which is how the
    70B sizing tests use this).

    With ``quantized=True`` the tree matches ``ops/quant.quantize_params``
    output: each matmul leaf becomes ``{"q": <same spec as the bf16
    weight>, "s": <weight spec with the contraction axis unsharded —
    it is size 1 in the scale, so the divisibility clamp replicates
    it>}``."""
    if params is None:
        if quantized:
            from llmq_tpu.models.llama import init_params_quantized
            params = jax.eval_shape(
                lambda: init_params_quantized(jax.random.PRNGKey(0), cfg))
        else:
            from llmq_tpu.models.llama import init_params
            params = jax.eval_shape(
                lambda: init_params(jax.random.PRNGKey(0), cfg))
    return resolve_rules(LLAMA_PARTITION_RULES, params, mesh)


def kv_cache_shardings(cfg: LlamaConfig, mesh: Mesh,
                       quantized: bool = False,
                       num_pages: int = 0) -> Dict[str, NamedSharding]:
    """(L, P, page_size, H_kv·head_dim) — shard the flat KV-head·dim axis
    on tp. Contiguous chunks of the flat axis are whole KV heads (the
    flat axis is H_kv-major), so partitioning it by tp when tp divides
    H_kv is exactly the KV-head sharding of the 5-D layout.

    ``num_pages`` > 0 additionally splits the PAGE axis over ``dp``
    (when the mesh has one that divides it): each dp replica then
    physically owns ``num_pages/dp`` pages — its page universe — and
    the host allocator (engine/kv_allocator.py ``dp_shards``) hands a
    sequence pages from the universe of the dp shard its batch row
    lives on, so steady-state page traffic never crosses dp. 0 keeps
    the page axis replicated (the pre-dp layout, and the sizing-test
    call shape).

    ``quantized``: the int8 cache adds (L, P, H_kv, page_size) scale
    pools — same head partitioning, KV-head axis at dim 2; the page
    axis rides the same dp split. The returned tree must match the
    cache tree exactly (jax zips them), so scale entries exist only
    when the cache has them."""
    tp_kv = _axis(mesh, "tp", cfg.n_kv_heads)
    dp = _axis(mesh, "dp", num_pages) if num_pages > 0 else None
    ns = NamedSharding(mesh, P(None, dp, None, tp_kv))
    out = {"k": ns, "v": ns}
    if quantized:
        s_ns = NamedSharding(mesh, P(None, dp, tp_kv, None))
        out["k_scale"] = s_ns
        out["v_scale"] = s_ns
    return out


def batch_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """Tokens/positions/etc: shard the batch dim over dp."""
    dp = "dp" if "dp" in mesh.axis_names else None
    return NamedSharding(mesh, P(dp, *([None] * (ndim - 1))))


def shard_params(params: Params, shardings: Params) -> Params:
    """Place (or re-place) a param pytree onto the mesh."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, s), params, shardings)


def describe(params: Params) -> Dict[str, str]:
    """Debug helper: leaf path → sharding string."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return {jax.tree_util.keystr(path): str(getattr(leaf, "sharding", "?"))
            for path, leaf in flat}
