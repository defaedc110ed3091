"""Model checkpointing (orbax) + Hugging Face weight import.

New scope (no reference counterpart — SURVEY.md §5 notes the reference
has no system checkpointing at all): save/restore the param pytree with
orbax, and map Hugging Face Llama checkpoints into our layout for real
Llama-3-8B/70B weights (BASELINE configs #2-#5)."""

from __future__ import annotations

import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from llmq_tpu.models.llama import LlamaConfig, Params
from llmq_tpu.utils.logging import get_logger

log = get_logger("checkpoint")


def save_checkpoint(path: str, params: Params) -> None:
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(path, params)
    ckptr.wait_until_finished()
    log.info("checkpoint saved to %s", path)


def load_checkpoint(path: str, template: Optional[Params] = None) -> Params:
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    ckptr = ocp.StandardCheckpointer()
    if template is not None:
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), template)
        return ckptr.restore(path, target=shapes)
    return ckptr.restore(path)


# -- Hugging Face import ------------------------------------------------------

def _permute_meta_rope(w: np.ndarray, n_heads: int) -> np.ndarray:
    """Meta-original → split-half rotary layout for q/k projections.

    Meta's consolidated ``.pth`` checkpoints interleave rotary pairs as
    (even, odd); our ``apply_rope`` (and HF safetensors) use the
    split-half ("rotate_half") layout. This is the same permutation HF's
    own conversion script applies. **HF safetensors checkpoints are
    already split-half and must be loaded verbatim** — applying this to
    them rotates wrong component pairs with wrong frequencies.
    w: (n_heads*head_dim, dim_in) in (out, in) orientation."""
    head_dim = w.shape[0] // n_heads
    dim_in = w.shape[1]
    w = w.reshape(n_heads, head_dim // 2, 2, dim_in)
    w = w.transpose(0, 2, 1, 3).reshape(n_heads * head_dim, dim_in)
    return w


def import_hf_llama(model_dir: str, cfg: LlamaConfig,
                    meta_rope_layout: bool = False) -> Params:
    """Convert a local Hugging Face Llama checkpoint directory
    (safetensors) into our stacked-layer pytree. Requires the
    ``safetensors`` package (bundled with transformers).

    HF q/k projections are loaded verbatim: they are already in the
    split-half rotary layout that ``ops/rope.apply_rope`` implements.
    Pass ``meta_rope_layout=True`` only for safetensors re-exports of
    Meta-original interleaved checkpoints."""
    tensors = _read_safetensors(model_dir)

    def get(name: str) -> np.ndarray:
        return tensors[name]

    L = cfg.n_layers
    dt = cfg.dtype

    def stack(fmt: str, transform=None) -> jnp.ndarray:
        mats = []
        for i in range(L):
            w = get(fmt.format(i=i))
            if transform is not None:
                w = transform(w)
            mats.append(w.T)  # HF stores (out, in); we use (in, out)
        return jnp.asarray(np.stack(mats), dtype=dt)

    params: Params = {
        "embed": jnp.asarray(get("model.embed_tokens.weight"), dtype=dt),
        "layers": {
            "wq": stack("model.layers.{i}.self_attn.q_proj.weight",
                        (lambda w: _permute_meta_rope(w, cfg.n_heads))
                        if meta_rope_layout else None),
            "wk": stack("model.layers.{i}.self_attn.k_proj.weight",
                        (lambda w: _permute_meta_rope(w, cfg.n_kv_heads))
                        if meta_rope_layout else None),
            "wv": stack("model.layers.{i}.self_attn.v_proj.weight"),
            "wo": stack("model.layers.{i}.self_attn.o_proj.weight"),
            "w_gate": stack("model.layers.{i}.mlp.gate_proj.weight"),
            "w_up": stack("model.layers.{i}.mlp.up_proj.weight"),
            "w_down": stack("model.layers.{i}.mlp.down_proj.weight"),
            "attn_norm": jnp.asarray(np.stack(
                [get(f"model.layers.{i}.input_layernorm.weight")
                 for i in range(L)]), dtype=dt),
            "mlp_norm": jnp.asarray(np.stack(
                [get(f"model.layers.{i}.post_attention_layernorm.weight")
                 for i in range(L)]), dtype=dt),
        },
        "final_norm": jnp.asarray(get("model.norm.weight"), dtype=dt),
    }
    if "lm_head.weight" in tensors:
        params["lm_head"] = jnp.asarray(get("lm_head.weight").T, dtype=dt)
    log.info("imported HF llama from %s (%d tensors)", model_dir, len(tensors))
    return params


def _read_safetensors(model_dir: str) -> Dict[str, np.ndarray]:
    from safetensors import safe_open  # type: ignore[import-not-found]

    files = sorted(f for f in os.listdir(model_dir)
                   if f.endswith(".safetensors"))
    if not files:
        raise FileNotFoundError(f"no safetensors in {model_dir}")
    tensors: Dict[str, np.ndarray] = {}
    for fname in files:
        with safe_open(os.path.join(model_dir, fname), framework="np") as f:
            for key in f.keys():
                tensors[key] = f.get_tensor(key)
    return tensors


def _deinterleave_rope(w: np.ndarray, n_heads: int,
                       rope_dim: int) -> np.ndarray:
    """Interleaved -> split-half rotary layout of the LAST ``rope_dim``
    output rows of each of ``n_heads`` heads. ``deepseek_v3``
    checkpoints keep the rotary pairs side by side (``rope_interleave``:
    the public ``apply_rotary_pos_emb_interleave`` regroups q and k as
    evens-then-odds before it rotates the halves); doing that once to
    the projections' rows leaves every score as it was and lets
    ``ops/rope.apply_rope`` serve both families. w: (n_heads * head_dim,
    dim_in), (out, in) orientation."""
    head_dim = w.shape[0] // n_heads
    w = w.reshape(n_heads, head_dim, -1)
    rope = w[:, head_dim - rope_dim:].reshape(
        n_heads, rope_dim // 2, 2, -1).transpose(0, 2, 1, 3).reshape(
        n_heads, rope_dim, -1)
    return np.concatenate([w[:, :head_dim - rope_dim], rope],
                          axis=1).reshape(n_heads * head_dim, -1)


def import_hf_deepseek_v3(model_dir: str, cfg) -> Params:
    """A local Hugging Face ``deepseek_v3`` checkpoint directory
    (safetensors) into ``models/deepseek_v3.py``'s tree
    (``param_shapes``). The tensor names are from memory of the public
    ``modeling_deepseek_v3.py`` (this sandbox has no network; the test
    builds a synthetic checkpoint under the same names):
    ``self_attn.{q_proj (or, with ``q_lora_rank``, q_a_proj,
    q_a_layernorm, q_b_proj), kv_a_proj_with_mqa, kv_a_layernorm,
    kv_b_proj, o_proj}``, ``mlp.{gate,up,down}_proj`` in the dense layers, and in
    the routed ones ``mlp.gate.weight``,
    ``mlp.gate.e_score_correction_bias``, ``mlp.experts.N.*`` and
    ``mlp.shared_experts.*``. q_proj's and kv_a_proj_with_mqa's rotary
    rows are de-interleaved (``_deinterleave_rope``); each expert's gate
    and up matrices are laid side by side (``we_gate_up``), a leaf a
    routed layer."""
    t = _read_safetensors(model_dir)
    dt, dr = cfg.dtype, cfg.qk_rope_head_dim
    Ld, E = cfg.first_k_dense, cfg.n_routed_experts

    def stack(layers, fmt: str, transform=None) -> jnp.ndarray:
        mats = []
        for i in layers:
            w = t[fmt.format(i=i)]
            mats.append((transform(w) if transform else w).T)
        return jnp.asarray(np.stack(mats), dtype=dt)

    def vec(layers, fmt: str, dtype=dt) -> jnp.ndarray:
        return jnp.asarray(np.stack([t[fmt.format(i=i)] for i in layers]),
                           dtype=dtype)

    every = range(cfg.n_layers)
    dense, routed = range(Ld), range(Ld, cfg.n_layers)
    attn, mlp = "model.layers.{i}.self_attn.", "model.layers.{i}.mlp."

    def experts(i: int) -> tuple:
        pre = f"model.layers.{i}.mlp.experts."
        gu = np.stack([np.concatenate(
            [t[f"{pre}{e}.gate_proj.weight"].T,
             t[f"{pre}{e}.up_proj.weight"].T], axis=1) for e in range(E)])
        down = np.stack([t[f"{pre}{e}.down_proj.weight"].T
                         for e in range(E)])
        return gu, down

    def rope_q(w):
        return _deinterleave_rope(w, cfg.n_heads, dr)

    per_layer = [experts(i) for i in routed]
    params: Params = {
        "embed": jnp.asarray(t["model.embed_tokens.weight"], dtype=dt),
        "lm_head": jnp.asarray(t["lm_head.weight"].T, dtype=dt),
        "final_norm": jnp.asarray(t["model.norm.weight"], dtype=dt),
        "layers": {
            **({"wq": stack(every, attn + "q_proj.weight", rope_q)}
               if not cfg.q_lora_rank else
               {"wq_a": stack(every, attn + "q_a_proj.weight"),
                "q_norm": vec(every, attn + "q_a_layernorm.weight"),
                "wq_b": stack(every, attn + "q_b_proj.weight", rope_q)}),
            "wkv_a": stack(every, attn + "kv_a_proj_with_mqa.weight",
                           lambda w: _deinterleave_rope(w, 1, dr)),
            "kv_norm": vec(every, attn + "kv_a_layernorm.weight"),
            "wkv_b": stack(every, attn + "kv_b_proj.weight"),
            "wo": stack(every, attn + "o_proj.weight"),
            "attn_norm": vec(every,
                             "model.layers.{i}.input_layernorm.weight"),
            "mlp_norm": vec(
                every, "model.layers.{i}.post_attention_layernorm.weight"),
        },
        "dense": {"w_gate": stack(dense, mlp + "gate_proj.weight"),
                  "w_up": stack(dense, mlp + "up_proj.weight"),
                  "w_down": stack(dense, mlp + "down_proj.weight")},
        "moe": {
            "router": stack(routed, mlp + "gate.weight"),
            "router_bias": vec(routed,
                               mlp + "gate.e_score_correction_bias",
                               jnp.float32),
            "we_gate_up": tuple(jnp.asarray(g, dtype=dt)
                                for g, _ in per_layer),
            "we_down": tuple(jnp.asarray(d, dtype=dt)
                             for _, d in per_layer),
            "ws_gate": stack(routed, mlp + "shared_experts.gate_proj.weight"),
            "ws_up": stack(routed, mlp + "shared_experts.up_proj.weight"),
            "ws_down": stack(routed,
                             mlp + "shared_experts.down_proj.weight"),
        },
    }
    log.info("imported HF deepseek_v3 from %s (%d tensors)", model_dir,
             len(t))
    return params


def import_hf_longcat_flash(model_dir: str, cfg) -> Params:
    """A local Hugging Face ``longcat_flash`` checkpoint directory
    (safetensors) into ``models/longcat_flash.py``'s tree
    (``param_shapes``): of each routed layer the HELD experts alone
    (``cfg.held``), of the vocabulary the first ``cfg.vocab_size`` rows.
    The tensor names are from memory of the public
    ``modeling_longcat_flash.py`` (this sandbox has no network; the test
    builds a synthetic checkpoint under the same names): a layer holds
    ``self_attn.{0,1}.{q_a_proj, q_a_layernorm, q_b_proj,
    kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj, o_proj}``,
    ``input_layernorm.{0,1}``, ``post_attention_layernorm.{0,1}``,
    ``mlps.{0,1}.{gate,up,down}_proj`` and ``mlp.router.classifier``,
    ``mlp.router.e_score_correction_bias``, ``mlp.experts.N.*``. The
    rotary rows of q_b_proj and kv_a_proj_with_mqa are de-interleaved as
    ``deepseek_v3``'s are."""
    t = _read_safetensors(model_dir)
    dt, dr, V = cfg.dtype, cfg.qk_rope_head_dim, cfg.vocab_size
    pairs = [(l, j) for l in range(cfg.n_layers) for j in (0, 1)]

    def stack(fmt: str, transform=None) -> jnp.ndarray:
        mats = []
        for l, j in pairs:
            w = t[f"model.layers.{l}." + fmt.format(j=j)]
            mats.append((transform(w) if transform else w).T)
        return jnp.asarray(np.stack(mats), dtype=dt)

    def vec(fmt: str) -> jnp.ndarray:
        return jnp.asarray(np.stack(
            [t[f"model.layers.{l}." + fmt.format(j=j)] for l, j in pairs]),
            dtype=dt)

    def experts(l: int) -> tuple:
        pre = f"model.layers.{l}.mlp.experts."
        held = range(*cfg.held)
        gu = np.stack([np.concatenate(
            [t[f"{pre}{e}.gate_proj.weight"].T,
             t[f"{pre}{e}.up_proj.weight"].T], axis=1) for e in held])
        return gu, np.stack([t[f"{pre}{e}.down_proj.weight"].T
                             for e in held])

    per_layer = [experts(l) for l in range(cfg.n_layers)]
    router = "model.layers.{l}.mlp.router."
    attn = "self_attn.{j}."
    params: Params = {
        "embed": jnp.asarray(t["model.embed_tokens.weight"][:V], dtype=dt),
        "lm_head": jnp.asarray(t["lm_head.weight"][:V].T, dtype=dt),
        "final_norm": jnp.asarray(t["model.norm.weight"], dtype=dt),
        "layers": {
            "wq_a": stack(attn + "q_a_proj.weight"),
            "q_norm": vec(attn + "q_a_layernorm.weight"),
            "wq_b": stack(attn + "q_b_proj.weight",
                          lambda w: _deinterleave_rope(w, cfg.n_heads, dr)),
            "wkv_a": stack(attn + "kv_a_proj_with_mqa.weight",
                           lambda w: _deinterleave_rope(w, 1, dr)),
            "kv_norm": vec(attn + "kv_a_layernorm.weight"),
            "wkv_b": stack(attn + "kv_b_proj.weight"),
            "wo": stack(attn + "o_proj.weight"),
            "attn_norm": vec("input_layernorm.{j}.weight"),
        },
        "ffn": {"w_gate": stack("mlps.{j}.gate_proj.weight"),
                "w_up": stack("mlps.{j}.up_proj.weight"),
                "w_down": stack("mlps.{j}.down_proj.weight"),
                "mlp_norm": vec("post_attention_layernorm.{j}.weight")},
        "moe": {
            "router": jnp.asarray(np.stack(
                [t[router.format(l=l) + "classifier.weight"].T
                 for l in range(cfg.n_layers)]), dtype=dt),
            "router_bias": jnp.asarray(np.stack(
                [t[router.format(l=l) + "e_score_correction_bias"]
                 for l in range(cfg.n_layers)]), dtype=jnp.float32),
            "we_gate_up": tuple(jnp.asarray(g, dtype=dt)
                                for g, _ in per_layer),
            "we_down": tuple(jnp.asarray(d, dtype=dt)
                             for _, d in per_layer),
        },
    }
    log.info("imported HF longcat_flash from %s (%d tensors)", model_dir,
             len(t))
    return params


def import_hf_granitemoehybrid(model_dir: str, cfg) -> Params:
    """A local Hugging Face ``granitemoehybrid`` checkpoint directory
    (safetensors) into ``models/granitemoehybrid.py``'s tree
    (``param_shapes``). The tensor names are from memory of the public
    ``modeling_granitemoehybrid.py`` (this sandbox has no network; the
    test builds a synthetic checkpoint under the same names): in a
    Mamba layer ``mamba.{in_proj, conv1d (weight (C, 1, K) and bias),
    dt_bias, A_log, D, norm, out_proj}``, in an attention layer
    ``self_attn.{q,k,v,o}_proj``, in every layer
    ``shared_mlp.{input_linear, output_linear}`` and the two layer
    norms. ``input_linear`` holds the gate's rows and then the up
    projection's (the published code chunks its output in two): split
    here into ``w_gate`` and ``w_up``. The head is tied to the
    embedding."""
    t = _read_safetensors(model_dir)
    dt = cfg.dtype
    kinds = list(cfg.layer_types)
    every = range(len(kinds))
    mamba = [l for l in every if kinds[l] == "mamba"]
    attn = [l for l in every if kinds[l] == "attention"]

    def stack(layers, name: str, transform=None, dtype=dt) -> jnp.ndarray:
        mats = []
        for l in layers:
            w = t[f"model.layers.{l}.{name}"]
            mats.append(transform(w) if transform else w)
        return jnp.asarray(np.stack(mats), dtype=dtype)

    F = cfg.ffn_dim
    f32 = jnp.float32
    params: Params = {
        "embed": jnp.asarray(t["model.embed_tokens.weight"], dtype=dt),
        "final_norm": jnp.asarray(t["model.norm.weight"], dtype=dt),
        "layers": {
            "attn_norm": stack(every, "input_layernorm.weight"),
            "mlp_norm": stack(every, "post_attention_layernorm.weight"),
            "w_gate": stack(every, "shared_mlp.input_linear.weight",
                            lambda w: w[:F].T),
            "w_up": stack(every, "shared_mlp.input_linear.weight",
                          lambda w: w[F:].T),
            "w_down": stack(every, "shared_mlp.output_linear.weight",
                            lambda w: w.T),
            "wq": stack(attn, "self_attn.q_proj.weight", lambda w: w.T),
            "wk": stack(attn, "self_attn.k_proj.weight", lambda w: w.T),
            "wv": stack(attn, "self_attn.v_proj.weight", lambda w: w.T),
            "wo": stack(attn, "self_attn.o_proj.weight", lambda w: w.T),
            "in_proj": stack(mamba, "mamba.in_proj.weight", lambda w: w.T),
            "conv_w": stack(mamba, "mamba.conv1d.weight",
                            lambda w: w[:, 0, :]),
            "conv_b": stack(mamba, "mamba.conv1d.bias"),
            "dt_bias": stack(mamba, "mamba.dt_bias", dtype=f32),
            "a_log": stack(mamba, "mamba.A_log", dtype=f32),
            "d_skip": stack(mamba, "mamba.D", dtype=f32),
            "ssm_norm": stack(mamba, "mamba.norm.weight"),
            "out_proj": stack(mamba, "mamba.out_proj.weight",
                              lambda w: w.T),
        },
    }
    log.info("imported HF granitemoehybrid from %s (%d tensors)", model_dir,
             len(t))
    return params


def import_hf_afmoe(model_dir: str, cfg) -> Params:
    """A local Hugging Face ``afmoe`` checkpoint directory (safetensors)
    into ``models/afmoe.py``'s tree (``param_shapes``): of each routed
    layer the HELD experts alone (``cfg.held``), of the vocabulary the
    first ``cfg.vocab_size`` rows, of the layers the first
    ``cfg.n_layers``. The tensor names are from memory of the public
    ``modeling_afmoe.py`` (this sandbox has no network; the test builds
    a synthetic checkpoint under the same names): a layer holds
    ``self_attn.{q,k,v,o,gate}_proj``, ``self_attn.{q,k}_norm``,
    ``input_layernorm``, ``post_attention_layernorm``,
    ``pre_mlp_layernorm``, ``post_mlp_layernorm`` and either
    ``mlp.{gate,up,down}_proj`` (a dense layer) or ``mlp.router.gate``,
    ``mlp.expert_bias``, ``mlp.shared_experts.*`` and
    ``mlp.experts.N.*``. The rotary halves are as the program rotates
    them (rotate-half): nothing is permuted."""
    t = _read_safetensors(model_dir)
    dt, V, Ld = cfg.dtype, cfg.vocab_size, cfg.n_dense_layers
    every, routed = range(cfg.n_layers), range(Ld, cfg.n_layers)

    def stack(layers, name: str, dtype=dt, matrix: bool = True):
        return jnp.asarray(np.stack(
            [t[f"model.layers.{l}.{name}"].T if matrix
             else t[f"model.layers.{l}.{name}"] for l in layers]),
            dtype=dtype)

    def experts(l: int) -> tuple:
        pre = f"model.layers.{l}.mlp.experts."
        held = range(*cfg.held)
        gu = np.stack([np.concatenate(
            [t[f"{pre}{e}.gate_proj.weight"].T,
             t[f"{pre}{e}.up_proj.weight"].T], axis=1) for e in held])
        return gu, np.stack([t[f"{pre}{e}.down_proj.weight"].T
                             for e in held])

    per_layer = [experts(l) for l in routed]
    norms = {"attn_norm": "input_layernorm",
             "post_attn_norm": "post_attention_layernorm",
             "mlp_norm": "pre_mlp_layernorm",
             "post_mlp_norm": "post_mlp_layernorm",
             "q_norm": "self_attn.q_norm", "k_norm": "self_attn.k_norm"}
    mats = {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj",
            "wg": "gate_proj", "wo": "o_proj"}
    return {
        "embed": jnp.asarray(t["model.embed_tokens.weight"][:V], dtype=dt),
        "lm_head": jnp.asarray(t["lm_head.weight"][:V].T, dtype=dt),
        "final_norm": jnp.asarray(t["model.norm.weight"], dtype=dt),
        "layers": {
            **{k: stack(every, f"self_attn.{v}.weight")
               for k, v in mats.items()},
            **{k: stack(every, f"{v}.weight", matrix=False)
               for k, v in norms.items()}},
        "dense": {f"w_{k}": stack(range(Ld), f"mlp.{k}_proj.weight")
                  for k in ("gate", "up", "down")},
        "moe": {
            "router": stack(routed, "mlp.router.gate.weight"),
            "router_bias": stack(routed, "mlp.expert_bias", jnp.float32,
                                 matrix=False),
            **{f"ws_{k}": stack(routed,
                                f"mlp.shared_experts.{k}_proj.weight")
               for k in ("gate", "up", "down")},
            "we_gate_up": tuple(jnp.asarray(g, dtype=dt)
                                for g, _ in per_layer),
            "we_down": tuple(jnp.asarray(d, dtype=dt)
                             for _, d in per_layer)},
    }


def import_hf(model_dir: str, cfg, **kw) -> Params:
    """A local Hugging Face checkpoint directory into the tree of
    ``cfg``'s model family, by that family's ``import_hf``
    (``models/__init__.py``); ``kw`` is the family's own (the Llama
    block's ``meta_rope_layout``)."""
    from llmq_tpu.models import family_of
    return family_of(cfg).import_hf(model_dir, cfg, **kw)
