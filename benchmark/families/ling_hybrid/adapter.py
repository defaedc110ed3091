"""The Ling-3.0-flash block in the program: the one file of the family
that imports ``llmq_tpu``. The surface is ``families/llama/adapter.py``'s,
and the procedure ``families/afmoe/adapter.py``'s:

- ``register(name, config)``: the configuration file (the public
  ``config.json``'s keys at its top level, with ``num_hidden_layers``,
  ``num_experts`` and ``vocab_size`` THIS CHIP'S share and
  ``dense_layers_held`` / ``router_experts`` / ``expert_share`` saying
  of what) as one more entry of the program's registry
  (``llmq_tpu/models/ling_hybrid.py`` ``MODEL_CONFIGS``) — the program
  is not edited;
- ``param_builder(mcfg, server_model)``: ``build(key) -> params``,
  random weights in the served type for ONE jitted call on the device;
- ``serving_path(mcfg, server)``: what the logits check drives — the
  program's own ``forward_prefill(last_only=True)`` and
  ``forward_decode`` through the latent pool AND the row state. For a
  configuration that states a ``tolerance`` it also hands the family's
  reference ``served_many`` (``reference.JUDGED``): the same programs
  over many positions, with the experts they chose.

A parent of the PR that brought this family has no such module in the
program: ``register`` then fails at its import, at once.
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial
from types import SimpleNamespace
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))
#: The router's selection bias is drawn uniform in (-b, b): not zero (a
#: program that used it in the gates would go unnoticed), small against
#: the scores' spread, as ``families/afmoe``'s (the same sigmoid scores).
ROUTER_BIAS = 0.02
#: Tokens a chunk of the program's scan (``ops/kda.kda_scan``): what a
#: decay a channel allows without a reference point inside the chunk.
KDA_CHUNK = 16
#: Teacher-forced decode steps ``served_many`` drives each of its rows
#: through the state and the pool, and the rows of its decode batch (the
#: check's own).
JUDGED_STEPS, JUDGED_ROWS = 128, 8
#: name -> the ``tolerance`` of the configuration ``register`` was given.
_TOLERANCE: Dict[str, Dict[str, Any]] = {}


def _part(name: str):
    from benchmark.harness import contract
    return contract.load_family(HERE, name)


def register(name: str, config: Dict[str, Any]):
    """``config`` holds the keys of ``shapes.MODEL_KEYS`` at its top
    level: the whole configuration file, or its ``model`` block."""
    import jax.numpy as jnp

    from llmq_tpu.models import ling_hybrid as lh

    if (config.get("score_function", "sigmoid") != "sigmoid"
            or config.get("q_lora_rank") is not None
            or config.get("use_mla_nope") or config.get("use_kda_lora")
            or not config.get("no_kda_lora", True)
            or not config.get("kda_safe_gate", True)
            or not config.get("linear_silu", True)
            or not config.get("use_qk_norm", True)
            or not config.get("moe_router_enable_expert_bias", True)
            or config.get("scale_router_input")
            or config.get("group_norm_size", 1) != 1
            or config.get("num_kv_heads_for_linear_attn", 0)
            or config.get("gated_attention_proj_granularity_type",
                          "head_wise") != "head_wise"
            or config.get("rotary_dim", config["qk_rope_head_dim"])
            != config["qk_rope_head_dim"]):
        raise ValueError(
            f"{name}: the program's ling_hybrid block has sigmoid scores "
            f"with a selection bias, a full query, rotated latent layers "
            f"with a head-wise gate, and KDA with full-rank gates, the "
            f"safe gate, silu, L2-normed q/k, one norm group a head and "
            f"as many key heads as query heads")
    shapes = _part("shapes")
    L = config["num_hidden_layers"]
    moe_f = config["moe_intermediate_size"]
    base = lh.LingHybridConfig(
        name=name, vocab_size=config["vocab_size"],
        dim=config["hidden_size"], n_layers=L,
        layer_group_size=config["layer_group_size"],
        first_k_dense=shapes.dense_layers(config),
        n_heads=config["num_attention_heads"],
        kda_head_dim=config["head_dim"],
        kda_conv=config["short_conv_kernel_size"],
        kda_lower_bound=float(config["kda_lower_bound"]),
        kda_chunk=KDA_CHUNK,
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        ffn_dim=config["intermediate_size"], moe_ffn_dim=moe_f,
        n_routed_experts=config["router_experts"],
        n_experts_per_tok=config["num_experts_per_tok"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        n_shared_experts=(config["moe_shared_expert_intermediate_size"]
                          // moe_f),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        held_experts=shapes.held_experts(config),
        expert_swiglu_limit=tuple(config["expert_swiglu_limit_list"]),
        shared_swiglu_limit=tuple(config["share_expert_swiglu_limit_list"]),
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]), dtype=jnp.bfloat16)
    if (len(base.expert_swiglu_limit) != L
            or len(base.shared_swiglu_limit) != L):
        raise ValueError(f"{name}: the SwiGLU limit lists name "
                         f"{len(base.expert_swiglu_limit)} and "
                         f"{len(base.shared_swiglu_limit)} layers of {L}")
    lh.check_serving(base)          # a held layer that clamps is refused
    lh.MODEL_CONFIGS[name] = lambda **kw: dataclasses.replace(base, **kw)
    if "tolerance" in config:
        _TOLERANCE[name] = config["tolerance"]
    return base


def param_builder(mcfg, server_model: Dict[str, Any]):
    """``build(key) -> params`` in the program's tree
    (``ling_hybrid.param_shapes`` / ``assemble``). A matrix is uniform in
    (-a, a) with a = sqrt(3 / fan_in) (the variance of the program's own
    normal init), the hardware generator ("rbg"), drawn one slice of its
    leading axis at a time; RMSNorm weights are ones; the router's
    selection bias is uniform in (-ROUTER_BIAS, ROUTER_BIAS); the
    decay's ``A_log`` and ``b_f`` are the program's own draw
    (``ling_hybrid.decay_init``: the configuration file's ``assumed``
    has the ranges and why). No matrix needs another scale: q and k are
    normalised a head, the KDA output is normalised a head before its
    gate, the latent is normalised before it is expanded."""
    import jax
    import jax.numpy as jnp

    from llmq_tpu.models import ling_hybrid as lh

    if server_model.get("quantization") or server_model.get(
            "kv_quantization"):
        lh.check_serving(
            mcfg, quantization=server_model.get("quantization", ""),
            kv_quantization=server_model.get("kv_quantization", ""))
    shapes = lh.param_shapes(mcfg)

    def draw(key, shape, fan_in):
        a = (3.0 / fan_in) ** 0.5

        def one(k, shp):
            return jax.random.uniform(k, shp, jnp.bfloat16, -a, a)

        if len(shape) >= 3:
            return jax.lax.map(lambda k: one(k, shape[1:]),
                               jax.random.split(key, shape[0]))
        return one(key, shape)

    def build(key):
        names = [(g, n) for g, leaves in shapes.items() for n in leaves]
        keys = jax.random.split(key, len(names) + 2)
        drawn: Dict[str, Dict[str, Any]] = {g: {} for g in shapes}
        for k, (g, n) in zip(keys, names):
            if g == "experts":     # a leaf of its own a routed layer
                drawn[g][n] = [draw(kk, *shapes[g][n]) for kk in
                               jax.random.split(k, mcfg.n_routed_layers)]
            else:
                drawn[g][n] = draw(k, *shapes[g][n])
        params = lh.assemble(mcfg, drawn, lh.decay_init(keys[-2], mcfg))
        bias = params["moe"]["router_bias"]
        params["moe"]["router_bias"] = jax.random.uniform(
            keys[-1], bias.shape, bias.dtype, -ROUTER_BIAS, ROUTER_BIAS)
        return params

    return build


def serving_path(mcfg, server: Dict[str, Any]) -> SimpleNamespace:
    """The serving path's model functions at the configuration's
    ``server`` block: ``cache(n)`` a latent pool of ``n`` pages beside
    the row state of the check's 8 rows, ``prefill`` (last position's
    logits) and ``decode`` as the served programs call them. The
    harness's check names no batch row: its sequence ``r`` decodes in
    batch row ``r`` and owns the block table ``1 + r * max_pages + ...``,
    so the prefill reads the row out of the table's first page."""
    import jax.numpy as jnp

    from llmq_tpu.models.ling_hybrid import (forward_decode, forward_prefill,
                                             init_kv_pages, init_row_state)

    page_size = int(server["executor"]["page_size"])
    check_rows = 8                       # harness/child.check_logits

    def cache(n_pages: int):
        return {"pages": init_kv_pages(mcfg, n_pages, page_size),
                "rows": init_row_state(mcfg, check_rows)}

    def prefill(params, cache, tokens, positions, lens, bts):
        rows = (bts[:, 0] - 1) // bts.shape[1]
        logits, pages, state = forward_prefill(
            params, mcfg, tokens, positions, lens, cache["pages"], bts,
            last_only=True, row_state=cache["rows"],
            rows=rows.astype(jnp.int32))
        return logits, {"pages": pages, "rows": state}

    def decode(params, cache, tokens, positions, bts, active):
        logits, pages, state = forward_decode(
            params, mcfg, tokens, positions, cache["pages"], bts,
            active=active, row_state=cache["rows"])
        return logits, {"pages": pages, "rows": state}

    if mcfg.name in _TOLERANCE:
        _part("reference").JUDGED = (_served_many(mcfg, server),
                                     _TOLERANCE[mcfg.name])
    return SimpleNamespace(cache=cache, prefill=prefill, decode=decode,
                           ident=str(mcfg), vocab_size=mcfg.vocab_size)


def judged_starts(n: int, steps: int):
    """Where ``served_many``'s rows start to decode in a sequence of
    ``n`` tokens: the last ``steps`` positions (row 0), a start behind a
    slice that ends mid-chunk (half the prompt and five), and one inside
    the first slice (a quarter and three) — those that leave ``steps``
    positions, the longest context first."""
    last = n - steps
    starts = [last] + [s for s in (last // 2 + 5, last // 4 + 3)
                       if 1 <= s < last]
    return sorted(set(starts), reverse=True)[:JUDGED_ROWS]


def _served_many(cfg, server: Dict[str, Any]):
    """``served_many(params, tokens) -> (groups, chosen)``
    (``reference.JUDGED``) over the serving path of ``cfg``, the prompt
    going in as the engine's own slices, every program asked for the
    experts it chose (``chosen=True``):

    - ``prefill`` (batch row 0): every position of ``tokens`` before the
      last ``JUDGED_STEPS``, through ``forward_prefill`` a bucket at a
      time, each slice continuing the state the chunked scan left in
      the row-state leaves and the pages the latent layers wrote;
    - ``mixed_to_<start>`` (the other rows): their prompts through
      ``forward_mixed``, one live slice a step, as a served mixed chunk
      runs them — the last position of each slice; their last slices
      end in the middle of a chunk of the scan;
    - ``decode_from_<start>`` (every row): ``JUDGED_STEPS``
      teacher-forced steps from the state the scan left, in ONE batch
      of the check's 8 rows, the others not active (the one-token
      update, in place, over the live rows).

    Each group carries what its row's KDA state held behind its last
    position: the prompt groups what the scan left, the decode groups
    what the update made of it; the decode groups also what the latent
    pool holds of the row's every position (``[c | k^rope]``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmq_tpu.models.ling_hybrid import (forward_decode, forward_mixed,
                                             forward_prefill, init_kv_pages,
                                             init_row_state)
    from llmq_tpu.ops.rows import pack_grid

    ex = server["executor"]
    ps = int(ex["page_size"])
    bucket = int(max(ex["prefill_buckets"]))
    mixed_cfg = ex.get("mixed_batch") or {}
    S = int(mixed_cfg.get("max_slices", 1))
    T = int(mixed_cfg.get("prefill_token_budget", bucket)) // S
    B = JUDGED_ROWS

    @partial(jax.jit, donate_argnums=(1, 2))
    def prefill_all(params, cache, state, tokens, start, n, bts, rows):
        positions = start + jnp.minimum(
            jnp.arange(bucket, dtype=jnp.int32)[None], n - 1)
        logits, cache, state, took = forward_prefill(
            params, cfg, tokens, positions, n[None], cache, bts,
            row_state=state, rows=rows, chosen=True)
        return logits[0].astype(jnp.float32), cache, state, took

    @partial(jax.jit, donate_argnums=(1, 2))
    def mixed(params, cache, state, dec_bts, pf_tok, pf_pos, pf_len,
              pf_start, pf_bts, pf_rows):
        zeros = jnp.zeros((B,), jnp.int32)
        _, pf_logits, cache, state, took = forward_mixed(
            params, cfg, zeros, zeros, cache, dec_bts, pf_tok, pf_pos,
            pf_len, pf_start, pf_bts, dec_active=jnp.zeros((B,), bool),
            row_state=state, pf_rows=pf_rows, chosen=True)
        return pf_logits[0].astype(jnp.float32), cache, state, took[:, :T]

    @partial(jax.jit, donate_argnums=(1, 2))
    def step(params, cache, state, tok, pos, bts, active):
        logits, cache, state, took = forward_decode(
            params, cfg, tok, pos, cache, bts, active=active,
            row_state=state, chosen=True)
        return logits.astype(jnp.float32), cache, state, took

    def states_of(state):
        """The rows' KDA states as the reference writes them: the leaf
        ``(L_k, rows, d_k, H * d_v)`` as ``(rows, L_k, H, d_k, d_v)``."""
        leaf = np.asarray(state["kda"][:, :B], np.float32)
        return leaf.reshape(leaf.shape[:3] + (cfg.n_heads, -1)).transpose(
            1, 0, 3, 2, 4)

    def served_many(params, tokens):
        tokens = np.asarray(tokens, np.int32)
        n = len(tokens)
        steps = min(JUDGED_STEPS, n // 2)
        if n > cfg.max_seq_len or steps < 1:
            raise ValueError(f"{n} tokens: the judged sequence holds 2 to "
                             f"{cfg.max_seq_len}")
        starts = judged_starts(n, steps)
        R = len(starts)
        pages = -(-n // ps)
        cache = init_kv_pages(cfg, 1 + R * pages, ps)
        state = init_row_state(cfg, B)
        bts = np.zeros((B, cfg.max_seq_len // ps), np.int32)
        bts[:R, :pages] = 1 + np.arange(R * pages,
                                        dtype=np.int32).reshape(R, pages)
        dev_bts = jnp.asarray(bts)
        groups: Dict[str, Any] = {}
        chosen = np.full((R, cfg.n_routed_layers, n, cfg.n_experts_per_tok),
                         -1, np.int32)
        # row 0: every prompt position, a bucket at a time
        every = []
        for a in range(0, starts[0], bucket):
            m = min(bucket, starts[0] - a)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :m] = tokens[a:a + m]
            logits, cache, state, took = prefill_all(
                params, cache, state, jnp.asarray(toks), jnp.int32(a),
                jnp.int32(m), dev_bts[:1], jnp.zeros((1,), jnp.int32))
            every.append(np.asarray(logits[:m]))
            chosen[0, :, a:a + m] = np.asarray(took)[:, :m]
        # the other rows: one live slice a mixed step
        mixed_in = {}
        for r in range(1, R):
            at, last = [], []
            for a in range(0, starts[r], T):
                m = min(T, starts[r] - a)
                g_t = np.zeros((S, T), np.int32)
                g_p = np.zeros((S, T), np.int32)
                g_t[0, :m], g_p[0, :m] = tokens[a:a + m], np.arange(a, a + m)
                lens = np.ones((S,), np.int32)
                lens[0] = m
                pf_tok, pf_pos, pf_start = pack_grid(g_t, g_p, lens, used=1)
                pf_bts = np.zeros((S, bts.shape[1]), np.int32)
                pf_bts[0] = bts[r]
                rows = np.full((S,), B, np.int32)
                rows[0] = r
                logits, cache, state, took = mixed(
                    params, cache, state, dev_bts, jnp.asarray(pf_tok),
                    jnp.asarray(pf_pos), jnp.asarray(lens),
                    jnp.asarray(pf_start), jnp.asarray(pf_bts),
                    jnp.asarray(rows))
                at.append(a + m - 1)
                last.append(np.asarray(logits))
                chosen[r, :, a:a + m] = np.asarray(took)[:, :m]
            mixed_in[r] = (np.asarray(at), np.stack(last))
        held = states_of(state)
        groups["prefill"] = dict(row=0, at=np.arange(starts[0]),
                                 logits=np.concatenate(every),
                                 states=held[0], latents=None)
        for r, (at, last) in mixed_in.items():
            groups[f"mixed_to_{starts[r]}"] = dict(
                row=r, at=at, logits=last, states=held[r], latents=None)
        active = jnp.asarray(np.arange(B) < R)
        first = np.asarray(starts)
        stepped = []
        for j in range(steps):
            tok, pos = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
            tok[:R], pos[:R] = tokens[first + j], first + j
            logits, cache, state, took = step(
                params, cache, state, jnp.asarray(tok), jnp.asarray(pos),
                dev_bts, active)
            stepped.append(np.asarray(logits[:R]))
            chosen[np.arange(R), :, first + j] = np.moveaxis(
                np.asarray(took)[:, :R], 1, 0)
        got = np.stack(stepped)                        # (steps, R, V)
        held = states_of(state)
        # what the pool holds of each row's every position: the prompt's
        # rows as the slices wrote them, the last as the decode steps did
        rows = np.asarray(cache["ckv"][:, dev_bts[:R, :pages]], np.float32)
        rows = rows.reshape(cfg.n_latent, R, pages * ps, -1)[
            ..., :cfg.kv_lora_rank + cfg.qk_rope_head_dim]
        for r in range(R):
            groups[f"decode_from_{starts[r]}"] = dict(
                row=r, at=starts[r] + np.arange(steps), logits=got[:, r],
                states=held[r], latents=rows[:, r, :starts[r] + steps])
        return groups, chosen

    return served_many
