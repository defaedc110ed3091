"""The Arcee ``afmoe`` block in the program: the one file of the family
that imports ``llmq_tpu``. The surface is ``families/llama/adapter.py``'s,
and the procedure ``families/longcat_flash/adapter.py``'s:

- ``register(name, config)``: the configuration file (the public
  ``config.json``'s keys at its top level, with ``num_hidden_layers``,
  ``num_experts`` and ``vocab_size`` THIS CHIP'S share and
  ``dense_layers_held`` / ``router_experts`` / ``expert_share`` saying
  of what) as one more entry of the program's registry
  (``llmq_tpu/models/afmoe.py`` ``MODEL_CONFIGS``) — the program is not
  edited;
- ``param_builder(mcfg, server_model)``: ``build(key) -> params``,
  random weights in the served type for ONE jitted call on the device;
- ``serving_path(mcfg, server)``: what the logits check drives — the
  program's own ``forward_prefill(last_only=True)`` and
  ``forward_decode`` through the page pool AND the sliding layers'
  slabs, with the kernels the served programs route to. For a
  configuration that states a ``tolerance`` it also hands the family's
  reference ``served_many`` (``reference.JUDGED``).

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
#: the scores' spread, as ``families/deepseek_v3``'s (the same sigmoid
#: scores).
ROUTER_BIAS = 0.02
#: Teacher-forced decode steps ``served_many`` drives each of its rows
#: through both kinds of cache, and the rows of its decode batch (the
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

    from llmq_tpu.models import afmoe

    if (config.get("score_func", "sigmoid") != "sigmoid"
            or config.get("tie_word_embeddings", False)):
        raise ValueError(f"{name}: the program's afmoe block routes by "
                         f"sigmoid scores and has an untied head")
    shapes = _part("shapes")
    L = config["num_hidden_layers"]
    base = afmoe.AfmoeConfig(
        name=name, vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        layer_types=tuple(config["layer_types"][:L]),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        sliding_window=config["sliding_window"],
        ffn_dim=config["intermediate_size"],
        n_dense_layers=shapes.dense_layers(config),
        moe_ffn_dim=config["moe_intermediate_size"],
        n_routed_experts=config["router_experts"],
        n_experts_per_tok=config["num_experts_per_tok"],
        n_shared_experts=config["num_shared_experts"],
        route_scale=float(config["route_scale"]),
        route_norm=bool(config["route_norm"]),
        held_experts=shapes.held_experts(config),
        mup_enabled=bool(config.get("mup_enabled", True)),
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]), dtype=jnp.bfloat16)
    afmoe.MODEL_CONFIGS[name] = (
        lambda **kw: dataclasses.replace(base, **kw))
    if "tolerance" in config:
        _TOLERANCE[name] = config["tolerance"]
    return base


def param_builder(mcfg, server_model: Dict[str, Any]):
    """``build(key) -> params`` in the program's tree
    (``afmoe.param_shapes`` / ``assemble``). Uniform in (-a, a) with
    a = sqrt(3 / fan_in) (the variance of the program's own normal
    init), the hardware generator ("rbg"); a leaf is drawn one slice of
    its leading axis at a time (a routed layer's gate-and-up leaf is
    0.6 GB: its random bits drawn at once are twice that). RMSNorm
    weights are ones; the router's selection bias is uniform in
    (-ROUTER_BIAS, ROUTER_BIAS). No matrix needs another scale: q and k
    are normalised per head (scores of unit variance), every sublayer's
    result is normalised before it joins the stream, and the embedding,
    drawn at 1 / hidden, comes out at unit scale after its sqrt(hidden)."""
    import jax
    import jax.numpy as jnp

    from llmq_tpu.models import afmoe

    if server_model.get("quantization") or server_model.get(
            "kv_quantization"):
        afmoe.check_serving(
            mcfg, quantization=server_model.get("quantization", ""),
            kv_quantization=server_model.get("kv_quantization", ""))
    shapes = afmoe.param_shapes(mcfg)

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
        keys = jax.random.split(key, len(names) + 1)
        drawn: Dict[str, Dict[str, Any]] = {g: {} for g in shapes}
        for k, (g, n) in zip(keys, names):
            if g == "experts":     # a leaf of its own a routed layer
                drawn[g][n] = [draw(kk, *shapes[g][n]) for kk in
                               jax.random.split(k, mcfg.n_routed_layers)]
            else:
                drawn[g][n] = draw(k, *shapes[g][n])
        params = afmoe.assemble(mcfg, drawn)
        bias = params["moe"]["router_bias"]
        params["moe"]["router_bias"] = jax.random.uniform(
            keys[-1], bias.shape, bias.dtype, -ROUTER_BIAS, ROUTER_BIAS)
        return params

    return build


def _bound(mcfg, server: Dict[str, Any]):
    """``mcfg`` with the slabs cut as the cell's executor cuts them:
    its pages, and a step's writes for one sequence the larger of its
    prefill bucket and one slice of its mixed step."""
    from llmq_tpu.models import afmoe

    ex = server["executor"]
    mixed = ex.get("mixed_batch") or {}
    step = max(max(ex["prefill_buckets"]),
               int(mixed.get("prefill_token_budget", 0))
               // max(1, int(mixed.get("max_slices", 1)))
               if mixed.get("enabled") else 0)
    return afmoe.bind_cache(afmoe.serving_config(mcfg),
                            page_size=int(ex["page_size"]), step_tokens=step)


def serving_path(mcfg, server: Dict[str, Any]) -> SimpleNamespace:
    """The serving path's model functions at the configuration's
    ``server`` block: ``cache(n)`` a page pool of ``n`` pages beside the
    slabs of the check's 8 rows, ``prefill`` (last position's logits)
    and ``decode`` as the served programs call them."""
    import jax.numpy as jnp

    from llmq_tpu.models.afmoe import (forward_decode, forward_prefill,
                                       init_kv_pages, init_row_state)

    cfg = _bound(mcfg, server)
    check_rows = 8                       # harness/child.check_logits

    def cache(n_pages: int):
        return {"pages": init_kv_pages(cfg, n_pages, cfg.page_size),
                "rows": init_row_state(cfg, check_rows)}

    def prefill(params, cache, tokens, positions, lens, bts):
        rows = (bts[:, 0] - 1) // bts.shape[1]     # the check's tables
        logits, pages, state = forward_prefill(
            params, cfg, tokens, positions, lens, cache["pages"], bts,
            last_only=True, row_state=cache["rows"],
            rows=rows.astype(jnp.int32))
        return logits, {"pages": pages, "rows": state}

    def decode(params, cache, tokens, positions, bts, active):
        logits, pages, state = forward_decode(
            params, cfg, tokens, positions, cache["pages"], bts,
            active=active, row_state=cache["rows"])
        return logits, {"pages": pages, "rows": state}

    if mcfg.name in _TOLERANCE:
        _part("reference").JUDGED = (_served_many(cfg, server),
                                     _TOLERANCE[mcfg.name])
    return SimpleNamespace(cache=cache, prefill=prefill, decode=decode,
                           ident=str(cfg), vocab_size=cfg.vocab_size)


def judged_starts(n: int, steps: int, window: int):
    """Where ``served_many``'s rows start to decode in a sequence of
    ``n`` tokens: the last ``steps`` positions, a run across the
    window's edge (from ``window - 6``), one well inside it (from a
    quarter of it) and one at half the prompt — those that leave
    ``steps`` positions, the first (the longest context) first."""
    last = n - steps
    starts = [last] + [s for s in (window - 6, window // 4, last // 2)
                       if 1 <= s < last]
    return sorted(set(starts), reverse=True)[:JUDGED_ROWS]


def _served_many(cfg, server: Dict[str, Any]):
    """``reference.JUDGED``'s ``served_many(params, tokens)`` over the
    serving path, the prompt going in as the engine's own slices:

    - ``prefill``: every position of ``tokens`` before the last
      ``JUDGED_STEPS``, through ``forward_prefill`` a bucket at a time
      in batch row 0, each slice continuing what the pool and the slab
      hold (at 6k+ tokens the slab has wrapped and the window's edge
      falls inside most slices); and, in the same group (a handful of
      positions are no distribution to judge), the other rows' prompts
      through ``forward_mixed``, one live slice a step, as a served
      mixed chunk runs them: the last position of each slice;
    - ``decode``: ``JUDGED_STEPS`` teacher-forced steps of every row
      (``judged_starts``: behind the longest context, across the
      window's edge, inside the window) in ONE batch of the check's 8
      rows, the others not active."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmq_tpu.models.afmoe import (forward_decode, forward_mixed,
                                       forward_prefill, init_kv_pages,
                                       init_row_state)
    from llmq_tpu.ops.rows import pack_grid

    ex = server["executor"]
    ps = cfg.page_size
    bucket = int(max(ex["prefill_buckets"]))
    mixed_cfg = ex.get("mixed_batch") or {}
    S = int(mixed_cfg.get("max_slices", 1))
    T = int(mixed_cfg.get("prefill_token_budget", bucket)) // S
    B = JUDGED_ROWS

    @partial(jax.jit, donate_argnums=(1, 2))
    def prefill_all(params, cache, state, tokens, start, n, bts, rows):
        positions = start + jnp.minimum(
            jnp.arange(bucket, dtype=jnp.int32)[None], n - 1)
        logits, cache, state = forward_prefill(
            params, cfg, tokens, positions, n[None], cache, bts,
            row_state=state, rows=rows)
        return logits[0].astype(jnp.float32), cache, state

    @partial(jax.jit, donate_argnums=(1, 2))
    def mixed(params, cache, state, dec_bts, pf_tok, pf_pos, pf_len,
              pf_start, pf_bts, pf_rows):
        zeros = jnp.zeros((B,), jnp.int32)
        _, pf_logits, cache, state = forward_mixed(
            params, cfg, zeros, zeros, cache, dec_bts, pf_tok, pf_pos,
            pf_len, pf_start, pf_bts, dec_active=jnp.zeros((B,), bool),
            row_state=state, pf_rows=pf_rows)
        return pf_logits[0].astype(jnp.float32), cache, state

    @partial(jax.jit, donate_argnums=(1, 2))
    def step(params, cache, state, tok, pos, bts, active):
        logits, cache, state = forward_decode(
            params, cfg, tok, pos, cache, bts, active=active,
            row_state=state)
        return logits.astype(jnp.float32), cache, state

    def served_many(params, tokens):
        tokens = np.asarray(tokens, np.int32)
        n = len(tokens)
        steps = min(JUDGED_STEPS, n // 2)
        if n > cfg.max_seq_len or steps < 1:
            raise ValueError(f"{n} tokens: the judged sequence holds 2 to "
                             f"{cfg.max_seq_len}")
        starts = judged_starts(n, steps, cfg.sliding_window)
        R = len(starts)
        pages = -(-n // ps)
        cache = init_kv_pages(cfg, 1 + R * pages, ps)
        state = init_row_state(cfg, B)
        bts = np.zeros((B, cfg.max_seq_len // ps), np.int32)
        bts[:R, :pages] = 1 + np.arange(R * pages,
                                        dtype=np.int32).reshape(R, pages)
        dev_bts = jnp.asarray(bts)
        out: Dict[str, Any] = {}
        # row 0: every prompt position, a bucket at a time
        every = []
        for a in range(0, starts[0], bucket):
            m = min(bucket, starts[0] - a)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :m] = tokens[a:a + m]
            logits, cache, state = prefill_all(
                params, cache, state, jnp.asarray(toks), jnp.int32(a),
                jnp.int32(m), dev_bts[:1], jnp.zeros((1,), jnp.int32))
            every.append(np.asarray(logits[:m]))
        # the other rows: one live slice a mixed step
        at, last = [np.arange(starts[0])], every
        for r in range(1, R):
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
                logits, cache, state = mixed(
                    params, cache, state, dev_bts, jnp.asarray(pf_tok),
                    jnp.asarray(pf_pos), jnp.asarray(lens),
                    jnp.asarray(pf_start), jnp.asarray(pf_bts),
                    jnp.asarray(rows))
                at.append(np.asarray([a + m - 1]))
                last.append(np.asarray(logits)[None])
        out["prefill"] = (np.concatenate(at), np.concatenate(last))
        active = jnp.asarray(np.arange(B) < R)
        first = np.asarray(starts)
        stepped = []
        for j in range(steps):
            tok, pos = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
            tok[:R], pos[:R] = tokens[first + j], first + j
            logits, cache, state = step(
                params, cache, state, jnp.asarray(tok), jnp.asarray(pos),
                dev_bts, active)
            stepped.append(np.asarray(logits[:R]))
        got = np.stack(stepped)                        # (steps, R, V)
        for r in range(R):
            out[f"decode_from_{starts[r]}"] = (
                starts[r] + np.arange(steps), got[:, r])
        return out

    return served_many
