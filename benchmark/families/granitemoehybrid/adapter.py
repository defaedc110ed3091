"""The Granite-4.0-H block in the program: the one file of the family
that imports ``llmq_tpu``. The surface is ``families/llama/adapter.py``'s:

- ``register(name, config)``: the configuration file (the public
  ``config.json``'s keys at its top level) as one more entry of the
  program's registry (``llmq_tpu/models/granitemoehybrid.py``
  ``MODEL_CONFIGS``) — the program is not edited. ``config["control"]``
  (never in a committed file: the builder's control runs set it) may
  name ``state_dtype``, the type the recurrent state is HELD in;
- ``param_builder(mcfg, server_model)``: ``build(key) -> params``,
  random weights in the served type for ONE jitted call on the device;
- ``serving_path(mcfg, server)``: what the logits check drives — the
  program's own ``forward_prefill(last_only=True)`` and
  ``forward_decode`` through the page pool AND the row state. The
  harness's check knows one cache; it is handed ``{"pages", "rows"}``
  and never looks inside. It names no batch row either: its sequence
  ``r`` decodes in batch row ``r`` and owns the block table ``1 + r *
  max_pages + ...``, so the prefill reads the row out of the table's
  first page. For a configuration that states ``tolerance.decode_rms``
  it also hands the family's reference ``served_many``
  (``reference.JUDGED``): a prompt in two slices through the MIXED step
  (state carried between programs), then ``JUDGED_STEPS`` decode steps
  at the served batch width.

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
#: Decode steps ``served_many`` drives through the carried state: of
#: the check's 510-token prompt the last 384, behind a prompt of 126 in
#: two slices. The bfloat16-state control's error grows with the steps
#: (1.08-1.26 x the served path's worst position after 128, 1.21-1.35 x
#: its last quarter's mean after 384: my chip runs, PR 39), so the more
#: steps the further apart the two readings lie; 384 cost the set-up
#: 8 s more than 128.
JUDGED_STEPS = 384
#: name -> the ``tolerance`` of the configuration ``register`` was given.
_TOLERANCE: Dict[str, Dict[str, Any]] = {}


def _part(name: str):
    from benchmark.harness import contract
    return contract.load_family(HERE, name)


def register(name: str, config: Dict[str, Any]):
    import jax.numpy as jnp

    from llmq_tpu.models import granitemoehybrid as gm

    if (config.get("num_local_experts", 0) or config.get("mamba_n_groups", 1)
            != 1 or config.get("position_embedding_type", "nope") != "nope"
            or config.get("attention_bias") or config.get("mamba_proj_bias")
            or not config.get("mamba_conv_bias", True)
            or not config.get("tie_word_embeddings", True)):
        raise ValueError(f"{name}: the program's granitemoehybrid block has "
                         f"no routed experts, one B/C group, no rotary "
                         f"embedding, a convolution bias and no other, and "
                         f"a tied head")
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    if inner != config["mamba_expand"] * config["hidden_size"]:
        raise ValueError(f"{name}: mamba heads x head != expand x hidden")
    control = config.get("control") or {}
    base = gm.GraniteHybridConfig(
        name=name, vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        ffn_dim=config["shared_intermediate_size"],
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_chunk_size=config["mamba_chunk_size"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        max_seq_len=config["max_position_embeddings"],
        norm_eps=float(config["rms_norm_eps"]), dtype=jnp.bfloat16,
        state_dtype=jnp.dtype(control.get("state_dtype", "float32")))
    if len(base.layer_types) != config["num_hidden_layers"]:
        raise ValueError(f"{name}: layer_types names "
                         f"{len(base.layer_types)} layers of "
                         f"{config['num_hidden_layers']}")
    gm.MODEL_CONFIGS[name] = lambda **kw: dataclasses.replace(base, **kw)
    if "decode_rms" in (config.get("tolerance") or {}):
        _TOLERANCE[name] = config["tolerance"]
    return base


def param_builder(mcfg, server_model: Dict[str, Any]):
    """``build(key) -> params`` in the program's tree
    (``granitemoehybrid.param_shapes``). A matrix is uniform in (-a, a)
    with a = sqrt(3 / fan_in) (the variance of the program's own normal
    init), the hardware generator ("rbg"), drawn one slice of its
    leading axis at a time; RMSNorm weights are ones; the convolution's
    bias is uniform in (-0.1, 0.1) (not zero: a program that dropped it
    would go unnoticed); ``A_log``, ``dt_bias`` and ``D`` are the
    program's own draw (``granitemoehybrid.recurrence_init``: A in
    (1, 16), dt log-uniform in (0.001, 0.1), D ones — the configuration
    file's ``assumed`` has why)."""
    import jax
    import jax.numpy as jnp

    from llmq_tpu.models import granitemoehybrid as gm

    if server_model.get("quantization") or server_model.get(
            "kv_quantization"):
        gm.check_serving(
            mcfg, quantization=server_model.get("quantization", ""),
            kv_quantization=server_model.get("kv_quantization", ""))
    shapes = gm.param_shapes(mcfg)

    def draw(key, shape, fan_in):
        a = (3.0 / fan_in) ** 0.5

        def one(k, shp):
            return jax.random.uniform(k, shp, jnp.bfloat16, -a, a)

        if len(shape) >= 3:
            return jax.lax.map(lambda k: one(k, shape[1:]),
                               jax.random.split(key, shape[0]))
        return one(key, shape)

    def build(key):
        names = sorted(shapes)
        keys = dict(zip(names, jax.random.split(key, len(names))))
        layers = {}
        for name in names:
            shape, fan_in = shapes[name]
            if fan_in:
                layers[name] = draw(keys[name], shape, fan_in)
            elif name.endswith("norm"):
                layers[name] = jnp.ones(shape, jnp.bfloat16)
        layers["conv_b"] = jax.random.uniform(
            keys["conv_b"], shapes["conv_b"][0], jnp.bfloat16, -0.1, 0.1)
        layers.update(gm.recurrence_init(keys["a_log"],
                                         shapes["a_log"][0]))
        return {"embed": draw(jax.random.fold_in(key, 1),
                              (mcfg.vocab_size, mcfg.dim), mcfg.dim),
                "layers": layers,
                "final_norm": jnp.ones((mcfg.dim,), jnp.bfloat16)}

    return build


def serving_path(mcfg, server: Dict[str, Any]) -> SimpleNamespace:
    """The serving path's model functions at the configuration's
    ``server`` block: ``cache(n)`` a page pool of ``n`` pages beside the
    row state of the check's 8 rows, ``prefill`` (last position's
    logits) and ``decode`` as the served programs call them."""
    import jax.numpy as jnp

    from llmq_tpu.models.granitemoehybrid import (forward_decode,
                                                  forward_prefill,
                                                  init_kv_pages,
                                                  init_row_state)

    page_size = int(server["executor"]["page_size"])
    check_rows = 8                       # harness/child.check_logits

    def cache(n_pages: int):
        return {"pages": init_kv_pages(mcfg, n_pages, page_size),
                "rows": init_row_state(mcfg, check_rows)}

    def prefill(params, cache, tokens, positions, lens, bts):
        rows = (bts[:, 0] - 1) // bts.shape[1]       # the module's text
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


def _served_many(mcfg, server: Dict[str, Any]):
    """``reference.JUDGED``'s ``served_many(params, tokens)``: the last
    ``JUDGED_STEPS`` positions of ``tokens`` through the served decode
    path after a prompt that reached the state through TWO slices.

    Two batch rows of the served width B run the same sequence: what
    precedes the judged positions is cut in two at different places
    (row 0 near a half, row 1 near a third) and each part goes through
    ``forward_mixed`` as the one live slice of a mixed step, in a
    program of its own — so the state is carried from program to program
    in the row-state leaves, as between two served chunks — and then
    both rows are teacher-forced ``JUDGED_STEPS`` decode steps in one
    batch of B rows (the others inactive), one jitted step a call as
    ``decode_chunk``'s loop body runs it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmq_tpu.models.granitemoehybrid import (forward_decode,
                                                  forward_mixed,
                                                  init_kv_pages,
                                                  init_row_state)
    from llmq_tpu.ops.rows import pack_grid

    ex = server["executor"]
    page_size = int(ex["page_size"])
    B = int(ex["max_batch_size"])
    S = int(ex["mixed_batch"]["max_slices"])
    T = int(ex["mixed_batch"]["prefill_token_budget"]) // S
    R = 2

    # (pool and row state donated, as the served programs take them:
    # two copies of 64 rows' state beside the weights pass the chip)
    @partial(jax.jit, donate_argnums=(1, 2))
    def mixed(params, cache, state, dec_bts, pf_tok, pf_pos, pf_len,
              pf_start, pf_bts, pf_rows):
        zeros = jnp.zeros((B,), jnp.int32)
        _, pf_logits, cache, state = forward_mixed(
            params, mcfg, zeros, zeros, cache, dec_bts, pf_tok, pf_pos,
            pf_len, pf_start, pf_bts, dec_active=jnp.zeros((B,), bool),
            row_state=state, pf_rows=pf_rows)
        return pf_logits, cache, state

    @partial(jax.jit, donate_argnums=(1, 2))
    def step(params, cache, state, tok, pos, bts, active):
        return forward_decode(params, mcfg, tok, pos, cache, bts,
                              active=active, row_state=state)

    def served_many(params, tokens):
        tokens = np.asarray(tokens, np.int32)
        n = len(tokens)
        first = n - JUDGED_STEPS
        if first < 2 or first > 2 * T:
            raise ValueError(f"{n} tokens: {JUDGED_STEPS} judged positions "
                             f"behind a prompt of 2 to {2 * T}")
        pages = -(-(n + 1) // page_size)
        cache = init_kv_pages(mcfg, 1 + R * pages, page_size)
        state = init_row_state(mcfg, B)
        max_pages = max(pages, 1)
        bts = np.zeros((B, max_pages), np.int32)
        bts[:R] = 1 + np.arange(R * pages, dtype=np.int32).reshape(R, pages)
        cuts = [max(1, min(T, first // 2)), max(1, min(T, first // 3))]
        cuts = [max(c, first - T) for c in cuts]
        for r in range(R):
            for a, b in ((0, cuts[r]), (cuts[r], first)):
                g_t = np.zeros((S, T), np.int32)
                g_p = np.zeros((S, T), np.int32)
                g_t[0, :b - a] = tokens[a:b]
                g_p[0, :b - a] = np.arange(a, b)
                lens = np.ones((S,), np.int32)
                lens[0] = b - a
                pf_tok, pf_pos, starts = pack_grid(g_t, g_p, lens, used=1)
                pf_bts = np.zeros((S, max_pages), np.int32)
                pf_bts[0] = bts[r]
                rows = np.full((S,), B, np.int32)
                rows[0] = r
                _, cache, state = mixed(
                    params, cache, state, jnp.asarray(bts),
                    jnp.asarray(pf_tok), jnp.asarray(pf_pos),
                    jnp.asarray(lens), jnp.asarray(starts),
                    jnp.asarray(pf_bts), jnp.asarray(rows))
        active = jnp.asarray(np.arange(B) < R)
        stepped = []
        for j in range(JUDGED_STEPS):
            tok = np.zeros((B,), np.int32)
            pos = np.zeros((B,), np.int32)
            tok[:R], pos[:R] = tokens[first + j], first + j
            logits, cache, state = step(params, cache, state,
                                        jnp.asarray(tok), jnp.asarray(pos),
                                        jnp.asarray(bts), active)
            stepped.append(np.asarray(logits[:R], np.float32))
        out = np.stack(stepped)                        # (steps, R, V)
        at = first + np.arange(JUDGED_STEPS)
        return {f"decode_row{r}": (at, out[:, r]) for r in range(R)}

    return served_many
