"""The ZAYA1 block in the program: the one file of the family that
imports ``llmq_tpu``. The surface is ``families/llama/adapter.py``'s, and
the procedure ``families/ling_hybrid/adapter.py``'s:

- ``register(name, config)``: the configuration file (the public
  ``config.json``'s keys at its top level) as one more entry of the
  program's registry (``llmq_tpu/models/zaya.py`` ``MODEL_CONFIGS``) —
  the program is not edited;
- ``param_builder(mcfg, server_model)``: ``build(key) -> params``,
  random weights in the served type for ONE jitted call on the device;
- ``serving_path(mcfg, server)``: what the logits check drives — the
  program's own ``forward_prefill(last_only=True)`` and
  ``forward_decode`` through the page pool AND the tails. For a
  configuration that states a ``tolerance`` it also hands the family's
  reference ``served_many`` (``reference.JUDGED``): the same programs
  and the mixed step over many positions, with the experts they chose.

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
#: Teacher-forced decode steps ``served_many`` drives each of its rows
#: through the tails and the pool, and the rows of its decode batch (the
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

    from llmq_tpu.models import zaya

    shapes = _part("shapes")
    L = config["num_hidden_layers"]
    types = tuple(config["layer_types"])
    if (len(types) != L or config.get("cca_time0", 2) != 2
            or config.get("cca_time1", 2) != 2
            or config.get("sliding_window") is not None
            or not config.get("tie_word_embeddings", True)
            or config.get("attention_bias") or config.get("lm_head_bias")
            or config.get("hidden_act", "silu") != "silu"):
        raise ValueError(
            f"{name}: the program's zaya block has as many layer_types as "
            f"layers, two 2-tap convolutions, no sliding window, a tied "
            f"head without bias, projections without bias and SiLU experts")
    base = zaya.ZayaConfig(
        name=name, vocab_size=config["vocab_size"],
        dim=config["hidden_size"], layer_types=types,
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rotary_dim=shapes.rotary_dim(config),
        n_experts=config["num_experts"],
        n_experts_per_tok=config["num_experts_per_tok"],
        moe_ffn_dim=config["moe_intermediate_size"],
        router_dim=config["router_hidden_size"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=shapes.rope_theta(config),
        norm_eps=float(config["rms_norm_eps"]), dtype=jnp.bfloat16)
    zaya.check_serving(base)        # a layer that is not hybrid is refused
    zaya.MODEL_CONFIGS[name] = lambda **kw: dataclasses.replace(base, **kw)
    if "tolerance" in config:
        _TOLERANCE[name] = config["tolerance"]
    return base


def param_builder(mcfg, server_model: Dict[str, Any]):
    """``build(key) -> params`` in the program's tree
    (``zaya.param_shapes`` / ``assemble``). A matrix is uniform in (-a,
    a) with a = sqrt(3 / fan_in) (variance 1 / fan_in), the hardware
    generator ("rbg"), drawn one slice of its leading axis at a time;
    what is no matrix is the program's own draw (``zaya.small_init``:
    gains ones; the residual scales, the biases, the carry's gain, the
    key temperature and the selection bias each uniform in a range the
    configuration file's ``assumed`` has, none at its neutral value).
    No matrix needs another scale: q and k are normalised a head, and
    every sublayer reads a normalised stream."""
    import jax
    import jax.numpy as jnp

    from llmq_tpu.models import zaya

    if server_model.get("quantization") or server_model.get(
            "kv_quantization"):
        zaya.check_serving(
            mcfg, quantization=server_model.get("quantization", ""),
            kv_quantization=server_model.get("kv_quantization", ""))
    shapes = zaya.param_shapes(mcfg)

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
            if g == "experts":     # a leaf of its own a layer
                drawn[g][n] = [draw(kk, *shapes[g][n]) for kk in
                               jax.random.split(k, mcfg.n_layers)]
            else:
                drawn[g][n] = draw(k, *shapes[g][n])
        return zaya.assemble(mcfg, drawn, zaya.small_init(keys[-1], mcfg))

    return build


def serving_path(mcfg, server: Dict[str, Any]) -> SimpleNamespace:
    """The serving path's model functions at the configuration's
    ``server`` block: ``cache(n)`` a page pool of ``n`` pages beside the
    tails of the check's 8 rows, ``prefill`` (last position's logits)
    and ``decode`` as the served programs call them. The harness's check
    names no batch row: its sequence ``r`` decodes in batch row ``r``
    and owns the block table ``1 + r * max_pages + ...``, so the prefill
    reads the row out of the table's first page."""
    import jax.numpy as jnp

    from llmq_tpu.models.zaya import (forward_decode, forward_prefill,
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
    slice that ends mid-bucket (half the prompt and five), and one
    inside the first slice (a quarter and three) — those that leave
    ``steps`` positions, the longest context first."""
    last = n - steps
    starts = [last] + [s for s in (last // 2 + 5, last // 4 + 3)
                       if 1 <= s < last]
    return sorted(set(starts), reverse=True)[:JUDGED_ROWS]


def _served_many(cfg, server: Dict[str, Any]):
    """``served_many(params, tokens) -> (groups, chosen)``
    (``reference.JUDGED``) over the serving path of ``cfg``, the prompt
    going in as the engine's own slices, every program asked for the
    experts it chose (``chosen=True``):

    - ``prefill`` (batch row 0): ``tokens`` before the last
      ``JUDGED_STEPS`` through ``forward_prefill(last_only=True)`` a
      bucket at a time, each slice continuing the tail its predecessor
      left and attending to the pages it wrote — the last position of
      each slice is judged (a position's logits are a megabyte at this
      vocabulary; every OTHER prompt position is judged through what it
      left in every layer's pages);
    - ``mixed_to_<start>`` (the other rows): their prompts through
      ``forward_mixed``, one live slice a step, as a served mixed chunk
      runs them — the last position of each slice;
    - ``decode_from_<start>`` (every row): ``JUDGED_STEPS``
      teacher-forced steps from the tail the slices left, in ONE batch
      of the check's 8 rows, the others not active.

    Each group carries what its row's tails held behind its last
    position; the decode groups also what the page pool holds of the
    row's every position (K and V after the mix)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmq_tpu.models.zaya import (forward_decode, forward_mixed,
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
    def prefill_last(params, cache, state, tokens, start, n, bts, rows):
        positions = start + jnp.minimum(
            jnp.arange(bucket, dtype=jnp.int32)[None], n - 1)
        logits, cache, state, took = forward_prefill(
            params, cfg, tokens, positions, n[None], cache, bts,
            last_only=True, row_state=state, rows=rows, chosen=True)
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

    def tails_of(state):
        """The rows' tails as the reference writes them: ``(rows,
        layers, 2 C + W)``."""
        return np.asarray(state["tail"][:, :B], np.float32).transpose(
            1, 0, 2)

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
        chosen = np.full((R, cfg.n_layers, n, cfg.n_experts_per_tok), -1,
                         np.int32)
        # row 0: the prompt a bucket at a time, each slice's last judged
        at, last = [], []
        for a in range(0, starts[0], bucket):
            m = min(bucket, starts[0] - a)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :m] = tokens[a:a + m]
            logits, cache, state, took = prefill_last(
                params, cache, state, jnp.asarray(toks), jnp.int32(a),
                jnp.int32(m), dev_bts[:1], jnp.zeros((1,), jnp.int32))
            at.append(a + m - 1)
            last.append(np.asarray(logits))
            chosen[0, :, a:a + m] = np.asarray(took)[:, :m]
        prompt_in = {0: (np.asarray(at), np.stack(last))}
        # the other rows: one live slice a mixed step
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
            prompt_in[r] = (np.asarray(at), np.stack(last))
        held = tails_of(state)
        for r, (at, last) in prompt_in.items():
            name = "prefill" if r == 0 else f"mixed_to_{starts[r]}"
            groups[name] = dict(row=r, at=at, logits=last, tails=held[r],
                                kv=None)
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
        held = tails_of(state)
        # what the pool holds of each row's every position: the prompt's
        # rows as the slices wrote them, the last as the decode steps did
        def rows_of(leaf):
            x = np.asarray(leaf[:, dev_bts[:R, :pages]], np.float32)
            return x.reshape(cfg.n_layers, R, pages * ps, -1)

        kv = np.concatenate([rows_of(cache["k"]), rows_of(cache["v"])], -1)
        for r in range(R):
            groups[f"decode_from_{starts[r]}"] = dict(
                row=r, at=starts[r] + np.arange(steps), logits=got[:, r],
                tails=held[r], kv=kv[:, r, :starts[r] + steps])
        return groups, chosen

    return served_many
