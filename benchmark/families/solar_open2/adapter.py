"""The Solar-Open2 block in the program: the one file of the family
that imports ``llmq_tpu``. The surface is ``families/llama/adapter.py``'s,
and the procedure ``families/ling_hybrid/adapter.py``'s:

- ``register(name, config)``: the configuration file (the public
  ``config.json``'s keys at its top level, with ``num_hidden_layers``,
  ``gqa_layers``, ``n_routed_experts`` and ``vocab_size`` THIS CHIP'S
  share and ``router_experts`` / ``expert_share`` saying of what) as one
  more entry of the program's registry
  (``llmq_tpu/models/solar_open2.py`` ``MODEL_CONFIGS``) — the program
  is not edited;
- ``param_builder(mcfg, server_model)``: ``build(key) -> params``,
  random weights in the served type for ONE jitted call on the device;
- ``serving_path(mcfg, server)``: what the logits check drives — the
  program's own ``forward_prefill(last_only=True)`` and
  ``forward_decode`` through the K/V pages AND the row state. For a
  configuration that states a ``tolerance`` it also hands the family's
  reference ``served_many`` (``reference.JUDGED``): the same programs
  at the SERVED shapes (the batch's rows, the mixed step's slices) over
  many positions — one row deeper than 8,192 tokens, mixed steps with
  several live slices beside decoding rows — with the experts they
  chose.

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
#: Teacher-forced decode positions ``served_many`` judges of each of its
#: rows, and the most rows it judges (each costs a pass of the reference
#: over its whole stretch of the sequence; the BATCH they sit in is the
#: served one, ``max_batch_size``).
JUDGED_STEPS, JUDGED_ROWS = 128, 5
#: name -> the ``tolerance`` of the configuration ``register`` was given.
_TOLERANCE: Dict[str, Dict[str, Any]] = {}


def _part(name: str):
    from benchmark.harness import contract
    return contract.load_family(HERE, name)


def register(name: str, config: Dict[str, Any]):
    """``config`` holds the keys of ``shapes.MODEL_KEYS`` at its top
    level: the whole configuration file, or its ``model`` block."""
    import jax.numpy as jnp

    from llmq_tpu.models import solar_open2 as so

    lin = config["linear_attn_config"]
    if (config.get("use_rope") or not config.get("use_gqa_gate", True)
            or config.get("kda_use_full_proj")
            or not config.get("kda_allow_neg_eigval", True)
            or config.get("first_k_dense_replace", 0)
            or config.get("tie_word_embeddings")
            or lin.get("num_kv_heads") not in (None, lin["num_heads"])):
        raise ValueError(
            f"{name}: the program's solar_open2 block has gated GQA "
            f"layers without rotary positions, KDA with low-rank gates, "
            f"beta in (0, 2) and as many key heads as query heads, every "
            f"layer routed, and an untied head")
    shapes = _part("shapes")
    L = config["num_hidden_layers"]
    base = so.SolarOpen2Config(
        name=name, vocab_size=config["vocab_size"],
        dim=config["hidden_size"], n_layers=L,
        gqa_layers=tuple(l for l in config["gqa_layers"] if l < L),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], kda_heads=lin["num_heads"],
        kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"],
        kda_rank=config.get("kda_gate_rank", lin["head_dim"]),
        kda_chunk=KDA_CHUNK, moe_ffn_dim=config["moe_intermediate_size"],
        n_routed_experts=config["router_experts"],
        n_experts_per_tok=config["num_experts_per_tok"],
        n_shared_experts=config["n_shared_experts"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        held_experts=shapes.held_experts(config),
        max_seq_len=config["max_position_embeddings"],
        norm_eps=float(config["rms_norm_eps"]), dtype=jnp.bfloat16)
    so.MODEL_CONFIGS[name] = lambda **kw: dataclasses.replace(base, **kw)
    if "tolerance" in config:
        _TOLERANCE[name] = config["tolerance"]
    return base


def param_builder(mcfg, server_model: Dict[str, Any]):
    """``build(key) -> params`` in the program's tree
    (``solar_open2.param_shapes`` / ``assemble``). A matrix is uniform in
    (-a, a) with a = sqrt(3 / fan_in) (the variance of the program's own
    normal init), the hardware generator ("rbg"), drawn one slice of its
    leading axis at a time; RMSNorm weights are ones; the router's
    selection bias is uniform in (-ROUTER_BIAS, ROUTER_BIAS); the
    decay's ``A_log`` and ``dt_bias`` are the program's own draw
    (``solar_open2.decay_init``: the configuration file's ``assumed``
    has the ranges and why). No matrix needs another scale: q and k are
    normalised a head, the KDA output is normalised a head before its
    gate; the GQA layers' scores are of 128 values of unit variance each,
    scaled 128^-1/2."""
    import jax
    import jax.numpy as jnp

    from llmq_tpu.models import solar_open2 as lh

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
                               jax.random.split(k, mcfg.n_layers)]
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
    ``server`` block: ``cache(n)`` a K/V pool of ``n`` pages beside
    the row state of the check's 8 rows, ``prefill`` (last position's
    logits) and ``decode`` as the served programs call them. The
    harness's check names no batch row: its sequence ``r`` decodes in
    batch row ``r`` and owns the block table ``1 + r * max_pages + ...``,
    so the prefill reads the row out of the table's first page."""
    import jax.numpy as jnp

    from llmq_tpu.models.solar_open2 import (forward_decode, forward_prefill,
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


def judged_starts(n: int, steps: int, rows: int):
    """Where ``served_many``'s rows start to decode in a sequence of
    ``n`` tokens: the last ``steps`` positions (row 0) and four starts
    behind slices that end mid-chunk (a half of row 0's prompt and
    five, a quarter and three, an eighth and three, a sixteenth and
    seven) — those that leave ``steps`` positions, the longest context
    first, at most ``rows`` of them."""
    last = n - steps
    starts = [last] + [s for s in (last // 2 + 5, last // 4 + 3,
                                   last // 8 + 3, last // 16 + 7)
                       if 1 <= s < last]
    return sorted(set(starts), reverse=True)[:min(rows, JUDGED_ROWS)]


def judged_places(n_rows: int, batch: int):
    """The batch row of each judged row: spread over the whole batch
    (0, 8, 16, 23, 31 of 32), so that a row's state, its block table and
    its place in the decode kernel's tiles are not its index here."""
    import numpy as np
    return np.round(np.linspace(0, batch - 1, n_rows)).astype(np.int32)


def _served_many(cfg, server: Dict[str, Any]):
    """``served_many(params, tokens) -> (groups, chosen)``
    (``reference.JUDGED``) over the serving path of ``cfg`` AT THE
    SERVED SHAPES — the batch of ``max_batch_size`` rows, the mixed
    step's ``max_slices`` slices of its budget — the prompts going in as
    the engine's own slices, every program asked for the experts it
    chose (``chosen=True``). Up to ``JUDGED_ROWS`` rows of the one
    sequence ``tokens`` (``judged_starts``), each in a batch row of its
    own (``judged_places``):

    - ``prefill`` (row 0): every position of ``tokens`` before the
      last ``JUDGED_STEPS``, through ``forward_prefill`` a bucket at a
      time, each slice continuing the state the chunked scan left in
      the row-state leaves and the pages the GQA layer wrote;
    - ``mixed_to_<start>`` (the other rows): their prompts through
      ``forward_mixed`` as a served mixed chunk runs them — in ONE
      call every row that still has prompt left takes its next slice
      (up to four live slices of different rows, the shortest row in
      the first slot) while every row whose prompt is in DECODES its
      next token (row 0 from the first step on). Judged: the last
      position of each slice (all the program computes of a slice), and
      the decode rows' logits with their decode group; the rows' last
      slices end in the middle of a chunk of the scan;
    - ``decode_from_<start>`` (every row): ``JUDGED_STEPS``
      teacher-forced positions from the state the scan left — those a
      row decoded in the mixed steps, then ``forward_decode`` (the
      one-token update, in place, over the live rows) until each row
      has its ``JUDGED_STEPS``: the rows end at different steps, and a
      row that has ended is not active in the steps that follow.

    Each group carries what its row's KDA state held behind its last
    position: the prompt groups what the scan left (read before the
    row's first decode step), the decode groups what the leaf holds at
    the END (a row that ended early kept it through the others' steps);
    the decode groups also what the K/V pages hold of the row's every
    position (``[k | v]``: the slices' writes and the decode steps')."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmq_tpu.models.solar_open2 import (forward_decode, forward_mixed,
                                             forward_prefill, init_kv_pages,
                                             init_row_state)
    from llmq_tpu.ops.rows import pack_grid

    ex = server["executor"]
    ps = int(ex["page_size"])
    bucket = int(max(ex["prefill_buckets"]))
    mixed_cfg = ex.get("mixed_batch") or {}
    S = int(mixed_cfg.get("max_slices", 1))
    T = int(mixed_cfg.get("prefill_token_budget", bucket)) // S
    B = int(ex["max_batch_size"])

    @partial(jax.jit, donate_argnums=(1, 2))
    def prefill_all(params, cache, state, tokens, start, n, bts, rows):
        positions = start + jnp.minimum(
            jnp.arange(bucket, dtype=jnp.int32)[None], n - 1)
        logits, cache, state, took = forward_prefill(
            params, cfg, tokens, positions, n[None], cache, bts,
            row_state=state, rows=rows, chosen=True)
        return logits[0].astype(jnp.float32), cache, state, took

    @partial(jax.jit, donate_argnums=(1, 2))
    def mixed(params, cache, state, dec_tok, dec_pos, dec_bts, dec_active,
              pf_tok, pf_pos, pf_len, pf_start, pf_bts, pf_rows):
        dec_logits, pf_logits, cache, state, took = forward_mixed(
            params, cfg, dec_tok, dec_pos, cache, dec_bts, pf_tok, pf_pos,
            pf_len, pf_start, pf_bts, dec_active=dec_active,
            row_state=state, pf_rows=pf_rows, chosen=True)
        return (dec_logits.astype(jnp.float32),
                pf_logits.astype(jnp.float32), cache, state, took)

    @partial(jax.jit, donate_argnums=(1, 2))
    def step(params, cache, state, tok, pos, bts, active):
        logits, cache, state, took = forward_decode(
            params, cfg, tok, pos, cache, bts, active=active,
            row_state=state, chosen=True)
        return logits.astype(jnp.float32), cache, state, took

    def states_of(state, places):
        """The KDA states of the batch rows ``places`` as the reference
        writes them: the leaf ``(L_k, rows, d_k, H * d_v)`` as
        ``(len(places), L_k, H, d_k, d_v)``."""
        leaf = np.asarray(state["kda"][:, jnp.asarray(places)], np.float32)
        return leaf.reshape(leaf.shape[:3] + (cfg.kda_heads, -1)).transpose(
            1, 0, 3, 2, 4)

    def served_many(params, tokens):
        tokens = np.asarray(tokens, np.int32)
        n = len(tokens)
        steps = min(JUDGED_STEPS, n // 2)
        if n > cfg.max_seq_len or steps < 1:
            raise ValueError(f"{n} tokens: the judged sequence holds 2 to "
                             f"{cfg.max_seq_len}")
        starts = judged_starts(n, steps, min(B, S + 1))
        R = len(starts)
        place = judged_places(R, B)
        pages = -(-n // ps)
        cache = init_kv_pages(cfg, 1 + R * pages, ps)
        state = init_row_state(cfg, B)
        bts = np.zeros((B, cfg.max_seq_len // ps), np.int32)
        bts[place, :pages] = 1 + np.arange(R * pages,
                                           dtype=np.int32).reshape(R, pages)
        dev_bts = jnp.asarray(bts)
        groups: Dict[str, Any] = {}
        chosen = np.full((R, cfg.n_layers, n, cfg.n_experts_per_tok),
                         -1, np.int32)
        # row 0: every prompt position, a bucket at a time
        every = []
        for a in range(0, starts[0], bucket):
            m = min(bucket, starts[0] - a)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :m] = tokens[a:a + m]
            logits, cache, state, took = prefill_all(
                params, cache, state, jnp.asarray(toks), jnp.int32(a),
                jnp.int32(m), dev_bts[place[:1]], jnp.asarray(place[:1]))
            every.append(np.asarray(logits[:m]))
            chosen[0, :, a:a + m] = np.asarray(took)[:, :m]
        groups["prefill"] = dict(
            row=0, at=np.arange(starts[0]), logits=np.concatenate(every),
            states=states_of(state, place[:1])[0], kv=None)
        # the mixed steps: every row with prompt left takes a slice,
        # every row whose prompt is in decodes
        done = np.zeros(R, np.int32)        # positions a row has decoded
        stepped = [[] for _ in range(R)]
        slices = [-(-s // T) for s in starts]
        mixed_in = {r: ([], []) for r in range(1, R)}
        for j in range(max(slices[1:], default=0)):
            live = [r for r in range(R - 1, 0, -1) if j < slices[r]]
            g_t = np.zeros((S, T), np.int32)
            g_p = np.zeros((S, T), np.int32)
            lens = np.ones((S,), np.int32)
            pf_bts = np.zeros((S, bts.shape[1]), np.int32)
            pf_rows = np.full((S,), B, np.int32)
            for s, r in enumerate(live):
                a = j * T
                m = min(T, starts[r] - a)
                g_t[s, :m], g_p[s, :m] = tokens[a:a + m], np.arange(a, a + m)
                lens[s], pf_bts[s], pf_rows[s] = m, bts[place[r]], place[r]
            pf_tok, pf_pos, pf_start = pack_grid(g_t, g_p, lens,
                                                 used=len(live))
            dec = [r for r in range(R) if r not in live and done[r] < steps]
            tok, pos = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
            active = np.zeros((B,), bool)
            for r in dec:
                at = starts[r] + done[r]
                tok[place[r]], pos[place[r]] = tokens[at], at
                active[place[r]] = True
            dec_logits, pf_logits, cache, state, took = mixed(
                params, cache, state, jnp.asarray(tok), jnp.asarray(pos),
                dev_bts, jnp.asarray(active), jnp.asarray(pf_tok),
                jnp.asarray(pf_pos), jnp.asarray(lens),
                jnp.asarray(pf_start), jnp.asarray(pf_bts),
                jnp.asarray(pf_rows))
            dec_logits, pf_logits, took = map(
                np.asarray, (dec_logits, pf_logits, took))
            for s, r in enumerate(live):
                a, m = j * T, int(lens[s])
                mixed_in[r][0].append(a + m - 1)
                mixed_in[r][1].append(pf_logits[s])
                chosen[r, :, a:a + m] = took[:, s * T:s * T + m]
                if j == slices[r] - 1:      # what the scan left the row
                    mixed_in[r] += (states_of(state, place[r:r + 1])[0],)
            for r in dec:
                stepped[r].append(dec_logits[place[r]])
                chosen[r, :, starts[r] + done[r]] = took[:, S * T + place[r]]
                done[r] += 1
        for r, (at, last, held) in mixed_in.items():
            groups[f"mixed_to_{starts[r]}"] = dict(
                row=r, at=np.asarray(at), logits=np.stack(last),
                states=held, kv=None)
        # the plain steps, until every row has its ``steps`` positions
        first = np.asarray(starts)
        while (done < steps).any():
            live = np.flatnonzero(done < steps)
            tok, pos = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
            active = np.zeros((B,), bool)
            at = first[live] + done[live]
            tok[place[live]], pos[place[live]] = tokens[at], at
            active[place[live]] = True
            logits, cache, state, took = step(
                params, cache, state, jnp.asarray(tok), jnp.asarray(pos),
                dev_bts, jnp.asarray(active))
            logits, took = np.asarray(logits), np.asarray(took)
            for r in live:
                stepped[r].append(logits[place[r]])
            chosen[live, :, at] = np.moveaxis(took[:, place[live]], 1, 0)
            done[live] += 1
        held = states_of(state, place)
        # what the pool holds of each row's every position: the prompt's
        # rows as the slices wrote them, the last as the decode steps did
        rows = np.concatenate(
            [np.asarray(cache[x][:, dev_bts[place, :pages]], np.float32)
             .reshape(cfg.n_gqa, R, pages * ps, -1) for x in ("k", "v")], -1)
        for r in range(R):
            groups[f"decode_from_{starts[r]}"] = dict(
                row=r, at=starts[r] + np.arange(steps),
                logits=np.stack(stepped[r]), states=held[r],
                kv=rows[:, r, :starts[r] + steps])
        return groups, chosen

    return served_many
