"""The JetBrains ``mellum`` block in the program: the one file of the
family that imports ``llmq_tpu``. The surface is
``families/llama/adapter.py``'s, and the procedure
``families/afmoe/adapter.py``'s (the same two caches):

- ``register(name, config)``: the configuration file (the public
  ``config.json``'s keys at its top level, ``num_hidden_layers`` the
  layers THIS CHIP holds, ``qk_norm`` the assumed per-head norm) as one
  more entry of the program's registry (``llmq_tpu/models/mellum.py``
  ``MODEL_CONFIGS``) — the program is not edited;
- ``param_builder(mcfg, server_model)``: ``build(key) -> params``,
  random weights in the served type for ONE jitted call on the device;
- ``serving_path(mcfg, server)``: what the logits check drives — the
  program's own ``forward_prefill(last_only=True)`` and
  ``forward_decode`` through the page pool AND the sliding layers'
  slabs. For a configuration that states a ``tolerance`` it also hands
  the family's reference ``served_many`` (``reference.JUDGED``), whose
  groups include the ADOPTED path: a tail exported from one batch row
  at a page boundary and imported into another, which then prefills
  the rest and decodes.

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
#: through both kinds of cache, and the rows of its decode batch (the
#: check's own).
JUDGED_STEPS, JUDGED_ROWS = 128, 8
#: Decode steps behind the adopted row's prefill.
ADOPTED_STEPS = 3
#: The fan-in the embedding is drawn by: rows of variance 4 (RMS 2),
#: where every other leaf has variance 1 / fan_in. The benchmark's
#: prompts are 62 letters (``harness/plan.ALPHABET``), so what attention
#: adds to the stream is at every position the same vector, the mean
#: value row of that alphabet (PERF.md section 7 (g)); against embedding
#: rows of RMS 0.02 (1 / hidden) it IS the stream, every token of every
#: row asks the router the same question, and how many experts a decode
#: step streams is a draw of the seed that a whole run holds. Measured
#: on the chip (PR 54; experts touched a layer in a traced run, beside
#: the count of as many rows that choose for themselves): 1 / hidden
#: 21.6-23.0 of 44.6, busiest expert 2.2-2.9 x the mean, one seed three
#: times 16.01-16.14 ms a token and another 16.95-17.27; variance 1,
#: the stream half shared, 35.7 of 48.9 and 28.2 of 35.6, and six seeds
#: 15.6-19.5 ms a token, the widest; variance 4, 38.5 of 39.4 at 1.29:
#: the rows route as a trained, balanced router's do, and a step reads
#: the bytes ``shapes.decode_step_bytes`` reckons.
EMBED_FAN_IN = 0.25
#: name -> the ``tolerance`` of the configuration ``register`` was given.
_TOLERANCE: Dict[str, Dict[str, Any]] = {}


def _part(name: str):
    from benchmark.harness import contract
    return contract.load_family(HERE, name)


def register(name: str, config: Dict[str, Any]):
    """``config`` holds the keys of ``shapes.MODEL_KEYS`` at its top
    level: the whole configuration file, or its ``model`` block."""
    import jax.numpy as jnp

    from llmq_tpu.models import mellum

    L = config["num_hidden_layers"]
    if (config.get("tie_word_embeddings", False)
            or set(config["mlp_layer_types"][:L]) != {"sparse"}):
        raise ValueError(f"{name}: the program's mellum block has an untied "
                         f"head and a routed feed-forward in every layer")
    rope = config["rope_parameters"]
    plain, full = rope["sliding_attention"], rope["full_attention"]
    if (plain.get("rope_type", "default") != "default"
            or plain["rope_theta"] != full["rope_theta"]):
        raise ValueError(f"{name}: the sliding layers rotate plainly, by "
                         f"the full layers' theta")
    yarn = None
    if full.get("rope_type", "default") == "yarn":
        yarn = mellum.Yarn(
            factor=float(full["factor"]),
            original_max_position=int(
                full["original_max_position_embeddings"]),
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"]))
    elif full.get("rope_type", "default") != "default":
        raise ValueError(f"{name}: rope_type {full['rope_type']!r}")
    base = mellum.MellumConfig(
        name=name, vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        layer_types=tuple(config["layer_types"][:L]),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        sliding_window=config["sliding_window"],
        moe_ffn_dim=config["moe_intermediate_size"],
        n_routed_experts=config["num_experts"],
        n_experts_per_tok=config["num_experts_per_tok"],
        route_norm=bool(config["norm_topk_prob"]),
        qk_norm=bool(config.get("qk_norm", True)),
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(plain["rope_theta"]), rope_full=yarn,
        norm_eps=float(config["rms_norm_eps"]), dtype=jnp.bfloat16)
    mellum.MODEL_CONFIGS[name] = (
        lambda **kw: dataclasses.replace(base, **kw))
    if "tolerance" in config:
        _TOLERANCE[name] = config["tolerance"]
    return base


def param_builder(mcfg, server_model: Dict[str, Any]):
    """``build(key) -> params`` in the program's tree
    (``mellum.param_shapes`` / ``assemble``). Uniform in (-a, a) with
    a = sqrt(3 / fan_in) (the variance of the program's own normal
    init), the hardware generator ("rbg"); a leaf is drawn one slice of
    its leading axis at a time (a layer's gate-and-up leaf is 0.53 GB:
    its random bits drawn at once are twice that). RMSNorm weights are
    ones. No matrix needs another scale: q and k are normalised per
    head (scores of unit variance before YaRN's factor), the router's
    logits of the normalised stream have unit variance over 64 experts,
    and the gates sum to 1.

    ONE leaf is drawn wider, ``EMBED_FAN_IN``: the embedding."""
    import jax
    import jax.numpy as jnp

    from llmq_tpu.models import mellum

    if server_model.get("quantization") or server_model.get(
            "kv_quantization"):
        mellum.check_serving(
            mcfg, quantization=server_model.get("quantization", ""),
            kv_quantization=server_model.get("kv_quantization", ""))
    shapes = mellum.param_shapes(mcfg)

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
        keys = jax.random.split(key, len(names))
        drawn: Dict[str, Dict[str, Any]] = {g: {} for g in shapes}
        for k, (g, n) in zip(keys, names):
            if g == "experts":     # a leaf of its own a layer
                drawn[g][n] = [draw(kk, *shapes[g][n]) for kk in
                               jax.random.split(k, mcfg.n_layers)]
            elif (g, n) == ("top", "embed"):
                drawn[g][n] = draw(k, shapes[g][n][0], EMBED_FAN_IN)
            else:
                drawn[g][n] = draw(k, *shapes[g][n])
        return mellum.assemble(mcfg, drawn)

    return build


def _bound(mcfg, server: Dict[str, Any]):
    """``mcfg`` with the slabs cut as the cell's executor cuts them:
    its pages, and a step's writes for one sequence the larger of its
    prefill bucket and one slice of its mixed step."""
    from llmq_tpu.models import mellum

    ex = server["executor"]
    mixed = ex.get("mixed_batch") or {}
    step = max(max(ex["prefill_buckets"]),
               int(mixed.get("prefill_token_budget", 0))
               // max(1, int(mixed.get("max_slices", 1)))
               if mixed.get("enabled") else 0)
    return mellum.bind_cache(mellum.serving_config(mcfg),
                             page_size=int(ex["page_size"]),
                             step_tokens=step)


def serving_path(mcfg, server: Dict[str, Any]) -> SimpleNamespace:
    """The serving path's model functions at the configuration's
    ``server`` block: ``cache(n)`` a page pool of ``n`` pages beside the
    slabs of the check's 8 rows, ``prefill`` (last position's logits)
    and ``decode`` as the served programs call them."""
    import jax.numpy as jnp

    from llmq_tpu.models.mellum import (forward_decode, forward_prefill,
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
    return sorted(set(starts), reverse=True)[:JUDGED_ROWS - 1]


def adopted_boundary(n: int, cfg) -> int:
    """The page boundary E the adopted row takes over at, in a judged
    sequence of ``n`` tokens: the last one that leaves half a bucket of
    prompt and ``ADOPTED_STEPS`` tokens behind it, so the ring has
    wrapped where the sequence is long enough (0: too short to adopt)."""
    from llmq_tpu.models.mellum import row_tail

    ps = cfg.page_size
    E = (n - ADOPTED_STEPS - ps // 2) // ps * ps
    return E if E >= row_tail(cfg)["pages"] * ps else 0


def _served_many(cfg, server: Dict[str, Any]):
    """``reference.JUDGED``'s ``served_many(params, tokens)`` over the
    serving path, the prompt going in as the engine's own slices:

    - ``prefill``: every position of ``tokens`` before the last
      ``JUDGED_STEPS``, through ``forward_prefill`` a bucket at a time
      in batch row 0, each slice continuing what the pool and the slab
      hold; and, in the same group, the other rows' prompts through
      ``forward_mixed``, one live slice a step: the last position of
      each slice;
    - ``decode_from_<start>``: ``JUDGED_STEPS`` teacher-forced steps of
      every row (``judged_starts``) in ONE batch of the check's 8 rows;
    - ``adopted``: what the prefix cache does for this family. While row
      0 prefills, its tail is EXPORTED where its stream passes
      ``adopted_boundary`` (E) — as the engine takes one on the way
      through a prefill; the last batch row then gets the full layers'
      pages of row 0 below E in its block table (shared, as a radix
      match shares them), IMPORTS the tail into its ring, prefills
      ``tokens[E:]`` from position E and decodes ``ADOPTED_STEPS``
      steps: every position from E on, held to the same reference.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmq_tpu.models import mellum
    from llmq_tpu.models.mellum import (forward_decode, forward_mixed,
                                        forward_prefill, init_kv_pages,
                                        init_row_state, init_row_tails)
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

    # (looked up when first traced, so that a probe can break one on
    # purpose: scripts/family_logits_probe.py --setattr)
    export = jax.jit(lambda *a: mellum.export_row_tail(cfg, *a),
                     donate_argnums=(1,))
    inject = jax.jit(lambda *a: mellum.import_row_tail(cfg, *a),
                     donate_argnums=(0,))

    def prefill_row(params, cache, state, tokens, lo, hi, bts_row, row,
                    at_boundary=None):
        """``tokens[lo:hi]`` through ``forward_prefill`` a bucket at a
        time in batch row ``row``; ``at_boundary(E, state)`` is called
        after the slice that passes ``E`` has been dispatched."""
        every = []
        for a in range(lo, hi, bucket):
            m = min(bucket, hi - a)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :m] = tokens[a:a + m]
            logits, cache, state = prefill_all(
                params, cache, state, jnp.asarray(toks), jnp.int32(a),
                jnp.int32(m), bts_row, jnp.full((1,), row, jnp.int32))
            every.append(np.asarray(logits[:m]))
            if at_boundary is not None and a < at_boundary[0] <= a + m:
                at_boundary[1](state)
        return every, cache, state

    def served_many(params, tokens):
        tokens = np.asarray(tokens, np.int32)
        n = len(tokens)
        steps = min(JUDGED_STEPS, n // 2)
        if n > cfg.max_seq_len or steps < 1:
            raise ValueError(f"{n} tokens: the judged sequence holds 2 to "
                             f"{cfg.max_seq_len}")
        starts = judged_starts(n, steps, cfg.sliding_window)
        E = adopted_boundary(n, cfg)
        if E > starts[0]:              # row 0's prompt must pass it
            E = starts[0] // ps * ps
        R = len(starts)
        pages = -(-n // ps)
        cache = init_kv_pages(cfg, 1 + (R + 1) * pages, ps)
        state = init_row_state(cfg, B)
        tails = [init_row_tails(cfg, 1)]
        bts = np.zeros((B, cfg.max_seq_len // ps), np.int32)
        bts[:R, :pages] = 1 + np.arange(R * pages,
                                        dtype=np.int32).reshape(R, pages)
        # the adopting row: row 0's pages below E, its own from E on
        bts[B - 1, :pages] = 1 + R * pages + np.arange(pages, dtype=np.int32)
        bts[B - 1, :E // ps] = bts[0, :E // ps]
        dev_bts = jnp.asarray(bts)
        out: Dict[str, Any] = {}

        def take(state):
            tails[0] = export(state, tails[0], jnp.int32(0),
                              jnp.int32(E // ps), jnp.int32(0))

        # row 0: every prompt position, a bucket at a time; its tail is
        # taken on the way through
        every, cache, state = prefill_row(
            params, cache, state, tokens, 0, starts[0], dev_bts[:1], 0,
            (E, take) if E else None)
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
        if E:
            # the adopted path: the tail into the last row's ring, the
            # rest of the prompt from E, then decode steps
            state = inject(state, tails[0], jnp.int32(0), jnp.int32(B - 1),
                           jnp.int32(E // ps))
            n_pf = n - ADOPTED_STEPS
            got, cache, state = prefill_row(
                params, cache, state, tokens, E, n_pf, dev_bts[B - 1:], B - 1)
            only = jnp.asarray(np.arange(B) == B - 1)
            for j in range(ADOPTED_STEPS):
                tok = np.zeros((B,), np.int32)
                pos = np.zeros((B,), np.int32)
                tok[B - 1], pos[B - 1] = tokens[n_pf + j], n_pf + j
                logits, cache, state = step(
                    params, cache, state, jnp.asarray(tok), jnp.asarray(pos),
                    dev_bts, only)
                got.append(np.asarray(logits[B - 1:]))
            out["adopted"] = (np.arange(E, n), np.concatenate(got))
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
