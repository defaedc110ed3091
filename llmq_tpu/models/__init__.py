"""The model families behind ``model.name``, and the one place that
knows which there are.

A family is a module of this package that defines

- ``MODEL_CONFIGS``: ``{name: factory(**overrides) -> config}``; a
  config is a frozen dataclass with a class attribute ``FAMILY`` (the
  key below) and at least ``name``, ``vocab_size``, ``n_layers``,
  ``max_seq_len`` and ``dtype``;
- parameters: ``init_params``, ``init_params_quantized``,
  ``param_count``, ``param_count_analytic``, ``active_param_count``
  (what one token multiplies with: the MFU estimate's count),
  ``weight_bytes``;
- the cache, which is PAGES and, for some families, ROW STATE beside
  them. Pages: ``init_kv_pages`` (a dict of ``(L, P, page_size, ...)``
  leaves, page 0 reserved: the executor, the allocator, the prefix
  cache, tiering and disaggregation treat it as a pytree of such
  leaves and never by key) and ``kv_bytes_per_token``. Row state:
  ``init_row_state(cfg, batch)``, a dict of leaves indexed ``(layer,
  batch row, ...)`` that a sequence carries from token to token
  whatever its length (a state-space layer's recurrent state; the
  leaves hold ``batch + 1`` rows, the last nobody's, as page 0 is: an
  unused slice of a program names it — ``models/granitemoehybrid.py``
  (Mamba-2 state) and ``models/solar_open2.py`` (delta-rule state)
  beside K/V pages, ``models/ling_hybrid.py`` beside a latent pool,
  all three on layers that own NO pages; ``models/zaya.py`` on the very
  layers that own K/V pages: the tail its mixer's convolutions and
  value shift need of the token before; or
  a window layer's keys and
  values, a SLAB of pool-shaped pages a batch row: ``models/afmoe.py``
  and, over the same slabs, ``models/mellum.py``,
  which also define the optional ``bind_cache(cfg, *, page_size,
  step_tokens)``, called by the executor before ``init_row_state``, and
  ``attention_window(cfg)``, what the engine counts such a cache by), or
  ``None`` for a family whose pages are its whole cache, and
  ``row_state_bytes_per_row(cfg)`` (0 then). A family WITH row state
  takes it in every forward function as ``row_state=`` and returns it
  after the cache, takes the batch row of each prefill sequence
  (``rows=``) and of each mixed slice (``pf_rows=``), zeroes a row's
  state inside the program where its sequence starts (position 0) and
  leaves the state of a decode row that is not active as it found it.
  The executor carries and donates it beside the pool; the engine
  adopts nothing that only pages could rebuild (``get_stats()
  ["row_state"]``: ``declined``) — unless the family says what DOES
  rebuild a row at a page boundary E. Optionally ``row_tail(cfg)``
  (``{"pages", "stride", "slack_tokens", "bytes"}``: a TAIL is that
  many pages of row state before E; one is taken at every multiple of
  ``stride`` on the way through a prefill and where a stream is
  published; a row that has written at most ``slack_tokens`` past E
  still holds it), ``init_row_tails(cfg, slots)`` (a pool of tails,
  leaves indexed ``(layer, slot * pages + page, ...)``) and the two
  programs' bodies ``export_row_tail(cfg, row_state, tails, row,
  end_page, slot) -> tails`` and ``import_row_tail(cfg, row_state,
  tails, slot, row, end_page) -> row_state``: a row that imports the
  tail of E continues at E as if it had prefilled the prefix. With them
  (and ``executor.prefix_cache.row_tail_slots`` > 0) the prefix cache
  adopts a hit for the family (``models/afmoe.py``, ``models/mellum.py``:
  W tokens of a window layer's K and V; ``docs/prefix_cache.md``
  "Tails"); a family whose state is a recurrence has none and goes on
  declining;
- the serving programs' model functions: ``forward_prefill``,
  ``forward_decode``, ``forward_mixed``, with
  ``models/llama.py``'s signatures and returns (``forward_mixed``
  takes its slices' tokens TIGHT with ``pf_starts`` — ``ops/rows.py`` —
  and returns of each slice the logits of its last valid position,
  (S, V): the one serving samples);
- ``mixed_live_rows(tokens, batch, slices, width)``: the rows
  ``forward_mixed``'s row-wise products run for that many prompt
  tokens, reckoned on the host by the rule the program runs by
  (``ops/rows.tile_rows`` where it runs row tiles, every row of the
  grid where it does not; the executor's ``slice_tokens`` of a mixed
  chunk);
- ``serving_config(cfg)``: ``cfg`` as the forward-only serving
  programs take it, and ``import_hf(model_dir, cfg, **kw)``: a local
  Hugging Face checkpoint directory into the family's tree;
- ``step_stats_size(cfg)``: how many int32 counters a forward pass
  returns after the cache when called with ``stats=True`` (0: the
  family counts nothing and takes no such argument), and
  ``step_stats_layout(cfg)``: where each lies — ``{"load": (first,
  end)}`` the tokens each held expert received, and the indices of
  ``"touched"`` (experts that received any, summed over the routed
  layers run), ``"runs"`` (routed layers run) and, for a family that
  counts them, ``"zero_slots"`` (slots that chose a zero-compute
  expert) and ``"away_slots"`` (slots whose expert another chip
  holds); ``{}`` for a family that counts nothing. The engine reads
  the counters by this and by nothing else
  (``get_stats()["moe"]``);
- optionally ``mixed_key_blocks(seq_lens, T, page_size, max_pages)``:
  for a family whose prefill attention is a loop over key blocks under
  XLA, ``(visited, the table holds)``: the blocks one attention of a
  mixed step runs over slices of these contexts, reckoned on the host
  by the rule the program runs by (``get_stats()["mixed_key_blocks"]``);
  a family without it (a prefill kernel that follows the context by
  itself) counts nothing;
- optionally ``hc_rows_live(tokens, batch, slices, width)``: for a family
  whose residual is several streams mixed at every sub-layer
  (``models/xing.py``, ``ops/hyper.py``), the rows its mixed STEP's
  sites run — the decode rows and ``mixed_live_rows`` behind them
  (``engine.dispatch``'s ``hc_rows_live``); such a family's
  ``step_stats_layout`` may name ``"hc_row_sum_err"``, the worst ``|row
  sum - 1|`` of a pass's mixing matrices x 1e6;
- optionally ``IDLE_ROW_CONTEXT``: the ``seq_len`` its decode step
  hands the fused decode kernel for a row that is not active, where
  that is not ``positions + 1`` of an empty seat's position 0 (the
  executor's ``attn_work`` counts an empty seat by it);
- optionally ``DEVICE_LAYOUT``: stacked leaf of ``params["layers"]``
  (layer, in, out) -> the layout its matrices want ON THE DEVICE, a
  name of ``engine/executor.LAYOUTS``: ``"transposed"`` (the contracted
  axis minor) or ``"row_major"`` (the output axis minor: not what the
  TPU gives a leaf whose width is no multiple of 128 lanes). The
  executor lays each such leaf so once, when it takes the parameters
  (``engine/executor.lay_params``: the physical layout alone; shape,
  values and every forward function stay), and lowers every serving
  program against what lies there; a leaf that is not a plain array
  (int8 with scales) and a family without the table are left as they
  are;
- ``routes(cfg, cache, *, batch, page_size, max_pages, decode,
  prefill_rows)``: which implementation each attention op of a program
  takes (``ops/attention.kernel_routes``'s form);
- ``check_serving(cfg, *, quantization, kv_quantization, mesh)``:
  raises ``ValueError`` naming the setting for what the family does not
  support.
"""

from __future__ import annotations

import importlib
from types import ModuleType
from typing import Dict

from llmq_tpu.models.llama import (  # noqa: F401
    LlamaConfig,
    MODEL_CONFIGS,
    init_params,
    forward_prefill,
    forward_decode,
)
from llmq_tpu.models.checkpoint import save_checkpoint, load_checkpoint  # noqa: F401

#: family -> its module. A model of a new family is a new entry here
#: and a module that defines the surface above.
FAMILIES: Dict[str, str] = {
    "llama": "llmq_tpu.models.llama",
    "deepseek_v3": "llmq_tpu.models.deepseek_v3",
    "longcat_flash": "llmq_tpu.models.longcat_flash",
    "granitemoehybrid": "llmq_tpu.models.granitemoehybrid",
    "afmoe": "llmq_tpu.models.afmoe",
    "ling_hybrid": "llmq_tpu.models.ling_hybrid",
    "zaya": "llmq_tpu.models.zaya",
    "solar_open2": "llmq_tpu.models.solar_open2",
    "mellum": "llmq_tpu.models.mellum",
    "xing": "llmq_tpu.models.xing",
}


def family(name: str) -> ModuleType:
    try:
        return importlib.import_module(FAMILIES[name])
    except KeyError:
        raise ValueError(f"unknown model family {name!r}; known: "
                         f"{sorted(FAMILIES)}") from None


def family_of(cfg) -> ModuleType:
    """The family module of a model config."""
    return family(cfg.FAMILY)


def model_names() -> Dict[str, str]:
    """Every registered ``model.name`` -> its family."""
    return {name: fam for fam in FAMILIES
            for name in family(fam).MODEL_CONFIGS}


def get_config(name: str, **kw):
    """The config of ``model.name`` with ``kw`` overriding its fields,
    whatever its family."""
    names = model_names()
    if name not in names:
        raise ValueError(f"unknown model {name!r}; known: {sorted(names)}")
    return family(names[name]).MODEL_CONFIGS[name](**kw)
