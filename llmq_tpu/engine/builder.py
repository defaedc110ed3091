"""Construct an engine from the typed config tree.

The wiring used by the entrypoints (``python -m llmq_tpu serve``) and the
benchmark harness: config → tokenizer + executor + engine, mirroring the
component construction the reference spreads over its cmd/ binaries."""

from __future__ import annotations

from typing import Optional, Tuple

from llmq_tpu.core.config import Config
from llmq_tpu.engine.engine import InferenceEngine
from llmq_tpu.engine.executor import EchoExecutor, JaxExecutor
from llmq_tpu.engine.tokenizer import get_tokenizer
from llmq_tpu.utils.logging import get_logger

log = get_logger("engine.builder")


def _mixed_geometry(ex) -> Tuple[int, int]:
    """Executor-side mixed geometry: S slice rows × T tokens (the
    compiled program's shapes). Disabled → S = 0 → no mixed program
    is built, and the engine keeps the exact unfused scheduling."""
    mixed = getattr(ex, "mixed_batch", None)
    if not getattr(mixed, "enabled", False):
        return 0, 0
    return int(getattr(mixed, "max_slices", 0)), int(mixed.slice_tokens)


def executor_geometry(cfg: Config) -> dict:
    """What ``cfg.executor`` / ``cfg.model`` say of a
    :class:`JaxExecutor`'s programs, as its keyword arguments: batch,
    pages, buckets, chunk, the cache's type and the mixed step's
    geometry. One reading for ``build_engine`` and for whoever lowers a
    configuration's programs without serving them
    (``scripts/whole_copies.py``)."""
    import jax.numpy as jnp

    ex = cfg.executor
    mixed_slices, mixed_slice_tokens = _mixed_geometry(ex)
    return dict(
        batch_size=ex.max_batch_size,
        page_size=ex.page_size,
        num_pages=ex.kv_pages,
        prefill_buckets=list(ex.prefill_buckets),
        chunk_size=ex.decode_chunk,
        prefill_batch=ex.prefill_batch,
        cache_dtype=(jnp.int8 if getattr(cfg.model, "kv_quantization", "")
                     == "int8" else None),
        mixed_prefill_slices=mixed_slices,
        mixed_slice_tokens=mixed_slice_tokens,
        row_tail_slots=(int(getattr(ex.prefix_cache, "row_tail_slots", 0))
                        if getattr(ex.prefix_cache, "enabled", False)
                        else 0))


def build_engine(cfg: Config, *, name: str = "engine0",
                 params=None, warmup: bool = False,
                 enable_metrics: Optional[bool] = None) -> InferenceEngine:
    """Build the engine described by ``cfg.executor`` / ``cfg.model``.

    ``backend="echo"`` needs no JAX at all (BASELINE config #1).
    ``backend="jax"`` loads/initialises the model (checkpoint if
    configured, else random init — fine for perf benches) and compiles
    the decode program up front when ``warmup``.
    """
    ex = cfg.executor
    # Boot decomposition (observability/critical_path.py, ROADMAP item
    # 3's measurement half): stamp weight-load / artifact / compile /
    # warmup stages onto the process boot record — opened here when no
    # entrypoint (serve boot, a replica pool) opened one first. One
    # no-op call when the critical-path plane is off.
    from llmq_tpu.observability import critical_path as _cp
    boot_id = _cp.current_boot_id()
    if boot_id is None and _cp.cp_enabled():
        _cp.boot_begin(name, "engine", process=True)
        boot_id = _cp.current_boot_id()
    tokenizer = get_tokenizer(getattr(cfg.model, "tokenizer_path", ""))
    metrics_on = cfg.metrics.enabled if enable_metrics is None else enable_metrics

    mixed = getattr(ex, "mixed_batch", None)
    mixed_on = bool(getattr(mixed, "enabled", False))
    pipe = getattr(ex, "async_pipeline", None)
    pipe_on = bool(getattr(pipe, "enabled", False))
    mesh_cfg = getattr(ex, "mesh", None)
    # Mesh-native serving (docs/multihost.md): executor.mesh is the
    # first-class knob (hard off-switch); the legacy tpu.mesh_shape
    # still builds a mesh when the block is off (back-compat alias).
    mesh_shape = None
    if mesh_cfg is not None and getattr(mesh_cfg, "enabled", False):
        mesh_shape = dict(mesh_cfg.shape)
    elif getattr(cfg.tpu, "mesh_shape", None):
        mesh_shape = dict(cfg.tpu.mesh_shape)
    mixed_slices, mixed_slice_tokens = _mixed_geometry(ex)

    if ex.backend == "echo":
        executor = EchoExecutor(
            batch_size=ex.max_batch_size,
            page_size=ex.page_size,
            num_pages=ex.kv_pages,
            max_pages_per_seq=max(
                1, cfg.model.max_seq_len // ex.page_size),
            eos_id=tokenizer.eos_id,
            chunk_size=ex.decode_chunk,
            mixed_prefill_slices=mixed_slices,
            mixed_slice_tokens=mixed_slice_tokens,
            # Futures-returning chunk API (docs/performance.md "Async
            # pipeline"): only exposed when the pipeline is on, so the
            # off-switch keeps the exact synchronous echo scheduling.
            async_chunks=pipe_on)
    elif ex.backend == "jax":
        import jax

        from llmq_tpu.models import family_of, get_config
        from llmq_tpu.models.checkpoint import import_hf, load_checkpoint

        from llmq_tpu.parallel import enable_compilation_cache
        enable_compilation_cache()

        mcfg = get_config(cfg.model.name, max_seq_len=cfg.model.max_seq_len)
        if cfg.model.vocab_size:
            mcfg = get_config(cfg.model.name,
                              max_seq_len=cfg.model.max_seq_len,
                              vocab_size=cfg.model.vocab_size)
        fam = family_of(mcfg)
        if tokenizer.vocab_size > mcfg.vocab_size:
            raise ValueError(
                f"tokenizer vocab ({tokenizer.vocab_size}) exceeds model "
                f"vocab ({mcfg.vocab_size}) — ids would silently clip and "
                f"EOS could never be sampled; set model.vocab_size or pick "
                f"a matching tokenizer")
        quant = getattr(cfg.model, "quantization", "")
        if quant not in ("", "int8"):
            raise ValueError(f"unknown model.quantization {quant!r} "
                             f"(supported: 'int8')")
        kv_quant = getattr(cfg.model, "kv_quantization", "")
        if kv_quant not in ("", "int8"):
            raise ValueError(f"unknown model.kv_quantization {kv_quant!r} "
                             f"(supported: 'int8')")
        fam.check_serving(
            mcfg, quantization=quant, kv_quantization=kv_quant,
            mesh=bool(mesh_shape))
        import time as _time
        t_weights0 = _time.perf_counter()
        if params is None:
            path = cfg.model.checkpoint_path
            if path and path.endswith(".safetensors.d"):
                params = import_hf(
                    path, mcfg, meta_rope_layout=cfg.model.meta_rope_layout)
            elif path:
                # An explicitly configured checkpoint that fails to load
                # must abort startup — silently serving random weights is
                # worse than not serving.
                params = load_checkpoint(path)
            if params is None:
                if quant == "int8":
                    # Quantize leaf-by-leaf during init: materializing the
                    # full bf16 tree first would OOM the very chip int8
                    # exists to fit (llama3-8b bf16 = 16 GB = all of v5e).
                    params = fam.init_params_quantized(
                        jax.random.PRNGKey(0), mcfg)
                else:
                    params = fam.init_params(jax.random.PRNGKey(0), mcfg)
        if quant == "int8":
            from llmq_tpu.ops.quant import quantize_params
            # Idempotent: a tree already quantized (init path above, or a
            # caller-provided quantized tree) passes through untouched.
            # Checkpoint-loaded bf16 trees are quantized here — for 8B
            # that requires the checkpoint itself to be loaded shard-wise
            # on a host with enough RAM (checkpoint.py loads to host).
            params = quantize_params(params)
        if boot_id is not None:
            # Checkpoint load / random init / quantization — the
            # "weights" boot stage.
            _cp.boot_stage(boot_id, "weights",
                           _time.perf_counter() - t_weights0)
        mesh = None
        if mesh_shape:
            # Sharded serving (BASELINE config #5, docs/multihost.md
            # "Mesh-native executor"): the engine runs the model dp×tp
            # over the declared mesh; the quantization flag flows into
            # param_shardings inside the executor, dp additionally
            # splits the batch rows and the paged pool's page axis.
            from llmq_tpu.parallel import make_mesh
            mesh = make_mesh(mesh_shape)
        executor = JaxExecutor(
            mcfg, params,
            **executor_geometry(cfg),
            eos_id=tokenizer.eos_id,
            mesh=mesh,
            telemetry_name=name,
            # Warmup runs before InferenceEngine can set the flag.
            telemetry_metrics=metrics_on)
        if warmup:
            executor.warmup()
            if boot_id is not None:
                # The executor decomposed its own warmup wall:
                # artifact (export-cache loads) vs compile (trace +
                # lower) vs warmup (smoke + step calibration).
                for stg, secs in getattr(executor, "warmup_split",
                                         {}).items():
                    _cp.boot_stage(boot_id, stg, secs)
    else:
        raise ValueError(f"unknown executor backend {ex.backend!r}")

    from llmq_tpu.core.types import Priority
    tier_max_wait = {Priority(lvl.priority): lvl.max_wait_time
                     for lvl in cfg.queue.levels}
    engine = InferenceEngine(
        executor, tokenizer,
        name=name,
        max_decode_steps=ex.max_decode_steps,
        preemption=ex.preemption,
        kv_pin_ttl=ex.kv_pin_ttl,
        enable_metrics=metrics_on,
        tier_max_wait=tier_max_wait,
        prefix_cache=getattr(ex, "prefix_cache", None),
        mixed_batch=mixed,
        async_pipeline=pipe,
        kv_tiering=getattr(ex, "kv_tiering", None))
    tier = getattr(ex, "kv_tiering", None)
    from llmq_tpu.observability.device import describe_device
    log.info("built %s engine %s on %s (slots=%d pages=%d page_size=%d "
             "mesh=%s prefix_cache=%s mixed_batch=%s "
             "async_pipeline=%s kv_tiering=%s)",
             ex.backend, name, describe_device(engine.device_identity()),
             ex.max_batch_size, ex.kv_pages, ex.page_size,
             (mesh_shape if (ex.backend == "jax" and mesh_shape)
              else "off"),
             "on" if getattr(ex.prefix_cache, "enabled", False) else "off",
             (f"on(budget={mixed.prefill_token_budget}"
              f"x{mixed_slices})" if mixed_on else "off"),
             (f"on(depth={pipe.depth})" if pipe_on else "off"),
             (f"on(host={tier.host_capacity_mb}MiB)"
              if getattr(tier, "enabled", False) else "off"))
    return engine
