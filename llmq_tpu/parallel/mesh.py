"""Device mesh construction + multi-host init."""

from __future__ import annotations

import os
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from llmq_tpu.utils.logging import get_logger

log = get_logger("mesh")


def make_mesh(shape: Dict[str, int],
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a named mesh, e.g. ``make_mesh({"dp": 1, "tp": 8})`` for a
    v5e-8 TP-only serving mesh, or ``{"dp": 2, "tp": 8}`` over a 2-host
    v5e-16. Axis sizes must multiply to the device count; an axis size of
    -1 is inferred."""
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    names = list(shape.keys())
    sizes = list(shape.values())
    if sizes.count(-1) > 1:
        raise ValueError("at most one mesh axis may be -1")
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1])) or 1
        if n % known:
            raise ValueError(f"cannot infer axis: {n} devices not divisible by {known}")
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes)) if sizes else 1
    if total != n:
        raise ValueError(
            f"mesh shape {dict(zip(names, sizes))} needs {total} devices, "
            f"have {n}")
    arr = np.array(devs).reshape(sizes)
    mesh = Mesh(arr, axis_names=tuple(names))
    log.info("mesh: %s over %d devices (%s)",
             dict(zip(names, sizes)), n, devs[0].platform)
    return mesh


#: Where the persistent caches live when ``JAX_COMPILATION_CACHE_DIR``
#: is not set: a FIXED path under the checkout (the directory is part
#: of the cache key on some backends, so a path made from a temp name,
#: a pid or the time never hits). Listed in ``.gitignore``.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> None:
    """Turn on JAX's persistent compilation cache — the ONE place a
    cache directory is decided, called by every jax-backend engine
    build (and the bench).

    One rule: with ``JAX_COMPILATION_CACHE_DIR`` in the environment JAX
    has already taken the directory, so this sets the thresholds only
    and no directory in code; without it, :data:`DEFAULT_CACHE_DIR`.
    The executor's ``jax.export`` artifact cache lives in
    ``<this dir>/export``.

    Programs are kept whatever they weigh (the small prefill buckets
    still pay full tracing + Mosaic lowering) once they took half a
    second to compile: a warm start must load every warm-up program
    (chip_smoke.py asserts it) without filing each eager one-liner.
    Safe to call repeatedly."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log.info("XLA compilation cache at %s",
             jax.config.jax_compilation_cache_dir)


def single_device_mesh(axis_names: Sequence[str] = ("dp", "tp")) -> Mesh:
    """A trivial mesh on one device — lets the same pjit code path run
    unsharded on a single chip (BASELINE config #2)."""
    dev = np.array(jax.devices()[:1]).reshape([1] * len(axis_names))
    return Mesh(dev, axis_names=tuple(axis_names))


def distributed_init(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     initialization_timeout: Optional[int] = None) -> None:
    """Multi-host bring-up: ``jax.distributed.initialize`` — the DCN-side
    coordination service (role of MPI ranks / NCCL bootstrap in GPU
    stacks). Idempotent: re-initialising an already-initialised runtime
    is a no-op; any OTHER failure (bad coordinator address, rank
    mismatch, timeout) propagates — a half-initialised multi-host
    serving process must fail fast, not limp along single-host.

    Exercised for real by tests/test_distributed.py: two OS processes
    rendezvous on a local coordinator and run a cross-process
    allgather over the CPU backend."""
    if jax.distributed.is_initialized():
        log.info("jax.distributed already initialised")
        return
    kwargs = {}
    if initialization_timeout is not None:
        kwargs["initialization_timeout"] = initialization_timeout
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id, **kwargs)
    log.info("jax.distributed initialised: process %d of %d",
             jax.process_index(), jax.process_count())
