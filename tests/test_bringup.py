"""Chip bring-up guards (ISSUE 21): nothing on the serve path may hide
which device it runs on, fall back silently, or place its caches where
a restart cannot find them. All CPU-runnable — what only a chip can
show is ``chip_smoke.py``'s job (tests/test_chip_smoke.py covers that
script's own logic)."""

from __future__ import annotations

import dataclasses
import os
import shutil

import pytest
import yaml

jax = pytest.importorskip("jax")

from llmq_tpu.core.config import (  # noqa: E402
    ReplicaPoolConfig,
    TPUConfig,
    default_config,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- one rule for the compile cache directory ----------------------------------


class TestCompilationCacheRule:
    @pytest.fixture
    def updates(self, monkeypatch):
        """Record ``jax.config.update`` calls without applying them
        (the session's real cache settings stay untouched)."""
        from llmq_tpu.parallel import mesh

        calls = {}
        monkeypatch.setattr(mesh.jax.config, "update",
                            lambda k, v: calls.__setitem__(k, v))
        return calls

    def test_env_variable_wins_and_code_sets_no_directory(
            self, updates, monkeypatch, tmp_path):
        from llmq_tpu.parallel.mesh import enable_compilation_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        enable_compilation_cache()
        assert "jax_compilation_cache_dir" not in updates
        # Thresholds only.
        assert updates["jax_persistent_cache_min_entry_size_bytes"] == 0
        assert "jax_persistent_cache_min_compile_time_secs" in updates

    def test_unset_uses_fixed_path_under_the_checkout(
            self, updates, monkeypatch, tmp_path):
        from llmq_tpu.parallel import mesh

        assert mesh.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(mesh, "DEFAULT_CACHE_DIR",
                            str(tmp_path / ".jax_cache"))
        mesh.enable_compilation_cache()
        assert updates["jax_compilation_cache_dir"] == str(
            tmp_path / ".jax_cache")
        assert (tmp_path / ".jax_cache").is_dir()

    def test_builder_always_enables_it_on_the_jax_backend(
            self, monkeypatch):
        """No config key switches the cache on or places it: the jax
        backend always calls the rule."""
        import llmq_tpu.parallel as parallel
        from llmq_tpu.engine import build_engine

        seen = []

        class Stop(Exception):
            pass

        def spy():
            seen.append("called")
            raise Stop

        monkeypatch.setattr(parallel, "enable_compilation_cache", spy)
        cfg = default_config()
        cfg.executor.backend = "jax"
        assert not hasattr(cfg.tpu, "compilation_cache_dir")
        with pytest.raises(Stop):
            build_engine(cfg)
        assert seen == ["called"]


# -- tpu.platform is gone: JAX_PLATFORMS chooses the platform ------------------


def test_tpu_platform_setting_is_gone():
    assert "platform" not in {f.name for f in dataclasses.fields(TPUConfig)}
    with open(os.path.join(REPO, "configs", "config.yaml")) as f:
        shipped = yaml.safe_load(f)
    assert set(shipped["tpu"]) == {f.name
                                   for f in dataclasses.fields(TPUConfig)}
    # (The config-parity lint over the tree is tests/test_analysis.py's.)


def test_env_overrides_validate_after_all_are_applied():
    """Settings that are only valid together must not depend on the
    order of the environment: ``chip_smoke.py --mesh`` exports the mesh
    switch and its shape, and the switch used to be validated before
    the shape was read."""
    from llmq_tpu.core.config import _apply_env

    for env in ({"LLMQ_EXECUTOR_MESH_ENABLED": "true",
                 "LLMQ_EXECUTOR_MESH_SHAPE": '{"dp": 2, "tp": 2}'},
                {"LLMQ_EXECUTOR_MESH_SHAPE": '{"dp": 2, "tp": 2}',
                 "LLMQ_EXECUTOR_MESH_ENABLED": "true"}):
        cfg = default_config()
        _apply_env(cfg, env)
        assert cfg.executor.mesh.enabled
        assert cfg.executor.mesh.shape == {"dp": 2, "tp": 2}
    with pytest.raises(ValueError, match="requires a shape"):
        _apply_env(default_config(), {"LLMQ_EXECUTOR_MESH_ENABLED": "true"})


# -- `check` runs the configured backend ---------------------------------------


def test_check_runs_the_configured_backend(monkeypatch, tmp_path):
    """``check`` used to force echo unless ``--backend`` was passed; it
    must build what the configuration says."""
    import llmq_tpu.engine as engine_pkg
    from llmq_tpu.__main__ import main

    built = []

    class Stop(Exception):
        pass

    def spy(cfg, **_kw):
        built.append(cfg.executor.backend)
        raise Stop

    monkeypatch.setattr(engine_pkg, "build_engine", spy)
    # --config exports LLMQ_CONFIG for child processes; restore it.
    monkeypatch.delenv("LLMQ_CONFIG", raising=False)
    path = tmp_path / "config.yaml"
    path.write_text("executor: {backend: jax}\n")
    with pytest.raises(Stop):
        main(["--config", str(path), "check"])
    with pytest.raises(Stop):
        main(["--config", str(path), "--backend", "echo", "check"])
    assert built == ["jax", "echo"]


# -- kernel routing is visible, and interpret mode is CPU-only -----------------


class TestKernelRoutes:
    #: The plan a fused decode route is logged with (rows a tile, tokens
    #: a chunk): the widest chunk, but for SmolLM2's 32 KV heads.
    PLAN = "(rows=8,chunk_tokens=256,ordered)"
    #: llama3-1b at the shipped serving geometry.
    GEOM = dict(batch=8, page_size=16, max_pages=128, n_kv_heads=8,
                head_dim=64, kv_itemsize=2, quant_kv=False, enabled=True,
                multi_ok=True)

    def test_cpu_auto_routes_everything_to_xla(self, monkeypatch):
        from llmq_tpu.ops.attention import kernel_routes

        monkeypatch.delenv("LLMQ_PALLAS", raising=False)
        routes = kernel_routes(decode=True, prefill_rows=2, **self.GEOM)
        assert set(routes.values()) == {"xla"}
        assert set(routes) == {"prefill_write", "prefill_attention",
                               "decode_attention", "decode_write"}

    def test_tpu_backend_names_the_kernels(self, monkeypatch):
        from llmq_tpu.ops import attention

        monkeypatch.delenv("LLMQ_PALLAS", raising=False)
        monkeypatch.setattr(attention.jax, "default_backend",
                            lambda: "tpu")
        routes = attention.kernel_routes(decode=True, prefill_rows=2,
                                         **self.GEOM)
        assert routes == {
            "prefill_write": "pallas:_kv_prefill_kernel",
            "prefill_attention": "pallas:_prefill_attn_kernel",
            "decode_attention": "pallas:_fused_kernel" + self.PLAN,
            "decode_write": "pallas:_fused_kernel" + self.PLAN}
        # The same predicates the dispatchers use: int8 KV at 16-token
        # pages is NOT kernel-eligible (scale page lane alignment) and
        # the report says so instead of leaving it to be discovered.
        q8 = dict(self.GEOM, quant_kv=True, kv_itemsize=1)
        assert attention.kernel_routes(decode=True, **q8) == {
            "decode_attention": "xla", "decode_write": "xla"}
        q8_128 = dict(q8, page_size=128, max_pages=16, batch=64)
        assert attention.kernel_routes(decode=True, **q8_128) == {
            "decode_attention": "pallas:_fused_kernel_q8" + self.PLAN,
            "decode_write": "pallas:_fused_kernel_q8" + self.PLAN}
        # int8-KV prefill attention follows the same page condition;
        # its write is a scatter at any page.
        assert attention.kernel_routes(prefill_rows=2, **q8) == {
            "prefill_write": "xla", "prefill_attention": "xla"}
        assert attention.kernel_routes(prefill_rows=2, **q8_128) == {
            "prefill_write": "xla",
            "prefill_attention": "pallas:_prefill_attn_kernel_q8"}
        assert attention.kernel_routes(
            prefill_rows=2, **dict(q8_128, multi_ok=False)) == {
            "prefill_write": "xla", "prefill_attention": "xla"}
        # A head that fills neither a divisor nor a multiple of 128
        # lanes has no head window: prefill attention alone goes to XLA.
        d96 = dict(self.GEOM, n_kv_heads=4, head_dim=96)
        assert attention.kernel_routes(prefill_rows=1, **d96) == {
            "prefill_write": "pallas:_kv_prefill_kernel",
            "prefill_attention": "xla"}
        # Mesh programs trace with pallas off.
        off = dict(self.GEOM, enabled=False)
        assert set(attention.kernel_routes(
            decode=True, prefill_rows=1, **off).values()) == {"xla"}

    @pytest.mark.parametrize("config, want", [
        ("smollm2-1.7b-bf16", {
            "prefill_write": "pallas:_kv_prefill_kernel",
            "prefill_attention": "pallas:_prefill_attn_kernel",
            # 128-token chunks: 16 MiB of scratch at 4 KiB a token.
            "decode_attention": "pallas:_fused_kernel(rows=8,chunk_tokens=128,ordered)",
            "decode_write": "pallas:_fused_kernel(rows=8,chunk_tokens=128,ordered)"}),
        # int8 pools: the write is a scatter, attention the _q8 twins.
        ("mistral-7b-v0.3-w8kv8", {
            "prefill_write": "xla",
            "prefill_attention": "pallas:_prefill_attn_kernel_q8",
            "decode_attention": "pallas:_fused_kernel_q8" + PLAN,
            "decode_write": "pallas:_fused_kernel_q8" + PLAN})])
    def test_kernel_routes_of_the_served_configurations(
            self, monkeypatch, served_geometry, config, want):
        """What each benchmark configuration's ``mixed_chunk`` (decode
        rows and prompt slices: every op) takes on the chip, at the
        file's own geometry. A geometry edit that drops a cell onto XLA
        fails here, on the CPU, not as a slow cell."""
        from llmq_tpu.ops import attention

        monkeypatch.delenv("LLMQ_PALLAS", raising=False)
        monkeypatch.setattr(attention.jax, "default_backend",
                            lambda: "tpu")
        cfg, ex, q8 = served_geometry(config)
        geom = dict(
            batch=ex["max_batch_size"], page_size=ex["page_size"],
            max_pages=cfg.max_seq_len // ex["page_size"],
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            kv_itemsize=1 if q8 else 2, quant_kv=q8, enabled=True,
            multi_ok=True)
        assert attention.kernel_routes(
            decode=True, prefill_rows=ex["mixed_batch"]["max_slices"],
            **geom) == want
        # The bucket programs run one row, or an admission wave.
        assert attention.kernel_routes(prefill_rows=1, **geom) == {
            k: v for k, v in want.items() if k.startswith("prefill")}

    def test_interpret_is_an_error_on_a_tpu_backend(self, monkeypatch):
        from llmq_tpu.ops import attention

        monkeypatch.setenv("LLMQ_PALLAS", "interpret")
        assert attention.pallas_mode() == "interpret"      # CPU: fine
        routes = attention.kernel_routes(decode=True, **self.GEOM)
        assert routes["decode_attention"] == (
            "pallas-interpret:_fused_kernel" + self.PLAN)
        monkeypatch.setattr(attention.jax, "default_backend",
                            lambda: "tpu")
        with pytest.raises(RuntimeError, match="interpret"):
            attention.pallas_mode()
        monkeypatch.setenv("LLMQ_PALLAS", "bogus")
        with pytest.raises(ValueError, match="LLMQ_PALLAS"):
            attention.pallas_mode()


# -- one process per chip ------------------------------------------------------


def test_subprocess_pool_refuses_a_jax_child_when_parent_holds_the_chip(
        monkeypatch):
    import logging

    from llmq_tpu.controlplane import pool as pool_mod
    from llmq_tpu.observability import device

    errors = []

    class Capture(logging.Handler):
        def emit(self, record):
            errors.append(record.getMessage())

    handler = Capture(level=logging.ERROR)
    pool_mod.log.addHandler(handler)
    monkeypatch.setattr(pool_mod.log, "disabled", False)

    def no_spawn(*_a, **_kw):
        raise AssertionError("a replica was spawned")

    monkeypatch.setattr(pool_mod.subprocess, "Popen", no_spawn)
    monkeypatch.setattr(device, "held_accelerator", lambda: "tpu")
    pool = pool_mod.SubprocessReplicaPool(ReplicaPoolConfig(
        kind="subprocess", base_port=18999, args=["--backend", "jax"],
        ready_timeout=0.1))
    try:
        assert pool.provision(1) is None
    finally:
        pool_mod.log.removeHandler(handler)
    assert any("a chip belongs to one process" in e for e in errors)
    # The backend the child would inherit counts too.
    monkeypatch.setenv("LLMQ_EXECUTOR_BACKEND", "jax")
    inherit = pool_mod.SubprocessReplicaPool(ReplicaPoolConfig(
        kind="subprocess", base_port=18999, args=[], ready_timeout=0.1))
    assert inherit.provision(1) is None
    # An echo child needs no chip: the guard lets it through (and the
    # spawn stub proves it got that far).
    echo = pool_mod.SubprocessReplicaPool(ReplicaPoolConfig(
        kind="subprocess", base_port=18999, args=["--backend", "echo"],
        ready_timeout=0.1))
    with pytest.raises(AssertionError, match="was spawned"):
        echo.provision(1)


def test_held_accelerator_reads_the_registry_only():
    from llmq_tpu.observability.device import (get_device_telemetry,
                                               held_accelerator)

    assert held_accelerator() == ""          # CPU engines hold no chip
    t = get_device_telemetry("held-probe", metrics=False)
    try:
        t.configure_model(platform="tpu", device_kind="TPU v5 lite",
                          device_count=1)
        assert held_accelerator() == "tpu"
    finally:
        t.configure_model()
    assert held_accelerator() == ""


# -- the chip sections of bench.py fail without a chip -------------------------


def test_bench_chip_sections_fail_without_a_tpu():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    with pytest.raises(RuntimeError, match="no TPU"):
        bench._require_tpu("tpu")
    src = open(os.path.join(REPO, "bench.py")).read()
    assert "LLMQ_BENCH_FORCE_CPU" not in src
    assert "LLMQ_BENCH_CACHE_DIR" not in src


# -- device identity on /health ------------------------------------------------


def test_health_and_stats_carry_the_device_identity():
    from llmq_tpu.api import ApiServer
    from llmq_tpu.engine import build_engine

    cfg = default_config()
    cfg.executor.backend = "jax"
    cfg.executor.prefill_buckets = [16]
    cfg.executor.decode_chunk = 1
    cfg.executor.mixed_batch.enabled = False
    cfg.model.max_seq_len = 64
    engine = build_engine(cfg, name="ident0", enable_metrics=False)
    assert engine.device_identity() == {
        "platform": "cpu", "kind": "cpu", "count": len(jax.devices())}
    api = ApiServer(cfg, engine=engine)
    status, body = api.health_check(None)
    assert status == 200 and body["device"]["platform"] == "cpu"
    model = engine.get_stats()["device"]["model"]
    assert model["platform"] == "cpu" and model["device_count"] >= 1
    # Echo engines sit on no device: the field is absent, not faked.
    echo_cfg = default_config()
    echo = build_engine(echo_cfg, name="ident-echo", enable_metrics=False)
    assert echo.device_identity() is None
    _, body = ApiServer(echo_cfg, engine=echo).health_check(None)
    assert "device" not in body


# -- the native core is rebuilt from the source's content ----------------------


@pytest.mark.skipif(shutil.which("g++") is None, reason="no C++ compiler")
def test_native_loader_decides_staleness_from_content(monkeypatch):
    """A tree copy rewrites every mtime: a stale .so with a NEWER mtime
    than the source must still be rebuilt when the source's digest does
    not match the one it was built from."""
    from llmq_tpu.native import loader

    assert loader._build_if_needed()
    with open(loader._STAMP) as f:
        good = f.read()
    so_mtime = os.path.getmtime(loader._SO)
    with open(loader._STAMP, "w") as f:
        f.write("0" * 64)                    # "built from other source"
    os.utime(loader._SO, (so_mtime + 3600, so_mtime + 3600))
    assert loader._build_if_needed()
    with open(loader._STAMP) as f:
        assert f.read() == good
    # rebuilt: the file's time is the build's, whatever the hour
    assert os.path.getmtime(loader._SO) != so_mtime + 3600
    # Up to date: no rebuild (the compiler is not invoked).
    monkeypatch.setattr(loader.subprocess, "run",
                        lambda *a, **k: pytest.fail("rebuilt"))
    assert loader._build_if_needed()
