"""``chip_smoke.py``'s own logic, on the CPU (ISSUE 21).

The script's verdict comes only from a chip; what can rot without one
is the parent: starting and stopping the server child, the request
phases, and the rule that a failed phase means a non-zero exit and no
result line. These run it against ``--backend echo`` (no device, so
the device phase can never pass) and ``scripts/chip_kernel_check.py``
at a tiny size."""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name: str, *path: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_smoke():
    return _load("chip_smoke", "chip_smoke.py")


def _load_kernel_check():
    return _load("chip_kernel_check", "scripts", "chip_kernel_check.py")


def test_parent_imports_neither_jax_nor_the_package():
    """One process per chip: the parent must leave the chip to its
    children, so it may not import anything that could touch JAX."""
    code = ("import sys; sys.argv=['chip_smoke.py','--help']\n"
            "import runpy\n"
            "try:\n"
            "    runpy.run_path('chip_smoke.py', run_name='__main__')\n"
            "except SystemExit:\n"
            "    pass\n"
            "bad = [m for m in sys.modules if m == 'jax' or "
            "m.startswith(('jax.', 'llmq_tpu'))]\n"
            "assert not bad, bad\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr


def test_failed_phase_means_nonzero_exit_and_no_result_line(tmp_path):
    """The whole script against echo: the server comes up and answers,
    the device phase fails (echo sits on no device), so the exit code
    is non-zero, no ``{"ok": ...}`` line is printed and no child is
    left running."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--backend", "echo", "--tag", f"unittest{os.getpid()}"],
        cwd=str(tmp_path), capture_output=True, text=True, timeout=120)
    assert out.returncode != 0, out.stdout
    assert '"ok"' not in out.stdout, out.stdout
    assert "healthy after" in out.stdout          # it did start
    assert "carries no device" in out.stderr      # and failed where it must
    log = os.path.join(REPO, "chiprun_out",
                       f"chip_smoke_serve_unittest{os.getpid()}.log")
    try:
        with open(log) as f:
            pid_line = f.read()
        assert "serving on" in pid_line
    finally:
        if os.path.exists(log):
            os.remove(log)
    leftovers = subprocess.run(
        ["pgrep", "-f", f"chip_smoke_serve_unittest{os.getpid()}"],
        capture_output=True, text=True)
    assert leftovers.stdout.strip() == ""


def test_request_phases_and_clean_shutdown_against_echo(tmp_path):
    """The serve-phase helpers the chip run relies on, end to end over
    HTTP against a real ``serve`` child: every tier answers, turn 2
    reuses cached tokens, the SSE stream is framed start → done with
    deltas adding up to the stored response, SIGTERM exits cleanly
    with one "shutting down"; a check that does not hold raises."""
    cs = _load_smoke()
    srv = cs.Server("echo", "cpu", {}, str(tmp_path / "serve.log"))
    report = {}
    try:
        srv.start()
        assert srv.wait_healthy(time.monotonic() + 60.0) < 60.0
        cs.phase_tiers(srv, report)
        assert set(report["tiers"]) == {"realtime", "high", "normal",
                                        "low"}
        cs.phase_conversation(srv, report)
        assert report["conversation"]["turn2_cached_tokens"] > 0
        cs.phase_stream(srv, report)
        assert report["stream"]["deltas"] > 0      # echo decodes to text
        with pytest.raises(cs.SmokeFailure, match="carries no device"):
            cs.phase_device(srv, report, 1)
        assert srv.stop() < cs.SHUTDOWN_S
    finally:
        srv.kill()
    assert srv.proc.poll() is not None


def test_metric_and_mesh_parsing():
    cs = _load_smoke()
    expo = ('# HELP llm_queue_compile_cache_misses_total x\n'
            'llm_queue_compile_cache_misses_total{engine="engine0"} 9.0\n'
            'llm_queue_compile_cache_misses_total{engine="e1"} 3.0\n'
            'llm_queue_warmup_progress{engine="engine0"} 1.0\n')
    assert cs.metric(expo, "compile_cache_misses_total") == 12.0
    assert cs.metric(expo, "warmup_progress") == 1.0
    assert cs.metric(expo, "absent_family") == 0.0
    assert cs.parse_mesh("dp=2,tp=2") == {"dp": 2, "tp": 2}
    with pytest.raises(Exception):
        cs.parse_mesh("tp4")


class TestKernelCheckLogic:
    """The kernel phase's pure parts (the end-to-end tiny run below is
    ``slow``)."""

    def test_a_cpu_run_is_refused_unless_tiny(self):
        kc = _load_kernel_check()
        with pytest.raises(kc.CheckFailure, match="not a TPU"):
            kc.main([])

    def test_compare_fails_outside_the_tolerance(self):
        import numpy as np

        kc = _load_kernel_check()
        a = {"decode.step0": np.zeros((2, 8), np.float32)}
        near = {"decode.step0": a["decode.step0"] + 0.1}
        far = {"decode.step0": a["decode.step0"] + 0.2}
        res = kc.compare(a, near, kc.TOL, "near")
        assert res["max_abs_delta"] == pytest.approx(0.1)
        assert res["by_program"] == {"decode": pytest.approx(0.1)}
        with pytest.raises(kc.CheckFailure, match="exceeds tolerance"):
            kc.compare(a, far, kc.TOL, "far")
        bad = {"decode.step0": np.full((2, 8), np.nan, np.float32)}
        with pytest.raises(kc.CheckFailure, match="non-finite"):
            kc.compare(a, bad, kc.TOL, "nan")
        # int8 weights: one logit's heavy-tail jump passes (the max is
        # only reported), a shift of the whole row does not.
        spike = {"decode.step0": a["decode.step0"].copy()}
        spike["decode.step0"][0, 0] = 0.3
        res = kc.compare(a, spike, None, "spike",
                         rms_tol=kc.TOL_W8A8_RMS)
        assert res["max_abs_delta"] == pytest.approx(0.3)
        assert res["rms_delta"] == pytest.approx(0.075)
        with pytest.raises(kc.CheckFailure, match="RMS"):
            kc.compare(a, far, None, "shift", rms_tol=kc.TOL_W8A8_RMS)

    def test_model_file_gives_the_benchmark_geometry(self, monkeypatch):
        """``--model-file``: the benchmark's SmolLM2 configuration (read,
        not edited) becomes a registered model, and its ``server`` block
        loads as the program's configuration: the shape the cells run."""
        from llmq_tpu.models import llama

        kc = _load_kernel_check()
        monkeypatch.setattr(llama, "MODEL_CONFIGS",
                            dict(llama.MODEL_CONFIGS))
        server = kc.register_model_file(os.path.join(
            REPO, "benchmark", "configs", "smollm2-1.7b-bf16.json"))
        mcfg = llama.get_config(server["model"]["name"])
        assert (mcfg.n_heads, mcfg.n_kv_heads, mcfg.head_dim) == (32, 32, 64)
        assert (mcfg.n_layers, mcfg.dim, mcfg.tie_embeddings) == (
            24, 2048, True)
        assert llama.get_config(mcfg.name, n_layers=2).n_layers == 2
        ex = server["executor"]
        assert (ex["page_size"], ex["max_batch_size"]) == (16, 32)
        assert server["model"]["max_seq_len"] // ex["page_size"] == 256

    def test_schedule_fits_the_executor_geometry(self):
        from types import SimpleNamespace

        kc = _load_kernel_check()
        for B, ps, pages in ((8, 16, 512), (64, 128, 264)):
            mp = 2048 // ps
            ex = SimpleNamespace(
                spec=SimpleNamespace(batch_size=B, page_size=ps,
                                     num_pages=pages,
                                     max_pages_per_seq=mp),
                model_cfg=SimpleNamespace(vocab_size=128256),
                mixed_prefill_slices=2,
                mixed_slice_tokens=64,
                prefill_buckets=[128, 512, 2048])
            sch = kc.schedule(ex)
            assert len(sch["rows"]) == B and sch["T"] == 64
            # One prompt lands in every bucket; pages are disjoint and
            # page 0 stays reserved.
            lens = [r["length"] for r in sch["rows"]]
            assert max(lens) > 512 and any(128 < n <= 512 for n in lens)
            used = [p for r in sch["rows"] for p in r["bt"] if p]
            assert len(used) == len(set(used)) and min(used) >= 1
            assert max(used) < pages


@pytest.mark.slow
def test_kernel_check_runs_at_a_tiny_size(tmp_path):
    """The kernel phase's script end to end on the CPU: it builds the
    configured engine, holds program text against the logged routes
    and compares teacher-forced logits across the serving, pure-JAX
    and float32 paths."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    env.update({
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
        "JAX_ENABLE_COMPILATION_CACHE": "false",
        "LLMQ_CONFIG": os.path.join(REPO, "configs", "config.yaml"),
        "LLMQ_MODEL_MAX_SEQ_LEN": "128",
        "LLMQ_EXECUTOR_PREFILL_BUCKETS": "[32, 64]",
        "LLMQ_EXECUTOR_MAX_BATCH_SIZE": "4",
        "LLMQ_EXECUTOR_KV_PAGES": "64",
        "LLMQ_EXECUTOR_DECODE_CHUNK": "4",
        "LLMQ_EXECUTOR_PREFILL_BATCH": "1",
        "LLMQ_EXECUTOR_MIXED_BATCH_PREFILL_TOKEN_BUDGET": "32",
    })
    out_path = tmp_path / "kernels.json"
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "chip_kernel_check.py"),
         "--tiny", "--out", str(out_path)],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    rep = json.loads(out_path.read_text())
    assert rep["device"]["platform"] == "cpu"
    assert set(rep["programs"]) >= {"prefill_b32", "prefill_b64",
                                    "decode_chunk", "mixed_chunk"}
    for prog in rep["programs"].values():
        assert prog["mosaic_calls"] == 0
        assert set(prog["routes"].values()) == {"xla"}
    logits = rep["logits"]
    assert logits["serving_vs_pure"]["max_abs_delta"] == 0.0
    assert 0.0 < logits["serving_vs_f32"]["max_abs_delta"] <= 0.15
