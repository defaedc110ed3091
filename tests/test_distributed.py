"""Multi-process distributed backend (SURVEY §2.2 "Distributed
communication backend"): two OS processes rendezvous through
``distributed_init`` (the DCN coordination analogue of NCCL/MPI
bootstrap) and run a cross-process collective on the CPU backend —
the same code path a multi-host v5e-16 deployment uses (BASELINE
config #5), minus the ICI.
"""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = r"""
import os, sys
import numpy as np

sys.path.insert(0, {repo!r})
import jax
from llmq_tpu.parallel.mesh import distributed_init, make_mesh

distributed_init(coordinator={coord!r}, num_processes=2,
                 process_id={pid}, initialization_timeout=60)
# Idempotency: a second call must be a clean no-op.
distributed_init(coordinator={coord!r}, num_processes=2,
                 process_id={pid})
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 4, len(jax.devices())   # 2 per process

# Cross-process collective: allgather each process's rank.
from jax.experimental import multihost_utils
got = multihost_utils.process_allgather(np.asarray([jax.process_index()]))
assert sorted(np.asarray(got).ravel().tolist()) == [0, 1], got

# A global mesh spanning both processes compiles + executes a psum.
mesh = make_mesh({{"dp": 4}})
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

x = jax.make_array_from_callback(
    (4,), NamedSharding(mesh, P("dp")),
    lambda idx: np.ones((1,), np.float32))
total = jax.jit(lambda a: jnp.sum(a),
                out_shardings=NamedSharding(mesh, P()))(x)
assert float(total) == 4.0, float(total)
print(f"proc {{jax.process_index()}} OK", flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _clean_env():
    """Child env with its OWN jax platform and device count: the test
    session's JAX_*/XLA_* settings (8 virtual devices, conftest) must
    not leak into a child that rendezvouses as one of two 2-device
    processes."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    return env


@pytest.mark.skipif(os.environ.get("LLMQ_SKIP_MULTIPROC") == "1",
                    reason="multi-process test disabled")
def test_two_process_rendezvous_and_collective(tmp_path):
    coord = f"127.0.0.1:{_free_port()}"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    procs = []
    try:
        for pid in range(2):
            script = _WORKER.format(repo=repo, coord=coord, pid=pid)
            procs.append(subprocess.Popen(
                [sys.executable, "-c", script], env=_clean_env(),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=120)
            outs.append(out)
    finally:
        for p in procs:   # no leaked workers on rendezvous timeout
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
    assert any("proc 0 OK" in o for o in outs)
    assert any("proc 1 OK" in o for o in outs)


def test_bad_coordinator_fails_fast():
    """distributed_init must FAIL FAST on a genuinely bad setup, not
    swallow the error and limp along single-host (round-1 advisory).
    jax's client surfaces a dead coordinator as a fatal abort (absl
    FATAL from the coordination service) — either way the process must
    die with a distributed-error diagnostic, never print SWALLOWED."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = (
        "import os, sys\n"
        f"sys.path.insert(0, {repo!r})\n"
        "from llmq_tpu.parallel.mesh import distributed_init\n"
        "try:\n"
        "    distributed_init(coordinator='127.0.0.1:1',"
        " num_processes=2, process_id=1, initialization_timeout=5)\n"
        "except Exception:\n"
        "    print('RAISED', flush=True); raise SystemExit(0)\n"
        "print('SWALLOWED', flush=True); raise SystemExit(1)\n")
    env = _clean_env()
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=90)
    out = p.stdout + p.stderr
    assert "SWALLOWED" not in out, out
    if "RAISED" not in out:   # fatal-abort path
        assert p.returncode != 0, out
        assert ("DEADLINE_EXCEEDED" in out
                or "CoordinationService" in out
                or "distributed service" in out), out


_TP_WORKER = r"""
import os, sys
import numpy as np

sys.path.insert(0, {repo!r})
import jax
import jax.numpy as jnp
from llmq_tpu.parallel.mesh import distributed_init, make_mesh

distributed_init(coordinator={coord!r}, num_processes=2,
                 process_id={pid}, initialization_timeout=60)
assert jax.process_count() == 2

from llmq_tpu.models.llama import (forward_decode, init_kv_pages,
                                   init_params, llama3_tiny)
from llmq_tpu.parallel.sharding import kv_cache_shardings, param_shardings
from jax.sharding import NamedSharding, PartitionSpec as P

# TP=4 across the two processes (2 devices each): a REAL cross-process
# tensor-parallel forward — the all-reduces after wo/w_down ride the
# inter-process transport (the DCN path of a multi-host v5e-16).
mesh = make_mesh({{"tp": 4}})
cfg = llama3_tiny(dim=128, n_layers=2, n_heads=8, n_kv_heads=4,
                  ffn_dim=256, vocab_size=256, max_seq_len=64,
                  dtype=jnp.float32)
params = init_params(jax.random.PRNGKey(0), cfg)   # identical per proc

def globalize(tree, shardings):
    return jax.tree.map(
        lambda x, s: jax.make_array_from_callback(
            x.shape, s, lambda idx: np.asarray(x)[idx]),
        tree, shardings)

gparams = globalize(params, param_shardings(cfg, mesh))
cache = init_kv_pages(cfg, 9, 8)
gcache = globalize(dict(cache), dict(kv_cache_shardings(cfg, mesh)))
repl = NamedSharding(mesh, P())
B = 2
tokens = np.array([3, 5], np.int32)
pos = np.zeros(B, np.int32)
bt = np.zeros((B, 8), np.int32)
bt[0, 0], bt[1, 0] = 1, 2
g = lambda x: jax.make_array_from_callback(  # noqa: E731
    x.shape, repl, lambda idx: x[idx])
logits, _ = forward_decode(gparams, cfg, g(tokens), g(pos), gcache, g(bt))
# GSPMD leaves the logits vocab-sharded (tp on the head); replicate so
# each process can read the full row locally.
logits = jax.jit(lambda x: x, out_shardings=repl)(logits)
tp_local = np.asarray(logits.addressable_shards[0].data)

# Single-process reference with the SAME weights, process-local.
ref_logits, _ = forward_decode(params, cfg, jnp.asarray(tokens),
                               jnp.asarray(pos),
                               init_kv_pages(cfg, 9, 8), jnp.asarray(bt))
ref = np.asarray(ref_logits)
assert np.allclose(tp_local, ref, atol=1e-4), np.abs(tp_local - ref).max()
print(f"proc {{jax.process_index()}} TP-forward OK", flush=True)
"""


@pytest.mark.slow      # runs on the CPU under jax 0.9 (it was skipped as
@pytest.mark.skipif(   # TPU-only before); tier-1 keeps the rendezvous test
    os.environ.get("LLMQ_SKIP_MULTIPROC") == "1",
    reason="multi-process test disabled")
def test_two_process_tensor_parallel_forward(tmp_path):
    """Shard a real Llama forward tp=4 across two OS processes and check
    it against the single-process reference (the
    2-process test covered dp only)."""
    coord = f"127.0.0.1:{_free_port()}"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    try:
        for pid in range(2):
            script = _TP_WORKER.format(repo=repo, coord=coord, pid=pid)
            procs.append(subprocess.Popen(
                [sys.executable, "-c", script], env=_clean_env(),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
    assert any("proc 0 TP-forward OK" in o for o in outs)
    assert any("proc 1 TP-forward OK" in o for o in outs)


@pytest.mark.skipif(os.environ.get("LLMQ_SKIP_MULTIPROC") == "1",
                    reason="multi-process test disabled")
def test_serve_entrypoints_join_cluster(tmp_path):
    """Two `python -m llmq_tpu gateway` processes with LLMQ_COORDINATOR
    env rendezvous into one jax.distributed cluster and both serve
    HTTP — the multi-host deployment path of docs/deployment.md."""
    coord_port = _free_port()
    ports = [_free_port() for _ in range(2)]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    try:
        for pid in range(2):
            env = _clean_env()
            env["PYTHONPATH"] = repo
            env["LLMQ_COORDINATOR"] = f"127.0.0.1:{coord_port}"
            env["LLMQ_NUM_PROCESSES"] = "2"
            env["LLMQ_PROCESS_ID"] = str(pid)
            env["LLMQ_CLUSTER_TIMEOUT"] = "60"
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "llmq_tpu", "--host", "127.0.0.1",
                 "--port", str(ports[pid]), "gateway"],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True))
        import urllib.request
        deadline = 60
        import time as _t
        healthy = 0
        t0 = _t.time()
        while _t.time() - t0 < deadline and healthy < 2:
            healthy = 0
            for p in ports:
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{p}/health", timeout=2):
                        healthy += 1
                except OSError:
                    pass
            if healthy < 2:
                _t.sleep(0.5)
        assert healthy == 2, "gateways did not become healthy"
    finally:
        outs = []
        for p in procs:
            p.terminate()
            try:
                outs.append(p.communicate(timeout=20)[0])
            except subprocess.TimeoutExpired:
                p.kill()
                outs.append(p.communicate()[0])
    joined = [o for o in outs
              if "jax.distributed initialised" in o]
    assert len(joined) == 2, outs
