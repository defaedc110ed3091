"""Test harness configuration.

Sets up the virtual 8-device CPU mesh BEFORE any jax import so sharding
tests exercise real multi-device code paths without TPU hardware
(SURVEY.md §4: "a CPU/jax emulated-device path so TPU code paths run in CI
without a TPU").
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Hermetic compile caches: every jax-backend engine build turns the
# persistent caches on (parallel/mesh.enable_compilation_cache), and an
# unset JAX_COMPILATION_CACHE_DIR means <checkout>/.jax_cache — state
# that would leak from one test RUN into the next. A per-session
# directory keeps runs independent (the export cache under it stays
# live, so warm-restart paths are still exercised within a session).
# XLA's own persistent cache stays off: XLA:CPU reloads log a
# machine-feature warning per program and the suite gains nothing.
import atexit  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

_CACHE_DIR = tempfile.mkdtemp(prefix="llmq-test-jax-cache-")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _CACHE_DIR
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
atexit.register(shutil.rmtree, _CACHE_DIR, ignore_errors=True)

import faulthandler  # noqa: E402
import signal  # noqa: E402

# Hung-test diagnosability (ISSUE 5 satellite): the tier-1 gate runs
# under `timeout -k 10 870`, which delivers SIGTERM on expiry — dump
# every thread's stack THEN die, so a wedged chaos/cluster test names
# the exact blocking frame instead of reading as a silent kill. SIGUSR1
# is registered non-fatally for live debugging of a stuck local run.
faulthandler.enable()
try:
    faulthandler.register(signal.SIGTERM, chain=True)
    faulthandler.register(signal.SIGUSR1, chain=False)
except (AttributeError, ValueError, OSError):
    # Platforms without register()/these signals (e.g. Windows): the
    # plain enable() above still covers hard crashes.
    pass

# Lockdep opt-in (docs/analysis.md): LLMQ_LOCKDEP=1 instruments every
# threading.Lock/RLock created from here on with the lock-order-graph
# tracker. MUST install before any llmq_tpu import below — module-level
# locks (native loader, metrics registry, usage ledger singletons) are
# created at import time and would otherwise go untracked. Violations
# (potential-deadlock cycles, held-lock blocking calls) fail the run at
# session end via pytest_sessionfinish.
from llmq_tpu.analysis import lockdep  # noqa: E402

if lockdep.enabled_by_env():
    lockdep.install()

import pytest  # noqa: E402

from llmq_tpu.core.clock import FakeClock  # noqa: E402


def pytest_sessionfinish(session, exitstatus):
    """Fail a lockdep-instrumented run on any recorded violation —
    after every test, so the report names all cycles at once rather
    than whichever test tripped first."""
    if not lockdep.is_installed():
        return
    v = lockdep.violations()
    if v:
        rep = getattr(session.config, "_lockdep_reported", False)
        if not rep:
            session.config._lockdep_reported = True
            import sys as _sys
            _sys.stderr.write(
                f"\nLOCKDEP: {len(v)} violation(s) recorded during this "
                "run:\n\n" + "\n\n".join(v) + "\n")
        session.exitstatus = 3


#: Memory mappings of a worker process past which the programs JAX has
#: compiled are dropped (``vm.max_map_count`` is 65,530 here; one test of
#: a routed family's engine adds some 4,000).
MAPPINGS_HIGH_WATER = 40_000


@pytest.fixture(autouse=True)
def _release_compiled_programs_before_the_mappings_run_out():
    """After a test, if the process holds more than
    ``MAPPINGS_HIGH_WATER`` memory mappings: drop every compiled program
    JAX keeps (``jax.clear_caches``) and collect what held them. Why: a
    compiled CPU program is MAPPINGS of the worker process, a worker
    runs file after file and keeps all of them, and past
    ``vm.max_map_count`` the next compile's ``mmap`` fails inside LLVM
    and the worker dies with a segmentation fault, in whatever test
    comes next (met in PR 52: a new file of 27 tests left ~40,000
    behind, and ``tests/test_afmoe.py`` on the same worker went down
    at the same test — twice with no guard, and once more with the
    release scoped to the new file alone: what fills a worker is the sum
    of the files it was dealt, so the guard is the suite's; released, a
    worker is back to some 700). Under the mark nothing happens: one
    read of ``/proc/self/maps``, 2-3 ms."""
    yield
    import sys
    jax = sys.modules.get("jax")
    if jax is None:
        return
    try:
        with open("/proc/self/maps", "rb") as f:
            mappings = sum(1 for _ in f)
    except OSError:
        return
    if mappings > MAPPINGS_HIGH_WATER:
        import gc
        jax.clear_caches()
        gc.collect()


@pytest.fixture
def fake_clock() -> FakeClock:
    return FakeClock()


@pytest.fixture(params=["python", "native"])
def queue_backend(request) -> str:
    """Every queue test runs against both the pure-Python and the C++
    native ordering core."""
    if request.param == "native":
        from llmq_tpu.native.loader import native_available
        if not native_available():
            pytest.skip("native queue core not buildable here")
    return request.param


@pytest.fixture(scope="session")
def served_geometry():
    """``served_geometry(name) -> (model config, executor block, int8
    KV?)`` of a benchmark configuration: its model registered as the
    benchmark's own child registers it and read back through
    ``get_config`` at the file's context, beside the file's
    ``server.executor`` block — so a test at "the served geometry"
    follows the file, not a copy of its numbers."""
    import json

    from benchmark.harness import contract
    from benchmark.harness.child import register_model
    from llmq_tpu.models.llama import get_config

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def read(name: str):
        with open(os.path.join(repo, "benchmark", "configs",
                               f"{name}.json"), encoding="utf-8") as f:
            doc = json.load(f)
        model = doc["server"]["model"]
        register_model(model["name"],
                       {k: doc[k] for k in contract.MODEL_KEYS if k in doc})
        cfg = get_config(model["name"], max_seq_len=model["max_seq_len"])
        return (cfg, doc["server"]["executor"],
                model.get("kv_quantization") == "int8")

    return read
