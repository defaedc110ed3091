"""Replica pool: the control plane's provision seam
(docs/controlplane.md).

The controller decides *that* a replica must be added or removed; a
:class:`ReplicaPool` knows *how*. The contract is deliberately small:

- ``provision(seq)`` brings a fresh replica up and returns a READY
  :class:`Endpoint` describing it (not yet registered with the load
  balancer — the controller does that), or None when the pool cannot
  provision (capacity exhausted, spawn failure). Pool-built endpoints
  carry ``metadata["pool"] = True`` — the controller only ever
  decommissions endpoints it provisioned, never static peers or the
  process's own engine.
- ``decommission(endpoint)`` tears the backing replica down. The
  controller drains the endpoint FIRST (no new dispatch, in-flight
  work finishes) and only then decommissions, so a pool never has to
  reason about live traffic.

Implementations:

- :class:`LocalEnginePool` — in-process engines from a factory
  callable, each optionally watched by its own
  :class:`~llmq_tpu.engine.supervisor.EngineSupervisor`. The test and
  bench harness, and the single-host serve story.
- :class:`SubprocessReplicaPool` — real ``python -m llmq_tpu serve``
  OS processes on this host (replica N on ``base_port + N``), drained
  via SIGTERM (the orchestrated-exit signal ``__main__`` already
  honors).
- :class:`ExecReplicaPool` — shell commands (the compose/k8s hook):
  ``provision_cmd`` scales the deployment up and names the new
  replica's URL (last stdout line, or ``url_template``);
  ``decommission_cmd`` scales it back down.
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, Optional

from llmq_tpu.core.clock import Clock, SYSTEM_CLOCK
from llmq_tpu.core.config import ReplicaPoolConfig, SupervisorConfig
from llmq_tpu.loadbalancer.load_balancer import Endpoint
from llmq_tpu.utils.logging import get_logger

log = get_logger("controlplane.pool")


def _wait_ready(url: str, timeout: float) -> Optional[Dict[str, Any]]:
    """Poll ``{url}/health`` until it answers 200 (the provision
    contract: a returned endpoint is immediately dispatchable — an
    endpoint registered before its replica serves would trip breakers
    and get itself declared dead while still booting).

    Returns the parsed /health JSON body (``{}`` when unparseable) so
    the pool can adopt the child's boot decomposition, or None on
    timeout."""
    import json
    import urllib.request
    deadline = time.monotonic() + timeout  # lint: allow-wallclock — replica readiness is real elapsed time
    while time.monotonic() < deadline:  # lint: allow-wallclock — see above
        try:
            with urllib.request.urlopen(f"{url}/health",
                                        timeout=1.0) as resp:
                if resp.status == 200:
                    try:
                        body = json.loads(resp.read().decode("utf-8"))
                    except Exception:  # noqa: BLE001 — health is up; body shape is best-effort
                        body = {}
                    return body if isinstance(body, dict) else {}
        except Exception:  # noqa: BLE001 — still coming up
            pass
        time.sleep(0.1)
    return None


def _adopt_child_boot(replica_id: str, kind: str,
                      health_body: Optional[Dict[str, Any]],
                      total_s: float) -> None:
    """Fold a child replica's /health ``boot`` block into this
    process's boot registry (provision = ready wall minus the stages
    the child stamped itself). No-op when the critical-path plane is
    off or the child predates the boot block."""
    from llmq_tpu.observability import critical_path as _cp
    if not _cp.cp_enabled():
        return
    boot = (health_body or {}).get("boot") or {}
    stages = boot.get("stages_s") or {}
    try:
        _cp.get_boot_registry().adopt(replica_id, kind, stages,
                                      total_s=total_s)
    except Exception:  # noqa: BLE001 — telemetry must not fail provision
        log.exception("boot adoption failed for %s", replica_id)


class ReplicaPool:
    """Base contract (see module docstring)."""

    kind = "base"

    #: Disagg role the NEXT provision should give its replica
    #: (docs/disaggregation.md "Role-aware scaling") — set by the
    #: controller right before ``provision``; None means unified/no
    #: preference. Subprocess/exec pools export it as
    #: ``LLMQ_DISAGG_ROLE`` so the child config picks it up.
    role_hint: Optional[str] = None

    def provision(self, seq: int) -> Optional[Endpoint]:
        raise NotImplementedError

    def decommission(self, endpoint: Endpoint) -> None:
        raise NotImplementedError

    def stop(self) -> None:
        """Tear down every replica the pool still owns (process
        shutdown path)."""

    def get_stats(self) -> Dict[str, Any]:
        return {"kind": self.kind}


class LocalEnginePool(ReplicaPool):
    """In-process engine replicas from a factory callable.

    ``engine_factory(seq)`` returns a started-or-startable engine (or
    None to refuse). Each engine gets its own crash supervisor by
    default, so a replica that crash-loops *fails out of rotation* (the
    LB probe consults ``engine.healthy()``) and the controller replaces
    it — the exact flow the chaos lane pins.
    """

    kind = "local"

    def __init__(self, engine_factory: Callable[[int], Any], *,
                 supervise: bool = True,
                 supervisor_config: Optional[SupervisorConfig] = None,
                 enable_metrics: bool = False) -> None:
        self._factory = engine_factory
        self._supervise = supervise
        self._supervisor_config = (supervisor_config
                                   or SupervisorConfig(
                                       check_interval=0.1))
        self._enable_metrics = enable_metrics
        self._mu = threading.Lock()
        self._engines: Dict[str, Any] = {}
        self._supervisors: Dict[str, Any] = {}
        self.provisioned = 0
        self.decommissioned = 0

    def provision(self, seq: int) -> Optional[Endpoint]:
        from llmq_tpu.observability import critical_path as _cp
        cp = _cp.cp_enabled()
        boot_rid = f"local-{seq}"
        t_boot0 = time.perf_counter()
        if cp:
            # Open the PROCESS boot record before the factory runs so
            # the engine builder stamps weights/compile/warmup into it
            # (and the engine stamps first_token later) instead of into
            # a previously provisioned replica's record.
            _cp.boot_begin(boot_rid, self.kind, process=True)
        engine = self._factory(seq)
        if engine is None:
            return None
        if not engine.running:
            engine.start()
        if cp:
            wall = time.perf_counter() - t_boot0
            rec = _cp.get_boot_registry().get(boot_rid) or {}
            known = sum(v for k, v in (rec.get("stages_s") or {}).items()
                        if k != "provision")
            _cp.boot_stage(boot_rid, "provision",
                           max(0.0, wall - known))
            _cp.boot_ready(boot_rid, wall)
        if self._supervise:
            from llmq_tpu.engine.supervisor import EngineSupervisor
            sup = EngineSupervisor(engine,
                                   config=self._supervisor_config,
                                   enable_metrics=self._enable_metrics)
            sup.start()
        else:
            sup = None
        eid = engine.name
        ep = Endpoint(id=eid, name=eid, url=f"local://{eid}",
                      metadata={"engine": engine, "pool": True,
                                "pool_seq": seq,
                                "boot_id": boot_rid})
        with self._mu:
            self._engines[eid] = engine
            if sup is not None:
                self._supervisors[eid] = sup
            self.provisioned += 1
        log.info("pool provisioned local engine %s (seq %d)", eid, seq)
        return ep

    def decommission(self, endpoint: Endpoint) -> None:
        with self._mu:
            engine = self._engines.pop(endpoint.id, None)
            sup = self._supervisors.pop(endpoint.id, None)
            self.decommissioned += 1
        if sup is not None:
            # BEFORE the engine's own stop: a supervisor outliving a
            # deliberate stop would "recover" it as a crash.
            sup.stop()
        if engine is None:
            return
        if not engine.running:
            # A crashed replica being replaced: fail its in-flight
            # handles over to the worker retry path NOW — parked
            # workers must not wait out their full deadlines against a
            # replica that is being removed (zero-loss under the chaos
            # kill scenario depends on this).
            try:
                engine.recover_after_crash()
            except Exception:  # noqa: BLE001 — teardown must proceed
                log.exception("crash recovery during decommission of "
                              "%s failed", endpoint.id)
        engine.stop()
        log.info("pool decommissioned local engine %s", endpoint.id)

    def stop(self) -> None:
        with self._mu:
            eids = list(self._engines)
        for eid in eids:
            self.decommission(Endpoint(id=eid))

    def get_stats(self) -> Dict[str, Any]:
        with self._mu:
            return {"kind": self.kind, "live": len(self._engines),
                    "provisioned": self.provisioned,
                    "decommissioned": self.decommissioned}


class SubprocessReplicaPool(ReplicaPool):
    """Real ``python -m llmq_tpu serve`` replicas on this host.

    Replica N listens on ``base_port + N``; provision blocks until its
    ``/health`` answers (up to ``ready_timeout``) so the returned
    endpoint is immediately dispatchable. Decommission sends SIGTERM —
    the replica's own ``App.drain`` path — and escalates to kill after
    a bounded grace.
    """

    kind = "subprocess"

    #: Seconds after SIGTERM before the process is killed outright.
    TERM_GRACE_S = 10.0

    def __init__(self, config: ReplicaPoolConfig, *,
                 clock: Optional[Clock] = None) -> None:
        self.config = config
        self._clock = clock or SYSTEM_CLOCK
        self._mu = threading.Lock()
        self._procs: Dict[str, subprocess.Popen] = {}
        self.provisioned = 0
        self.decommissioned = 0

    def _child_backend(self) -> str:
        """The executor backend a spawned replica will run: its
        ``--backend`` argument, else what the configuration it inherits
        (LLMQ_CONFIG + LLMQ_* environment) resolves to."""
        args = [str(a) for a in (self.config.args or [])]
        for i, a in enumerate(args):
            if a == "--backend" and i + 1 < len(args):
                return args[i + 1]
            if a.startswith("--backend="):
                return a.split("=", 1)[1]
        from llmq_tpu.core.config import load_config
        return load_config().executor.backend

    def provision(self, seq: int) -> Optional[Endpoint]:
        from llmq_tpu.observability.device import held_accelerator
        held = held_accelerator()
        if held and self._child_backend() == "jax":
            # One process per chip: this process's engine holds the
            # device, and the child inherits this environment unchanged
            # — it would wait out ready_timeout and never come up.
            log.error(
                "refusing to spawn a --backend jax replica: this process "
                "holds the %s device(s) and a chip belongs to one process "
                "at a time. Launch one serve process per chip from "
                "outside with each pinned to its own chip "
                "(docs/deployment.md \"One process per chip\") and list "
                "them as cluster.peers, or use controlplane.pool.kind=exec",
                held)
            return None
        port = int(self.config.base_port) + int(seq)
        url = f"http://127.0.0.1:{port}"
        cmd = ([sys.executable, "-m", "llmq_tpu", "--host", "127.0.0.1",
                "--port", str(port)]
               + [str(a) for a in (self.config.args or [])]
               + ["serve"])
        env = dict(os.environ)
        # A provisioned replica must not itself route to peers or
        # recursively provision — but it DOES inherit the parent's
        # config (LLMQ_CONFIG is exported by __main__ when --config
        # was given, and all LLMQ_* overrides pass through), so it
        # serves the same model/limits/tenancy settings. The env form
        # "[]" overrides even a YAML-configured peer list.
        env["LLMQ_CLUSTER_PEERS"] = "[]"
        env["LLMQ_CONTROLPLANE_ENABLED"] = "false"
        if self.role_hint:
            # Role-aware scaling: the controller picked which disagg
            # side this replica joins; the env override reaches the
            # child's DisaggConfig through _apply_env.
            env["LLMQ_DISAGG_ROLE"] = str(self.role_hint)
        t_boot0 = time.perf_counter()
        try:
            proc = subprocess.Popen(cmd, env=env,
                                    stdout=subprocess.DEVNULL,
                                    stderr=subprocess.DEVNULL)
        except OSError:
            log.exception("replica subprocess spawn failed (seq %d)",
                          seq)
            return None
        health = _wait_ready(url, float(self.config.ready_timeout))
        if health is None:
            log.error("replica %s never became ready; killing", url)
            proc.kill()
            proc.wait(timeout=5.0)
            return None
        eid = f"127.0.0.1:{port}"
        _adopt_child_boot(eid, self.kind, health,
                          time.perf_counter() - t_boot0)
        with self._mu:
            self._procs[eid] = proc
            self.provisioned += 1
        log.info("pool provisioned subprocess replica %s (pid %d)",
                 eid, proc.pid)
        return Endpoint(id=eid, name=eid, url=url,
                        metadata={"pool": True, "pool_seq": seq,
                                  "pid": proc.pid})

    def decommission(self, endpoint: Endpoint) -> None:
        with self._mu:
            proc = self._procs.pop(endpoint.id, None)
            self.decommissioned += 1
        if proc is None:
            return
        proc.terminate()               # SIGTERM → replica drains itself
        try:
            proc.wait(timeout=self.TERM_GRACE_S)
        except subprocess.TimeoutExpired:
            log.warning("replica %s ignored SIGTERM; killing",
                        endpoint.id)
            proc.kill()
            proc.wait(timeout=5.0)
        log.info("pool decommissioned subprocess replica %s",
                 endpoint.id)

    def stop(self) -> None:
        with self._mu:
            eids = list(self._procs)
        for eid in eids:
            self.decommission(Endpoint(id=eid))

    def get_stats(self) -> Dict[str, Any]:
        with self._mu:
            return {"kind": self.kind, "live": len(self._procs),
                    "provisioned": self.provisioned,
                    "decommissioned": self.decommissioned}


class ExecReplicaPool(ReplicaPool):
    """Deployment-hook pool: shell out to scale the real orchestrator.

    ``provision_cmd`` runs with ``LLMQ_REPLICA_SEQ`` in its env and
    must leave a serving replica reachable; the replica's base URL is
    ``url_template.format(seq=...)`` when set, else the command's last
    stdout line. ``decommission_cmd`` runs with ``LLMQ_REPLICA_SEQ`` /
    ``LLMQ_REPLICA_ID`` / ``LLMQ_REPLICA_URL``.
    """

    kind = "exec"

    def __init__(self, config: ReplicaPoolConfig) -> None:
        self.config = config
        self._mu = threading.Lock()
        self._urls: Dict[str, str] = {}
        self._seqs: Dict[str, int] = {}
        self.provisioned = 0
        self.decommissioned = 0

    def provision(self, seq: int) -> Optional[Endpoint]:
        if not self.config.provision_cmd:
            return None
        t_boot0 = time.perf_counter()
        env = dict(os.environ)
        env["LLMQ_REPLICA_SEQ"] = str(seq)
        if self.role_hint:
            env["LLMQ_DISAGG_ROLE"] = str(self.role_hint)
        try:
            out = subprocess.run(
                self.config.provision_cmd, shell=True, env=env,
                capture_output=True, text=True,
                timeout=float(self.config.ready_timeout))
        except subprocess.TimeoutExpired:
            log.error("provision_cmd timed out (seq %d)", seq)
            return None
        if out.returncode != 0:
            log.error("provision_cmd failed (seq %d, rc %d): %s", seq,
                      out.returncode, out.stderr.strip()[-500:])
            return None
        if self.config.url_template:
            url = self.config.url_template.format(seq=seq)
        else:
            lines = [ln.strip() for ln in out.stdout.splitlines()
                     if ln.strip()]
            url = lines[-1] if lines else ""
        if not url.startswith(("http://", "https://")):
            log.error("provision_cmd yielded no replica URL (seq %d, "
                      "got %r)", seq, url)
            return None
        url = url.rstrip("/")
        eid = url.split("://", 1)[-1]
        # Same readiness contract as the subprocess pool: the
        # orchestrator's scale-up returns long before the pod/container
        # serves. Registering early would dispatch into a booting
        # replica, trip its breaker and get it declared dead mid-boot.
        health = _wait_ready(url, float(self.config.ready_timeout))
        if health is None:
            log.error("exec replica %s never became ready; running "
                      "decommission_cmd to roll back", url)
            self._run_decommission(seq, eid, url)
            return None
        _adopt_child_boot(eid, self.kind, health,
                          time.perf_counter() - t_boot0)
        with self._mu:
            self._urls[eid] = url
            self._seqs[eid] = seq
            self.provisioned += 1
        log.info("pool provisioned exec replica %s", url)
        return Endpoint(id=eid, name=eid, url=url,
                        metadata={"pool": True, "pool_seq": seq})

    def decommission(self, endpoint: Endpoint) -> None:
        with self._mu:
            url = self._urls.pop(endpoint.id, endpoint.url)
            seq = self._seqs.pop(endpoint.id, -1)
            self.decommissioned += 1
        self._run_decommission(seq, endpoint.id, url or "")

    def _run_decommission(self, seq: int, eid: str, url: str) -> None:
        if not self.config.decommission_cmd:
            return
        env = dict(os.environ)
        env["LLMQ_REPLICA_SEQ"] = str(seq)
        env["LLMQ_REPLICA_ID"] = eid
        env["LLMQ_REPLICA_URL"] = url
        try:
            out = subprocess.run(
                self.config.decommission_cmd, shell=True, env=env,
                capture_output=True, text=True, timeout=60.0)
            if out.returncode != 0:
                log.error("decommission_cmd failed for %s (rc %d): %s",
                          eid, out.returncode,
                          out.stderr.strip()[-500:])
        except subprocess.TimeoutExpired:
            log.error("decommission_cmd timed out for %s", eid)

    def stop(self) -> None:
        with self._mu:
            eids = list(self._urls)
        for eid in eids:
            self.decommission(Endpoint(id=eid))

    def get_stats(self) -> Dict[str, Any]:
        with self._mu:
            return {"kind": self.kind, "live": len(self._urls),
                    "provisioned": self.provisioned,
                    "decommissioned": self.decommissioned}


def build_pool(cfg: ReplicaPoolConfig) -> Optional[ReplicaPool]:
    """Pool from config; None for ``kind: none`` (the controller then
    self-heals and degrades but never provisions)."""
    if cfg.kind == "subprocess":
        return SubprocessReplicaPool(cfg)
    if cfg.kind == "exec":
        return ExecReplicaPool(cfg)
    return None
