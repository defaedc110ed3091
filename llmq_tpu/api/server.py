"""REST API server — route-table parity with the reference Gin server.

Implements every route of reference api/handlers.go:75-118 (stdlib
``http.server``; no third-party web framework), with these deliberate
upgrades over the reference:

- ``GET /api/v1/messages[/:id]`` and the admin queue-delete /
  dead-letter-requeue routes are **implemented** (the reference returns
  HTTP 501 for all of them, handlers.go:222-256,622-697).
- ``POST /api/v1/messages`` pushes to the per-tier queue that actually
  exists. (The reference pushes to a queue named ``fmt.Sprint(priority)``
  on a manager that only ever created a queue named "standard",
  handlers.go:202 vs cmd/server/main.go:174 — every submit fails with
  ErrQueueNotFound at runtime.)
- ``estimated_wait`` uses measured per-tier queue stats when available,
  falling back to the reference's fixed table (handlers.go:729-744).
- Prometheus exposition is actually mounted at ``/metrics`` (the
  reference configures a metrics port but never mounts promhttp).
- Admin preprocessor rules are functional, not log-only
  (handlers.go:560-588).

CORS middleware mirrors handlers.go:121-148 (origin allow-list, ``*``
wildcard, OPTIONS preflight → 204).
"""

from __future__ import annotations

import json
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional, Tuple
from urllib.parse import parse_qs, urlparse

from llmq_tpu import __version__, observability
from llmq_tpu.api.message_store import MessageStore
from llmq_tpu.core.config import Config, default_config
from llmq_tpu.core.errors import (QueueFullError, QueueNotFoundError,
                                  WALError)
from llmq_tpu.core.types import (ConversationState, Message,
                                 MessageStatus, Priority, new_id)
from llmq_tpu.utils.logging import get_logger

log = get_logger("api")

#: Fallback per-tier wait estimates, seconds (handlers.go:729-744).
_WAIT_TABLE = {Priority.REALTIME: 1.0, Priority.HIGH: 5.0,
               Priority.NORMAL: 15.0, Priority.LOW: 30.0}

Handler = Callable[["_Request"], Tuple[int, Any]]


class _Deadline:
    """Minimal ProcessContext stand-in for the sync-generate RPC: the
    engine's worker seam only consults ``remaining()``."""

    def __init__(self, secs: float) -> None:
        self._deadline = time.monotonic() + secs

    def remaining(self) -> float:
        return self._deadline - time.monotonic()


class _SSEStream:
    """Dispatch payload marker: iterate and write each yielded string as
    it is produced (``text/event-stream``), instead of buffering one
    JSON body. Events must already be SSE-framed
    (``event:.../data:...\\n\\n``). ``on_close`` (idempotent) runs when
    the HTTP handler is done with the stream — including failure paths
    where the generator was never started, which a generator-finally
    alone cannot cover."""

    def __init__(self, events, on_close=None, headers=None) -> None:
        self.events = events
        self.on_close = on_close
        #: Extra response headers (e.g. ``traceparent`` so a streaming
        #: client can correlate its SSE stream with the trace plane).
        self.headers = headers or {}

    def __iter__(self):
        return iter(self.events)


class ApiError(Exception):
    def __init__(self, status: int, message: str,
                 retry_after: Optional[float] = None) -> None:
        super().__init__(message)
        self.status = status
        self.message = message
        #: Seconds the client should wait before retrying (overload
        #: shedding, docs/robustness.md). Surfaces as BOTH a
        #: ``Retry-After`` response header and a ``retry_after`` body
        #: field (dispatch() callers see the body; HTTP clients the
        #: header).
        self.retry_after = retry_after


class _Request:
    """Parsed request handed to route handlers."""

    def __init__(self, method: str, path: str, params: Dict[str, str],
                 query: Dict[str, List[str]], body: bytes,
                 headers: Optional[Dict[str, str]] = None) -> None:
        self.method = method
        self.path = path
        self.params = params          # path captures, e.g. {"id": ...}
        self.query = query
        self._body = body
        #: Request headers, lower-cased keys (HTTP headers are
        #: case-insensitive; direct dispatch() callers pass any case).
        self.headers = {str(k).lower(): v
                        for k, v in (headers or {}).items()}

    def json(self) -> Dict[str, Any]:
        if not self._body:
            raise ApiError(400, "request body required")
        try:
            data = json.loads(self._body)
        except json.JSONDecodeError as e:
            raise ApiError(400, f"invalid JSON: {e}") from None
        if not isinstance(data, dict):
            raise ApiError(400, "JSON object expected")
        return data

    def q(self, name: str, default: str = "") -> str:
        vals = self.query.get(name)
        return vals[0] if vals else default


class ApiServer:
    """Aggregates the L2 services behind the v1 REST contract — the
    counterpart of the reference APIServer struct (handlers.go:24-34),
    plus the execution-plane engine the reference lacks."""

    def __init__(
        self,
        config: Optional[Config] = None,
        *,
        queue_factory=None,
        preprocessor=None,
        state_manager=None,
        load_balancer=None,
        resource_scheduler=None,
        engine=None,
        cluster_router=None,
        controller=None,
        drain_hook: Optional[Callable[[], None]] = None,
        message_store: Optional[MessageStore] = None,
        allowed_origins: Optional[List[str]] = None,
        manager_name: str = "standard",
    ) -> None:
        self.config = config or default_config()
        self.factory = queue_factory
        self.preprocessor = preprocessor
        self.state_manager = state_manager
        self.load_balancer = load_balancer
        self.resource_scheduler = resource_scheduler
        self.engine = engine
        self.cluster_router = cluster_router
        #: Control-plane controller (llmq_tpu/controlplane/,
        #: docs/controlplane.md) — None when controlplane.enabled is
        #: false. ``__main__`` wires it after construction (the
        #: controller needs this server's shedder).
        self.controller = controller
        #: Process-level drain trigger (App.drain); run in a background
        #: thread by the admin route so the HTTP response isn't held
        #: hostage by the drain's in-flight wait.
        self.drain_hook = drain_hook
        #: When True, /health answers status "draining" — peers' probes
        #: (transport.HttpEngineClient.healthy) then take this process
        #: out of their rotation with no other coordination.
        self.draining = False
        self.store = message_store or MessageStore()
        self.allowed_origins = allowed_origins or ["*"]
        self.manager_name = manager_name
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._routes: List[Tuple[str, re.Pattern, Handler]] = []
        # SSE admission control: streams bypass the queue plane, so
        # without this a stream flood grows engine pending without
        # bound (satellite fix; see _acquire_stream_slot).
        self._stream_mu = threading.Lock()
        self._active_streams = 0
        # Overload shedding (api/overload.py, docs/robustness.md):
        # None when overload.enabled is false — the submit path then
        # runs exactly the pre-shedding code.
        from llmq_tpu.api.overload import build_shedder
        self.shedder = build_shedder(self.config, engine=engine,
                                     resource_scheduler=resource_scheduler)
        self._setup_routes()

    # -- SSE admission -------------------------------------------------------

    def _acquire_stream_slot(self) -> None:
        """Admission gate for the SSE path: 429 past the concurrent-
        stream cap, 503 when the engine's pending queue is already deep
        (shedding beats unbounded backlog — the queue plane's
        max_queue_size bound does not cover direct engine submits)."""
        scfg = self.config.server
        limit = getattr(scfg, "stream_pending_limit", 0)
        # Prefer the cheap depth probe; fall back to full stats for
        # engine-likes that only expose get_stats.
        depth_fn = getattr(self.engine, "pending_count", None)
        stats_fn = getattr(self.engine, "get_stats", None)
        if limit and limit > 0 and (depth_fn or stats_fn):
            pending = (depth_fn() if depth_fn
                       else stats_fn().get("pending", 0))
            if pending >= limit:
                raise ApiError(
                    503, f"engine backlog too deep for streaming "
                         f"({pending} pending >= {limit})")
        cap = getattr(scfg, "max_concurrent_streams", 0)
        with self._stream_mu:
            if cap and cap > 0 and self._active_streams >= cap:
                raise ApiError(
                    429, f"too many concurrent streams (max {cap})")
            self._active_streams += 1

    def _release_stream_slot(self) -> None:
        with self._stream_mu:
            if self._active_streams > 0:
                self._active_streams -= 1

    # -- routing table (parity: handlers.go:75-118) --------------------------

    def _route(self, method: str, pattern: str, handler: Handler) -> None:
        # "/api/v1/messages/:id" → named captures
        rx = re.sub(r":(\w+)", r"(?P<\1>[^/]+)", pattern)
        self._routes.append((method, re.compile(f"^{rx}$"), handler))

    def _setup_routes(self) -> None:
        r = self._route
        r("GET", "/health", self.health_check)
        r("GET", "/metrics", self.metrics_exposition)
        v1 = "/api/v1"
        r("POST", f"{v1}/messages", self.submit_message)
        r("GET", f"{v1}/messages/:id", self.get_message)
        r("GET", f"{v1}/messages", self.list_messages)
        r("POST", f"{v1}/conversations", self.create_conversation)
        r("GET", f"{v1}/conversations/:id", self.get_conversation)
        r("POST", f"{v1}/conversations/:id/messages",
          self.add_message_to_conversation)
        r("PUT", f"{v1}/conversations/:id/state",
          self.update_conversation_state)
        r("GET", f"{v1}/users/:user_id/conversations",
          self.list_user_conversations)
        r("GET", f"{v1}/queues/stats", self.get_queue_stats)
        r("POST", f"{v1}/resources", self.register_resource)
        r("GET", f"{v1}/resources", self.list_resources)
        r("GET", f"{v1}/resources/stats", self.get_resource_stats)
        r("POST", f"{v1}/endpoints", self.register_endpoint)
        r("GET", f"{v1}/endpoints", self.list_endpoints)
        r("GET", f"{v1}/endpoints/stats", self.get_endpoint_stats)
        r("POST", f"{v1}/endpoints/:id/drain", self.drain_endpoint)
        r("DELETE", f"{v1}/endpoints/:id", self.delete_endpoint)
        r("GET", f"{v1}/cluster/stats", self.get_cluster_stats)
        r("GET", f"{v1}/cluster/overview", self.get_cluster_overview)
        r("GET", f"{v1}/engine/stats", self.get_engine_stats)
        r("GET", f"{v1}/usage", self.get_usage)
        r("GET", f"{v1}/analysis/critical-path", self.get_critical_path)
        r("GET", f"{v1}/tenancy", self.get_tenancy)
        r("POST", f"{v1}/generate", self.generate_sync)
        r("GET", f"{v1}/requests/:id/trace", self.get_request_trace)
        adm = f"{v1}/admin"
        r("GET", f"{adm}/flightrecorder", self.get_flight_recorder)
        r("POST", f"{adm}/profile", self.start_profile)
        r("GET", f"{adm}/profile", self.get_profile_status)
        r("POST", f"{adm}/controller", self.set_controller_state)
        r("GET", f"{adm}/controller", self.get_controller_state)
        r("POST", f"{adm}/drain", self.drain_self)
        r("POST", f"{adm}/preprocessor/rules", self.add_priority_rule)
        r("GET", f"{adm}/preprocessor/rules", self.list_priority_rules)
        r("POST", f"{adm}/preprocessor/user-priorities", self.set_user_priority)
        r("DELETE", f"{adm}/queues/:queue_type/:id", self.remove_message)
        r("POST", f"{adm}/dead-letter/requeue/:id",
          self.requeue_dead_letter_message)
        r("POST", f"{adm}/dead-letter/requeue-all",
          self.requeue_all_dead_letter_messages)

    def dispatch(self, method: str, raw_path: str, body: bytes,
                 headers: Optional[Dict[str, str]] = None,
                 ) -> Tuple[int, Any, str]:
        """Route one request. Returns (status, payload, content_type)."""
        parsed = urlparse(raw_path)
        path = parsed.path.rstrip("/") or "/"
        query = parse_qs(parsed.query)
        matched_path = False
        for m, rx, handler in self._routes:
            match = rx.match(path)
            if not match:
                continue
            matched_path = True
            if m != method:
                continue
            req = _Request(method, path, match.groupdict(), query, body,
                           headers)
            from llmq_tpu.utils.logging import (bind_log_context,
                                                reset_log_context)
            ltoken = bind_log_context(endpoint=path)
            try:
                status, payload = handler(req)
            except ApiError as e:
                body: Dict[str, Any] = {"error": e.message}
                if e.retry_after is not None:
                    body["retry_after"] = round(float(e.retry_after), 3)
                return e.status, body, "application/json"
            except QueueNotFoundError as e:
                return 404, {"error": str(e)}, "application/json"
            except QueueFullError as e:
                return 503, {"error": str(e)}, "application/json"
            except WALError as e:
                # Durability journal can't record the op (disk full /
                # IO fault): explicit 503 shed + Retry-After — the
                # worker loop stays up (docs/robustness.md).
                return 503, {"error": str(e), "retry_after": 1.0}, \
                    "application/json"
            except Exception as e:  # noqa: BLE001
                log.exception("handler error on %s %s", method, path)
                return 500, {"error": f"internal error: {e}"}, "application/json"
            finally:
                reset_log_context(ltoken)
            if isinstance(payload, bytes):
                return status, payload, "text/plain; version=0.0.4"
            if isinstance(payload, _SSEStream):
                return status, payload, "text/event-stream"
            return status, payload, "application/json"
        if matched_path:
            return 405, {"error": "method not allowed"}, "application/json"
        return 404, {"error": "not found"}, "application/json"

    # -- helpers -------------------------------------------------------------

    def _manager(self, name: Optional[str] = None):
        if self.factory is None:
            raise ApiError(503, "queue factory not configured")
        mgr = self.factory.get_queue_manager(name or self.manager_name)
        if mgr is None:
            if name:  # client-named manager → not found
                raise ApiError(404, f"no queue manager named {name!r}")
            raise ApiError(500, "failed to access message queue")
        return mgr

    def _require_state_manager(self):
        if self.state_manager is None:
            raise ApiError(503, "conversation service not configured")
        return self.state_manager

    def estimate_wait(self, priority: Priority) -> float:
        """Measured per-tier estimate (avg wait scaled by backlog) with the
        reference's fixed table as a cold-start fallback."""
        fallback = _WAIT_TABLE.get(priority, 15.0)
        if self.factory is None:
            return fallback
        mgr = self.factory.get_queue_manager(self.manager_name)
        if mgr is None:
            return fallback
        try:
            stats = mgr.get_stats(priority.tier_name)
        except QueueNotFoundError:
            return fallback
        if stats.wait_samples == 0:
            return fallback
        backlog_factor = 1.0 + stats.pending_count / max(
            1, stats.completed_count + stats.processing_count)
        return round(stats.avg_wait_time * backlog_factor, 4)

    def _ingest_message(self, data: Dict[str, Any],
                        conversation_id: str = "",
                        tenant_header: str = "") -> Message:
        """Shared submit pipeline: parse → id/timestamps → preprocess →
        analysis metadata → push → conversation update → store."""
        try:
            msg = Message.from_dict(data)
        except (ValueError, TypeError) as e:
            raise ApiError(400, f"invalid message: {e}") from None
        # Usage-plane billing identity: X-Tenant-Id header wins over
        # the body field; unset → "default" (docs/observability.md
        # "Usage & goodput").
        msg.tenant_id = observability.sanitize_tenant(
            tenant_header or msg.tenant_id)
        if conversation_id:
            msg.conversation_id = conversation_id
        if not msg.id:
            msg.id = new_id()
        now = time.time()
        msg.created_at = now
        msg.updated_at = now
        if self.preprocessor is not None:
            msg = self.preprocessor.process_message(msg)
            if self.preprocessor.enable_content_analysis:
                # Reference stores the analysis as a JSON string under
                # metadata["analysis"] (handlers.go:181-191 — gated there
                # on the unrelated EnableMetrics flag; we gate on the
                # preprocessor's own switch and reuse the keys
                # process_message already annotated instead of running
                # the regex pass twice).
                msg.metadata["analysis"] = json.dumps(
                    {k: msg.metadata[k]
                     for k in ("word_count", "char_count", "sentiment",
                               "is_question") if k in msg.metadata})
        mgr = self._manager()
        if self.shedder is not None:
            # Shed BEFORE the enqueued stamp: a rejected request never
            # entered the queue plane, and its 429/503 + Retry-After is
            # its complete, explicit outcome.
            self.shedder.admit(msg, mgr, self.estimate_wait(msg.priority))
        # Stamp BEFORE the push: a near-idle worker can pop and stamp
        # "scheduled" before this thread resumes, and a scheduled <
        # enqueued inversion would drop the queue_wait sample exactly
        # in the low-latency regime it measures. (A push rejection
        # leaves a lone enqueued event — ring-bounded, harmless.)
        observability.record(msg.id, "enqueued",
                             priority=msg.priority.tier_name,
                             conversation_id=msg.conversation_id,
                             user_id=msg.user_id)
        mgr.push_message(msg)
        self.store.record(msg)
        if msg.conversation_id and self.state_manager is not None:
            try:
                # add_message get-or-creates the conversation itself.
                self.state_manager.add_message(msg.conversation_id, msg)
            except Exception:  # noqa: BLE001 — parity: log, don't fail submit
                log.exception("conversation update failed for %s", msg.id)
        return msg

    # -- handlers ------------------------------------------------------------

    def health_check(self, req: _Request) -> Tuple[int, Any]:
        status = "draining" if self.draining else "ok"
        out = {"status": status, "version": __version__,
               "time": time.time()}
        if self.engine is not None:
            out["engine"] = "running" if self.engine.running else "stopped"
            ident = getattr(self.engine, "device_identity", None)
            device = ident() if callable(ident) else None
            if device is not None:
                # What the engine actually sits on: a server that came
                # up on the CPU must not read like one on a chip.
                # Device-free backends (echo) omit the field.
                out["device"] = device
            role = getattr(self.engine, "disagg_role", "unified")
            if role != "unified":
                # Disagg role advertisement (docs/disaggregation.md):
                # peers' routers learn the prefill/decode split from
                # the same probes that learn liveness. Unified replicas
                # omit the field — pre-disagg health bodies stay
                # byte-identical.
                out["role"] = role
        if self.controller is not None:
            # Paused is an OPERATOR state distinct from disabled (a
            # disabled control plane has no controller and no field
            # here at all) — visible to probes and peers.
            out["controller"] = ("paused" if self.controller.paused
                                 else "running")
        try:
            # Boot decomposition advertisement (critical-path plane):
            # a parent ReplicaPool adopts these stages across the
            # process seam. Absent when the plane is off or no
            # entrypoint opened a process boot record — pre-feature
            # health bodies stay byte-identical.
            from llmq_tpu.observability.critical_path import (
                cp_enabled, process_boot_snapshot)
            if cp_enabled():
                boot = process_boot_snapshot()
                if boot is not None:
                    out["boot"] = boot
        except Exception:  # noqa: BLE001 — health must never fail on telemetry
            pass
        store_block = self._store_block()
        if store_block is not None:
            # Store fault domain (docs/robustness.md): present only
            # when the resilience wrapper is active — pre-feature
            # health bodies stay byte-identical.
            out["store"] = store_block
        return 200, out

    def _store_block(self) -> Optional[Dict[str, Any]]:
        """The resilience wrapper's health/overview block, or None when
        the store plane is off (raw backend / no state manager)."""
        sm = self.state_manager
        if sm is None:
            return None
        stats_fn = getattr(getattr(sm, "store", None),
                           "resilience_stats", None)
        if not callable(stats_fn):
            return None
        try:
            block = dict(stats_fn())
            pending = getattr(sm, "replay_pending", None)
            if callable(pending):
                block["replay_pending"] = pending()
            return block
        except Exception:  # noqa: BLE001 — health must never fail on
            return None    # the store plane

    def metrics_exposition(self, req: _Request) -> Tuple[int, Any]:
        from llmq_tpu.metrics.registry import exposition
        return 200, exposition()

    def submit_message(self, req: _Request) -> Tuple[int, Any]:
        data = req.json()
        stream = data.pop("stream", False)
        if stream is None:
            stream = False          # optional-field serializers emit null
        if isinstance(stream, str):
            low = stream.strip().lower()
            if low in ("true", "1", "yes", "on"):
                stream = True
            elif low in ("false", "0", "no", "off", ""):
                stream = False
            else:
                # A truthy-but-garbage string must be a client error,
                # not an accidental stream (or a 500 downstream).
                raise ApiError(400, f"invalid stream value {stream!r}")
        elif not isinstance(stream, (bool, int)):
            raise ApiError(400, "stream must be a boolean")
        if stream:
            return self._stream_message(
                data, tenant_header=req.headers.get("x-tenant-id", ""))
        msg = self._ingest_message(
            data, tenant_header=req.headers.get("x-tenant-id", ""))
        return 202, {
            "message_id": msg.id,
            "priority": int(msg.priority),
            "queue_time": time.time(),
            "estimated_wait": self.estimate_wait(msg.priority),
        }

    def _stream_message(self, data: Dict[str, Any],
                        tenant_header: str = "") -> Tuple[int, Any]:
        """``POST /api/v1/messages`` with ``"stream": true`` — token
        streaming over SSE (SURVEY §7 bridge design: "tokens-out +
        streaming"). The message bypasses the queue plane and goes
        straight to the engine with an ``on_token`` subscription: the
        user-perceived metric for a realtime tier is FIRST-token
        latency, and a queue→worker→blocking-process_fn round cannot
        surface tokens before completion. The message is still
        recorded in the store and the conversation updated, so the
        query API sees streamed messages like queued ones."""
        if self.engine is None:
            raise ApiError(503, "streaming requires an attached engine")
        from queue import Empty, Queue

        from llmq_tpu.engine.engine import GenRequest

        # Read the CLIENT's timeout before Message.from_dict fills the
        # dataclass default (30 s) — an unset field must get the
        # streaming default, not be silently capped at 30 s. Validate it
        # HERE: a non-numeric value must 400, not 500 when the float()
        # below would otherwise raise mid-handler.
        explicit_timeout = data.get("timeout")
        if explicit_timeout is not None:
            try:
                explicit_timeout = float(explicit_timeout)
            except (TypeError, ValueError):
                raise ApiError(
                    400, f"timeout must be a number, "
                         f"got {explicit_timeout!r}") from None
        try:
            msg = Message.from_dict(data)
        except (ValueError, TypeError) as e:
            raise ApiError(400, f"invalid message: {e}") from None
        msg.tenant_id = observability.sanitize_tenant(
            tenant_header or msg.tenant_id)
        if self.shedder is not None:
            # Engine-down / SLA shedding for streams (no manager: the
            # stream cap + backlog gates below are the queue-side
            # equivalents on this path).
            self.shedder.admit(msg, None, 0.0)
        # Admission: the SSE path bypasses queue admission entirely, so
        # it carries its own gate (429 stream cap / 503 backlog shed).
        self._acquire_stream_slot()
        try:
            if not msg.id:
                msg.id = new_id()
            msg.created_at = msg.updated_at = time.time()
            if self.preprocessor is not None:
                msg = self.preprocessor.process_message(msg)
            msg.status = MessageStatus.PROCESSING
            self.store.record(msg)
            if msg.conversation_id and self.state_manager is not None:
                try:
                    self.state_manager.add_message(msg.conversation_id, msg)
                except Exception:  # noqa: BLE001 — parity: log, don't fail
                    log.exception("conversation update failed for %s",
                                  msg.id)

            observability.record(msg.id, "enqueued",
                                 priority=msg.priority.tier_name,
                                 conversation_id=msg.conversation_id,
                                 user_id=msg.user_id, stream=True)
            tokens: "Queue[int]" = Queue()
            handle = self.engine.submit(GenRequest.from_message(msg),
                                        on_token=tokens.put)
            # The SSE path bypasses queue + router: submit IS the
            # dispatch (engine-side events follow from the handle).
            observability.record(msg.id, "dispatched",
                                 endpoint=getattr(self.engine, "name",
                                                  "engine"),
                                 reason="stream",
                                 priority=msg.priority.tier_name)
            tokenizer = self.engine.tokenizer
            timeout = (explicit_timeout
                       if explicit_timeout and explicit_timeout > 0
                       else 120.0)
        except BaseException:
            # Setup failed after the slot was taken — give it back.
            self._release_stream_slot()
            raise

        def events():
            yield ("event: start\ndata: "
                   + json.dumps({"message_id": msg.id,
                                 "priority": int(msg.priority)})
                   + "\n\n")
            ids: List[int] = []
            sent = ""
            deadline = time.monotonic() + timeout

            def drain_delta(final: bool = False) -> str:
                nonlocal sent
                # Cumulative decode then slice: per-id decode would
                # break multi-byte/multi-token graphemes at chunk
                # boundaries. Trailing U+FFFD is HELD BACK mid-stream:
                # it usually marks a multi-byte sequence whose tail
                # lands in the next burst — emitting it would lock the
                # mangled char into the stream (the cumulative decode
                # later fixes it, but the prefix was already sent).
                # The final flush emits everything (a real invalid
                # byte stays a replacement char).
                full = tokenizer.decode(ids)
                safe = full
                if not final:
                    while safe and safe[-1] == "�":
                        safe = safe[:-1]
                if len(safe) < len(sent):
                    return ""
                delta, sent = safe[len(sent):], safe
                return delta

            try:
                while True:
                    try:
                        ids.append(tokens.get(timeout=0.05))
                    except Empty:
                        if handle.done:
                            break
                        if time.monotonic() > deadline:
                            handle.cancel()
                            break
                        continue
                    while not tokens.empty():   # commit bursts → one event
                        ids.append(tokens.get_nowait())
                    delta = drain_delta()
                    if delta:
                        yield ("data: " + json.dumps({"token": delta})
                               + "\n\n")
                handle.wait(5.0)
                while not tokens.empty():
                    ids.append(tokens.get_nowait())
                delta = drain_delta(final=True)
                if delta:
                    yield "data: " + json.dumps({"token": delta}) + "\n\n"
                res = handle.result
                first_ms = None
                if "first_token" in handle.marks:
                    first_ms = round((handle.marks["first_token"]
                                      - handle.submitted_at) * 1e3, 1)
                msg.response = res.text if res else sent
                msg.status = (MessageStatus.COMPLETED
                              if res and res.finish_reason in
                              ("eos", "length") else MessageStatus.FAILED)
                msg.updated_at = time.time()
                usage = {
                    "prompt_tokens": res.prompt_tokens if res else 0,
                    "completion_tokens": len(res.tokens) if res else 0,
                }
                if handle.usage is not None:
                    # Attribution ledger summary (docs/observability.md
                    # "Usage & goodput"): the stream's final event
                    # carries what this request cost.
                    usage.update(handle.usage)
                done = {
                    "message_id": msg.id,
                    "finish_reason": res.finish_reason if res else "timeout",
                    "first_token_ms": first_ms,
                    "usage": usage,
                }
                yield "event: done\ndata: " + json.dumps(done) + "\n\n"
            except GeneratorExit:
                # Client went away mid-stream: stop generating for it
                # and close out the stored record (it must not sit in
                # PROCESSING forever — eviction prefers terminal
                # messages, so a stuck live record is near-immortal).
                handle.cancel()
                msg.status = MessageStatus.FAILED
                msg.updated_at = time.time()
                raise
            except Exception:  # noqa: BLE001 — mid-stream failure
                handle.cancel()
                msg.status = MessageStatus.FAILED
                msg.updated_at = time.time()
                raise

        # Idempotent slot release: reachable from the generator's
        # finally (normal completion, disconnect, mid-stream failure)
        # AND from the handler's on_close (header-write failure before
        # the generator ever starts — a never-started generator's
        # finally does not run). In that never-started case the
        # generator's own cleanup (engine cancel + terminal message
        # state) also never fired, so release_once does it: otherwise
        # the engine decodes a full response for a dead client and the
        # stored record sits in PROCESSING forever.
        released = threading.Event()
        started = threading.Event()

        def release_once():
            if released.is_set():
                return
            released.set()
            self._release_stream_slot()
            if not started.is_set():
                handle.cancel()
                msg.status = MessageStatus.FAILED
                msg.updated_at = time.time()

        def guarded():
            started.set()
            try:
                yield from events()
            finally:
                release_once()

        return 200, _SSEStream(
            guarded(), on_close=release_once,
            headers={"traceparent": observability.make_traceparent(msg.id),
                     "X-Request-Id": msg.id})

    def get_message(self, req: _Request) -> Tuple[int, Any]:
        msg = self.store.get(req.params["id"])
        if msg is None:
            return 404, {"error": "message not found"}
        return 200, msg.to_dict()

    def list_messages(self, req: _Request) -> Tuple[int, Any]:
        try:
            limit = int(req.q("limit", "10"))
            offset = int(req.q("offset", "0"))
        except ValueError:
            raise ApiError(400, "limit/offset must be integers") from None
        msgs = self.store.list(
            user_id=req.q("user_id"),
            conversation_id=req.q("conversation_id"),
            status=req.q("status"),
            limit=limit, offset=offset)
        return 200, {"messages": [m.to_dict() for m in msgs],
                     "count": len(msgs)}

    def create_conversation(self, req: _Request) -> Tuple[int, Any]:
        data = req.json()
        user_id = data.get("user_id")
        if not user_id:
            raise ApiError(400, "user_id is required")
        sm = self._require_state_manager()
        conv = sm.create(user_id, metadata=data.get("metadata") or {})
        return 201, {
            "conversation_id": conv.id,
            "user_id": conv.user_id,
            "created_at": conv.created_at,
            "state": conv.state.value,
        }

    def get_conversation(self, req: _Request) -> Tuple[int, Any]:
        sm = self._require_state_manager()
        try:
            conv = sm.get(req.params["id"])
        except KeyError:
            return 404, {"error": "conversation not found"}
        return 200, conv.to_dict()

    def add_message_to_conversation(self, req: _Request) -> Tuple[int, Any]:
        conv_id = req.params["id"]
        msg = self._ingest_message(
            req.json(), conversation_id=conv_id,
            tenant_header=req.headers.get("x-tenant-id", ""))
        return 202, {
            "message_id": msg.id,
            "conversation_id": conv_id,
            "priority": int(msg.priority),
            "queue_time": time.time(),
            "estimated_wait": self.estimate_wait(msg.priority),
        }

    def update_conversation_state(self, req: _Request) -> Tuple[int, Any]:
        data = req.json()
        state = data.get("state")
        if not state:
            raise ApiError(400, "state is required")
        try:
            new_state = ConversationState(state)
        except ValueError:
            raise ApiError(
                400, f"invalid state {state!r}; valid: "
                f"{[s.value for s in ConversationState]}") from None
        sm = self._require_state_manager()
        try:
            sm.update_state(req.params["id"], new_state)
        except KeyError:
            return 404, {"error": "conversation not found"}
        return 200, {"status": "updated"}

    def list_user_conversations(self, req: _Request) -> Tuple[int, Any]:
        sm = self._require_state_manager()
        convs = sm.user_conversations(req.params["user_id"])
        return 200, {"conversations": [c.to_dict(include_messages=False)
                                       for c in convs]}

    def get_queue_stats(self, req: _Request) -> Tuple[int, Any]:
        if self.factory is None:
            raise ApiError(503, "queue factory not configured")
        stats: Dict[str, Any] = {}
        for name in self.factory.manager_names():
            mgr = self.factory.get_queue_manager(name)
            if mgr is None:
                continue
            stats[name] = {qn: s.to_dict()
                           for qn, s in mgr.get_all_stats().items()}
            stats[name]["workers"] = self.factory.get_worker_stats(name)
            # Which ordering core serves (NativeMLQ, or _PyBackend when
            # the C++ build failed and the loader fell back).
            stats[name]["core"] = mgr.queue.backend_name
            dlq = self.factory.get_dead_letter_queue(name)
            if dlq is not None:
                stats[name]["dead_letter_size"] = dlq.size()
        return 200, stats

    def register_resource(self, req: _Request) -> Tuple[int, Any]:
        if self.resource_scheduler is None:
            raise ApiError(503, "resource scheduler not configured")
        from llmq_tpu.scheduling.resource_scheduler import (Resource,
                                                            ResourceStatus,
                                                            ResourceType)
        data = req.json()
        try:
            capacity = {ResourceType(k): float(v)
                        for k, v in (data.get("capacity") or {}).items()}
            res = Resource(
                id=data.get("id") or new_id(),
                model_type=data.get("model_type", "llm"),
                capabilities=set(data.get("capabilities") or []),
                capacity=capacity,
                endpoint=data.get("endpoint", ""),
                status=ResourceStatus(data.get("status", "online")),
                metadata=data.get("metadata") or {},
            )
        except (ValueError, TypeError) as e:
            raise ApiError(400, f"invalid resource: {e}") from None
        self.resource_scheduler.register_resource(res)
        return 201, {"resource_id": res.id, "status": res.status.value}

    def list_resources(self, req: _Request) -> Tuple[int, Any]:
        if self.resource_scheduler is None:
            raise ApiError(503, "resource scheduler not configured")
        return 200, {"resources": [r.to_dict()
                                   for r in self.resource_scheduler.resources()]}

    def get_resource_stats(self, req: _Request) -> Tuple[int, Any]:
        if self.resource_scheduler is None:
            raise ApiError(503, "resource scheduler not configured")
        return 200, self.resource_scheduler.get_stats()

    def register_endpoint(self, req: _Request) -> Tuple[int, Any]:
        if self.load_balancer is None:
            raise ApiError(503, "load balancer not configured")
        from llmq_tpu.loadbalancer.load_balancer import (Endpoint,
                                                         EndpointStatus)
        data = req.json()
        try:
            ep = Endpoint(
                id=data.get("id") or new_id(),
                name=data.get("name", ""),
                url=data.get("url", ""),
                model_type=data.get("model_type", "llm"),
                weight=float(data.get("weight", 1.0)),
                max_connections=int(data.get("max_connections", 0)),
                status=EndpointStatus(data.get("status", "healthy")),
                metadata=data.get("metadata") or {},
            )
        except (ValueError, TypeError) as e:
            raise ApiError(400, f"invalid endpoint: {e}") from None
        self.load_balancer.add_endpoint(ep)
        return 201, {"endpoint_id": ep.id, "status": ep.status.value}

    def list_endpoints(self, req: _Request) -> Tuple[int, Any]:
        if self.load_balancer is None:
            raise ApiError(503, "load balancer not configured")
        return 200, {"endpoints": [e.to_dict()
                                   for e in self.load_balancer.endpoints()]}

    def get_endpoint_stats(self, req: _Request) -> Tuple[int, Any]:
        if self.load_balancer is None:
            raise ApiError(503, "load balancer not configured")
        return 200, self.load_balancer.get_stats()

    def drain_endpoint(self, req: _Request) -> Tuple[int, Any]:
        """Take one replica out of NEW dispatch (in-flight finishes).
        Body ``{"drain": false}`` re-admits it (via DEGRADED; the probe
        restores full traffic). Prefers the live cluster router (so
        drain counters move); a bare LoadBalancer works too."""
        eid = req.params["id"]
        drain = True
        if self._body_present(req):
            drain = bool(req.json().get("drain", True))
        lb = self.load_balancer
        if lb is None and self.cluster_router is not None:
            lb = self.cluster_router.lb
        if lb is None:
            raise ApiError(503, "load balancer not configured")
        if lb.get_endpoint_by_id(eid) is None:
            return 404, {"error": f"no endpoint {eid!r}"}
        # 404 only for a genuinely unknown endpoint: drain_endpoint's
        # bool also reports "idle yet?", and an endpoint mid-flight IS
        # draining — a 404 there would make automation retry/abort a
        # drain that took effect.
        if self.cluster_router is not None:
            if drain:
                self.cluster_router.drain_endpoint(eid)
            else:
                self.cluster_router.undrain_endpoint(eid)
        else:
            lb.set_draining(eid, drain)
        return 200, {"endpoint_id": eid,
                     "status": "draining" if drain else "degraded"}

    def delete_endpoint(self, req: _Request) -> Tuple[int, Any]:
        if self.load_balancer is None:
            raise ApiError(503, "load balancer not configured")
        eid = req.params["id"]
        if not self.load_balancer.remove_endpoint(eid):
            return 404, {"error": f"no endpoint {eid!r}"}
        return 200, {"status": "removed", "endpoint_id": eid}

    def get_cluster_stats(self, req: _Request) -> Tuple[int, Any]:
        if self.cluster_router is None:
            raise ApiError(503, "cluster router not configured "
                                "(set cluster.peers / --peers)")
        out = self.cluster_router.get_stats()
        out["draining"] = self.draining
        return 200, out

    def drain_self(self, req: _Request) -> Tuple[int, Any]:
        """Process-level graceful drain: /health flips to "draining"
        immediately (peers stop routing here); the App-level drain hook
        (stop pulling new work, wait out in-flight) runs in the
        background."""
        self.draining = True
        if self.drain_hook is not None:
            threading.Thread(target=self.drain_hook, name="api-drain",
                             daemon=True).start()
        return 202, {"status": "draining"}

    @staticmethod
    def _body_present(req: _Request) -> bool:
        return bool(req._body)  # noqa: SLF001 — same module

    def get_engine_stats(self, req: _Request) -> Tuple[int, Any]:
        if self.engine is None:
            raise ApiError(503, "engine not configured")
        out = self.engine.get_stats()
        try:
            # Process-level SLO burn rates ride the engine stats
            # payload (the cluster overview rolls them up per replica).
            # Drain the recorder's deferred feed first: this route must
            # show real burn even when nothing is scraping /metrics —
            # a broken scrape is exactly when an operator reads it.
            from llmq_tpu.observability.recorder import get_recorder
            from llmq_tpu.observability.slo import get_slo_tracker
            get_recorder().flush_metrics()
            out["slo"] = get_slo_tracker().snapshot()
        except Exception:  # noqa: BLE001 — stats must not fail on SLO plane
            pass
        try:
            # Usage rollups ride the same payload (the cluster overview
            # aggregates them per replica).
            from llmq_tpu.observability.usage import get_usage_ledger
            led = get_usage_ledger()
            if led.enabled:
                out["usage"] = led.snapshot(top_conversations=0)
        except Exception:  # noqa: BLE001 — stats must not fail on usage plane
            pass
        try:
            # Boot decomposition rides along too: the overview joins a
            # replica's serving telemetry to what its boot cost.
            from llmq_tpu.observability.critical_path import (
                cp_enabled, process_boot_snapshot)
            if cp_enabled():
                boot = process_boot_snapshot()
                if boot is not None:
                    out["boot"] = boot
        except Exception:  # noqa: BLE001 — stats must not fail on boot plane
            pass
        return 200, out

    def get_critical_path(self, req: _Request) -> Tuple[int, Any]:
        """Critical-path rollup (docs/observability.md "Critical path &
        boot telemetry"): fleet-wide per-segment time totals/shares,
        dominant-segment counts, recent decompositions, and every known
        replica boot decomposition. ``?recent=N`` sizes the recent
        list."""
        from llmq_tpu.observability.critical_path import (
            get_boot_registry, get_critical_path)
        ana = get_critical_path()
        if not ana.enabled:
            raise ApiError(503, "critical-path plane disabled "
                                "(set observability.critical_path"
                                ".enabled)")
        try:
            # Drain the recorder's deferred feed first: the rollup must
            # include every finished request even when nothing scrapes
            # /metrics (same discipline as the SLO/usage surfaces).
            observability.get_recorder().flush_metrics()
        except Exception:  # noqa: BLE001 — rollup must not fail on trace plane
            pass
        try:
            recent = int(req.q("recent") or 20)
        except ValueError:
            raise ApiError(400, "recent must be an integer")
        out = ana.snapshot(recent=max(0, min(recent, 256)))
        out["boot"] = get_boot_registry().snapshot()
        return 200, out

    def get_usage(self, req: _Request) -> Tuple[int, Any]:
        """Usage-ledger rollups (docs/observability.md "Usage &
        goodput"): per-tenant/priority/engine device-seconds, KV
        page-seconds, waste decomposition and the rolling goodput.
        ``?tenant=`` narrows to one tenant's rollup."""
        from llmq_tpu.observability.usage import get_usage_ledger
        led = get_usage_ledger()
        if not led.enabled:
            raise ApiError(503, "usage plane disabled "
                                "(set observability.usage.enabled)")
        try:
            # Drain the recorder's deferred feed first so the goodput
            # join reflects every finished request even when nothing
            # scrapes /metrics (same discipline as the SLO surfaces).
            observability.get_recorder().flush_metrics()
        except Exception:  # noqa: BLE001 — usage must not fail on trace plane
            pass
        snap = led.snapshot()
        tenant = req.q("tenant")
        if tenant:
            return 200, {
                "tenant": tenant,
                "usage": snap["tenants"].get(tenant),
                "goodput": snap["goodput"],
            }
        return 200, snap

    def get_tenancy(self, req: _Request) -> Tuple[int, Any]:
        """Tenancy-plane state (docs/tenancy.md): configured classes,
        live queue-depth/in-flight counters, quota-rejection totals,
        and — per manager — the fair dequeue's virtual times, served
        tokens and achieved-share ratios."""
        from llmq_tpu.tenancy import get_tenant_registry
        reg = get_tenant_registry()
        if not reg.enabled:
            raise ApiError(503, "tenancy plane disabled "
                                "(set tenancy.enabled)")
        out: Dict[str, Any] = reg.snapshot()
        if self.factory is not None:
            fair = {}
            for name in self.factory.manager_names():
                mgr = self.factory.get_queue_manager(name)
                snap = (mgr.fair_snapshot()
                        if mgr is not None else None)
                if snap is not None:
                    fair[name] = snap
            out["fair"] = fair
        return 200, out

    def get_cluster_overview(self, req: _Request) -> Tuple[int, Any]:
        """Cluster-wide device-telemetry rollup: per-replica MFU, tok/s,
        HBM and step decomposition through the existing transport
        (docs/observability.md "Device telemetry")."""
        if self.cluster_router is None:
            raise ApiError(503, "cluster router not configured "
                                "(set cluster.peers / --peers)")
        out = self.cluster_router.overview()
        if self.controller is not None:
            # Control-plane block (docs/controlplane.md): current rung,
            # last action + reason, target vs live replicas, burn
            # inputs — the operator's one-stop view.
            out["controller"] = self.controller.snapshot()
        store_block = self._store_block()
        if store_block is not None:
            # Store fault domain block (docs/robustness.md): breaker
            # state, degraded consumers, replay backlog. Absent when
            # the plane is off — pre-feature bodies stay byte-identical.
            out["store"] = store_block
        return 200, out

    def generate_sync(self, req: _Request) -> Tuple[int, Any]:
        """Synchronous inference RPC — the server half of the
        remote-engine transport (loadbalancer/transport.py): a peer
        host's router/worker POSTs a drained message here and gets the
        completion back in the response. This is the dispatch seam the
        reference invents worker URLs for but never implements
        (scheduler.go:299-301 fabricates ``http://llm-processor-N``;
        nothing ever calls them)."""
        if self.engine is None:
            raise ApiError(503, "no engine attached to this process")
        if not getattr(self.engine, "running", True):
            # Fail FAST: a submit to a stopped engine would otherwise
            # block the caller for its whole generation budget — the
            # peer's router needs the quick 503 to fail over within the
            # same worker call.
            raise ApiError(503, "engine not running on this host")
        data = req.json()
        timeout = float(data.pop("timeout", 0) or 120.0)
        try:
            msg = Message.from_dict(data)
        except (ValueError, TypeError) as e:
            raise ApiError(400, f"invalid message: {e}") from None
        if not msg.id:
            msg.id = new_id()
        # Cross-process stitch, replica half (docs/observability.md):
        # the caller's W3C trace context is recorded onto this host's
        # timeline (same trace id — both sides derive it from msg.id;
        # the header makes the link explicit and spec-visible), and the
        # hop arrival doubles as the replica-local "dispatched" stamp
        # so admission latency is measurable from this host alone.
        traceparent = req.headers.get(observability.TRACEPARENT_HEADER)
        parsed_tp = observability.parse_traceparent(traceparent)
        observability.record(
            msg.id, "dispatched", reason="remote",
            priority=msg.priority.tier_name,
            traceparent=traceparent or "",
            parent_span_id=parsed_tp.span_id if parsed_tp else "")
        try:
            self.engine.process_fn(_Deadline(timeout), msg)
        except TimeoutError as e:
            raise ApiError(504, str(e)) from None
        except RuntimeError as e:
            raise ApiError(500, f"generation failed: {e}") from None
        out = {"message_id": msg.id, "response": msg.response,
               "usage": msg.metadata.get("usage", {})}
        if getattr(self.config.observability, "propagate_trace", True):
            rec = observability.get_recorder()
            if rec.enabled:
                tl = rec.get(msg.id)
                if tl is not None:
                    # Ship this host's events back for the gateway's
                    # recorder to merge into one stitched timeline.
                    out["trace"] = [e.to_dict()
                                    for e in tl.sorted_events()]
        return 200, out

    # -- observability (docs/observability.md) -------------------------------

    def get_request_trace(self, req: _Request) -> Tuple[int, Any]:
        """One request's stitched lifecycle timeline — gateway- and
        replica-side stage events in one host-labeled view.
        ``?format=chrome`` exports a chrome://tracing / Perfetto
        document, stitching in the executor's SpanRecorder spans (and
        a pointer to the jax.profiler capture when LLMQ_TRACE_DIR is
        live)."""
        rec = observability.get_recorder()
        if not rec.enabled:
            raise ApiError(503, "observability disabled "
                                "(set observability.enabled)")
        tl = rec.get(req.params["id"])
        if tl is None:
            return 404, {"error": "no trace for that request id "
                                  "(evicted or never recorded)"}
        if req.q("format") == "chrome":
            from llmq_tpu.utils.profiling import trace_dir
            spans = None
            prof = getattr(self.engine, "_prof", None)
            if prof is not None:
                spans = prof.snapshot()
            return 200, observability.chrome_trace(
                [tl], spans=spans, jax_trace_dir=trace_dir())
        out = tl.to_dict()
        try:
            # Per-request critical-path decomposition rides the trace
            # payload for finished requests (None mid-flight).
            from llmq_tpu.observability.critical_path import (
                cp_enabled, decompose)
            if cp_enabled():
                d = decompose(tl)
                if d is not None:
                    d["segments"] = {k: round(v, 6)
                                     for k, v in d["segments"].items()}
                    out["critical_path"] = d
        except Exception:  # noqa: BLE001 — trace must not fail on cp plane
            pass
        return 200, out

    def get_flight_recorder(self, req: _Request) -> Tuple[int, Any]:
        """Flight-recorder state: ring stats, the most recent request
        timelines, and the slow/failed retention buffer."""
        rec = observability.get_recorder()
        try:
            limit = int(req.q("limit", "50"))
        except ValueError:
            raise ApiError(400, "limit must be an integer") from None
        return 200, {
            **rec.get_stats(),
            "recent": [t.summary() for t in rec.recent(limit)],
            "slow": [t.summary() for t in rec.slow()],
        }

    # -- admin ---------------------------------------------------------------

    def start_profile(self, req: _Request) -> Tuple[int, Any]:
        """On-demand bounded ``jax.profiler`` capture
        (docs/observability.md "Device telemetry"): kicks off a
        background trace via the ``utils/profiling.trace`` hook and
        answers 202 with the trace path immediately. SINGLE-FLIGHT:
        the profiler session is process-global, so a concurrent
        capture answers 409 with the active capture's path."""
        from llmq_tpu.observability import device
        data = req.json() if self._body_present(req) else {}
        try:
            duration_s = float(data.get("duration_ms", 1000.0)) / 1e3
        except (TypeError, ValueError):
            raise ApiError(400, "duration_ms must be a number") from None
        label = re.sub(r"[^\w.-]", "_",
                       str(data.get("label") or "ondemand"))[:64]
        try:
            # Output location is SERVER-controlled (LLMQ_TRACE_DIR or a
            # fresh tempdir) — a request-body path would let any API
            # caller write trace trees to arbitrary filesystem
            # locations; every other on-disk path here comes from
            # operator env/config, and this route is no exception.
            import os as _os
            info = device.start_profile(
                duration_s=duration_s, label=label,
                base_dir=_os.environ.get("LLMQ_TRACE_DIR") or None)
        except device.ProfileInProgress as e:
            raise ApiError(409, str(e)) from None
        return 202, info

    def get_profile_status(self, req: _Request) -> Tuple[int, Any]:
        from llmq_tpu.observability import device
        return 200, device.profile_status()

    def _require_controller(self):
        if self.controller is None:
            raise ApiError(503, "control plane disabled "
                                "(set controlplane.enabled)")
        return self.controller

    def get_controller_state(self, req: _Request) -> Tuple[int, Any]:
        """Controller snapshot (docs/controlplane.md): rung, target vs
        live replicas, burn inputs, recovery state, action counts."""
        return 200, self._require_controller().snapshot()

    def set_controller_state(self, req: _Request) -> Tuple[int, Any]:
        """Operator pause/resume: ``{"action": "pause"|"resume"}``.
        Paused ≠ disabled — the controller keeps observing (snapshot
        stays fresh, /health shows "paused") but takes no action."""
        ctl = self._require_controller()
        action = str(req.json().get("action", "")).strip().lower()
        if action == "pause":
            ctl.pause()
        elif action == "resume":
            ctl.resume()
        else:
            raise ApiError(400,
                           f"action must be 'pause' or 'resume' "
                           f"(got {action!r})")
        return 200, {"status": "paused" if ctl.paused else "running"}

    def add_priority_rule(self, req: _Request) -> Tuple[int, Any]:
        if self.preprocessor is None:
            raise ApiError(503, "preprocessor not configured")
        data = req.json()
        pattern = data.get("pattern")
        if not pattern:
            raise ApiError(400, "pattern is required")
        try:
            priority = Priority.parse(data.get("priority", "normal"))
        except (ValueError, TypeError):
            raise ApiError(400, f"invalid priority {data.get('priority')!r}") \
                from None
        try:
            rule = self.preprocessor.add_rule(pattern, priority,
                                              name=data.get("name", ""))
        except re.error as e:
            raise ApiError(400, f"invalid pattern: {e}") from None
        return 201, {"status": "rule added", "rule": rule.to_dict()}

    def list_priority_rules(self, req: _Request) -> Tuple[int, Any]:
        if self.preprocessor is None:
            raise ApiError(503, "preprocessor not configured")
        return 200, {"rules": [r.to_dict()
                               for r in self.preprocessor.list_rules()]}

    def set_user_priority(self, req: _Request) -> Tuple[int, Any]:
        if self.preprocessor is None:
            raise ApiError(503, "preprocessor not configured")
        data = req.json()
        user_id = data.get("user_id")
        prio_raw = data.get("priority")
        if not user_id or prio_raw is None:
            raise ApiError(400, "user_id and priority are required")
        try:
            priority = Priority.parse(prio_raw)
        except (ValueError, TypeError):
            # Parity: the reference silently maps unknown names to normal
            # (handlers.go:600-612); we reject instead.
            raise ApiError(400, f"invalid priority {prio_raw!r}") from None
        self.preprocessor.set_user_priority(user_id, priority)
        return 200, {"status": "user priority set"}

    def remove_message(self, req: _Request) -> Tuple[int, Any]:
        mgr = self._manager(req.params["queue_type"])
        msg = mgr.remove_message(req.params["id"])
        if msg is None:
            return 404, {"error": "no pending message with that id"}
        return 200, {"status": "removed", "message_id": msg.id}

    def requeue_dead_letter_message(self, req: _Request) -> Tuple[int, Any]:
        if self.factory is None:
            raise ApiError(503, "queue factory not configured")
        name = req.q("manager", self.manager_name)
        dlq = self.factory.get_dead_letter_queue(name)
        if dlq is None:
            raise ApiError(404, f"no dead-letter queue for manager {name!r}")
        mgr = self._manager(name)
        try:
            msg = dlq.requeue(req.params["id"], mgr)
        except KeyError:
            return 404, {"error": "message not in dead-letter queue"}
        return 200, {"status": "requeued", "message_id": msg.id}

    def requeue_all_dead_letter_messages(self, req: _Request) -> Tuple[int, Any]:
        if self.factory is None:
            raise ApiError(503, "queue factory not configured")
        name = req.q("manager", self.manager_name)
        dlq = self.factory.get_dead_letter_queue(name)
        if dlq is None:
            raise ApiError(404, f"no dead-letter queue for manager {name!r}")
        mgr = self._manager(name)
        requeued = dlq.batch_requeue(mgr)
        return 200, {"status": "requeued", "count": len(requeued)}

    # -- HTTP plumbing -------------------------------------------------------

    def _make_handler(self):
        server = self

        class _HTTPHandler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def _respond(self) -> None:
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length) if length else b""
                status, payload, ctype = server.dispatch(
                    self.command, self.path, body,
                    dict(self.headers.items()))
                if isinstance(payload, _SSEStream):
                    # Streaming: chunked, flushed per event; length
                    # unknown up front, so close delimits the body.
                    # Header writes sit INSIDE the try: a client that
                    # disconnects before headers go out must still hit
                    # the finally (slot release / generator close), or
                    # each such disconnect would leak a stream slot.
                    try:
                        self.send_response(status)
                        self.send_header("Content-Type", ctype)
                        self.send_header("Cache-Control", "no-cache")
                        self.send_header("Connection", "close")
                        for hk, hv in payload.headers.items():
                            self.send_header(hk, hv)
                        self._cors_headers()
                        self.end_headers()
                        for event in payload:
                            self.wfile.write(event.encode("utf-8"))
                            self.wfile.flush()
                    except (BrokenPipeError, ConnectionResetError):
                        pass   # client hung up
                    finally:
                        # Deterministic cleanup: closing the generator
                        # raises GeneratorExit inside it → the stream
                        # cancels its engine request. on_close covers
                        # the never-started-generator case.
                        close = getattr(payload.events, "close", None)
                        if close is not None:
                            close()
                        if payload.on_close is not None:
                            try:
                                payload.on_close()
                            except Exception:  # noqa: BLE001
                                log.exception("SSE on_close failed")
                    self.close_connection = True
                    return
                try:
                    data = (payload if isinstance(payload, bytes)
                            else json.dumps(payload).encode())
                except (TypeError, ValueError, RuntimeError) as e:
                    log.exception("response serialization failed")
                    status = 500
                    ctype = "application/json"
                    data = json.dumps(
                        {"error": f"serialization error: {e}"}).encode()
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                if isinstance(payload, dict) and "retry_after" in payload:
                    # Overload shed (docs/robustness.md): the standard
                    # header form (integer seconds, rounded up — a
                    # too-early retry is the thing being prevented).
                    import math
                    self.send_header(
                        "Retry-After",
                        str(max(1, math.ceil(float(
                            payload["retry_after"])))))
                self._cors_headers()
                self.end_headers()
                self.wfile.write(data)

            def _cors_headers(self) -> None:
                origin = self.headers.get("Origin", "")
                if not origin:
                    return
                exact = origin in server.allowed_origins
                if exact or "*" in server.allowed_origins:
                    self.send_header("Access-Control-Allow-Origin", origin)
                    # The allow-origin value varies per request; caches
                    # must key on Origin or they serve one origin's CORS
                    # headers to another.
                    self.send_header("Vary", "Origin")
                    self.send_header("Access-Control-Allow-Methods",
                                     "GET, POST, PUT, DELETE, OPTIONS")
                    self.send_header("Access-Control-Allow-Headers",
                                     "Content-Type, Authorization")
                    # Credentials only for an explicitly allow-listed
                    # origin — never for the wildcard (the reference
                    # reflects any origin WITH credentials,
                    # handlers.go:121-148; that combination lets any
                    # site ride a browser's session).
                    if exact:
                        self.send_header("Access-Control-Allow-Credentials",
                                         "true")

            def do_OPTIONS(self) -> None:  # noqa: N802 — preflight → 204
                self.send_response(204)
                self._cors_headers()
                self.send_header("Content-Length", "0")
                self.end_headers()

            do_GET = do_POST = do_PUT = do_DELETE = _respond  # noqa: N815

            def log_message(self, fmt: str, *args) -> None:
                log.debug("%s %s", self.address_string(), fmt % args)

        return _HTTPHandler

    def start(self, host: Optional[str] = None,
              port: Optional[int] = None) -> int:
        """Serve in a background thread. Returns the bound port (useful
        with port=0 in tests)."""
        host = host if host is not None else self.config.server.host
        port = port if port is not None else self.config.server.port
        self._httpd = ThreadingHTTPServer((host, port), self._make_handler())
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="api-server", daemon=True)
        self._thread.start()
        bound = self._httpd.server_address[1]
        log.info("API server listening on %s:%d", host, bound)
        return bound

    def stop(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
