"""The relay: HTTP endpoint, options and lifecycle over the store.

Reference: apps/server/src/index.ts (258 LoC, Express +
better-sqlite3): the 20 MB body limit (index.ts:222) and the
`GET /ping` health check (index.ts:250-252) live here; the storage
shape and the sync pipeline (index.ts:60-216) are `server/store.py`.
Observability extensions (no reference equivalent): `GET /metrics`
(Prometheus v0.0.4 text from the process registry) and `GET /stats`
(JSON: per-shard row counts + request counters + latency percentile
estimates) — see docs/OBSERVABILITY.md. Replication extension (no
reference equivalent): `POST /replicate/summary` + `POST
/replicate/pull`, the Merkle anti-entropy gossip surface between relay
peers (`server/replicate.py`; `RelayServer(peers=[...])`).

The top box of `server/`: `store.py` ← `engine.py` ← `scheduler.py` ←
this module, each importing only what is left of it. The store names
are imported here for the handler's and the servers' own use, which
is also what keeps `from evolu_tpu.server.relay import RelayStore,
ShardedRelayStore` working for every embedder.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Sequence

from evolu_tpu.obs import anatomy, flight, ledger, metrics, trace
from evolu_tpu.server.scheduler import (
    SchedulerQueueFull,
    SyncScheduler,
    format_retry_after,
)
from evolu_tpu.server.store import (
    RelayStore,
    ShardedRelayStore,
    _open_store,
    serve_single_request,
)
from evolu_tpu.sync import aead, protocol
from evolu_tpu.utils.log import log

MAX_BODY_BYTES = 20 * 1024 * 1024  # index.ts:222


def _count_ingest_mix(messages) -> None:
    """Ingest wire-format observability (the relay stays E2EE-blind:
    the 3-byte version magic is framing, not content). v2 records ride
    the store/Merkle/replication paths as opaquely as v1 — these
    counters are how an operator SEES the negotiated fleet actually
    carrying v2 traffic. Call only on the SERVING relay, after any
    fleet routing, so each message counts once fleet-wide."""
    if not messages:
        return
    n_v2 = aead.count_v2(messages)
    if n_v2:
        metrics.inc("evolu_crypto_v2_relay_messages_total", n_v2)
    if n_v2 < len(messages):
        metrics.inc("evolu_crypto_v1_relay_messages_total",
                    len(messages) - n_v2)


def _notify_tags(request: "protocol.SyncRequest"):
    """Lane tags for a push wakeup: the scope clause's per-message lane
    assignment, when the pushing client sent one. None (= wake every
    waiter, the PR-13 over-approximation stance) whenever lanes are
    unknown — v1 pushes, scoped pulls with no pushed rows, untagged
    rows mixed in."""
    s = getattr(request, "scope", None)
    if s is None or not s.push_tags:
        return None
    tags = frozenset(s.push_tags)
    return None if "" in tags else tags


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def mesh_stats_payload() -> dict:
    """The `mesh` section of GET /stats — the `evolu_mesh_*` family
    read back from the metrics registry (docs/OBSERVABILITY.md): device
    count, sharded dispatches, cross-device reduce counts by kind, and
    the occupancy/padding-waste distribution the stable placement
    trades LPT balance for. Pure registry reads — never imports jax."""
    occ = metrics.registry.get_histogram("evolu_mesh_shard_rows")
    waste = metrics.registry.get_histogram("evolu_mesh_padding_waste_rows")
    return {
        "devices": metrics.get_gauge("evolu_mesh_devices"),
        "dispatches_total": metrics.get_counter("evolu_mesh_dispatches_total"),
        "xdev_reduce_total": {
            kind: metrics.get_counter("evolu_mesh_xdev_reduce_total", kind=kind)
            for kind in ("digest", "owner_delta_partials",
                         "winner_minute_partials")
        },
        "shard_rows": {
            "count": (occ or (None, None, 0.0, 0))[3],
            "p50": metrics.quantile("evolu_mesh_shard_rows", 0.50),
            "p99": metrics.quantile("evolu_mesh_shard_rows", 0.99),
        },
        "padding_waste_rows": {
            "count": (waste or (None, None, 0.0, 0))[3],
            "p50": metrics.quantile("evolu_mesh_padding_waste_rows", 0.50),
            "p99": metrics.quantile("evolu_mesh_padding_waste_rows", 0.99),
        },
    }


def relay_stats_payload(store, replication=None, fleet=None,
                        write_behind=None, mesh_engine: bool = False,
                        push_hub=None, conn_tier=None) -> dict:
    """The GET /stats JSON: store-derived row counts per shard (shared
    truth in a MultiprocessRelay — every worker reads the same files)
    plus this process's request counters from the metrics registry
    (per-process by nature; a multiprocess deploy scrapes each worker's
    /metrics or sums /stats over workers). With a ReplicationManager
    attached, a `replication` section reports per-peer gossip health
    (docs/OBSERVABILITY.md)."""
    shards = store.stats() if hasattr(store, "stats") else []
    for s in shards:
        s["requests"] = metrics.get_counter(
            "evolu_relay_shard_requests_total", shard=str(s["index"])
        )
    payload = {
        "shards": shards,
        "messages": sum(s["messages"] for s in shards),
        "users": sum(s["users"] for s in shards),
        "requests_total": metrics.get_counter(
            "evolu_relay_requests_total", endpoint="/"
        ),
        "errors_total": metrics.get_counter("evolu_relay_errors_total"),
        "latency_ms": {
            "count": (metrics.registry.get_histogram("evolu_relay_request_ms") or
                      (None, None, 0.0, 0))[3],
            "p50": metrics.quantile("evolu_relay_request_ms", 0.50),
            "p99": metrics.quantile("evolu_relay_request_ms", 0.99),
        },
    }
    # The conservation ledger's station totals + the in-stream-safe
    # audit (barrier-only equations skipped: /stats must not force a
    # drain barrier; GET /ledger runs the full audit).
    payload["ledger"] = {
        "stations": ledger.totals(),
        "violations": ledger.audit(at_barrier=False),
    }
    if replication is not None:
        payload["replication"] = replication.stats_payload()
    if fleet is not None:
        payload["fleet"] = fleet.stats_payload()
    if write_behind is not None:
        payload["write_behind"] = write_behind.stats_payload()
    if mesh_engine:
        payload["mesh"] = mesh_stats_payload()
    if push_hub is not None:
        payload["push"] = push_hub.stats_payload()
    if conn_tier is not None:
        payload["conn"] = conn_tier.stats_payload()
    # Per-stage count / total / mean of the evolu_stage_ms histogram.
    payload["stages"] = anatomy.stages_payload()
    return payload


# GET /profile single-flight: jax.profiler supports one capture per
# process; a second concurrent request answers 429 instead of racing
# start_trace (which raises — or worse, interleaves captures).
_PROFILE_LOCK = threading.Lock()


def capture_live_profile(duration_ms: float) -> dict:
    """Capture `duration_ms` of live traffic as one loadable
    Chrome-trace JSON document (perfetto/chrome://tracing both open
    it). Three lanes share the timebase:

    - the jax.profiler device+runtime timeline, captured only when jax
      is ALREADY loaded in this process (a relay that never touched
      jax must stay jax-free — the obs import-hygiene contract; many
      relays serve pure-host workloads). PR-4 trace annotations are
      enabled for the window so `kernel:*` span names appear inside
      the profiler timeline too, then restored.
    - the logger span ring (`kernel:*` and sync spans always land
      there), exported as host-lane complete events.
    - sampled obs.trace spans in the window via the PR-10 chrome
      export (same event shape, their own lanes).

    Never raises on profiler trouble: a failed jax capture degrades to
    the host lanes with the error string in metadata — an operator
    profiling a live relay must get *a* trace, not a 500."""
    import gzip
    import shutil
    import sys
    import tempfile

    from evolu_tpu.utils import log as log_mod

    t_start = time.time()
    pid = os.getpid()
    events: List[dict] = []
    meta: Dict[str, object] = {"requested_ms": duration_ms}
    prof_dir = None
    jax_on = False
    annotations_were_on = log_mod._trace_annotation_cls is not None
    if "jax" in sys.modules:
        try:
            import jax  # already in sys.modules — no fresh import

            log_mod.enable_trace_annotations(True)
            prof_dir = tempfile.mkdtemp(prefix="evolu-profile-")
            jax.profiler.start_trace(prof_dir)
            jax_on = True
        except Exception as e:  # noqa: BLE001 - degrade to host lanes
            meta["jax_error"] = f"{type(e).__name__}: {e}"
    time.sleep(max(float(duration_ms), 0.0) / 1e3)
    if jax_on:
        try:
            import jax

            jax.profiler.stop_trace()
            for root, _dirs, files in os.walk(prof_dir):
                for fname in files:
                    if not fname.endswith(".trace.json.gz"):
                        continue
                    with gzip.open(os.path.join(root, fname), "rt",
                                   encoding="utf-8") as f:
                        doc = json.load(f)
                    for ev in doc.get("traceEvents", []):
                        # Real profiler dumps end with a bare {} and may
                        # omit pid on metadata rows — keep the merged
                        # document uniformly loadable.
                        if not isinstance(ev, dict) or not ev.get("ph"):
                            continue
                        ev.setdefault("pid", pid)
                        events.append(ev)
        except Exception as e:  # noqa: BLE001
            meta["jax_error"] = f"{type(e).__name__}: {e}"
            jax_on = False
        finally:
            if not annotations_were_on:
                log_mod.enable_trace_annotations(False)
    if prof_dir is not None:
        shutil.rmtree(prof_dir, ignore_errors=True)
    meta["jax_profiler"] = jax_on
    t_end = time.time()

    # Host lane 1: logger span ring events overlapping the window.
    n_host = 0
    for ev in log_mod.logger.recent_events():
        if ev.duration_ms is None:
            continue
        s0 = ev.t - ev.duration_ms / 1e3
        if ev.t < t_start or s0 > t_end:
            continue
        n_host += 1
        events.append({
            "name": f"{ev.target}|{ev.message}" if ev.message else ev.target,
            "cat": "evolu-host",
            "ph": "X",
            "ts": s0 * 1e6,
            "dur": ev.duration_ms * 1e3,
            "pid": pid,
            "tid": 0,
            "args": {k: str(v) for k, v in ev.fields.items()},
        })
    # Host lane 2: sampled distributed-trace spans in the window (the
    # PR-10 export keeps their per-thread lanes + trace/span ids).
    win_spans = [
        s for s in trace.recorder.dump()
        if s.t_start <= t_end and s.t_start + s.duration_ms / 1e3 >= t_start
    ]
    events.extend(trace.export_chrome(win_spans)["traceEvents"])
    meta.update(captured_at=t_start, wall_ms=(t_end - t_start) * 1e3,
                host_span_events=n_host, trace_span_events=len(win_spans),
                device_kind=anatomy.get_device_kind())
    return {"displayTimeUnit": "ms", "traceEvents": events,
            "metadata": meta}


class _round_leg(anatomy.batched_stage):
    """One handler-thread leg of the served round, kept as a plain
    `evolu_relay_stage_ms{stage=…}` observation in `closed` for the
    handler to post in ONE `metrics.observe_many` at the end of the
    round — these fire per request on 25 threads, so they skip the
    stage totals (and the trace ring, which already holds
    relay.sync/relay.respond), take the registry lock once a round,
    and read no CPU clock (`anatomy.stage`: five system
    calls a round under the interpreter lock)."""

    __slots__ = ()
    family = "evolu_relay_stage_ms"

    def __init__(self, name: str):
        super().__init__(name, cpu=False)

    def since(self, name: str, instant: float) -> None:
        """`name`: from `instant` (a `perf_counter` reading of ANOTHER
        thread) to where this running leg began. It crosses threads, so
        it is an observation beside the legs: no annotation."""
        if self._at is not None:  # None: the registry is disabled
            self.closed.append((self.family, (self._at[0] - instant) * 1e3,
                                {"stage": name}))


# The connection's leg on a threaded-tier handler thread: `conn_head`,
# running from the thread's first instruction, with `conn_spawn` already
# in its `closed`. do_POST takes it and makes it the round's.
_conn = threading.local()
_ACCEPTOR = "evolu_relay_acceptor_seconds_total"
_HANDLER = "evolu_relay_handler_seconds_total"
_IDLE, _BUSY, _WALL, _CPU = ({"state": s} for s in ("idle", "busy", "wall", "cpu"))
# Connections between two posts of the acceptor's seconds, and between
# two handler threads that are timed: on these threads a registry
# acquisition a connection cost the served relay over 1 % of its rate
# each, and a read of the CPU clock is a system call (my chip runs,
# PR 37, PERF.md §6).
_POST_EVERY = 32


class _accept_extent(anatomy.stage):
    """One connection on the acceptor thread, `accept()` to
    `Thread.start()` returning, as the dispatcher keeps its own time:
    `evolu_relay_acceptor_seconds_total{state}`, `busy` this extent,
    `idle` everything since the extent before (in `select`), so idle +
    busy is the thread's wall time; `cpu` the thread's own CPU seconds
    (`time.thread_time` counts from the thread's start, and `select`
    burns none). Summed on the server object, which only this thread
    writes, and posted in one `inc_many` every `_POST_EVERY`
    connections."""

    __slots__ = ("server",)

    def __init__(self, server: "_RelayHTTPServer"):
        super().__init__("accept", cpu=False)
        self.server = server

    def _record(self, seconds: float, wait) -> None:
        opened, server = self._at[0], self.server
        server.idle_s += opened - server.busy_end
        server.busy_s += seconds
        server.busy_end = opened + seconds
        if server.accepted % _POST_EVERY == 0:
            cpu = time.thread_time()
            metrics.inc_many(((_ACCEPTOR, server.idle_s, _IDLE),
                              (_ACCEPTOR, server.busy_s, _BUSY),
                              (_ACCEPTOR, cpu - server.cpu_posted, _CPU)))
            server.idle_s = server.busy_s = 0.0
            server.cpu_posted = cpu


class _Handler(BaseHTTPRequestHandler):
    store: RelayStore  # injected by RelayServer
    scheduler = None  # SyncScheduler when continuous batching is on
    replication = None  # ReplicationManager when the relay has peers
    fleet = None  # FleetManager when the relay is an owner-sharded fleet member
    write_behind = None  # WriteBehindQueue when the PR-11 inversion is on
    mesh_engine = False  # PR-12 sharded engine: adds the /stats mesh section
    push_hub = None  # PushHub when push subscriptions are on (server/push.py)
    conn_tier = None  # EventLoopHTTPServer when that tier serves this relay
    # Capabilities this relay echoes back (intersected with the
    # request's advertised set — sync/protocol.py capability
    # extension). A request with no capabilities gets the v1 wire,
    # byte-identical.
    capabilities = protocol.KNOWN_CAPABILITIES

    def _negotiate_caps(self, request: "protocol.SyncRequest", out: bytes) -> bytes:
        """Append the negotiated capability fields to an encoded sync
        response — AFTER the serve path (fused C wire bytes or object
        path alike; proto3 field order is free). Only fires when the
        client advertised, so capability-less peers round-trip
        byte-identically."""
        caps = tuple(c for c in request.capabilities if c in self.capabilities)
        if not caps:
            return out
        metrics.inc("evolu_crdt_capability_negotiations_total")
        for cap in caps:
            # Per-capability negotiation counts (bounded label set: only
            # capabilities WE serve ever reach here — never raw client
            # strings). `aead-batch-v1` echoes are the relay-side signal
            # that clients may start emitting v2 envelopes.
            metrics.inc("evolu_crypto_capability_echoes_total", capability=cap)
        return out + protocol.encode_response_capabilities(caps)

    def log_message(self, format: str, *args) -> None:
        # Target-gated like every other runtime signal (config.log):
        # quiet by default, switchable via the `dev` target instead of
        # unconditionally discarded. The is_enabled pre-check keeps the
        # disabled-default path allocation-free (this fires per
        # request); _flight=False because per-request access lines
        # would evict the sparse events the flight ring is for.
        from evolu_tpu.utils.log import logger

        if logger.is_enabled("dev"):
            log("dev", f"relay {self.address_string()} {format % args}",
                _flight=False)

    def _body_length(self) -> Optional[int]:
        """Harden Content-Length parsing: a non-numeric header used to
        raise an uncaught ValueError out of `int(...)` (connection
        reset instead of an HTTP answer), and a NEGATIVE value passed
        the `> MAX_BODY_BYTES` check and then `rfile.read(-1)` read
        UNBOUNDED. → the parsed length, or None after answering 400.
        The MAX_BODY_BYTES cap stays at the call sites (413)."""
        raw = self.headers.get("Content-Length", "0")
        try:
            length = int(raw)
        except (TypeError, ValueError):
            length = -1
        if length < 0:
            metrics.inc("evolu_relay_errors_total")
            self.send_error(400, "invalid Content-Length")
            return None
        return length

    def _respond(self, code: int, body: bytes, content_type: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _respond_retry_after(self, retry_after: float) -> None:
        """503 + Retry-After: the ONE flow-control answer shape —
        scheduler backpressure, a fleet owner mid-install, a forward
        target briefly down. Clients back off and retry; never counted
        in errors_total."""
        self.send_response(503)
        self.send_header("Retry-After", format_retry_after(retry_after))
        self.send_header("Content-Length", "0")
        self.end_headers()

    def _serve_request(self, request: "protocol.SyncRequest") -> Optional[bytes]:
        """Serve one LOCAL sync request through whichever path this
        relay runs (scheduler vs per-request) — shared by the sync
        POST handler and `/fleet/forward` (the recipes must never
        drift). → response bytes, or None after having answered 503
        backpressure itself."""
        if request.scope is not None and \
                protocol.CAP_SYNC_SCOPE not in (self.capabilities or ()):
            # This relay doesn't serve scopes (capability off): strip
            # the clause and answer the full serve — conservative
            # over-approximation, never an error. A well-behaved client
            # won't send one unnegotiated (emission gate); a hostile
            # one gets exactly the unscoped behavior.
            request = dataclasses.replace(request, scope=None)
        if self.scheduler is not None:
            try:
                return self.scheduler.submit(request)
            except SchedulerQueueFull as e:
                # Backpressure is flow control, not a pipeline error
                # (errors_total stays an error-rate): tell the client
                # when to come back instead of letting handler threads
                # pile up unboundedly. The shed IS these messages'
                # terminal station — nothing was stored (the engine
                # raises before any ACK/commit on this path).
                metrics.inc("evolu_relay_backpressure_total")
                ledger.count(ledger.SHED_BACKPRESSURE,
                             len(request.messages), owner=request.user_id)
                self._respond_retry_after(e.retry_after)
                return None
        return serve_single_request(self.store, request)

    def _obs_authorized(self) -> bool:
        """Optional token gate for the observability read surface
        (`GET /metrics`, `/stats`, `/trace/*`, `/profile`): with EVOLU_OBS_TOKEN
        set, demand the matching header (constant-time compare — the
        EVOLU_FLEET_RELOAD_TOKEN pattern from /fleet/reload). /stats
        and /trace enumerate owner ids, which the sync path treats as
        capabilities. Unset = open, the trusted-network default,
        unchanged. False → 403 already answered."""
        token = os.environ.get("EVOLU_OBS_TOKEN")
        if not token:
            return True
        import hmac

        got = self.headers.get("X-Evolu-Obs-Token", "")
        # Compare BYTES: compare_digest raises TypeError on non-ASCII
        # str inputs, and a hostile header must answer 403, not crash
        # the handler thread.
        if hmac.compare_digest(got.encode("utf-8", "replace"),
                               token.encode("utf-8")):
            return True
        metrics.inc("evolu_relay_errors_total")
        self.send_error(403, "observability token mismatch")
        return False

    def _do_trace(self) -> None:
        """GET /trace → recent trace ids; GET /trace/<id> → the span
        tree for one trace (fan-in spans included via their links);
        `?format=chrome` → the Chrome-trace export of those spans.
        A non-hex / wrong-length id answers 404 (it can never name a
        trace), never a 500."""
        import urllib.parse

        parts = urllib.parse.urlsplit(self.path)
        fmt = urllib.parse.parse_qs(parts.query).get("format", [""])[0]
        tail = parts.path[len("/trace"):].strip("/")
        if not tail:
            body = json.dumps({
                "recent": trace.recorder.recent_trace_ids(),
                "span_ring": trace.recorder.size(),
            }).encode("utf-8")
        elif len(tail) != 32 or not all(c in "0123456789abcdef" for c in tail):
            self.send_error(404, "not a trace id")
            return
        elif fmt == "chrome":
            body = json.dumps(
                trace.export_chrome(trace.recorder.spans_for(tail))
            ).encode("utf-8")
        else:
            body = json.dumps(trace.serve_trace(tail)).encode("utf-8")
        self._respond(200, body, "application/json")

    def do_GET(self) -> None:  # /ping (index.ts:250-252) + observability
        if self.path == "/ping":
            body = b"ok"
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif self.path == "/metrics":
            metrics.inc("evolu_relay_requests_total", endpoint="/metrics")
            if not self._obs_authorized():
                return
            try:
                # Refresh the process gauges at scrape time (uptime,
                # RSS) — no background sampler thread needed.
                metrics.update_process_gauges()
                body = metrics.render_prometheus().encode("utf-8")
            except Exception as e:  # noqa: BLE001 - scraper gets a clean 500
                metrics.inc("evolu_relay_errors_total")
                self.send_error(500, str(e))
                return
            self._respond(200, body, metrics.PROMETHEUS_CONTENT_TYPE)
        elif self.path == "/ledger" or self.path.startswith("/ledger?"):
            # The conservation-ledger read surface (obs/ledger.py):
            # station totals, owner sub-ledgers, and the audit verdict.
            # With a write-behind queue the audit runs AT a drain
            # barrier (wb.queued == wb.drained must hold there); either
            # way, concurrently in-flight requests can show as
            # transient deltas — the hard zero-violation gate is the
            # model-check episodes' quiescent audit, not a live scrape.
            metrics.inc("evolu_relay_requests_total", endpoint="/ledger")
            if not self._obs_authorized():
                return
            try:
                if self.write_behind is not None:
                    with self.write_behind.drain_barrier():
                        payload = ledger.snapshot(at_barrier=True)
                else:
                    payload = ledger.snapshot(at_barrier=True)
                body = json.dumps(payload).encode("utf-8")
            except Exception as e:  # noqa: BLE001 - reader gets a clean 500
                metrics.inc("evolu_relay_errors_total")
                self.send_error(500, str(e))
                return
            self._respond(200, body, "application/json")
        elif self.path == "/trace" or self.path.startswith("/trace/") \
                or self.path.startswith("/trace?"):
            # One fixed endpoint label — raw paths must never mint
            # registry series (the /replicate 404-before-metric rule).
            metrics.inc("evolu_relay_requests_total", endpoint="/trace")
            if not self._obs_authorized():
                return
            try:
                self._do_trace()
            except Exception as e:  # noqa: BLE001 - reader gets a clean 500
                metrics.inc("evolu_relay_errors_total")
                self.send_error(500, str(e))
            return
        elif self.path == "/stats":
            metrics.inc("evolu_relay_requests_total", endpoint="/stats")
            if not self._obs_authorized():
                return
            try:
                # store.stats() runs SQL: a shard closing mid-scrape
                # must surface as an HTTP 500, not a dropped connection.
                body = json.dumps(
                    relay_stats_payload(self.store, self.replication,
                                        self.fleet, self.write_behind,
                                        mesh_engine=self.mesh_engine,
                                        push_hub=self.push_hub,
                                        conn_tier=self.conn_tier)
                ).encode("utf-8")
            except Exception as e:  # noqa: BLE001
                metrics.inc("evolu_relay_errors_total")
                self.send_error(500, str(e))
                return
            self._respond(200, body, "application/json")
        elif self.path == "/health":
            # Readiness, not liveness (/ping is liveness): "serving"
            # vs "bootstrap/install in progress" via the PR-5 install
            # state machine's persisted phase marker (+ per-owner
            # rebalance state when fleet-configured) — fleet failover
            # probes and the bench must never route to a relay
            # mid-install. 503 while installing so dumb HTTP checks
            # (LB health probes) read it without parsing the body.
            metrics.inc("evolu_relay_requests_total", endpoint="/health")
            try:
                if self.fleet is not None:
                    serving, detail = self.fleet.health_payload()
                else:
                    from evolu_tpu.server.snapshot import install_phase

                    phase = install_phase(self.store)
                    serving = phase is None
                    detail = {
                        "status": "serving" if serving else "installing",
                        "install_phase": phase,
                    }
                if self.scheduler is not None:
                    # Saturation signal for operators / load-aware
                    # probing — readiness itself stays install-driven
                    # (a full queue answers 503 per request already).
                    detail["queue_depth"] = self.scheduler.depth()
                if self.write_behind is not None:
                    # Backlog + drain watermark (PR-11): fleet failover
                    # and the rebalance readiness probe must not route
                    # onto a relay whose materialization backlog is at
                    # its admission bound — a saturated queue IS
                    # not-ready (it would 503 the rerouted traffic
                    # anyway; better to fail over before sending it).
                    wbd = self.write_behind.health_payload()
                    detail["write_behind"] = wbd
                    if wbd["saturated"] or wbd["failing"]:
                        # Saturated OR persistently failing drain: not
                        # ready. The failing case matters because the
                        # backlog may sit BELOW max_rows while every
                        # flush-needing request hangs on the wedged
                        # drain — without this, fleet failover would
                        # keep routing onto a relay that cannot serve.
                        serving = False
                        detail["status"] = (
                            "backlogged" if wbd["saturated"]
                            else "drain-failing"
                        )
            except Exception as e:  # noqa: BLE001 - probe gets a clean 500
                metrics.inc("evolu_relay_errors_total")
                self.send_error(500, str(e))
                return
            self._respond(200 if serving else 503,
                          json.dumps(detail).encode("utf-8"),
                          "application/json")
        elif self.path == "/fleet":
            if self.fleet is None:
                self.send_error(404)
                return
            metrics.inc("evolu_relay_requests_total", endpoint="/fleet")
            try:
                body = json.dumps(self.fleet.stats_payload()).encode("utf-8")
            except Exception as e:  # noqa: BLE001
                metrics.inc("evolu_relay_errors_total")
                self.send_error(500, str(e))
                return
            self._respond(200, body, "application/json")
        elif self.path == "/profile" or self.path.startswith("/profile?"):
            # Live profiling (ISSUE 16): capture ?ms= of real traffic
            # as a loadable chrome/perfetto trace. Token-gated like the
            # rest of the obs surface (span names carry owner ids);
            # single-flight because jax.profiler allows one capture
            # per process.
            metrics.inc("evolu_relay_requests_total", endpoint="/profile")
            if not self._obs_authorized():
                return
            import urllib.parse

            q = urllib.parse.parse_qs(urllib.parse.urlsplit(self.path).query)
            try:
                ms = float(q.get("ms", ["250"])[0])
            except ValueError:
                self.send_error(400, "ms must be a number")
                return
            # Clamp: long enough to catch a batch, short enough that a
            # fat-fingered ms=3600000 cannot park a handler for an hour.
            ms = min(max(ms, 10.0), 30_000.0)
            if not _PROFILE_LOCK.acquire(blocking=False):
                self.send_error(429, "a profile capture is already running")
                return
            try:
                body = json.dumps(capture_live_profile(ms)).encode("utf-8")
            except Exception as e:  # noqa: BLE001 - reader gets a clean 500
                metrics.inc("evolu_relay_errors_total")
                self.send_error(500, str(e))
                return
            finally:
                _PROFILE_LOCK.release()
            self._respond(200, body, "application/json")
        elif self.path.startswith("/push/poll"):
            self._do_push_poll()
        else:
            self.send_error(404)

    def _do_push_poll(self) -> None:
        """GET /push/poll — the long-poll subscription leg
        (server/push.py). On THIS tier the poll parks the handler
        thread on an Event (the reference shape, fine at small scale);
        the event-loop tier (server/conn.py) intercepts the same path
        before the handler pool and parks the bare connection instead.
        This branch is also that tier's byte-identity fallback for the
        shapes it won't answer itself (no hub → 404, malformed query
        → 400). Framing here and in conn.frame_response must stay
        aligned — the twin-relay oracle test pins it."""
        from evolu_tpu.server import push as push_mod

        metrics.inc("evolu_relay_requests_total", endpoint="/push/poll")
        if self.push_hub is None:
            self.send_error(404)
            return
        import urllib.parse

        parts = urllib.parse.urlsplit(self.path)
        try:
            owner, node, cursor, timeout, tags = push_mod.parse_poll_query(
                parts.query)
        except ValueError as e:
            metrics.inc("evolu_relay_errors_total")
            self.send_error(400, str(e))
            return
        if self.fleet is not None:
            # A subscription lives at the owner's PLACED relay — where
            # its mutations are served and hub-notified. 307 even in
            # forward mode: proxying a long-poll would pin a handler
            # (or a poller, on the event tier) for the whole park.
            from evolu_tpu.server.fleet import FleetNotReady

            try:
                action, peer = self.fleet.route(owner)
            except FleetNotReady as e:
                self._respond_retry_after(e.retry_after)
                return
            if action != "local":
                metrics.inc("evolu_push_redirects_total")
                self.send_response(307)
                self.send_header("Location", peer + self.path)
                self.send_header("Content-Length", "0")
                self.end_headers()
                return
        try:
            body = self.push_hub.poll_blocking(owner, node, cursor, timeout,
                                               tags=tags)
        except push_mod.HubFull as e:
            self._respond_retry_after(e.retry_after)
            return
        self._respond(200, body, "application/json")

    def do_POST(self) -> None:  # POST / (index.ts:224-248)
        if self.path.startswith("/replicate/"):
            if self.replication is None:
                # Only a relay CONFIGURED for replication exposes the
                # gossip/snapshot surface: /replicate/summary and the
                # snapshot manifest enumerate owner ids, which the sync
                # path treats as capabilities — a plain client-facing
                # relay must not disclose them.
                self.send_error(404)
                return
            self._do_replicate()
            return
        if self.path.startswith("/fleet/"):
            self._do_fleet()
            return
        # The round, tiled on this handler thread: `read_decode` from
        # here to the call of _serve_request (body read, decode, ledger
        # ingress, routing), then — inside the scheduler —
        # evolu_sched_queue_wait_ms, the pass, evolu_sched_wake_ms, then
        # `respond_write` from _serve_request returning to the socket
        # write returning. evolu_relay_round_ms is the whole server
        # side of a 200 round; the client's median minus it is what the
        # client, the TCP connect and the accept queue cost.
        t0 = time.perf_counter()
        leg = getattr(_conn, "leg", None)
        if leg is None:  # event-loop tier, or a handler driven directly
            leg = _round_leg("read_decode").start()
        else:  # the threaded tier's: `conn_head` ends where the round begins
            _conn.leg = None
            leg.then("read_decode")
        before_round = len(leg.closed)  # conn_spawn and conn_head
        answered = False
        try:
            answered = self._sync_round(t0, leg)
        finally:
            leg.stop()
            if answered:
                leg.closed.append(("evolu_relay_round_ms",
                                   (time.perf_counter() - t0) * 1e3, {}))
            else:  # the connection's legs count the rounds round_ms counts
                del leg.closed[:before_round]
            metrics.observe_many(leg.closed)

    def _sync_round(self, t0: float, leg: _round_leg) -> bool:
        """POST /: one sync round. → True once a 200 was written."""
        # Count the request BEFORE any reject so errors_total can never
        # exceed requests_total (error-rate = errors/requests must stay
        # a fraction).
        metrics.inc("evolu_relay_requests_total", endpoint="/")
        length = self._body_length()
        if length is None:
            return False
        if length > MAX_BODY_BYTES:
            metrics.inc("evolu_relay_errors_total")
            self.send_error(413)
            return False
        body = self.rfile.read(length)
        metrics.observe("evolu_relay_request_bytes", len(body),
                        buckets=metrics.SIZE_BUCKETS)
        # Incoming trace context (obs/trace.py): a malformed or
        # oversized traceparent parses to None and the request simply
        # proceeds untraced — NEVER a 4xx/5xx (header-fuzz-pinned).
        tctx = trace.parse_traceparent(
            self.headers.get(trace.TRACEPARENT_HEADER)
        )
        srv_span = trace.start_span("relay.sync", parent=tctx,
                                    attrs={"endpoint": "/"})
        _tok = trace.activate(srv_span.context)
        request = None
        served = False
        try:
            request = protocol.decode_sync_request(body)
            srv_span.set_attr("owner", request.user_id)
            # Ledger ingress at the decode boundary (a body that never
            # decoded never became messages): every message of this
            # delivery attempt must reach exactly one terminal station
            # — store classification, a shed/reject answer, or a fleet
            # egress (obs/ledger.py `server-flow`).
            ledger.count(ledger.INGRESS_SYNC, len(request.messages),
                         owner=request.user_id)
            if self.fleet is not None:
                if not self._route_fleet(request, body):
                    served = True  # egress/shed terminal counted there
                    return False  # answered: 307/forwarded/503-not-ready
            shard = (
                self.store.shard_index(request.user_id)
                if hasattr(self.store, "shard_index") else 0
            )
            metrics.inc("evolu_relay_shard_requests_total", shard=str(shard))
            leg.stop()
            out = self._serve_request(request)
            leg.then("respond_write")
            served = True  # terminals counted (store path or 503 shed)
            if out is None:
                return False  # 503 backpressure already answered
            # Ingest-mix counters AFTER routing AND a successful
            # serve: a 307'd/forwarded request never counts at a
            # relay whose store it skips, and a 503-shed or errored
            # round (retried by the client) never counts at all —
            # each message counts once fleet-wide, at the relay that
            # actually ingested it.
            _count_ingest_mix(request.messages)
            if self.push_hub is not None and request.messages:
                # Wake parked subscriptions AFTER the serve committed
                # (a woken client's sync round must observe the rows);
                # the timestamps carry the author-node metadata the
                # hub's own-write exclusion gates on (server/push.py).
                self.push_hub.notify(
                    request.user_id,
                    [m.timestamp for m in request.messages],
                    tags=_notify_tags(request))
        except Exception as e:  # noqa: BLE001 - index.ts:231-233
            # The flight dump rides the exception (server-side only —
            # the wire response stays a bare 500, no event leakage).
            flight.attach(e)
            srv_span.set_attr("error", repr(e))
            metrics.inc("evolu_relay_errors_total")
            if request is not None and not served:
                # Ingressed but never reached a store terminal: the 500
                # answer IS the terminal (the client's retry is a fresh
                # delivery attempt with its own ingress count).
                ledger.count(ledger.REJECT_INVALID, len(request.messages),
                             owner=request.user_id)
            log("dev", "relay sync request failed", error=repr(e))
            self.send_error(500, str(e))
            return False
        finally:
            trace.deactivate(_tok)
            srv_span.end()
            metrics.observe(
                "evolu_relay_request_ms", (time.perf_counter() - t0) * 1e3,
                exemplar=srv_span.trace_id,
            )
        if self.replication is not None and request.messages:
            # Debounced write hint: fresh rows should reach peer relays
            # at gossip-debounce latency, not interval latency. The
            # hint carries the write's trace context so the gossip
            # round that ships these rows records into the SAME trace
            # (the fleet-wide convergence trace, obs/trace.py).
            self.replication.hint(origin=srv_span.context)
        # The respond leg gets its own span (explicitly parented — the
        # server span above already closed so the request_ms exemplar
        # and the latency split stay consistent): queue-wait
        # (sched.queue) vs engine (engine.batch, linked) vs respond.
        rspan = trace.start_span("relay.respond", parent=srv_span.context)
        out = self._negotiate_caps(request, out)
        metrics.observe("evolu_relay_response_bytes", len(out),
                        buckets=metrics.SIZE_BUCKETS)
        rspan.set_attr("bytes", len(out))
        # End BEFORE the socket write: the client can race a
        # GET /trace/<id> the instant it reads the response, and the
        # span must already be in the ring (the write itself is the
        # kernel's, not ours to time).
        rspan.end()
        self._respond(200, out, "application/octet-stream")
        return True

    def _do_replicate(self) -> None:
        """POST /replicate/{summary,pull,snapshot,snapshot/chunk} — the
        peer gossip + bootstrap surface (server/replicate.py,
        server/snapshot.py). Malformed bodies answer 400 (the wire
        decoders raise ValueError only; unknown/expired snapshot ids
        are a deliberate 400 too — the puller's restart signal);
        anything else is a 500 like the sync path."""
        from evolu_tpu.server import replicate, snapshot

        if self.path not in ("/replicate/summary", "/replicate/pull",
                             "/replicate/snapshot", "/replicate/snapshot/chunk"):
            # 404 BEFORE any metric: the endpoint label must only ever
            # take allowlisted values — counting raw unknown paths
            # would let any caller mint registry series without bound.
            self.send_error(404)
            return
        metrics.inc("evolu_relay_requests_total", endpoint=self.path)
        length = self._body_length()
        if length is None:
            return
        if length > MAX_BODY_BYTES:
            metrics.inc("evolu_relay_errors_total")
            self.send_error(413)
            return
        body = self.rfile.read(length)
        # The gossiping peer's round span context rides the
        # traceparent header; its trace id is the ORIGIN trace of the
        # write that armed the round (replicate.hint) — serving spans
        # here land in the same fleet-wide convergence trace.
        tctx = trace.parse_traceparent(
            self.headers.get(trace.TRACEPARENT_HEADER)
        )
        sspan = trace.start_span(
            "repl.serve", parent=tctx,
            attrs={"leg": self.path.rsplit("/replicate/", 1)[-1]},
        )
        from contextlib import nullcontext

        # Every /replicate serve READS the store (summaries, pulls,
        # snapshot capture): with write-behind on, force a drain first
        # and hold the drain lock for the serve — peers and snapshot
        # pullers must only ever see COMMITTED state (a snapshot of
        # half-materialized rows would install as truth elsewhere).
        barrier = (
            self.write_behind.drain_barrier()
            if self.write_behind is not None else nullcontext()
        )
        try:
            with sspan, trace.use(sspan.context), barrier:
                if self.path == "/replicate/summary":
                    out = replicate.serve_summary(
                        self.store, body, self.replication, origin=tctx
                    )
                elif self.path == "/replicate/pull":
                    out = replicate.serve_pull(
                        self.store, body,
                        per_owner=self.replication.pull_messages_per_owner,
                        per_response=self.replication.pull_messages_per_response,
                    )
                elif self.path == "/replicate/snapshot":
                    out = snapshot.serve_snapshot(self.store, body, self.replication)
                else:
                    out = snapshot.serve_snapshot_chunk(self.store, body, self.replication)
        except ValueError as e:
            metrics.inc("evolu_relay_errors_total")
            self.send_error(400, str(e))
            return
        except Exception as e:  # noqa: BLE001 - peer gets a clean 500
            flight.attach(e)
            metrics.inc("evolu_relay_errors_total")
            log("dev", "relay replicate request failed", error=repr(e))
            self.send_error(500, str(e))
            return
        self._respond(200, out, "application/octet-stream")

    # -- fleet routing (server/fleet.py) --

    def _route_fleet(self, request: "protocol.SyncRequest", body: bytes) -> bool:
        """Owner-sharded placement check for one sync POST. True →
        this relay is placed for the owner and ready: caller serves
        locally. False → already answered: 307 + the authoritative
        peer URL (redirect mode), the peer's proxied response (forward
        mode), or 503 + Retry-After (owner mid-install / target
        briefly unreachable — the client's backoff retries)."""
        from evolu_tpu.server.fleet import FleetNotReady

        n_msgs = len(request.messages)
        try:
            action, target = self.fleet.route(request.user_id)
        except FleetNotReady as e:
            ledger.count(ledger.SHED_BACKPRESSURE, n_msgs,
                         owner=request.user_id)
            self._respond_retry_after(e.retry_after)
            return False
        if action == "local":
            return True
        if action == "redirect":
            metrics.inc("evolu_fleet_redirects_total")
            ledger.count(ledger.EGRESS_REDIRECT, n_msgs,
                         owner=request.user_id)
            # Zero-duration event span: the trace shows WHERE the
            # client was bounced (its own sync.redirect span shows the
            # follow; this one shows the relay that answered 307).
            trace.record_span("fleet.redirect", trace.current(),
                              time.time(), 0.0, {"target": target})
            self.send_response(307)
            self.send_header("Location", target + "/")
            self.send_header("Content-Length", "0")
            self.end_headers()
            return False
        # forward: wrap the UNTOUCHED client body in the hop-guarded
        # envelope and relay the peer's raw response back. The forward
        # POST carries the ambient trace context (headers only — the
        # envelope bytes are exactly the client's).
        metrics.inc("evolu_fleet_forwards_total")
        import urllib.error

        from evolu_tpu.sync.client import _http_post

        env = protocol.encode_fleet_forward(
            protocol.FleetForward(body, self.fleet.self_url, 1)
        )
        fwd_span = trace.start_span("fleet.forward", parent=trace.current(),
                                    attrs={"target": target})
        try:
            with fwd_span:
                # The FORWARD span's context rides the header (not the
                # ambient server span's) so the peer's
                # fleet.forward.serve span parents under this hop —
                # same rule as replicate's per-leg spans.
                out = _http_post(
                    target + "/fleet/forward", env, retries=1,
                    headers=trace.inject_headers(ctx=fwd_span.context))
        except urllib.error.HTTPError as e:
            if e.code in (429, 503):
                # The peer is shedding load: flow control, relayed.
                metrics.inc("evolu_fleet_forward_failures_total")
                ledger.count(ledger.SHED_BACKPRESSURE, n_msgs,
                             owner=request.user_id)
                self._respond_retry_after(0.25)
                return False
            # A DEFINITIVE answer (404 = peer not fleet-enabled, 400 =
            # envelope rejected, 500 = peer pipeline failure) is not
            # transient — masking it as 503 would make clients spin
            # backoff forever while errors_total reads healthy. 502 it.
            metrics.inc("evolu_relay_errors_total")
            metrics.inc("evolu_fleet_forward_failures_total")
            ledger.count(ledger.REJECT_INVALID, n_msgs,
                         owner=request.user_id)
            log("dev", "fleet forward rejected by peer", peer=target,
                code=e.code)
            self.send_error(502, f"fleet forward target answered {e.code}")
            return False
        except Exception as e:  # noqa: BLE001 - target down mid-window:
            # flow control, not an error — the next route() re-probes
            # and fails over.
            metrics.inc("evolu_fleet_forward_failures_total")
            ledger.count(ledger.SHED_BACKPRESSURE, n_msgs,
                         owner=request.user_id)
            log("dev", "fleet forward failed", peer=target, error=repr(e))
            self._respond_retry_after(0.25)
            return False
        # Forwarded and answered by the peer: these messages left this
        # process — egress.forward is their terminal HERE; the peer's
        # ingress.forward accounts them in ITS ledger.
        ledger.count(ledger.EGRESS_FORWARD, n_msgs, owner=request.user_id)
        metrics.observe("evolu_relay_response_bytes", len(out),
                        buckets=metrics.SIZE_BUCKETS)
        self._respond(200, out, "application/octet-stream")
        return False

    def _do_fleet(self) -> None:
        """POST /fleet/{forward,reload} — the fleet peer/operator
        surface. `/fleet/forward` carries a hop-guarded peer envelope
        (octet-stream, ValueError→400 like every wire decoder);
        `/fleet/reload` is the static-config push (JSON body =
        FleetConfig.to_json; a stale version answers 400)."""
        if self.fleet is None or self.path not in ("/fleet/forward",
                                                   "/fleet/reload"):
            # 404 BEFORE any metric: the endpoint label must only ever
            # take allowlisted values.
            self.send_error(404)
            return
        metrics.inc("evolu_relay_requests_total", endpoint=self.path)
        length = self._body_length()
        if length is None:
            return
        if length > MAX_BODY_BYTES:
            metrics.inc("evolu_relay_errors_total")
            self.send_error(413)
            return
        body = self.rfile.read(length)
        request = None
        served = False
        try:
            if self.path == "/fleet/forward":
                env = protocol.decode_fleet_forward(body)
                if env.hops != 1:
                    # The enforced hop guard: forwarders always send
                    # hops=1 and this handler never forwards again, so
                    # anything else is a malformed or replayed
                    # envelope — reject before any side effect.
                    raise ValueError(
                        f"fleet forward from {env.origin!r} carries "
                        f"hops={env.hops}; only single-hop envelopes "
                        "are served"
                    )
                request = protocol.decode_sync_request(env.payload)
                # NO route() here: a forwarded request is served where
                # it lands, even if the rings disagree mid-reload
                # (scoped gossip drains any stray owner).
                metrics.inc("evolu_fleet_forwarded_served_total")
                # Ledger ingress: the forwarding hop counted
                # egress.forward in ITS ledger; these messages enter
                # THIS process here.
                ledger.count(ledger.INGRESS_FORWARD, len(request.messages),
                             owner=request.user_id)
                # The forwarder's span context rode the traceparent
                # header: the serve span here joins the same trace, so
                # GET /trace/<id> on THIS relay shows the hop the
                # client never saw (malformed header → None → fresh
                # trace, never an error).
                tctx = trace.parse_traceparent(
                    self.headers.get(trace.TRACEPARENT_HEADER)
                )
                fspan = trace.start_span(
                    "fleet.forward.serve", parent=tctx,
                    attrs={"owner": request.user_id, "origin": env.origin},
                )
                with fspan, trace.use(fspan.context):
                    out = self._serve_request(request)
                served = True  # terminals counted (store path or shed)
                if out is None:
                    return  # 503 backpressure already answered
                _count_ingest_mix(request.messages)
                if self.push_hub is not None and request.messages:
                    # The forward SERVE is where the owner's rows land
                    # — and where its subscriptions are parked (push
                    # polls 307 to placement): notify here, never at
                    # the forwarding hop.
                    self.push_hub.notify(
                        request.user_id,
                        [m.timestamp for m in request.messages],
                        tags=_notify_tags(request))
                if self.replication is not None and request.messages:
                    self.replication.hint(origin=fspan.context)
                out = self._negotiate_caps(request, out)
                # Recorded before the socket write — see do_POST's
                # respond span.
                trace.start_span("relay.respond", parent=fspan.context,
                                 attrs={"bytes": len(out)}).end()
                self._respond(200, out, "application/octet-stream")
                return
            # /fleet/reload is a control-plane MUTATION on the
            # client-facing port: with EVOLU_FLEET_RELOAD_TOKEN set,
            # demand the matching header (constant-time compare) —
            # else anyone who can reach the sync port could hijack the
            # ring with a high-version config. Unset = open, for
            # trusted-network meshes like the /replicate/* surface
            # (docs/FLEET.md).
            token = os.environ.get("EVOLU_FLEET_RELOAD_TOKEN")
            if token:
                import hmac

                got = self.headers.get("X-Evolu-Fleet-Token", "")
                if not hmac.compare_digest(got, token):
                    metrics.inc("evolu_relay_errors_total")
                    self.send_error(403, "fleet reload token mismatch")
                    return
            cfg_json = json.loads(body.decode("utf-8"))
            from evolu_tpu.utils.config import FleetConfig

            cfg = FleetConfig.from_json(cfg_json)
            rebalancing = self.fleet.apply_config(cfg)
            out = json.dumps({
                "ring_version": self.fleet.config.version,
                "rebalancing": rebalancing,
            }).encode("utf-8")
            self._respond(200, out, "application/json")
        except ValueError as e:
            metrics.inc("evolu_relay_errors_total")
            if request is not None and not served:
                ledger.count(ledger.REJECT_INVALID, len(request.messages),
                             owner=request.user_id)
            self.send_error(400, str(e))
        except Exception as e:  # noqa: BLE001 - clean 500, like sync
            flight.attach(e)
            metrics.inc("evolu_relay_errors_total")
            if request is not None and not served:
                ledger.count(ledger.REJECT_INVALID, len(request.messages),
                             owner=request.user_id)
            log("dev", "relay fleet request failed", error=repr(e))
            self.send_error(500, str(e))


class _RelayHTTPServer(ThreadingHTTPServer):
    # The reference's deploy allows 25 concurrent connections
    # (examples/server-nodejs/fly.toml); socketserver's default listen
    # backlog of 5 resets simultaneous connects well below that.
    request_queue_size = 128

    # What a round costs before do_POST (docs/OBSERVABILITY.md, "Is the
    # acceptor a second one?"): the acceptor's extent a connection, then
    # `conn_spawn` (accept() returned → the handler thread's first
    # instruction; it crosses threads, so an observation and no
    # annotation), then `conn_head` (→ do_POST: handler set-up, request
    # line, headers); and of one handler thread in `_POST_EVERY`, its
    # whole life: evolu_relay_handler_seconds_total{state=wall|cpu},
    # first instruction → shutdown_request returning, `cpu` ONE read of
    # the CPU clock at its end (the thread is born for the connection).

    def serve_forever(self, poll_interval: float = 0.5) -> None:
        self.busy_end = time.perf_counter()  # of the acceptor's last extent
        self.accepted, self.idle_s, self.busy_s = 0, 0.0, 0.0
        self.cpu_posted = time.thread_time()
        super().serve_forever(poll_interval)

    def _handle_request_noblock(self) -> None:
        with _accept_extent(self):
            super()._handle_request_noblock()

    def process_request(self, request, client_address) -> None:
        # ThreadingMixIn's, with the instant accept() returned and
        # whether this thread is timed as the thread's arguments (daemon
        # threads, as ThreadingHTTPServer sets: server_close has none to
        # join).
        self.accepted += 1
        threading.Thread(
            target=self.process_request_thread, daemon=True,
            args=(request, client_address, time.perf_counter(),
                  self.accepted % _POST_EVERY == 0)).start()

    def process_request_thread(self, request, client_address,
                               accepted: float, timed: bool) -> None:
        leg = _conn.leg = _round_leg("conn_head").start()
        leg.since("conn_spawn", accepted)
        born = time.perf_counter()
        try:
            super().process_request_thread(request, client_address)
        finally:
            _conn.leg = None
            leg.stop()  # running only if no POST / took it
            if timed:
                metrics.inc_many((
                    (_HANDLER, time.perf_counter() - born, _WALL),
                    (_HANDLER, time.thread_time(), _CPU)))


class RelayServer:
    """ThreadingHTTPServer wrapper; `url` once started.

    `batching=True` (or an explicit `scheduler`) routes sync POSTs
    through the continuous-batching scheduler
    (`evolu_tpu.server.scheduler.SyncScheduler`): handler threads
    coalesce into single `BatchReconciler` passes, queue-full answers
    503 + Retry-After, and `stop()` drains in-flight batches before
    the store closes. Default off — the per-request path is the
    reference relay's shape and stays the baseline.

    `peers=[url, ...]` (or an explicit `replication` manager) turns on
    relay↔relay Merkle anti-entropy (`server/replicate.py`): the
    manager gossips per-owner tree summaries with each peer, pulls only
    diverged ranges, and — when this relay also batches — submits the
    pulled messages through the scheduler so replication traffic
    coalesces with live client traffic into the same fused engine
    passes. `peers=[]` (non-None) makes a pure LISTENER: it serves the
    gossip endpoints without polling anyone. Relays NOT configured for
    replication answer 404 on `/replicate/*` — the summary endpoint
    (and the snapshot manifest) enumerate owner ids (capabilities on
    the sync path), so the surface is for peer meshes on trusted
    networks, not for clients. `bootstrap_lag_owners` enables snapshot
    bootstrap (`server/snapshot.py`): an empty peer — or one lacking at
    least that many advertised owners — installs a donor snapshot
    instead of crawling history through capped pulls.

    `checkpoint_interval_s` (with `checkpoint_path`, defaulting to
    `<store path>.checkpoint` for file-backed stores) runs periodic
    local snapshot checkpoints for crash-consistent fast restart
    (`snapshot.write_checkpoint` / `snapshot.restore_checkpoint`).

    `connection_tier` (ISSUE 13, `server/conn.py`): "threaded" (the
    reference-shaped ThreadingHTTPServer — default, and every
    byte-identity pin's baseline) or "eventloop" (one selectors loop
    owns every socket, requests run the same handler on a bounded
    pool, push long-polls park the bare connection — 10^4-10^5 idle
    subscriptions cost FDs, not threads). `push` enables the
    long-poll subscription hub (`server/push.py`, default on — a new
    GET endpoint, zero effect on existing responses) on either tier.
    `start()`/`stop()` own every lifecycle."""

    def __init__(self, store: Optional[RelayStore] = None, host: str = "127.0.0.1",
                 port: int = 0, batching: bool = False, scheduler=None,
                 peers: Optional[Sequence[str]] = None, replication=None,
                 replication_interval_s: float = 30.0,
                 bootstrap_lag_owners: Optional[int] = None,
                 checkpoint_interval_s: Optional[float] = None,
                 checkpoint_path: Optional[str] = None,
                 capabilities: Optional[Sequence[str]] = None,
                 write_behind: Optional[bool] = None,
                 write_behind_log: Optional[str] = None,
                 mesh_engine: Optional[bool] = None,
                 mesh_ctx=None,
                 connection_tier: Optional[str] = None,
                 push: Optional[bool] = None):
        self.store = store or RelayStore()
        # capabilities=() emulates a v1 peer (never echoes the
        # extension — tests pin the byte-identical fallback with it).
        self.capabilities = (
            protocol.KNOWN_CAPABILITIES if capabilities is None
            else tuple(capabilities)
        )
        from evolu_tpu.utils.config import default_config

        # PR-11 storage inversion (docs/WRITE_BEHIND.md): opt-in via
        # constructor arg, EVOLU_WRITE_BEHIND=1, or Config.write_behind
        # — default OFF (the synchronous path is the reference shape
        # and every byte-identity pin's baseline). It rides the
        # batching engine, so enabling it implies batching.
        if write_behind is None:
            env = os.environ.get("EVOLU_WRITE_BEHIND", "")
            if env:
                # A SET env var wins in both directions — an operator
                # must be able to force the synchronous reference path
                # (EVOLU_WRITE_BEHIND=0) over a Config default when
                # bisecting, not just force the inversion on.
                write_behind = env.lower() not in ("0", "false", "no", "off")
            else:
                write_behind = default_config.write_behind
        self.write_behind = None
        if write_behind:
            from evolu_tpu.storage.write_behind import WriteBehindQueue

            if write_behind_log is None:
                shards = getattr(self.store, "shards", None)
                base = getattr(
                    getattr((shards[0] if shards else self.store), "db", None),
                    "path", None,
                )
                if base and base != ":memory:":
                    write_behind_log = base + ".wblog"
            # PR-19 parallel drain knobs (same env-wins-both-ways rule
            # as EVOLU_WRITE_BEHIND): worker count + process-per-shard
            # mode resolve here so an operator can steer a deployed
            # relay without a Config edit.
            env_workers = os.environ.get("EVOLU_WB_DRAIN_WORKERS", "")
            drain_workers = (
                int(env_workers) if env_workers
                else default_config.wb_drain_workers
            )
            env_proc = os.environ.get("EVOLU_WB_DRAIN_PROCESS", "")
            drain_process = (
                env_proc.lower() not in ("0", "false", "no", "off")
                if env_proc else default_config.wb_drain_process
            )
            self.write_behind = WriteBehindQueue(
                self.store, log_path=write_behind_log,
                max_rows=default_config.write_behind_max_rows,
                drain_batch_rows=default_config.write_behind_drain_rows,
                drain_workers=drain_workers,
                drain_process=drain_process,
            )
            batching = True
        # PR-12 mesh-sharded engine (docs/MESH.md): opt-in via
        # constructor arg, EVOLU_MESH_ENGINE, or Config.mesh_engine —
        # default OFF until the parity gate is green in a deployment.
        # It is a property of the ENGINE pass, so enabling it implies
        # batching; the mesh context itself is resolved lazily on the
        # scheduler's dispatcher thread (importing jax here would break
        # the no-backend-at-import contract).
        if mesh_engine is None and mesh_ctx is None:
            env = os.environ.get("EVOLU_MESH_ENGINE", "")
            if env:
                mesh_engine = env.lower() not in ("0", "false", "no", "off")
            else:
                mesh_engine = default_config.mesh_engine
        self.mesh_engine = bool(mesh_engine) or mesh_ctx is not None
        if self.mesh_engine:
            batching = True
        self.scheduler = scheduler
        if batching and scheduler is None:
            self.scheduler = SyncScheduler(
                self.store, write_behind=self.write_behind,
                mesh_ctx=mesh_ctx, mesh_engine=self.mesh_engine,
            )
        self.replication = replication
        if peers is not None and replication is None:
            from evolu_tpu.server.replicate import ReplicationManager

            self.replication = ReplicationManager(
                self.store, peers, scheduler=self.scheduler,
                interval_s=replication_interval_s,
                bootstrap_lag_owners=bootstrap_lag_owners,
                write_behind=self.write_behind,
            )
        self.checkpointer = None
        if checkpoint_interval_s is None:
            from evolu_tpu.utils.config import default_config

            checkpoint_interval_s = default_config.checkpoint_interval_s
        if checkpoint_interval_s is not None:
            from evolu_tpu.server.snapshot import CheckpointWriter

            if checkpoint_path is None:
                store_path = getattr(getattr(self.store, "db", None), "path", None)
                if not store_path or store_path == ":memory:":
                    raise ValueError(
                        "checkpoint_interval_s needs checkpoint_path for "
                        "non-file-backed stores"
                    )
                checkpoint_path = store_path + ".checkpoint"
            self.checkpointer = CheckpointWriter(
                self.store, checkpoint_path, checkpoint_interval_s,
                barrier=(self.write_behind.drain_barrier
                         if self.write_behind is not None else None),
            )
        self.fleet = None
        # Push subscriptions (ISSUE 13, server/push.py): on by default
        # — a new GET endpoint, zero effect on existing responses.
        # Both connection tiers serve the same hub.
        if push is None:
            push = default_config.push_subscriptions
        self.push_hub = None
        if push:
            from evolu_tpu.server.push import PushHub

            self.push_hub = PushHub(
                max_subscriptions=default_config.push_max_subscriptions,
                default_timeout_s=default_config.push_poll_timeout_s,
            )
            if self.replication is not None and getattr(
                    self.replication, "push_hub", None) is None:
                # Replication ingest is a wakeup source too: rows a
                # gossip round lands (a partition HEALING) must wake
                # this relay's parked subscribers — they will never
                # arrive as a local sync POST.
                self.replication.push_hub = self.push_hub
        # Connection tier (ISSUE 13 tentpole, server/conn.py):
        # "threaded" (the reference-shaped ThreadingHTTPServer,
        # default) or "eventloop" (idle connections cost FDs, not
        # threads). Constructor arg > EVOLU_CONN_TIER > Config.
        if connection_tier is None:
            connection_tier = (os.environ.get("EVOLU_CONN_TIER")
                               or default_config.connection_tier)
        if connection_tier not in ("threaded", "eventloop"):
            raise ValueError(
                f"connection_tier must be 'threaded' or 'eventloop', "
                f"got {connection_tier!r}")
        self.connection_tier = connection_tier
        self._handler_cls = type(
            "BoundHandler", (_Handler,),
            {"store": self.store, "scheduler": self.scheduler,
             "replication": self.replication,
             "capabilities": self.capabilities,
             "write_behind": self.write_behind,
             "mesh_engine": self.mesh_engine,
             "push_hub": self.push_hub},
        )
        if connection_tier == "eventloop":
            from evolu_tpu.server.conn import EventLoopHTTPServer

            self._httpd = EventLoopHTTPServer(
                (host, port), self._handler_cls,
                push_hub=self.push_hub,
                handler_threads=default_config.conn_handler_threads,
                max_pending=default_config.conn_max_pending,
                read_timeout_s=default_config.conn_read_timeout_s,
                write_timeout_s=default_config.conn_write_timeout_s,
                max_header_bytes=default_config.conn_max_header_bytes,
            )
            self._handler_cls.conn_tier = self._httpd
        else:
            self._httpd = _RelayHTTPServer((host, port), self._handler_cls)
        self._thread: Optional[threading.Thread] = None

    def enable_fleet(self, config, self_url: Optional[str] = None):
        """Join an owner-sharded fleet (server/fleet.py): install the
        placement ring, start answering non-placed sync POSTs with
        307/forward, scope this relay's replication gossip to
        placement, and expose `/fleet/reload` + the fleet `/health`
        detail. The server socket binds at CONSTRUCTION, so call this
        between construction and `start()` when the relay has peers:
        the replication loop's first gossip round fires immediately on
        start and must already be placement-scoped (an unscoped first
        round would pull owners this member is not placed for). The
        FleetConfig must be the same object of truth on every member —
        see utils/config.py."""
        from evolu_tpu.server.fleet import FleetManager

        self.fleet = FleetManager(
            self.store, config, self_url or self.url,
            replication=self.replication,
            write_behind=self.write_behind,
        )
        self._handler_cls.fleet = self.fleet
        if self.replication is not None:
            self.replication.fleet = self.fleet
        return self.fleet

    @property
    def url(self) -> str:
        host, port = self._httpd.server_address[:2]
        return f"http://{host}:{port}"

    def _publish_build_info(self) -> None:
        """`evolu_build_info` (constant 1, facts in labels): which
        build/topology THIS relay process runs — a fleet dashboard must
        tell a mesh-sharded event-loop relay from a default one without
        SSH. Never raises: identity labels are not worth a failed
        start."""
        try:
            from evolu_tpu import __version__
            from evolu_tpu.utils.config import default_config

            shards = getattr(self.store, "shards", None)
            mesh_devices = default_config.mesh_devices
            metrics.set_build_info(
                version=__version__,
                backend=("native" if getattr(self.store, "packed", False)
                         else "python"),
                shards=(len(shards) if shards else 1),
                batching=int(self.scheduler is not None),
                write_behind=int(self.write_behind is not None),
                mesh_engine=int(self.mesh_engine),
                mesh_devices=("auto" if mesh_devices is None
                              else mesh_devices),
                connection_tier=self.connection_tier,
                push=int(self.push_hub is not None),
            )
        except Exception:  # noqa: BLE001,S110 - see docstring
            pass

    def start(self) -> "RelayServer":
        self._publish_build_info()
        self._thread = threading.Thread(target=self._httpd.serve_forever, daemon=True, name="evolu-relay")
        self._thread.start()
        if self.replication is not None:
            self.replication.start()
        if self.checkpointer is not None:
            self.checkpointer.start()
        return self

    def stop(self) -> None:
        if self.push_hub is not None:
            # BEFORE the HTTP server stops: resolve every parked
            # long-poll (wake=false) so threaded-tier handler threads
            # unblock and the event tier can flush the responses in
            # its shutdown drain window.
            self.push_hub.close()
        self._httpd.shutdown()
        if self._thread:
            self._thread.join()
        if self.fleet is not None:
            # Before replication/store teardown: a rebalance thread may
            # still be ingesting through the store (stop joins it).
            self.fleet.stop()
        if self.checkpointer is not None:
            # Before the store closes; a capture in flight finishes its
            # read transactions first (stop joins the loop thread).
            self.checkpointer.stop()
        if self.replication is not None:
            # Before the scheduler drains and WELL before the store
            # closes: an in-flight gossip round may still be submitting
            # pulled messages (stop() joins the loop thread).
            self.replication.stop()
        if self.scheduler is not None:
            # Drain BEFORE the store closes — injected or owned alike
            # (stop() is idempotent): every queued request is served
            # through full-size batches, handler threads blocked in
            # submit() get their responses, and only then does the
            # storage go away. Post-drain submits answer 503.
            self.scheduler.stop()
        if self.write_behind is not None:
            # After the scheduler drained (its final batches appended
            # records), before the store closes: flush everything to
            # SQLite and stop the drain thread. The log is empty at
            # this point — a clean shutdown leaves nothing to replay.
            self.write_behind.close()
        self._httpd.server_close()
        self.store.close()


def serve(path: str = ":memory:", host: str = "0.0.0.0", port: int = 4000) -> RelayServer:
    """The `examples/server-nodejs` entry point analog."""
    server = RelayServer(RelayStore(path), host, port)
    return server.start()


# -- pre-forked multiprocess relay (VERDICT r2 #8) --



def _mp_worker_main(host: str, port: int, path: str, shards: int, backend: str) -> None:
    """One relay worker process: bind its own SO_REUSEPORT listening
    socket on the shared port (the kernel load-balances incoming
    connections across the workers' accept queues) and serve the
    SHARED file-backed sharded store — cross-process safety comes from
    SQLite WAL + busy_timeout (set in RelayStore for file paths)."""
    import socket

    store = _open_store(path, backend, shards)
    handler = type("BoundHandler", (_Handler,), {"store": store})

    class _ReuseportServer(_RelayHTTPServer):
        def server_bind(self):
            self.socket.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
            super().server_bind()

    httpd = _ReuseportServer((host, port), handler)
    print("READY", flush=True)  # parent waits for every worker's listen()
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:  # pragma: no cover - parent terminates us
        pass


class MultiprocessRelay:
    """Pre-forked relay: N worker PROCESSES accept on one SO_REUSEPORT
    port and share one file-backed (sharded) store. This is the
    multi-core deployment shape — the reference's fly.io deploy runs
    one Node process, this scales the accept path and the Python/HTTP
    work across cores while SQLite WAL serializes per-shard writes.
    Requires a file path (processes cannot share :memory:)."""

    def __init__(self, path: str, workers: int = 2, shards: int = 8,
                 backend: str = "auto", host: str = "127.0.0.1", port: int = 0):
        import socket

        if path == ":memory:":
            raise ValueError("MultiprocessRelay needs a file-backed store")
        self.host = host
        self._path, self._workers, self._shards, self._backend = (
            path, workers, shards, backend,
        )
        self._procs: list = []
        # Reserve the port in the REUSEPORT group (bound, NOT
        # listening, so no connection ever lands here); workers are
        # spawned in start() so a never-started or failed construction
        # leaks nothing but this socket (closed by stop()).
        self._anchor = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._anchor.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
        try:
            self._anchor.bind((host, port))
            self.port = self._anchor.getsockname()[1]
            # One store open in the parent creates the schema before
            # any worker races to serve (workers use IF NOT EXISTS too).
            _open_store(path, backend, shards).close()
        except BaseException:
            self._anchor.close()
            raise

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def start(self) -> "MultiprocessRelay":
        # Plain subprocesses (`python -m evolu_tpu.server.relay_worker`):
        # no fork of this process's jax state, and no
        # multiprocessing-spawn re-import of __main__ (which breaks
        # under pytest/stdin drivers). A worker serves the per-request
        # store path only (no scheduler, no engine) and must never
        # initialise a JAX backend: a chip belongs to ONE process, and
        # a second one that reaches for it fails or hangs.
        import subprocess
        import sys
        import time
        import urllib.request

        env = dict(os.environ)
        env["PYTHONPATH"] = (
            _REPO_ROOT + (os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        )
        import select

        try:
            self._procs = [
                subprocess.Popen(
                    [sys.executable, "-m", "evolu_tpu.server.relay_worker",
                     self.host, str(self.port), self._path,
                     str(self._shards), self._backend],
                    env=env, stdout=subprocess.PIPE, text=True,
                )
                for _ in range(self._workers)
            ]
            # EVERY worker must report READY (post-listen) — returning
            # on the first responsive worker would let an N-worker
            # config silently run under-provisioned (and skew the
            # per-worker-count benchmark rows).
            waiting = {p.stdout.fileno(): p for p in self._procs}
            deadline = time.time() + 30
            while waiting and time.time() < deadline:
                dead = [p for p in self._procs if p.poll() is not None]
                if dead:
                    raise RuntimeError(
                        f"{len(dead)}/{len(self._procs)} relay workers exited "
                        f"at startup (rc={[p.returncode for p in dead]})"
                    )
                ready, _, _ = select.select(list(waiting), [], [], 0.1)
                for fd in ready:
                    if "READY" in waiting[fd].stdout.readline():
                        del waiting[fd]
            if waiting:
                raise RuntimeError(
                    f"{len(waiting)}/{len(self._procs)} relay workers did not come up"
                )
            with urllib.request.urlopen(self.url + "/ping", timeout=5):
                pass
            return self
        except BaseException:
            self.stop()
            raise

    def stop(self) -> None:
        for p in self._procs:
            if p.poll() is None:
                p.terminate()
        for p in self._procs:
            try:
                p.wait(timeout=5)
            except Exception:  # noqa: BLE001 - wedged: escalate AND reap
                p.kill()
                try:
                    p.wait(timeout=5)
                except Exception:  # noqa: BLE001,S110 - unreapable; parent
                    pass           # exit collects it
        self._procs = []
        self._anchor.close()

