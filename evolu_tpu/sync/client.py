"""The sync transport — SyncWorker analog.

Reference: packages/evolu/src/sync.worker.ts. One input shape (a sync
request carrying optional fresh messages + the clock), one pipeline
(sync.worker.ts:177-229): encrypt each message's content → protobuf
SyncRequest → HTTP POST octet-stream → parse SyncResponse → decrypt →
hand the result back to the DbWorker as a Receive command.

Network failure is swallowed by design — offline is a normal state,
recovery is the next sync trigger (sync.worker.ts:217-227). Every
round runs under the per-database sync lock, making sync mutually
exclusive across clients of the same database (syncLock.ts:8-12).
"""

from __future__ import annotations

import queue
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from typing import Callable, Optional

from evolu_tpu.core.timestamp import timestamp_from_string
from evolu_tpu.core.types import CrdtMessage, UnknownError
from evolu_tpu.obs import anatomy, metrics, trace
from evolu_tpu.runtime.messages import OnError, SyncRequestInput
from evolu_tpu.runtime.synclock import SyncLock
from evolu_tpu.sync import protocol
from evolu_tpu.sync.crypto import decrypt_symmetric, encrypt_symmetric
from evolu_tpu.utils.config import Config
from evolu_tpu.utils.log import log


def encrypt_messages(messages, mnemonic: str):
    """sync.worker.ts:50-91 — per-message protobuf-encode + encrypt;
    the timestamp stays plaintext (the relay orders and diffs by it).
    The transport always encodes with extensions allowed: the wire gate
    (incl. strict interop, Config.wire_extensions=False) is enforced at
    MUTATION time (worker._send), so anything in the log is either
    authored encodable or arrived from a remote peer — and a relay must
    forward remote messages verbatim, never refuse them (refusing here
    would wedge anti-entropy resends forever).

    Hot loop #3 (SURVEY.md): the batched C++ path handles canonical
    values (~8× the pure loop, docs/BENCHMARKS.md); None means some
    value needs the pure loop's error surface, so it re-runs here."""
    if messages:
        from evolu_tpu.sync import native_crypto

        native = native_crypto.encrypt_batch(messages, mnemonic)
        if native is not None:
            return native
    out = []
    for m in messages:
        content = protocol.encode_content(m.table, m.row, m.column, m.value)
        out.append(
            protocol.EncryptedCrdtMessage(m.timestamp, encrypt_symmetric(content, mnemonic))
        )
    return tuple(out)


def encrypt_messages_v2(messages, mnemonic: str):
    """The aead-batch-v1 twin of `encrypt_messages` (sync/aead.py):
    session-keyed GCM records instead of per-message OpenPGP S2K. Only
    the NEGOTIATED push path calls this — the pure loop here is the
    fallback behind the fused C wire leg, and it raises exactly what
    the v1 pure loop raises for unencodable values (encode_content owns
    the TypeError surface in both)."""
    from evolu_tpu.sync import aead

    session = aead.get_session(mnemonic, records=len(messages))
    out = []
    for m in messages:
        content = protocol.encode_content(m.table, m.row, m.column, m.value)
        out.append(
            protocol.EncryptedCrdtMessage(
                m.timestamp, aead.encrypt_record(session.key, session.salt, content)
            )
        )
    return tuple(out)


def decrypt_messages(messages, mnemonic: str):
    """sync.worker.ts:135-173. Canonical rows decrypt on the batched
    C++ path; everything else — including the whole batch when the
    library is unavailable — re-runs through the Python oracle at its
    original position (identical errors, first-failure order)."""
    from evolu_tpu.sync import native_crypto

    return native_crypto.decrypt_batch(messages, mnemonic)


def _accepts_headers(fn) -> bool:
    """Whether an http_post callable takes a `headers` kwarg — the
    trace-context hop is optional so injected 2-arg transports (tests,
    embedders, fault injectors) keep working unchanged. Probed at
    call time (the transport is swappable after construction) but
    memoized per callable: inspect.signature builds a full Signature
    object, far too heavy to re-run on every POST/gossip leg."""
    try:
        return _ACCEPTS_HEADERS_MEMO[fn]
    except TypeError:
        return _accepts_headers_probe(fn)  # unhashable callable
    except KeyError:
        pass
    ok = _accepts_headers_probe(fn)
    try:
        if len(_ACCEPTS_HEADERS_MEMO) > 256:  # unbounded-growth guard
            _ACCEPTS_HEADERS_MEMO.clear()
        _ACCEPTS_HEADERS_MEMO[fn] = ok
    except TypeError:
        pass
    return ok


_ACCEPTS_HEADERS_MEMO: dict = {}


def _accepts_headers_probe(fn) -> bool:
    import inspect

    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return False
    params = sig.parameters
    return "headers" in params or any(
        p.kind == p.VAR_KEYWORD for p in params.values()
    )


class SyncTransport:
    """Owns a transport thread; `request_sync` enqueues a round.

    `on_receive(messages, merkle_tree, previous_diff)` is called with
    the decrypted response — typically `Evolu.receive`, closing the
    anti-entropy loop (SURVEY.md §3.3).
    """

    def __init__(
        self,
        config: Config,
        on_receive: Callable[[tuple, str, Optional[int]], None],
        sync_lock: Optional[SyncLock] = None,
        on_error: Optional[Callable[[Exception], None]] = None,
        http_post: Optional[Callable[[str, bytes], bytes]] = None,
        http_probe: Optional[Callable[[str], None]] = None,
        on_reconnect: Optional[Callable[[], None]] = None,
    ):
        self.config = config
        self.on_receive = on_receive
        self.sync_lock = sync_lock or SyncLock()
        self.on_error = on_error or (lambda _e: None)
        self._http_post = http_post or _http_post
        self._http_probe = http_probe or _http_ping
        self.on_reconnect = on_reconnect or (lambda: None)
        self._queue: "queue.Queue[object]" = queue.Queue()
        self._stop = object()
        # Learned owner→relay routes (fleet 307 redirects,
        # server/fleet.py). Touched only on the transport thread.
        # Invalidated by the next 307 (re-learn), a 404 (stale route —
        # the owner moved or the relay left the fleet), or a
        # connection failure on the learned URL (fail back to the
        # configured relay before declaring offline).
        self._routes: dict = {}
        # Negotiated wire capabilities per relay URL (sync/protocol.py
        # capability extension): what the LAST response from that relay
        # echoed back from our advertised set. Empty/absent = a v1 peer
        # — typed CRDT traffic still relays byte-identically (ops are
        # E2EE-opaque), this is the app's signal that the fleet
        # understands them.
        self.negotiated_capabilities: dict = {}
        # Reconnect probing state (db.ts:390-412 analog): offline is
        # entered by a swallowed fetch error, left by the first probe
        # success or successful round — either fires on_reconnect.
        self._probe_lock = threading.Lock()
        self._probe_stop = threading.Event()
        self._prober: Optional[threading.Thread] = None
        self._offline = False
        self._pending_reconnect = False  # transport-thread only
        # Optional push-subscription leg (ISSUE 13, server/push.py):
        # attached by connect() under Config.push_subscribe. Bound
        # lazily from the first successful round (which is where the
        # owner id, the clock's node id, and the owner's PLACED relay
        # become known on this thread).
        self.push_subscriber = None
        self._thread = threading.Thread(target=self._loop, daemon=True, name="evolu-sync")
        self._thread.start()

    def request_sync(self, request: SyncRequestInput) -> None:
        self._queue.put(request)

    def stop(self) -> None:
        if self.push_subscriber is not None:
            self.push_subscriber.stop()
        self._probe_stop.set()
        with self._probe_lock:
            prober = self._prober
        if prober is not None and prober is not threading.current_thread():
            # Bounded: the prober may be mid-GET with a 5s socket
            # timeout; it is a daemon thread that only touches the
            # network, so don't stall dispose() on it.
            prober.join(timeout=0.2)
        self._queue.put(self._stop)
        self._thread.join()

    # -- offline → online transitions --

    def _note_offline(self) -> None:
        """A fetch error was swallowed: start probing GET /ping until
        the transport comes back (unless probing is disabled)."""
        interval = self.config.reconnect_probe_interval
        with self._probe_lock:
            self._offline = True
            if interval is None or self._probe_stop.is_set():
                return
            if self._prober is not None and self._prober.is_alive():
                return
            self._prober = threading.Thread(
                target=self._probe_loop, args=(interval,),
                daemon=True, name="evolu-sync-probe",
            )
            self._prober.start()

    def _probe_loop(self, interval: float) -> None:
        ping_url = _ping_url(self.config.sync_url)
        delay = interval
        try:
            while not self._probe_stop.wait(delay):
                with self._probe_lock:
                    if not self._offline:
                        return  # a successful round beat the probe
                try:
                    self._http_probe(ping_url)
                except urllib.error.HTTPError:
                    # The server ANSWERED (e.g. /ping 404s behind a
                    # path-prefixed deployment): the transport is up —
                    # same classification as _sync_round's.
                    pass
                except Exception:  # noqa: BLE001 - still offline; back
                    # off so an hours-long outage doesn't hammer 1/s
                    delay = min(delay * 2, max(30.0, interval))
                    continue
                self._came_back()
                # Back off after a reconnect attempt too: if /ping
                # succeeds but the sync POST keeps failing (POST-only
                # firewall, MTU blackhole), each probe success fires a
                # doomed round — without this the cycle storms at
                # `interval` forever. A true recovery exits at the next
                # _offline check; the next outage gets a fresh prober
                # starting at `interval` again.
                delay = min(delay * 2, max(30.0, interval))
                # Do NOT return: loop back to the _offline check — a
                # network flap may already have re-marked us offline,
                # and exiting here while _note_offline still saw this
                # thread alive would leave NO prober running.
        finally:
            # Closes the remaining flap window: if offline was re-set
            # between our last check and this exit, restart a fresh
            # prober (suppressed during stop()).
            with self._probe_lock:
                self._prober = None
                restart = self._offline and not self._probe_stop.is_set()
            if restart:
                self._note_offline()

    def _note_online(self) -> None:
        """A round succeeded (or the server answered an error — either
        way the transport is up); if we were offline this IS the
        reconnect. Firing is deferred to the loop, after the sync lock
        is released (see _loop)."""
        with self._probe_lock:
            was_offline = self._offline
            self._offline = False
        if was_offline:
            self._pending_reconnect = True

    def _came_back(self) -> None:
        with self._probe_lock:
            # stop() joins the daemon prober with a short timeout, so a
            # probe can complete mid-dispose — don't fire the reconnect
            # hook into an already-disposed Evolu instance.
            if self._probe_stop.is_set() or not self._offline:
                return
            self._offline = False
        self._fire_reconnect()

    def _fire_reconnect(self) -> None:
        log("sync:reconnect")
        try:
            self.on_reconnect()
        except Exception as e:  # noqa: BLE001 - hook must not kill transport
            self.on_error(UnknownError(e))

    def flush(self) -> None:
        done = threading.Event()
        self._queue.put(done)
        done.wait()

    def _loop(self) -> None:
        while True:
            item = self._queue.get()
            if item is self._stop:
                return
            if isinstance(item, threading.Event):
                item.set()
                continue
            with self.sync_lock.hold():
                received = self._sync_round(item)
            # Everything below runs with the sync lock RELEASED. The
            # worker's _receive skips its anti-entropy resend while the
            # lock is pending/held — handing it the response under the
            # lock would race that gate and silently drop the resend
            # (observed: an offline-born mutation never pushed after
            # reconnect). Same for the reconnect hook's pull round.
            if received is not None:
                try:
                    self.on_receive(*received)
                except Exception as e:  # noqa: BLE001
                    self.on_error(UnknownError(e))
            if self._pending_reconnect:
                self._pending_reconnect = False
                self._fire_reconnect()

    def _aead_negotiated(self, url: str, caps) -> bool:
        """v2 emission gate: we advertise aead-batch-v1 AND the LAST
        response from `url` echoed it back. Everything else — first
        contact, a v1 relay, a failover target we never spoke to —
        gets the v1 wire. Decoding needs no gate (records
        self-describe), so this only ever controls what we WRITE."""
        return (
            protocol.CAP_AEAD_BATCH in caps
            and protocol.CAP_AEAD_BATCH in self.negotiated_capabilities.get(url, ())
        )

    def _scope_negotiated(self, url: str, caps) -> bool:
        """Scope-clause emission gate (the aead gate's twin): we
        advertise sync-scope-v1 AND the last response from `url` echoed
        it. A non-advertising relay never receives a scope clause — it
        gets the full serve instead (over-approximation; the worker's
        materialization filter still applies client-side)."""
        return (
            protocol.CAP_SYNC_SCOPE in caps
            and protocol.CAP_SYNC_SCOPE
            in self.negotiated_capabilities.get(url, ())
        )

    def _drop_negotiated(self, url: str) -> None:
        """Invalidate the cached capability set alongside a route
        invalidation: the relay at `url` is gone/stale, and a failover
        replica must be treated as un-negotiated (v1) until its own
        response says otherwise — never send v2 at a relay that didn't
        advertise it."""
        if self.negotiated_capabilities.pop(url, None) is not None:
            metrics.inc("evolu_crypto_capability_invalidations_total")

    def _encode_push(self, request: SyncRequestInput, node_id: str,
                     caps, use_v2: bool,
                     scope_clause=None) -> bytes:
        """One request body. v1: the fused C wire path (byte-identical
        to the pre-v2 encoder — pinned), pure per-message OpenPGP
        behind it. v2 (negotiated only): ONE session key schedule +
        one GCM record per message (`encode_push_request_aead`), pure
        aead loop behind it. Capabilities append identically on every
        path; absent caps = the v1 wire byte-for-byte. `scope_clause`
        (negotiated only — sync-scope-v1) appends as field 6 the same
        way; None = byte-identical to the unscoped wire."""
        from evolu_tpu.sync import native_crypto

        body = None
        if use_v2 and request.messages:
            from evolu_tpu.sync import aead

            session = aead.get_session(request.owner.mnemonic,
                                       records=len(request.messages))
            body = native_crypto.encode_push_request_aead(
                request.messages, session.key, session.salt,
                request.owner.id, node_id, request.merkle_tree,
            )
            if body is None:
                # (encrypt_messages_v2 re-counts the records against a
                # session it fetches itself — double-counting toward
                # the rotation bound is conservative and harmless.)
                encrypted = encrypt_messages_v2(request.messages, request.owner.mnemonic)
                body = protocol.encode_sync_request(
                    protocol.SyncRequest(encrypted, request.owner.id, node_id,
                                         request.merkle_tree)
                )
        if body is None:
            body = native_crypto.encode_push_request(
                request.messages, request.owner.mnemonic,
                request.owner.id, node_id, request.merkle_tree,
            )
        if body is None:
            encrypted = encrypt_messages(request.messages, request.owner.mnemonic)
            body = protocol.encode_sync_request(
                protocol.SyncRequest(encrypted, request.owner.id, node_id,
                                     request.merkle_tree)
            )
        if caps:
            # Advertise as appended field-5 bytes: identical on the
            # fused C and pure encode paths, absent (v1 wire,
            # byte-identical) when the config advertises nothing.
            body = body + protocol.encode_request_capabilities(caps)
        if scope_clause is not None:
            body = body + protocol.encode_request_scope(scope_clause)
        return body

    def _post_traced(self, url: str, body: bytes) -> bytes:
        """The sync POST with the ambient trace context as a
        traceparent header (headers only — the body bytes are
        untouched). An injected 2-arg http_post (tests, embedders —
        probed at call time, the transport is swappable) is served
        without the header rather than broken."""
        hdrs = trace.inject_headers()
        if hdrs and _accepts_headers(self._http_post):
            return self._http_post(url, body, headers=hdrs)
        return self._http_post(url, body)

    def _sync_round(self, request: SyncRequestInput):
        """One encrypt→POST→decrypt round under the sync lock, traced
        end to end (obs/trace.py): the round span joins the mutation's
        trace when the request carries one (runtime/worker.py mints it
        at Send) and roots a fresh trace for pull-only rounds; the
        POST carries this span's context as its traceparent header.
        Returns what `_sync_round_body` returns."""
        rspan = trace.start_span(
            "sync.round", parent=getattr(request, "trace", None),
            attrs={"messages": len(request.messages)},
        )
        with rspan, trace.use(rspan.context):
            return self._sync_round_body(request)

    def _sync_round_body(self, request: SyncRequestInput):
        """The round itself. Returns the decoded (messages,
        merkle_tree, previous_diff) for the caller to hand to
        on_receive AFTER releasing the lock, or None when there is
        nothing to receive."""
        caps = tuple(self.config.sync_capabilities or ())
        owner_id = request.owner.id
        base = self.config.sync_url
        url = self._routes.get(owner_id, base)
        use_v2 = self._aead_negotiated(url, caps)
        scope = getattr(self.config, "sync_scope", None)
        clause = None
        if scope is not None and not scope.is_noop \
                and self._scope_negotiated(url, caps):
            # The scope clause rides only a negotiated wire; the push
            # lane assignment names each pushed message's table (even
            # out-of-scope tables — the relay's lanes must stay
            # truthful for OTHER scoped clients of this owner).
            clause = scope.wire_clause(
                request.owner.mnemonic,
                push_tables=tuple(m.table for m in request.messages),
            )
        try:
            node_id = timestamp_from_string(request.clock_timestamp).node
            body = self._encode_push(request, node_id, caps, use_v2,
                                     scope_clause=clause)
        except Exception as e:  # noqa: BLE001
            self.on_error(UnknownError(e))
            return None
        metrics.inc("evolu_sync_requests_total")
        metrics.inc("evolu_sync_request_messages_total", len(request.messages))
        metrics.observe("evolu_sync_request_bytes", len(body),
                        buckets=metrics.SIZE_BUCKETS)
        log("sync:request", url=url,
            messages=len(request.messages), bytes=len(body))

        class _Abort(Exception):
            pass

        downgraded = False

        def retarget(new_url: str):
            """Move this round to another relay. If the body was a v2
            envelope but the new target is not negotiated for it,
            silently re-emit the round as v1 — a failover replica must
            NEVER receive v2 records it didn't advertise for (the
            regression this guards: 2-relay fleet failover to a v1
            replica)."""
            nonlocal url, body, use_v2, downgraded, clause
            url = new_url
            need_v1 = use_v2 and not self._aead_negotiated(new_url, caps)
            drop_scope = (clause is not None
                          and not self._scope_negotiated(new_url, caps))
            if not (need_v1 or drop_scope):
                return
            if need_v1:
                use_v2 = False
                downgraded = True
            if drop_scope:
                # The PR-8 retarget lesson, applied to scope: a
                # non-advertising failover target must NEVER receive a
                # scope clause — re-emit unscoped (a full serve is the
                # conservative answer; the worker still filters).
                clause = None
                metrics.inc("evolu_scope_downgrades_total",
                            reason="failover")
            try:
                body = self._encode_push(request, node_id, caps, use_v2,
                                         scope_clause=clause)
            except Exception as e:  # noqa: BLE001 - encode must never
                # kill the transport thread; surface and end the round
                self.on_error(UnknownError(e))
                raise _Abort() from e
            if need_v1:
                metrics.inc("evolu_crypto_v1_fallback_total", reason="failover")
                log("sync:request", "aead downgrade for failover", url=new_url)

        followed = False
        # `sync_post`, on this thread: the body handed to the POST to the
        # answer's bytes read, retries and a followed redirect included.
        t_post = time.perf_counter()
        try:
            while True:
                try:
                    response_bytes = self._post_traced(url, body)
                    break
                except urllib.error.HTTPError as e:
                    # A fleet relay answers a non-placed sync POST with
                    # 307 + the authoritative peer URL (server/fleet.py).
                    # Follow AT MOST ONE redirect per request and cache
                    # the learned owner→relay route; each hop's POST
                    # keeps its own full 429/503/connection backoff
                    # schedule inside _http_post, so backpressure at the
                    # redirected relay still backs off normally.
                    location = e.headers.get("Location") if e.headers else None
                    if e.code == 307 and location and not followed:
                        followed = True
                        target = urllib.parse.urljoin(url, location)
                        self._routes[owner_id] = target
                        metrics.inc("evolu_sync_redirects_total")
                        # The redirect hop is a leg of the mutation's
                        # journey: record it into the round's trace so
                        # GET /trace/<id> shows WHERE the client was
                        # bounced (zero-duration event span).
                        trace.record_span(
                            "sync.redirect", trace.current(), time.time(),
                            0.0, {"target": target},
                        )
                        log("sync:request", "fleet redirect", url=target)
                        retarget(target)
                        continue
                    if e.code in (307, 404) and self._routes.pop(owner_id, None):
                        # A second 307 (ring churn) or a 404 (the
                        # learned relay no longer serves this owner):
                        # the cached route is stale — and so is anything
                        # we thought that relay had negotiated.
                        metrics.inc("evolu_sync_route_invalidations_total")
                        self._drop_negotiated(url)
                        if e.code == 404 and url != base:
                            retarget(base)
                            continue
                    # The server answered: that's a real error
                    # (4xx/5xx), not offline — surface it so divergence
                    # isn't silent. The transport is demonstrably UP, so
                    # clear any offline state.
                    metrics.inc("evolu_sync_http_errors_total")
                    self._note_online()
                    self.on_error(UnknownError(e))
                    return None
                except (urllib.error.URLError, OSError):
                    if url != base and self._routes.pop(owner_id, None):
                        # The LEARNED relay is unreachable — that says
                        # nothing about the configured one: drop the
                        # route (and its negotiated capability set) and
                        # fail over to it before declaring offline.
                        metrics.inc("evolu_sync_route_invalidations_total")
                        self._drop_negotiated(url)
                        retarget(base)
                        continue
                    # Offline is not an error (sync.worker.ts:217-227)
                    # — but it arms the reconnect probe.
                    metrics.inc("evolu_sync_offline_rounds_total")
                    self._note_offline()
                    return None
        except _Abort:
            return None
        post_s = time.perf_counter() - t_post
        self._note_online()
        if self.push_subscriber is not None:
            # Bind/retarget the push leg with what this round learned:
            # the owner, the clock's node id (its own-write exclusion
            # key), and the relay that actually served — the placed
            # one, after any 307 follow.
            # A scoped client's subscription carries its lane tags so
            # the hub can skip wakes its filter provably can't see —
            # only when the round's relay negotiated the scope (the
            # same emission gate as the clause itself).
            sub_tags = None
            if scope is not None and scope.tables \
                    and self._scope_negotiated(url, caps):
                from evolu_tpu.sync.scope import derive_scope_tag

                sub_tags = tuple(
                    derive_scope_tag(request.owner.mnemonic, t)
                    for t in scope.tables
                )
            self.push_subscriber.ensure(owner_id, node_id, url,
                                        tags=sub_tags)
        # Push-mix counters AFTER the POST landed: a round that ended
        # offline, errored, or was downgraded mid-flight must count as
        # what actually reached a relay, not what was first encoded
        # (the failover downgrade itself is an event — counted in
        # retarget; `use_v2` here reflects the FINAL body).
        if request.messages:
            if use_v2:
                metrics.inc("evolu_crypto_v2_push_legs_total")
                metrics.inc("evolu_crypto_v2_push_messages_total",
                            len(request.messages))
            elif protocol.CAP_AEAD_BATCH in caps and not downgraded:
                metrics.inc("evolu_crypto_v1_fallback_total",
                            reason="not_negotiated")
        if caps:
            try:
                negotiated = protocol.scan_sync_response_capabilities(response_bytes)
            except ValueError:
                negotiated = ()  # decode error surfaces below, on the real decoder
            self.negotiated_capabilities[url] = negotiated
            metrics.set_gauge(
                "evolu_crdt_capability_negotiated",
                1 if protocol.CAP_CRDT_TYPES in negotiated else 0,
            )
            metrics.set_gauge(
                "evolu_crdt_list_capability_negotiated",
                1 if protocol.CAP_CRDT_LIST in negotiated else 0,
            )
            metrics.set_gauge(
                "evolu_crdt_tensor_capability_negotiated",
                1 if protocol.CAP_CRDT_TENSOR in negotiated else 0,
            )
            metrics.set_gauge(
                "evolu_crypto_aead_negotiated",
                1 if protocol.CAP_AEAD_BATCH in negotiated else 0,
            )
        try:
            from evolu_tpu.sync import native_crypto

            # Fully-fused receive: protobuf parse + decrypt +
            # columnarization in one C call → PackedReceive, feeding
            # the worker's packed apply with zero per-row objects. Any
            # non-canonical shape → the object-path fused decoder →
            # the pure decoder (identical error surfaces down the
            # chain).
            packed = native_crypto.decrypt_response_columns(
                response_bytes, request.owner.mnemonic
            )
            if packed is not None:
                messages, merkle_tree = packed
            else:
                fused = native_crypto.decrypt_response(
                    response_bytes, request.owner.mnemonic
                )
                if fused is not None:
                    messages, merkle_tree = fused
                else:
                    response = protocol.decode_sync_response(response_bytes)
                    messages = decrypt_messages(response.messages, request.owner.mnemonic)
                    merkle_tree = response.merkle_tree
            # Its rows are the answer's messages, known only once it is
            # decoded: the interval is posted here, as the one record a
            # stage makes.
            anatomy.record_stage("sync_post", post_s, rows=len(messages),
                                 nbytes=len(response_bytes))
            metrics.inc("evolu_sync_responses_total")
            metrics.inc("evolu_sync_response_messages_total", len(messages))
            metrics.observe("evolu_sync_response_bytes", len(response_bytes),
                            buckets=metrics.SIZE_BUCKETS)
            log("sync:response", messages=len(messages), bytes=len(response_bytes))
            return (messages, merkle_tree, request.previous_diff)
        except Exception as e:  # noqa: BLE001
            self.on_error(UnknownError(e))
            return None


# Transport backoff policy. A sync POST is idempotent (INSERT OR
# IGNORE + pure diff), so retrying a 429/503 or a connection failure is
# always safe. Bounded: after the retries are spent, the original
# error surfaces — a 4xx/5xx to on_error (divergence must not be
# silent), a connection error to the offline/probe machinery (offline
# remains a normal state, not an error). Before this, one queue-full
# 503 from the relay's continuous-batching scheduler surfaced straight
# as UnknownError with no retry.
BACKOFF_RETRIES = 3
BACKOFF_BASE_S = 0.05
BACKOFF_MAX_S = 5.0
RETRYABLE_HTTP = (429, 503)


def _retry_after_seconds(error: urllib.error.HTTPError) -> Optional[float]:
    """Parse a Retry-After header: RFC 7231 delay-seconds (we also
    accept a float — our relay emits sub-second values for local
    deploys). HTTP-date form and garbage fall back to our own backoff
    schedule (None)."""
    raw = error.headers.get("Retry-After") if error.headers else None
    if raw is None:
        return None
    try:
        value = float(raw.strip())
    except ValueError:
        return None
    return value if value >= 0 else None


def _http_post(url: str, body: bytes, *, retries: int = BACKOFF_RETRIES,
               base_delay: float = BACKOFF_BASE_S, max_delay: float = BACKOFF_MAX_S,
               sleep=None, rng=None, headers: Optional[dict] = None) -> bytes:
    """POST with bounded exponential backoff + full jitter on 429/503
    (honoring Retry-After — the relay's backpressure contract) and on
    connection errors. `sleep`/`rng` are injectable for tests.
    `headers` (e.g. the traceparent trace-context hop, obs/trace.py)
    merge over the defaults — context rides HTTP headers only, the
    body bytes are never touched."""
    import random
    import time

    sleep = sleep or time.sleep
    rng = rng or random.random
    attempt = 0
    base_headers = {"Content-Type": "application/octet-stream"}
    if headers:
        base_headers.update(headers)
    while True:
        req = urllib.request.Request(
            url, data=body, headers=base_headers, method="POST"
        )
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                return resp.read()
        except urllib.error.HTTPError as e:
            if e.code not in RETRYABLE_HTTP or attempt >= retries:
                raise
            delay = _retry_after_seconds(e)
            if delay is None:
                # Full jitter: delay ∈ [0, base * 2^attempt] — the
                # standard de-synchronizer for a fleet of clients all
                # bounced by the same overloaded relay.
                delay = min(max_delay, base_delay * (2 ** attempt)) * rng()
            metrics.inc("evolu_sync_backoff_retries_total", reason=str(e.code))
            log("sync:request", "backoff retry", code=e.code, delay_s=round(delay, 4))
        except (urllib.error.URLError, OSError):
            if attempt >= retries:
                raise
            delay = min(max_delay, base_delay * (2 ** attempt)) * rng()
            metrics.inc("evolu_sync_backoff_retries_total", reason="connection")
        sleep(min(delay, max_delay))
        attempt += 1


def _ping_url(sync_url: str) -> str:
    """The relay's health endpoint (index.ts:250-252) lives at /ping on
    the same origin as the sync POST endpoint."""
    from urllib.parse import urlsplit, urlunsplit

    parts = urlsplit(sync_url)
    return urlunsplit((parts.scheme, parts.netloc, "/ping", "", ""))


def _http_ping(url: str) -> None:
    """One cheap GET — raises while offline, returns once reachable."""
    with urllib.request.urlopen(url, timeout=5) as resp:
        resp.read()


class _NoRedirect(urllib.request.HTTPRedirectHandler):
    """Surface 3xx as HTTPError instead of auto-following: the push
    loop must LEARN the placed relay from a 307's Location (and cache
    it), not pay a redirect hop on every poll."""

    def redirect_request(self, *a, **k):
        return None


_PUSH_OPENER = urllib.request.build_opener(_NoRedirect)


def _push_get(url: str, timeout: float) -> bytes:
    with _PUSH_OPENER.open(url, timeout=timeout) as resp:
        return resp.read()


class PushSubscriber:
    """The client half of relay-held push subscriptions (ISSUE 13,
    server/push.py): one daemon thread long-polls
    `GET /push/poll?owner&node&cursor` against the owner's placed
    relay and fires `on_wake` — typically `evolu.sync` — whenever the
    relay reports foreign-authored rows. The parked poll replaces the
    polling interval: mutation→visible becomes the push round trip.

    Robustness mirrors the sync transport's: at most one 307 follow
    per poll with the learned route cached (invalidated on 404/error/
    connection failure, failing back to the bound URL), bounded
    exponential backoff + full jitter while the relay is unreachable
    (offline is a normal state), cursor-resume across reconnects (the
    hub answers a conservative wake for a cursor its ring outgrew —
    a wakeup is never missed, ISSUE 13). `ensure` is idempotent and
    re-callable: every successful sync round re-binds the target, so
    the subscription follows fleet placement exactly as the sync leg
    does."""

    def __init__(self, config: Config, on_wake: Callable[[], None],
                 http_get: Optional[Callable[[str, float], bytes]] = None,
                 poll_timeout_s: Optional[float] = None):
        self.config = config
        self.on_wake = on_wake
        self._http_get = http_get or _push_get
        self._poll_timeout_s = (
            float(poll_timeout_s) if poll_timeout_s is not None
            else float(config.push_poll_timeout_s)
        )
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._owner: Optional[str] = None
        self._node: Optional[str] = None
        self._base: Optional[str] = None  # bound by ensure()
        self._route: Optional[str] = None  # learned via 307
        self._tags: Optional[Tuple[str, ...]] = None  # scope lanes
        self.cursor = 0
        self.wakes = 0  # total on_wake firings (tests/bench read it)

    def ensure(self, owner_id: str, node: str, url: str,
               tags: Optional[Tuple[str, ...]] = None) -> None:
        """Bind (or re-bind) the subscription; starts the loop thread
        on first call. Safe from any thread, idempotent. `tags` scopes
        the subscription to those lanes (sync/scope.py — None = wake on
        every foreign write, unchanged)."""
        with self._lock:
            self._owner, self._node = owner_id, node
            self._base = url.rstrip("/")
            self._tags = tuple(tags) if tags else None
            start = self._thread is None and not self._stop.is_set()
            if start:
                self._thread = threading.Thread(
                    target=self._loop, daemon=True, name="evolu-push")
                self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            # Bounded: the loop may be parked in a long poll; it is a
            # daemon thread that only touches the network.
            t.join(timeout=0.2)

    def _target(self) -> Tuple[str, str, str, Optional[Tuple[str, ...]]]:
        with self._lock:
            return (self._route or self._base, self._owner, self._node,
                    self._tags)

    def _loop(self) -> None:
        import json as _json
        import random

        delay = BACKOFF_BASE_S
        attempt = 0
        follows = 0  # consecutive 307s without a successful poll
        while not self._stop.is_set():
            base, owner, node, tags = self._target()
            url = (
                f"{base}/push/poll?owner={urllib.parse.quote(owner)}"
                f"&node={node}&cursor={self.cursor}"
                f"&timeout={self._poll_timeout_s}"
            )
            if tags:
                url += "&tags=" + urllib.parse.quote(",".join(tags))
            try:
                raw = self._http_get(url, self._poll_timeout_s + 10.0)
            except urllib.error.HTTPError as e:
                if e.code == 307:
                    location = e.headers.get("Location") if e.headers else None
                    follows += 1
                    if location and follows <= 1:
                        with self._lock:
                            self._route = urllib.parse.urljoin(
                                base + "/", location).split("/push/", 1)[0]
                        metrics.inc("evolu_push_client_redirects_total")
                        continue
                    # A SECOND consecutive 307 means the relays'
                    # rings disagree (mid-rebalance ping-pong, the
                    # sync transport's one-follow rule): drop the
                    # learned route and back off instead of spinning
                    # a hot redirect loop (review finding).
                    with self._lock:
                        self._route = None
                    if self._stop.wait(min(BACKOFF_MAX_S, delay)):
                        return
                    delay = min(BACKOFF_MAX_S, delay * 2)
                    follows = 0
                    continue
                if e.code in (429, 503):
                    # Flow control (hub full / relay shedding): honor
                    # Retry-After, degrade toward polling cadence.
                    ra = _retry_after_seconds(e)
                    if self._stop.wait(ra if ra is not None else
                                       min(BACKOFF_MAX_S, delay)):
                        return
                    delay = min(BACKOFF_MAX_S, max(delay * 2, BACKOFF_BASE_S))
                    continue
                # Definitive rejection (404: stale route or push-less
                # relay; 400): drop the learned route, fail back, and
                # back off — never spin.
                with self._lock:
                    self._route = None
                metrics.inc("evolu_push_client_errors_total")
                if self._stop.wait(min(BACKOFF_MAX_S, delay)):
                    return
                delay = min(BACKOFF_MAX_S, delay * 2)
                continue
            except Exception:  # noqa: BLE001 - offline: backoff + jitter
                with self._lock:
                    self._route = None
                metrics.inc("evolu_push_client_offline_total")
                jittered = min(BACKOFF_MAX_S,
                               BACKOFF_BASE_S * (2 ** attempt))
                if self._stop.wait(jittered * random.random() + 0.01):
                    return
                attempt = min(attempt + 1, 10)
                continue
            attempt = 0
            delay = BACKOFF_BASE_S
            follows = 0
            metrics.inc("evolu_push_client_polls_total")
            try:
                body = _json.loads(raw)
                cursor = int(body["cursor"])
                wake = bool(body["wake"])
            except (ValueError, KeyError, TypeError):
                metrics.inc("evolu_push_client_errors_total")
                if self._stop.wait(min(BACKOFF_MAX_S, delay)):
                    return
                delay = min(BACKOFF_MAX_S, delay * 2)
                continue
            # ADOPT the relay's cursor, never max() it: cursors are
            # per-hub sequence numbers, and a relay restart (or a
            # retarget to a different relay) legitimately answers a
            # SMALLER one. Clinging to the old epoch's larger value
            # would make qualifies() read fresh events as already-seen
            # — silently missed wakeups until the new hub's seq caught
            # up (review finding; the hub's cursor>seq conservative
            # wake is the server-side half of this fix).
            self.cursor = cursor
            if wake and not self._stop.is_set():
                self.wakes += 1
                metrics.inc("evolu_push_client_wakes_total")
                try:
                    self.on_wake()
                except Exception:  # noqa: BLE001 - the wake hook must
                    pass           # never kill the subscription loop


class PeriodicSyncer:
    """Timer analog of the reference's load/online/focus sync triggers
    (db.ts:390-412): posts a pull-only sync round every `interval`
    seconds until stopped."""

    def __init__(self, evolu, interval: float):
        self._evolu = evolu
        self._interval = interval
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True, name="evolu-autosync")
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(self._interval):
            try:
                self._evolu.sync(refresh_queries=False)
            except Exception:  # noqa: BLE001 — never kill the timer
                pass

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not threading.current_thread():
            self._thread.join()


def connect(evolu, config: Optional[Config] = None) -> SyncTransport:
    """Wire a client to its relay: transport → Evolu.receive, and
    Evolu's post_sync → transport (db.ts:134-156's channel setup).
    When the config sets `sync_interval`, a periodic pull starts too
    (stopped by `evolu.dispose()`)."""
    cfg = config or evolu.config

    def on_reconnect():
        # The reference's online listener re-syncs immediately
        # (db.ts:390-412); app listeners (the `online` event analog)
        # fire first so they observe the transition itself. The
        # disposed gate closes the straggler-probe race: stop() only
        # joins the prober for 0.2s, so a probe completing mid-dispose
        # may still invoke this hook.
        if getattr(evolu, "_disposed", False):
            return
        evolu._fire_reconnect()
        evolu.sync(refresh_queries=False)

    transport = SyncTransport(
        cfg,
        on_receive=evolu.receive,
        sync_lock=evolu.worker.sync_lock,
        on_error=lambda e: evolu._dispatch_output(OnError(e)),
        on_reconnect=on_reconnect,
    )
    if cfg.push_subscribe:
        # The push leg (ISSUE 13): wake-driven sync rounds instead of
        # a timer. A wake only means "foreign rows may exist" — the
        # sync round it triggers is the same anti-entropy round a
        # timer would fire, so correctness is unchanged and a spurious
        # wake costs one empty round.
        def on_push_wake():
            if getattr(evolu, "_disposed", False):
                return
            evolu.sync(refresh_queries=False)

        transport.push_subscriber = PushSubscriber(cfg, on_push_wake)
    evolu.attach_transport(transport)
    prev = getattr(evolu, "_auto_syncer", None)
    if prev is not None:
        prev.stop()
        evolu._auto_syncer = None
    if cfg.sync_interval:
        evolu._auto_syncer = PeriodicSyncer(evolu, cfg.sync_interval)
    return transport
