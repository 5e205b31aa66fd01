"""Relay↔relay replication: Merkle anti-entropy between relay peers.

No reference equivalent — the reference relay (apps/server, 258 LoC)
is a single node whose one SQLite file is the whole fleet. This module
turns N relays into a converging cluster using the primitive the
framework already owns: per-owner Merkle trees with base-3 minute keys
(`core/merkle.py`). Merkle-CRDTs (Sanjuán et al., arXiv:2004.00107)
and the anti-entropy literature make this the standard construction:
gossip tree digests, pull only from the diverged minute, and bandwidth
is proportional to DIVERGENCE, not to database size.

One gossip round against one peer:

1. `POST /replicate/summary` carrying MY owner→tree map; the response
   is the PEER's map. (The peer's handler also compares the incoming
   map against its own store and arms its manager's debounced hint on
   divergence, so healing propagates from both directions of a
   partition without waiting out either side's interval.)
2. Host-side `diff_merkle_trees` per owner whose serialized trees
   differ → the earliest diverged minute → a 46-char sync timestamp
   (`create_sync_timestamp`, the same range cursor the client sync
   path uses).
3. `POST /replicate/pull` with the (owner, since) list (chunked at
   `PULL_OWNERS_PER_REQUEST`); the peer answers every stored message
   after `since` per owner — NO node exclusion (a relay is not a
   message author) — plus its tree string at fetch time.
4. Ingest as ordinary `SyncRequest`s: through the PR-2 continuous-
   batching scheduler when the relay runs one (submitted concurrently
   so replication traffic COALESCES with live client traffic into the
   same fused `BatchReconciler.run_batch_wire` passes — one device
   pass covers a whole peer's diverged owner set via the engine's
   `deltas_dispatch`/`owner_minute_deltas` kernels), else through the
   per-request `serve_single_request` path. Either way the request's
   `merkle_tree` field carries the PEER's tree, so a fully-healed
   owner's response is empty — the serve leg stays divergence-bounded
   too. Idempotence is the store's own INSERT OR IGNORE + changes==1
   XOR gate: re-pulling an overlapping range can never double-XOR a
   tree.

Failure handling: offline peers get bounded exponential backoff with
jitter (the PR-2 client backoff shape — `sync/client.py` constants;
`_http_post` itself already retries 429/503/connection blips inside a
round), a per-peer health gauge, and automatic recovery on the first
successful round. The relay stays E2EE-blind throughout: rows are
(timestamp, userId, ciphertext), trees are digests of timestamps.

Observability (docs/OBSERVABILITY.md): rounds/failures/owners-diffed/
messages-pulled counters per (replica, peer), messages-served on the
answering side, a convergence-lag histogram (first divergence
observation → first fully-converged round), and a health gauge —
surfaced by `GET /metrics` and the `replication` section of
`GET /stats`.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

from evolu_tpu.core.merkle import diff_merkle_trees, merkle_tree_from_string
from evolu_tpu.core.timestamp import (
    SYNC_NODE_ID,
    create_sync_timestamp,
    iso_to_millis,
    timestamp_to_string,
)
from evolu_tpu.core.types import TimestampParseError
from evolu_tpu.obs import ledger, metrics, trace
from evolu_tpu.server.store import serve_single_request
from evolu_tpu.sync import aead, protocol
from evolu_tpu.sync.client import _accepts_headers
from evolu_tpu.utils.log import log

# One pull POST covers at most this many owners — bounds request bodies
# (the relay's 20 MB cap applies to peers too) without bounding a
# round's total coverage.
PULL_OWNERS_PER_REQUEST = 256

# serve_pull bounds what one response materializes: at most this many
# messages per owner (the EARLIEST of the range — ingesting them
# advances the diff minute, so the next round's pull resumes exactly
# where this one stopped) and per response in total (owners past the
# budget are omitted entirely). A truncated pull leaves the puller's
# tree differing from the peer's, which re-arms the post-pull hint —
# deep catch-ups proceed incrementally at debounce cadence instead of
# livelocking on one response too large to build or ship inside a
# socket timeout. The engine's batch-bucket shapes stay bounded too.
PULL_MESSAGES_PER_OWNER = 8192
PULL_MESSAGES_PER_RESPONSE = 65536


def owner_tree_map(store) -> List[Tuple[str, str]]:
    """Every owner the store knows, with its STORED tree text verbatim
    (no parse→re-dump; both sides write trees via
    `merkle_tree_to_string`, so string equality IS tree equality). ONE
    bulk query where the store offers it — per-owner reads are N+1
    SELECTs per round; the fallback serves generic stores."""
    if hasattr(store, "owner_trees"):
        return store.owner_trees()
    return [(u, store.get_merkle_tree_string(u)) for u in store.user_ids()]


def serve_summary(store, body: bytes, manager: Optional["ReplicationManager"],
                  origin=None) -> bytes:
    """Handler body for `POST /replicate/summary`: decode the caller's
    summary, arm the local manager's debounced hint if the caller
    advertises anything we diverge from (heal flows both ways), and
    answer with OUR summary. ONE store scan serves both the divergence
    check and the response. `origin` is the caller's trace context
    (obs/trace.py — the relay handler parses it off the traceparent
    header): a divergence-armed hint carries it forward so OUR next
    round records into the same fleet-wide convergence trace. Raises
    ValueError only on malformed input (the wire-decoder contract —
    the handler maps it to 400)."""
    incoming = protocol.decode_replica_summary(body)
    mine = owner_tree_map(store)
    if manager is not None:
        by_owner = dict(mine)
        # "{}" is what get_merkle_tree_string answers for an unseen
        # owner — an owner we lack entirely is divergence too.
        if any(by_owner.get(uid, "{}") != tree for uid, tree in incoming.trees):
            manager.hint(origin=origin)
    fleet = getattr(manager, "fleet", None) if manager is not None else None
    if fleet is not None and incoming.peer_url:
        # Placement-scoped answer (server/fleet.py): the caller told
        # us its URL — advertise only the owners placed on IT, so a
        # converged fleet's summary traffic is O(R), not O(fleet).
        # Owners WE store that belong to the caller are included even
        # if we are not placed for them: that is exactly how a stray
        # owner (written here mid-reload) drains to its placement.
        # An empty peer_url (pre-fleet peers, the bench's oracle
        # reads) still gets everything — interop unchanged.
        mine = [(uid, t) for uid, t in mine
                if fleet.placed_on(uid, incoming.peer_url)]
    return protocol.encode_replica_summary(
        protocol.ReplicaSummary(
            tuple(mine), manager.replica_id if manager is not None else "",
            fleet.self_url if fleet is not None else "",
        )
    )


def serve_pull(store, body: bytes, per_owner: Optional[int] = None,
               per_response: Optional[int] = None) -> bytes:
    """Handler body for `POST /replicate/pull`: ranged per-owner reads
    (strictly after `since`, every node's messages, earliest-first and
    capped — see PULL_MESSAGES_PER_OWNER) + the tree string at fetch
    time. Owners past the response budget are omitted; the puller's
    convergence check treats them as still-diverged and the next round
    resumes. The caps default to the module constants but are
    configurable per relay (`ReplicationManager(pull_messages_per_
    owner=..., pull_messages_per_response=...)` — the bench sweeps
    them honestly). ValueError only on malformed input."""
    cap_owner = PULL_MESSAGES_PER_OWNER if per_owner is None else int(per_owner)
    cap_resp = (
        PULL_MESSAGES_PER_RESPONSE if per_response is None else int(per_response)
    )
    req = protocol.decode_replica_pull(body)
    chunks = []
    served = 0
    for uid, since in req.pulls:
        if served >= cap_resp:
            break
        msgs = store.replica_messages(
            uid, since,
            min(cap_owner, cap_resp - served),
        )
        served += len(msgs)
        chunks.append(
            protocol.OwnerMessages(uid, msgs, store.get_merkle_tree_string(uid))
        )
    # Unlabeled on purpose: the wire `replica_id` is untrusted input —
    # minting a metric label per distinct value would let any caller
    # grow the registry without bound. Per-peer breakdowns live on the
    # PULLING side's counters, whose labels come from configuration.
    metrics.inc("evolu_repl_messages_served_total", served)
    return protocol.encode_replica_pull_response(protocol.ReplicaPullResponse(tuple(chunks)))


class _ManagerStopping(Exception):
    """Raised between a round's HTTP legs once stop() is underway: the
    round aborts promptly (idempotence makes a half-ingested round
    safe) instead of holding the loop thread through more socket
    timeouts while the server tears down."""


class _Peer:
    """Per-peer gossip state machine: due time, consecutive-failure
    count driving the bounded backoff, and the first-divergence mark
    feeding the convergence-lag histogram."""

    __slots__ = ("url", "failures", "next_due", "diverged_since")

    def __init__(self, url: str, now: float):
        self.url = url.rstrip("/")
        self.failures = 0
        self.next_due = now  # gossip immediately on start
        self.diverged_since: Optional[float] = None


class ReplicationManager:
    """Owns the gossip loop for one relay: a background thread runs a
    round against each peer when due (periodic `interval_s`, pulled
    earlier by `hint()` after local writes, pushed later by backoff
    after failures). `run_once()` runs one synchronous round against
    every peer on the calling thread — the unit-test / bench surface.

    `http_post` is injectable (fault-injection tests partition the
    cluster by raising from it); the default is the PR-2 client
    transport `sync.client._http_post` with `retries=0`: the
    round-level peer backoff owns ALL retry pacing — inner transport
    retries would multiply a black-holed peer's socket timeout on the
    single loop thread, head-of-line-blocking gossip to every healthy
    peer."""

    def __init__(
        self,
        store,
        peers: Sequence[str],
        replica_id: Optional[str] = None,
        scheduler=None,
        interval_s: float = 30.0,
        debounce_s: float = 0.05,
        backoff_base_s: Optional[float] = None,
        backoff_max_s: float = 30.0,
        http_post: Optional[Callable[[str, bytes], bytes]] = None,
        rng=None,
        pull_chunk: int = PULL_OWNERS_PER_REQUEST,
        pull_messages_per_owner: Optional[int] = None,
        pull_messages_per_response: Optional[int] = None,
        bootstrap_lag_owners: Optional[int] = None,
        snapshot_chunk_bytes: Optional[int] = None,
        write_behind=None,
        push_hub=None,
    ):
        import functools
        import random

        from evolu_tpu.sync.client import BACKOFF_BASE_S, _http_post
        from evolu_tpu.utils.config import default_config

        # Any knob left at None falls back to the process default_config
        # (utils/config.py) — one place to tune a whole fleet — and only
        # then to the module constants at serve time.
        if pull_messages_per_owner is None:
            pull_messages_per_owner = default_config.pull_messages_per_owner
        if pull_messages_per_response is None:
            pull_messages_per_response = default_config.pull_messages_per_response
        if bootstrap_lag_owners is None:
            bootstrap_lag_owners = default_config.bootstrap_lag_owners

        self.store = store
        self.scheduler = scheduler
        self.replica_id = replica_id or f"relay-{random.getrandbits(48):012x}"
        self.interval_s = float(interval_s)
        self.debounce_s = float(debounce_s)
        self.backoff_base_s = (
            BACKOFF_BASE_S if backoff_base_s is None else float(backoff_base_s)
        )
        self.backoff_max_s = float(backoff_max_s)
        self.pull_chunk = int(pull_chunk)
        # serve_pull caps this relay answers with (None = the module
        # defaults, read at serve time so tests can monkeypatch them).
        self.pull_messages_per_owner = pull_messages_per_owner
        self.pull_messages_per_response = pull_messages_per_response
        # Snapshot bootstrap (server/snapshot.py): None disables the
        # trigger entirely (incremental anti-entropy only — the PR-3
        # behavior and the default). An int N arms it: a peer whose
        # store is EMPTY, or that lacks >= N owners a donor advertises,
        # installs a full snapshot instead of crawling history through
        # capped pulls, then gossips from the manifest watermark.
        self.bootstrap_lag_owners = bootstrap_lag_owners
        self.snapshot_chunk_bytes = snapshot_chunk_bytes
        self._snapshot_cache = None
        self._snapshot_cache_lock = threading.Lock()
        self._post = http_post or functools.partial(_http_post, retries=0)
        self._rng = rng or random.random
        # Trace contexts of recent write hints (origin traces for the
        # fleet-wide convergence trace): drained by the next round,
        # bounded — a write burst keeps the newest few origins, which
        # is exactly what a debounced hint coalesces anyway.
        self._hint_origins: List = []
        # Owner-sharded fleet membership (server/fleet.py), attached by
        # RelayServer.enable_fleet: scopes summaries/pulls to placement
        # (O(R) gossip) and hands the snapshot path to the fleet's
        # owner-granular rebalance (the whole-store bootstrap trigger
        # stays off — a partitioned relay must never install every
        # owner of a donor).
        self.fleet = None
        # PR-11: with a write-behind queue on this relay, outbound
        # gossip summaries read the store directly (owner_trees) — a
        # round starts by draining so we only ever ADVERTISE committed
        # state (a tree advertised ahead of its rows would make peers
        # pull ranges the store cannot yet serve). PR-19: flush() is
        # the COMPOSED barrier — it waits out every shard's drain
        # worker, so the guarantee holds per shard.
        self.write_behind = write_behind
        # ISSUE 13: rows this manager ingests (anti-entropy pulls,
        # partition heals) are newly visible at THIS relay — parked
        # push subscriptions for those owners must wake exactly as for
        # a local client write (server/push.py; attached by
        # RelayServer alongside the hub).
        self.push_hub = push_hub
        now = time.monotonic()
        self._peers = [_Peer(u, now) for u in peers]
        self._swap_checked = False
        self._cv = threading.Condition()
        self._hint_at: Optional[float] = None
        self._stopping = False
        self._thread: Optional[threading.Thread] = None
        self._pool = None
        metrics.set_gauge("evolu_repl_peers", len(self._peers), replica=self.replica_id)
        for p in self._peers:
            metrics.set_gauge(
                "evolu_repl_peer_healthy", 1, replica=self.replica_id, peer=p.url
            )

    # -- lifecycle --

    def start(self) -> "ReplicationManager":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="evolu-replicate"
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        """Idempotent; joins the loop thread. `_post_checked` aborts an
        in-flight round at its next HTTP leg, so the join normally
        returns within one socket timeout. If a leg is still blocked
        past the timeout, the daemon thread is left to finish on its
        own — the pool is NOT torn from under it (`_ingest_pool`
        refuses new work while stopping), and a subsequent store close
        surfaces as a clean closed-database error inside `_round`'s
        failure handling, never a crash."""
        with self._cv:
            self._stopping = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=35.0)
            if self._thread.is_alive():
                log("server", "replication loop still blocked at stop; "
                    "leaving the daemon thread", replica=self.replica_id)
                return
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    def add_peer(self, url: str) -> None:
        """Register a peer after construction (mutual peering needs
        both relays' URLs, which only exist once both servers bind —
        tests, dynamic topologies, and fleet reloads use this).
        Idempotent under its own lock: racing registrations (two
        concurrent /fleet/reload pushes) must not gossip one peer
        twice per round forever. Gossips immediately."""
        with self._cv:
            if any(p.url == url.rstrip("/") for p in self._peers):
                return
            p = _Peer(url, time.monotonic())
            self._peers.append(p)
            metrics.set_gauge(
                "evolu_repl_peers", len(self._peers), replica=self.replica_id
            )
            metrics.set_gauge(
                "evolu_repl_peer_healthy", 1, replica=self.replica_id, peer=p.url
            )
            self._cv.notify()

    def hint(self, origin=None) -> None:
        """Debounced write hint: a burst of local writes (or a peer's
        summary showing divergence) coalesces into ONE early gossip
        sweep `debounce_s` after the first hint. Peers in failure
        backoff are NOT pulled forward — hints must not defeat the
        bounded backoff. `origin` (the hinting write's trace context,
        obs/trace.py) is remembered — bounded, deduped — so the round
        this hint arms records its spans into the SAME trace the
        client's mutation started: that is the fleet-wide convergence
        trace."""
        with self._cv:
            if self._stopping:
                return
            if origin is not None and origin.sampled:
                if not any(o.trace_id == origin.trace_id
                           for o in self._hint_origins):
                    self._hint_origins.append(origin)
                    del self._hint_origins[:-8]  # keep the newest 8
            if self._hint_at is None:
                self._hint_at = time.monotonic() + self.debounce_s
                metrics.inc("evolu_repl_hints_total", replica=self.replica_id)
                self._cv.notify()

    # -- the loop --

    def _loop(self) -> None:
        while True:
            with self._cv:
                due: List[_Peer] = []
                while not self._stopping:
                    now = time.monotonic()
                    if self._hint_at is not None and now >= self._hint_at:
                        self._hint_at = None
                        for p in self._peers:
                            if p.failures == 0:
                                p.next_due = now
                    due = [p for p in self._peers if p.next_due <= now]
                    if due:
                        break
                    wakes = [p.next_due for p in self._peers]
                    if self._hint_at is not None:
                        wakes.append(self._hint_at)
                    # Cap the sleep so a long interval (or an empty
                    # peer set — peers may be added later) still
                    # notices stop() promptly even without a notify.
                    wake_in = (min(wakes) - now) if wakes else 5.0
                    self._cv.wait(timeout=max(0.0, min(wake_in, 5.0)))
                if self._stopping:
                    return
            for p in due:
                with self._cv:
                    if self._stopping:
                        return
                self._round(p)

    def run_once(self) -> None:
        """One synchronous gossip round against every peer, on the
        calling thread (ignores due times; respects nothing else of the
        loop's pacing). Unit-test / bench / embedding surface."""
        for p in self._peers:
            self._round(p)

    @property
    def snapshot_cache(self):
        """Donor-side snapshot cache, built lazily (only relays whose
        peers actually bootstrap pay the capture memory). Lock-guarded:
        two peers' concurrent first /replicate/snapshot requests (the
        threaded HTTP server) must share ONE instance — a second
        instance would orphan the first peer's snapshot id mid-fetch
        and double the capture cost."""
        with self._snapshot_cache_lock:
            if self._snapshot_cache is None:
                from evolu_tpu.server.snapshot import (
                    SNAPSHOT_CHUNK_BYTES, SnapshotCache,
                )

                self._snapshot_cache = SnapshotCache(
                    self.store,
                    chunk_bytes=self.snapshot_chunk_bytes or SNAPSHOT_CHUNK_BYTES,
                )
            return self._snapshot_cache

    def _post_checked(self, url: str, body: bytes) -> bytes:
        """The round's transport, with a stop check before each leg —
        a multi-leg round against a black-holing peer must not hold
        stop() through every remaining socket timeout. Every leg counts
        one HTTP round-trip (the unit the snapshot-vs-anti-entropy
        acceptance ratio is asserted in)."""
        if self._stopping:
            raise _ManagerStopping()
        leg = url.rsplit("/replicate/", 1)[-1] if "/replicate/" in url else "other"
        metrics.inc(
            "evolu_repl_round_trips_total", replica=self.replica_id, leg=leg
        )
        # Each HTTP leg is a child span of the ambient round span and
        # carries its context as the traceparent header (headers only;
        # the peer wire bytes are untouched) — the serving peer's
        # repl.serve span joins the same convergence trace.
        lspan = trace.start_span(f"repl.{leg}", parent=trace.current())
        with lspan:
            hdrs = trace.inject_headers(ctx=lspan.context)
            # Header support is probed at CALL time (memoized per
            # callable): `_post` is swappable after construction
            # (fault injectors wrap it), and a 2-arg transport must
            # be served without the header rather than broken.
            if hdrs and _accepts_headers(self._post):
                return self._post(url, body, headers=hdrs)
            return self._post(url, body)

    def _finish_pending_swap_once(self) -> None:
        """A crash between shard swaps leaves a verified install half
        swapped in (phase=swap). `_bootstrap` would finish it, but the
        half-swapped live tables may advertise enough owners that the
        bootstrap trigger never fires again — so the FIRST round of any
        manager unconditionally finishes a pending swap. Probe via
        sqlite_master first: a store that never bootstrapped must not
        grow a state table just from being gossiped."""
        if self._swap_checked:
            return
        self._swap_checked = True
        try:
            shard0 = (getattr(self.store, "shards", None) or [self.store])[0]
            have = shard0.db.exec_sql_query(
                "SELECT name FROM sqlite_master WHERE type='table' "
                "AND name='snapshotBootstrapState'"
            )
            if not have:
                return
            from evolu_tpu.server import snapshot as snap

            inst = snap.SnapshotInstaller(self.store)
            st = inst.pending()
            if st is not None and st["phase"] == "swap":
                inst.finish_swap()
                metrics.inc(
                    "evolu_snap_installs_total", result="ok",
                    replica=self.replica_id, peer=st["peer"],
                )
                log("server", "finished stranded snapshot swap",
                    snapshot=st["snapshot_id"], peer=st["peer"])
        except Exception as e:  # noqa: BLE001 - recovery must never
            # block gossip; the pending state stays for the next try.
            self._swap_checked = False
            log("server", "pending snapshot swap check failed", error=repr(e))

    def _round(self, peer: _Peer) -> None:
        self._finish_pending_swap_once()
        labels = {"replica": self.replica_id, "peer": peer.url}
        # Drain the write-hint origins: the round span joins the FIRST
        # origin's trace (the convergence trace the client's mutation
        # started) and LINKS the rest — a span has one trace, extra
        # concurrent writes ride as fan-in links, exactly like the
        # scheduler's batch span. Origins are restored on failure so a
        # retried round still lands in the right trace.
        with self._cv:
            origins, self._hint_origins = self._hint_origins, []
        rspan = trace.start_span(
            "repl.round", parent=origins[0] if origins else None,
            links=origins[1:], attrs={"peer": peer.url},
        )
        try:
            with rspan, trace.use(rspan.context):
                if self.write_behind is not None:
                    # Advertise only committed state (see __init__). A
                    # drain failure lands in the round's failure
                    # handling — peer backoff, never a thread crash.
                    self.write_behind.flush()
                converged, pulled = self._gossip(peer)
        except _ManagerStopping:
            with self._cv:
                self._hint_origins = origins + self._hint_origins
                del self._hint_origins[:-8]
            return  # tearing down — not a peer failure
        except Exception as e:  # noqa: BLE001 - a peer failure must
            # never kill the loop: count, mark unhealthy, back off.
            with self._cv:
                self._hint_origins = origins + self._hint_origins
                del self._hint_origins[:-8]
            peer.failures += 1
            metrics.inc("evolu_repl_peer_failures_total", **labels)
            metrics.inc("evolu_repl_rounds_total", result="error", **labels)
            metrics.set_gauge("evolu_repl_peer_healthy", 0, **labels)
            # Bounded exponential backoff + jitter (the PR-2 shape):
            # delay ∈ [0.5, 1.0] × min(max, base·2^failures) — never
            # zero, so a dead peer cannot be hammered in a hot loop.
            delay = min(
                self.backoff_max_s, self.backoff_base_s * (2 ** min(peer.failures, 20))
            ) * (0.5 + 0.5 * self._rng())
            peer.next_due = time.monotonic() + delay
            log("server", "replication round failed", peer=peer.url,
                error=repr(e), failures=peer.failures, retry_s=round(delay, 3))
            return
        peer.failures = 0
        metrics.inc("evolu_repl_rounds_total", result="ok", **labels)
        metrics.set_gauge("evolu_repl_peer_healthy", 1, **labels)
        if converged and peer.diverged_since is not None:
            metrics.observe(
                "evolu_repl_convergence_lag_ms",
                (time.monotonic() - peer.diverged_since) * 1e3,
                exemplar=rspan.trace_id,
                **labels,
            )
            peer.diverged_since = None
        peer.next_due = time.monotonic() + self.interval_s
        if pulled:
            # Freshly pulled rows may need to travel further (chain
            # topologies — A↔B↔C with no A↔C edge): arm the debounced
            # hint so the next hop leaves at debounce latency, not
            # interval latency. A converged mesh pulls nothing, so the
            # hint chain terminates. The hint carries this round's
            # context so the next hop stays in the convergence trace.
            self.hint(origin=rspan.context)

    # -- one gossip round --

    def _gossip(self, peer: _Peer) -> Tuple[bool, int]:
        """Summary exchange → per-owner diff → ranged pull → ingest.
        → (converged, messages_pulled): converged is True when this
        round ends with every advertised owner byte-identical to the
        peer's snapshot (convergence for lag accounting; the peer may
        of course write more afterwards)."""
        labels = {"replica": self.replica_id, "peer": peer.url}
        local = dict(owner_tree_map(self.store))  # ONE bulk read
        send = local
        if self.fleet is not None:
            # Placement scope (server/fleet.py): advertise to this
            # peer only the owners placed on IT — including strays we
            # store but are not placed for (they drain to placement) —
            # and carry our URL so the peer scopes its answer the same
            # way. Gossip traffic drops from O(fleet) to O(R).
            send = {uid: t for uid, t in local.items()
                    if self.fleet.placed_on(uid, peer.url)}
        mine = protocol.ReplicaSummary(
            tuple(send.items()), self.replica_id,
            self.fleet.self_url if self.fleet is not None else "",
        )
        resp = protocol.decode_replica_summary(
            self._post_checked(peer.url + "/replicate/summary", protocol.encode_replica_summary(mine))
        )
        if self._should_bootstrap(local, resp.trees):
            if peer.diverged_since is None:
                peer.diverged_since = time.monotonic()
            installed = self._bootstrap(peer)
            # Not "converged" yet: the donor may have written past the
            # snapshot watermark — the nonzero return arms the hint so
            # the NEXT round diffs from the watermark at debounce
            # latency and pulls only the post-snapshot tail.
            return False, installed
        diverged: List[Tuple[str, str]] = []  # (owner, since)
        for uid, peer_tree_s in resp.trees:
            if self.fleet is not None and not self.fleet.placed_on(
                    uid, self.fleet.self_url):
                # Not ours to hold: never pull an owner we are not
                # placed for (a scoping peer won't advertise one, but
                # the wire is untrusted — enforce locally too).
                continue
            # Compare and diff the SAME bulk snapshot — no per-owner
            # re-reads (N+1 on a converged mesh), and no chance of
            # diffing a different tree than the one compared. A local
            # write landing mid-round at worst re-pulls rows the
            # ingest's INSERT OR IGNORE already holds — idempotent.
            local_s = local.get(uid, "{}")
            if local_s == peer_tree_s:
                continue
            diff = diff_merkle_trees(
                merkle_tree_from_string(local_s),
                merkle_tree_from_string(peer_tree_s),
            )
            if diff is None:
                continue  # hash-equal roots; nothing to pull
            diverged.append((uid, timestamp_to_string(create_sync_timestamp(diff))))
        if not diverged:
            return True, 0
        if peer.diverged_since is None:
            peer.diverged_since = time.monotonic()
        metrics.inc("evolu_repl_owners_diffed_total", len(diverged), **labels)
        log("server", "replication divergence", peer=peer.url, owners=len(diverged))

        peer_tree_at_pull = {}
        requests: List[protocol.SyncRequest] = []
        freshness: dict = {}  # owner -> newest pulled HLC millis
        pulled = 0
        for i in range(0, len(diverged), self.pull_chunk):
            chunk = diverged[i : i + self.pull_chunk]
            pull = protocol.ReplicaPull(tuple(chunk), self.replica_id)
            pr = protocol.decode_replica_pull_response(
                self._post_checked(peer.url + "/replicate/pull", protocol.encode_replica_pull(pull))
            )
            for om in pr.chunks:
                peer_tree_at_pull[om.user_id] = om.merkle_tree
                pulled += len(om.messages)
                if om.messages:
                    # The peer's tree rides as the request's client
                    # tree: once ingest makes our tree equal it, the
                    # serve diff is None and the (discarded) response
                    # is empty — the serve leg stays divergence-bounded.
                    requests.append(
                        protocol.SyncRequest(
                            om.messages, om.user_id, SYNC_NODE_ID, om.merkle_tree
                        )
                    )
                    try:
                        # Messages arrive timestamp-ordered; the last
                        # one's HLC millis is the owner's watermark.
                        # Rows already carry the clock — no new clocks,
                        # no wire change. Non-canonical timestamps just
                        # skip the gauge (they still ingest through
                        # the host-oracle route like always) —
                        # iso_to_millis raises TimestampParseError on
                        # them, which must never abort the round.
                        freshness[om.user_id] = max(
                            freshness.get(om.user_id, 0),
                            iso_to_millis(om.messages[-1].timestamp[:24]),
                        )
                    except (ValueError, TimestampParseError):
                        pass
        metrics.inc("evolu_repl_messages_pulled_total", pulled, **labels)
        ispan = trace.start_span(
            "repl.ingest", parent=trace.current(),
            attrs={"peer": peer.url, "owners": len(requests),
                   "messages": pulled},
        )
        with ispan:
            self._ingest(requests)
        # The convergence plane (ISSUE 10): per-(owner, peer)
        # freshness watermarks — the newest HLC millis this replica
        # has SEEN from that peer per owner — and the end-to-end
        # write→visible-at-this-replica lag, measured from the HLC
        # millis the rows already carry against this host's wall
        # clock. Gauges are data-labeled, so the registry's
        # label-cardinality bound is what keeps them finite.
        now_ms = time.time() * 1e3
        for uid, newest in freshness.items():
            metrics.set_gauge(
                "evolu_conv_owner_freshness_millis", newest,
                replica=self.replica_id, peer=peer.url, owner=uid,
            )
            metrics.observe(
                "evolu_conv_write_visible_ms", max(0.0, now_ms - newest),
                exemplar=ispan.trace_id,
                replica=self.replica_id, peer=peer.url,
            )
        converged = all(
            self.store.get_merkle_tree_string(uid)
            == peer_tree_at_pull.get(uid, object())
            for uid, _since in diverged
        )
        return converged, pulled

    # -- snapshot bootstrap (server/snapshot.py) --

    def _should_bootstrap(self, local: dict, advertised) -> bool:
        """Arm the O(state) cold-start instead of O(history) capped
        pulls: the local store is empty, or it lacks BOTH at least
        `bootstrap_lag_owners` owners the peer advertises AND the
        majority of the advertised set (a relay restored from an old
        disk). The majority clause keeps routine fleet growth on the
        incremental path: one new owner appearing on a converged
        100-owner mesh is a ranged pull, never a full-store
        re-snapshot, whatever the threshold. None disables (PR-3
        behavior). A FLEET member never whole-store bootstraps: its
        moves are owner-granular through the fleet rebalance
        (server/fleet.py) — installing a donor's full snapshot would
        un-partition the tier."""
        if self.fleet is not None:
            return False
        if self.bootstrap_lag_owners is None or not advertised:
            return False
        if not local:
            return True
        unknown = sum(1 for uid, _t in advertised if uid not in local)
        # max(1, ·): a converged mesh has unknown == 0 and must never
        # re-bootstrap, whatever the configured threshold.
        return (unknown >= max(1, self.bootstrap_lag_owners)
                and unknown * 2 > len(advertised))

    def bootstrap_from(self, peer_url: str) -> int:
        """Run one snapshot bootstrap against `peer_url` on the calling
        thread (the unit-test / bench / operator surface — `run_once`'s
        analog). Returns the number of message rows installed."""
        return self._bootstrap(_Peer(peer_url, time.monotonic()))

    def _bootstrap(self, peer: _Peer) -> int:
        """Manifest → resumable chunk fetches → crash-consistent
        install → golden-parity verify → atomic swap. The chunk
        watermark lives in the STORE (snapshotBootstrapState), so a
        SIGKILL anywhere in the fetch loop resumes from the last
        committed chunk without re-transferring completed ones; a
        donor-side snapshot expiry (HTTP 400 on the chunk leg) drops
        the stale install and the next round restarts fresh.

        With a write-behind queue the whole bootstrap runs behind its
        `drain_barrier` (review finding): the swap replaces shard
        contents, and a record ACKed against the PRE-swap tree base
        would later drain its stale tree string over the installed
        one — permanent tree/message divergence. The barrier makes the
        window airtight, not just drained-at-entry: it clears the
        serve-time tree cache, so any concurrent serve's base-tree
        read blocks on `db_lock` until the swap is complete and then
        reads post-swap truth. (Coarse — whole-store bootstrap is a
        cold-start/operator event, same tradeoff as the fleet owner
        move.)"""
        if self.write_behind is not None:
            with self.write_behind.drain_barrier():
                return self._bootstrap_locked(peer)
        return self._bootstrap_locked(peer)

    def _bootstrap_locked(self, peer: _Peer) -> int:
        import urllib.error

        from evolu_tpu.server import snapshot as snap

        labels = {"replica": self.replica_id, "peer": peer.url}
        inst = snap.SnapshotInstaller(self.store)
        t0 = time.perf_counter()
        manifest, start = None, 0
        st = inst.pending()
        if st is not None and st["phase"] == "swap":
            # Died between shard swaps: finish (idempotent), done — the
            # data was fully verified before the swap began, and the
            # swap is peer-independent (WHICHEVER peer this round
            # targets, aborting would strand already-swapped shards on
            # the snapshot and throw away verified data).
            inst.finish_swap()
            metrics.observe(
                "evolu_snap_install_ms", (time.perf_counter() - t0) * 1e3
            )
            metrics.inc("evolu_snap_installs_total", result="ok", **labels)
            return 0
        if st is not None and st["peer"] != peer.url:
            with self._cv:
                known = any(p.url == st["peer"] for p in self._peers)
            if known:
                # The watermark belongs to ANOTHER configured peer
                # (multi-peer mesh, first round after a crash happened
                # to target a different donor): resume against the
                # original donor instead of discarding completed
                # chunks — only IT still serves this snapshot id.
                peer = _Peer(st["peer"], time.monotonic())
                labels = {"replica": self.replica_id, "peer": peer.url}
            else:
                inst.abort()  # an unconfigured peer's stale install
                st = None
        if st is not None:
            manifest, start = st["manifest"], st["next_chunk"]
            if start:
                metrics.inc("evolu_snap_resumes_total", **labels)
                log("server", "snapshot bootstrap resuming", peer=peer.url,
                    snapshot=manifest.snapshot_id, next_chunk=start,
                    chunks=len(manifest.chunk_sizes))
        if manifest is None:
            body = protocol.encode_snapshot_request(
                protocol.SnapshotRequest(
                    self.replica_id, self.snapshot_chunk_bytes or 0
                )
            )
            manifest = protocol.decode_snapshot_manifest(
                self._post_checked(peer.url + "/replicate/snapshot", body)
            )
            inst.begin(manifest, peer.url)
            log("server", "snapshot bootstrap starting", peer=peer.url,
                snapshot=manifest.snapshot_id, owners=len(manifest.owners),
                rows=manifest.message_count, bytes=manifest.total_bytes,
                chunks=len(manifest.chunk_sizes))
        try:
            for i in range(start, len(manifest.chunk_sizes)):
                req = protocol.encode_snapshot_chunk_request(
                    protocol.SnapshotChunkRequest(
                        manifest.snapshot_id, i, self.replica_id
                    )
                )
                try:
                    raw = self._post_checked(
                        peer.url + "/replicate/snapshot/chunk", req
                    )
                except urllib.error.HTTPError as e:
                    if e.code == 400:
                        # The donor no longer serves this snapshot id:
                        # the persisted watermark is worthless — drop it
                        # so the next round begins a fresh bootstrap.
                        inst.abort()
                        metrics.inc(
                            "evolu_snap_installs_total", result="expired", **labels
                        )
                    raise
                chunk = protocol.decode_snapshot_chunk(raw)
                if (chunk.snapshot_id != manifest.snapshot_id
                        or chunk.index != i
                        or len(chunk.payload) != manifest.chunk_sizes[i]
                        or chunk.crc != manifest.chunk_crcs[i]):
                    raise snap.SnapshotInstallError(
                        f"snapshot chunk {i}: response does not match the "
                        "manifest (id/index/size/crc)"
                    )
                inst.install_chunk(i, chunk.payload,
                                   expected_crc=manifest.chunk_crcs[i])
                metrics.inc("evolu_snap_chunks_fetched_total", **labels)
                metrics.inc(
                    "evolu_snap_bytes_fetched_total", len(chunk.payload), **labels
                )
            inst.verify(manifest)
        except (_ManagerStopping, urllib.error.URLError, OSError):
            # Transport interruptions keep the watermark: resume next
            # round without re-transferring completed chunks.
            raise
        except snap.SnapshotInstallError:
            # Integrity failure: the shipped bytes are not trustworthy —
            # drop everything and refetch fresh. Live tables untouched.
            inst.abort()
            metrics.inc("evolu_snap_installs_total", result="error", **labels)
            raise
        inst.swap()
        metrics.observe(
            "evolu_snap_install_ms", (time.perf_counter() - t0) * 1e3
        )
        metrics.inc("evolu_snap_installs_total", result="ok", **labels)
        log("server", "snapshot bootstrap installed", peer=peer.url,
            snapshot=manifest.snapshot_id, rows=manifest.message_count,
            owners=len(manifest.owners))
        if self.push_hub is not None:
            # A whole-store install changed arbitrarily many owners at
            # once: per-row attribution is gone, so wake everything —
            # the changed-set contract's "don't know escalates" rule.
            self.push_hub.notify_all(reason="conservative")
        return manifest.message_count

    def _ingest(self, requests: List[protocol.SyncRequest]) -> None:
        """Apply pulled messages through the relay's OWN serving paths
        (never a raw insert — the changes==1 Merkle gate and the
        non-canonical host-oracle routing must apply to replication
        exactly as to clients). With a scheduler the requests are
        submitted CONCURRENTLY so the dispatcher coalesces them — with
        each other and with live client traffic — into fused
        `run_batch_wire` engine passes; without one they take the
        per-request path handler threads use."""
        if not requests:
            return
        n_v2 = sum(aead.count_v2(r.messages) for r in requests)
        if n_v2:
            # Peer pulls carry stored ciphertext verbatim — an
            # aead-batch-v1 record replicates as opaquely as an OpenPGP
            # one (never re-encrypted, never downgraded per hop). This
            # counter is how an operator confirms v2 traffic actually
            # crossing the replication surface (docs/OBSERVABILITY.md).
            metrics.inc("evolu_crypto_v2_replicated_messages_total", n_v2)
        if self.scheduler is not None:
            futures = [
                self._ingest_pool().submit(self.scheduler.submit, r) for r in requests
            ]
            first_err: Optional[BaseException] = None
            served = []
            for r, f in zip(requests, futures):
                e = f.exception()
                if e is None:
                    served.append(r)
                first_err = first_err or e
            # Notify BEFORE re-raising: the requests that DID commit
            # made rows visible, and their subscribers must wake even
            # when a batchmate failed (review finding — the raise used
            # to skip the notify for all of them).
            self._notify_push(served)
            self._ledger_ingress(served)
            if first_err is not None:
                raise first_err
            return
        served = []
        try:
            for r in requests:
                serve_single_request(self.store, r)
                served.append(r)
        finally:
            self._notify_push(served)
            self._ledger_ingress(served)

    @staticmethod
    def _ledger_ingress(served: List[protocol.SyncRequest]) -> None:
        """Ledger ingress for pulled messages that the serve path
        actually landed: the serve posted their store terminals (the
        relay's own paths — changes==1 gate and all), so only
        SUCCESSFULLY served requests ingress. A failed submit posted
        neither side, and the next round's re-pull is a fresh delivery
        attempt."""
        for r in served:
            ledger.count(ledger.INGRESS_REPLICATION, len(r.messages),
                         owner=r.user_id)

    def _notify_push(self, requests: List[protocol.SyncRequest]) -> None:
        """Wake parked push subscriptions for rows replication just
        landed (AFTER the serve committed them). The pulled messages'
        plaintext timestamps carry the ORIGINAL author nodes, so the
        hub's own-write exclusion still holds across relays — a
        subscriber never wakes for rows it authored, whichever relay
        they arrive through."""
        if self.push_hub is None:
            return
        for r in requests:
            if r.messages:
                self.push_hub.notify(
                    r.user_id, [m.timestamp for m in r.messages],
                    reason="replication",
                )

    def _ingest_pool(self):
        if self._stopping:
            # Never mint a fresh executor during teardown: stop() has
            # (or will have) shut the pool down, and a new one here
            # would leak.
            raise _ManagerStopping()
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._pool = ThreadPoolExecutor(
                max_workers=8, thread_name_prefix="evolu-repl-ingest"
            )
        return self._pool

    # -- observability --

    def stats_payload(self) -> dict:
        """The `replication` section of GET /stats: per-peer health +
        the per-(replica, peer) counters from the process registry."""
        peers = []
        for p in self._peers:
            labels = {"replica": self.replica_id, "peer": p.url}
            peers.append({
                "url": p.url,
                "healthy": p.failures == 0,
                "failures": p.failures,
                "rounds_ok": metrics.get_counter(
                    "evolu_repl_rounds_total", result="ok", **labels
                ),
                "rounds_error": metrics.get_counter(
                    "evolu_repl_rounds_total", result="error", **labels
                ),
                "owners_diffed": metrics.get_counter(
                    "evolu_repl_owners_diffed_total", **labels
                ),
                "messages_pulled": metrics.get_counter(
                    "evolu_repl_messages_pulled_total", **labels
                ),
                "convergence_lag_p99_ms": metrics.quantile(
                    "evolu_repl_convergence_lag_ms", 0.99, **labels
                ),
                "snapshot_bootstraps": metrics.get_counter(
                    "evolu_snap_installs_total", result="ok", **labels
                ),
                "snapshot_chunks_fetched": metrics.get_counter(
                    "evolu_snap_chunks_fetched_total", **labels
                ),
                "snapshot_bytes_fetched": metrics.get_counter(
                    "evolu_snap_bytes_fetched_total", **labels
                ),
            })
        return {
            "replica_id": self.replica_id,
            "peers": peers,
            # Donor-side snapshot service (unlabeled — served to
            # whoever asked, like messages_served).
            "snapshot": {
                "captures": metrics.get_counter("evolu_snap_captures_total"),
                "capture_rows": metrics.get_counter(
                    "evolu_snap_capture_rows_total"
                ),
                "capture_bytes": metrics.get_counter(
                    "evolu_snap_capture_bytes_total"
                ),
                "manifests_served": metrics.get_counter(
                    "evolu_snap_manifests_served_total"
                ),
                "chunks_served": metrics.get_counter(
                    "evolu_snap_chunks_served_total"
                ),
                "chunk_bytes_served": metrics.get_counter(
                    "evolu_snap_chunk_bytes_served_total"
                ),
                "checkpoints": metrics.get_counter(
                    "evolu_snap_checkpoints_total"
                ),
                "install_p99_ms": metrics.quantile("evolu_snap_install_ms", 0.99),
            },
        }
