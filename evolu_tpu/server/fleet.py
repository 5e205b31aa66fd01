"""Owner-sharded relay fleet: placement ring, routing, rebalancing.

No reference equivalent — the reference relay (apps/server, 258 LoC)
is a single node. PRs 1-5 built every piece of a multi-relay tier
(metrics, batching scheduler, Merkle anti-entropy, snapshot
bootstrap), but the replication topology was still FULL: every relay
gossiped every owner to every peer, O(fleet) traffic and O(fleet)
storage per relay. This module composes the pieces into a fleet that
*partitions* owners across relays:

* **Placement ring** — a deterministic hash ring over owner ids with
  virtual nodes (`HashRing`): every relay holding the same
  `FleetConfig` (utils/config.py — relays, replication factor R,
  vnodes, seed) computes the same owner→[primary, replica, ...]
  placement with no coordination. Merkle-CRDTs (arXiv:2004.00107)
  makes per-owner tree summaries exactly the unit that keeps
  placement-scoped anti-entropy sound; replication-factor-bounded
  propagation is the standard escape from O(fleet) gossip
  (arXiv:2310.18220 §replication).

* **Request routing** — a sync POST landing on a non-placed relay is
  answered with `307 + Location: <authoritative relay>` (the client
  follows once and caches the owner→relay route, sync/client.py) or
  proxy-forwarded through `POST /fleet/forward` (`FleetConfig.
  forward=True`; the envelope's hop guard means a forwarded request is
  NEVER forwarded again — ring disagreement during a reload degrades
  to local service + gossip heal, not a cycle). A down primary fails
  over to the next ring replica, gated on a readiness probe
  (`GET /health`, cached briefly).

* **Scoped replication** — `ReplicationManager` with a fleet attached
  sends each peer only the owners placed on that peer (the summary
  carries our own URL so the peer scopes its answer the same way) and
  pulls only owners placed on itself: gossip drops from O(fleet) to
  O(R), and stray owners (written to the wrong relay mid-reload)
  drain to their placement instead of replicating everywhere.

* **Snapshot-driven rebalancing** — a ring change (join/leave via
  `POST /fleet/reload`, a static config push) makes the gaining relay
  bootstrap the moved owners from the losing relay's PR-5 snapshot:
  manifest → crc-checked chunks → owner-filtered install through the
  store's own changes==1 XOR gate → per-owner cutover at the Merkle
  watermark (the manifest's root-hash + tree-crc digests). An owner
  being installed answers 503 + Retry-After ("not ready") and only
  starts being served once its recomputed tree matches the watermark;
  writes ACKed by the loser after capture heal through scoped gossip
  (the loser keeps its copy and remains a summary source). Failure
  anywhere degrades to incremental anti-entropy — never data loss.

The relay stays E2EE-blind throughout; placement hashes opaque owner
ids. That blindness is also what makes the `aead-batch-v1` wire
(docs/WIRE_V2.md) fleet-safe with NO code here: negotiation binds a
(client, relay) pair per hop, relays never re-encrypt, and every fleet
surface — hop-guarded forwards, scoped peer pulls, snapshot chunks,
rebalance installs — carries stored ciphertext verbatim, so v1 and v2
records cross the fleet identically. The one hop that matters is
client→serving-relay: on a forward the SERVING relay computes the
capability echo (it decodes the forwarded body, `relay._do_fleet_
forward` → `_serve_request`), so a client talking through a
forwarding front-end negotiates with the relay that actually stores
its rows; on failover the client re-encodes v2 rounds as v1 itself
(sync/client.py::retarget — a relay that didn't advertise never
receives v2). Observability: the `evolu_fleet_*` families
(docs/OBSERVABILITY.md) + a `fleet` section under `GET /stats`; the
ingest wire-format mix shows up per serving relay as
`evolu_crypto_v{1,2}_relay_messages_total`.

`python -m evolu_tpu.server.fleet` runs one fleet relay process (the
unit `benchmarks/fleet_scaling.py` multiplies into N-process fleets).
"""

from __future__ import annotations

import bisect
import hashlib
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

from evolu_tpu.obs import ledger, metrics
from evolu_tpu.server.store import RelayStore
from evolu_tpu.sync import protocol
from evolu_tpu.utils.config import FleetConfig
from evolu_tpu.utils.log import log

# How long one readiness probe result is trusted. Short: failover
# freshness beats probe savings (a probe is one local-network GET);
# long enough that a burst of requests for one owner pays one probe.
PROBE_TTL_S = 1.0
# What a "busy" (owner mid-install / no ready replica) answer tells
# the client to wait before retrying — the same Retry-After contract
# as the scheduler's backpressure 503.
NOT_READY_RETRY_S = 0.25


def _h64(data: str, seed: int) -> int:
    """Stable 64-bit ring coordinate. blake2b, not crc32: placement
    quality is balance, and 32-bit crc collisions across vnode points
    are not rare at fleet scale. Seeded so disjoint fleets sharing a
    wire never agree on placement by accident."""
    return int.from_bytes(
        hashlib.blake2b(
            f"{seed}|{data}".encode("utf-8"), digest_size=8
        ).digest(),
        "big",
    )


class HashRing:
    """Consistent-hash placement: owner id → an ordered tuple of R
    distinct relay URLs (primary first). Pure function of the
    FleetConfig — every member computes identical placement, and a
    membership change moves only the owners whose arc changed
    (~moved_fraction ≈ joined/total, the consistent-hashing property
    the rebalance bench leans on)."""

    def __init__(self, config: FleetConfig):
        self.config = config
        relays: List[str] = []
        for u in config.relays:
            if u not in relays:  # dedupe, order-preserving
                relays.append(u)
        self.relays = tuple(relays)
        points: List[Tuple[int, str]] = []
        for url in self.relays:
            for v in range(max(1, config.virtual_nodes)):
                points.append((_h64(f"relay|{url}#{v}", config.seed), url))
        points.sort()
        self._points = [p for p, _u in points]
        self._urls = [u for _p, u in points]
        self._r = max(1, min(config.replication_factor, len(self.relays)))

    def placement(self, owner_id: str) -> Tuple[str, ...]:
        """The R distinct relays for `owner_id`, primary first —
        clockwise walk from the owner's ring coordinate."""
        if not self._points:
            return ()
        h = _h64(f"owner|{owner_id}", self.config.seed)
        i = bisect.bisect_right(self._points, h)
        out: List[str] = []
        n = len(self._points)
        for k in range(n):
            url = self._urls[(i + k) % n]
            if url not in out:
                out.append(url)
                if len(out) == self._r:
                    break
        return tuple(out)

    def primary(self, owner_id: str) -> str:
        return self.placement(owner_id)[0]


class FleetNotReady(Exception):
    """The owner is placed here but mid-install (or no placed relay is
    ready): the relay answers 503 + Retry-After — flow control, like
    the scheduler's backpressure, never an error count."""

    def __init__(self, retry_after: float = NOT_READY_RETRY_S):
        super().__init__(f"owner not ready; retry after {retry_after}s")
        self.retry_after = retry_after


class FleetManager:
    """One relay's view of the fleet: the ring, its own URL, the
    owner-readiness set, the rebalance machinery, and the health
    probe cache. Attach to a RelayServer with `enable_fleet` — the
    handler consults `route()` per sync POST; the ReplicationManager
    reads `placed_on()` to scope gossip."""

    def __init__(self, store, config: FleetConfig, self_url: str,
                 replication=None, http_post=None, http_get=None,
                 probe_ttl_s: float = PROBE_TTL_S, write_behind=None):
        import functools

        from evolu_tpu.sync.client import _http_post

        self.store = store
        self.self_url = self_url.rstrip("/")
        self.replication = replication
        # PR-11: the rebalance installer is a direct store writer; on a
        # write-behind relay each owner move runs behind the queue's
        # drain barrier (drained + drain-locked across EVERY shard
        # worker since PR-19 — coarse, but owner moves are operator
        # events, and the moved owners are FleetNotReady during the
        # install so no serving-path state races them). Backlog-driven readiness lives in the relay's
        # /health handler: a saturated backlog answers 503, so peer
        # failover and the rebalance readiness probe route around it.
        self.write_behind = write_behind
        self._post = http_post or functools.partial(_http_post, retries=0)
        self._get = http_get or _http_get_status
        self._probe_ttl_s = float(probe_ttl_s)
        self._lock = threading.RLock()
        self._installing: set = set()  # owners mid-rebalance (not served)
        self._probe_cache: Dict[str, Tuple[float, bool]] = {}
        self._rebalance_serial = threading.Lock()  # one rebalance at a time
        self._threads: List[threading.Thread] = []
        self._stopping = False
        self._manifest_owners: Optional[Tuple] = None  # last install's watermarks
        self.config: Optional[FleetConfig] = None
        self.ring: Optional[HashRing] = None
        self.apply_config(config, rebalance=False)

    # -- placement queries --

    def placement(self, owner_id: str) -> Tuple[str, ...]:
        return self.ring.placement(owner_id)

    def placed_on(self, owner_id: str, url: str) -> bool:
        return url.rstrip("/") in self.ring.placement(owner_id)

    def is_primary(self, owner_id: str) -> bool:
        return self.ring.primary(owner_id) == self.self_url

    # -- request routing --

    def route(self, owner_id: str) -> Tuple[str, Optional[str]]:
        """→ ("local", None) | ("redirect"|"forward", peer_url).
        Raises FleetNotReady for an owner placed here but mid-install
        (serve-after-cutover is the zero-lost-writes gate) or placed
        nowhere ready. Non-placed requests go to the first placed
        relay whose readiness probe passes — a down primary fails over
        to the next ring replica; if NO probe passes, the primary is
        still named (the client's own retry/backoff may outlive a
        probe-window blip)."""
        placement = self.ring.placement(owner_id)
        if self.self_url in placement:
            with self._lock:
                if owner_id in self._installing:
                    metrics.inc("evolu_fleet_not_ready_total")
                    raise FleetNotReady()
            return ("local", None)
        mode = "forward" if self.config.forward else "redirect"
        for url in placement:
            if self._peer_serving(url):
                if url != placement[0]:
                    metrics.inc("evolu_fleet_failovers_total")
                return (mode, url)
        if not placement:
            return ("local", None)
        if mode == "redirect":
            # Name the primary anyway: the CLIENT pays the retry, and
            # its own backoff may outlive a probe-window blip.
            return (mode, placement[0])
        # Forward mode would make THIS relay synchronously POST to a
        # known-down peer — each request would pin a handler thread
        # through the transport timeouts. Shed instead; the next
        # route() re-probes.
        metrics.inc("evolu_fleet_not_ready_total")
        raise FleetNotReady()

    def _peer_serving(self, url: str) -> bool:
        now = time.monotonic()
        with self._lock:
            hit = self._probe_cache.get(url)
            if hit is not None and hit[0] > now:
                return hit[1]
        try:
            serving = self._get(url + "/health") == 200
        except Exception:  # noqa: BLE001 - unreachable peer = not serving
            serving = False
        with self._lock:
            self._probe_cache[url] = (now + self._probe_ttl_s, serving)
        return serving

    # -- health / observability --

    def installing_owners(self) -> int:
        with self._lock:
            return len(self._installing)

    def health_payload(self) -> Tuple[bool, dict]:
        """→ (serving, detail). NOT serving while a PR-5 whole-store
        snapshot install is pending (its phase marker persists across
        crashes) or any owner is mid-rebalance — fleet failover and
        the bench must never route to a relay mid-install."""
        from evolu_tpu.server.snapshot import install_phase

        phase = install_phase(self.store)
        n_inst = self.installing_owners()
        serving = phase is None and n_inst == 0
        return serving, {
            "status": "serving" if serving else "installing",
            "install_phase": phase,
            "installing_owners": n_inst,
            "ring_version": self.config.version,
            "members": len(self.ring.relays),
        }

    def stats_payload(self) -> dict:
        owners = self.store.user_ids()
        placed = [u for u in owners if self.placed_on(u, self.self_url)]
        primary = [u for u in placed if self.is_primary(u)]
        metrics.set_gauge("evolu_fleet_owners", len(placed))
        metrics.set_gauge("evolu_fleet_primary_owners", len(primary))
        return {
            "self_url": self.self_url,
            "ring_version": self.config.version,
            "members": list(self.ring.relays),
            "replication_factor": self.ring._r,
            "owners_stored": len(owners),
            "owners_placed": len(placed),
            "owners_primary": len(primary),
            "installing_owners": self.installing_owners(),
            "redirects": metrics.get_counter("evolu_fleet_redirects_total"),
            "forwards": metrics.get_counter("evolu_fleet_forwards_total"),
            "forwarded_served": metrics.get_counter(
                "evolu_fleet_forwarded_served_total"
            ),
            "reloads": metrics.get_counter("evolu_fleet_reloads_total"),
            "rebalanced_owners": metrics.get_counter(
                "evolu_fleet_rebalanced_owners_total"
            ),
            "rebalanced_messages": metrics.get_counter(
                "evolu_fleet_rebalanced_messages_total"
            ),
            "cutovers_verified": metrics.get_counter(
                "evolu_fleet_cutover_verified_total"
            ),
            "cutovers_superset": metrics.get_counter(
                "evolu_fleet_cutover_superset_total"
            ),
            "failovers": metrics.get_counter("evolu_fleet_failovers_total"),
            "rebalance_failures": metrics.get_counter(
                "evolu_fleet_rebalance_failures_total"
            ),
        }

    # -- config reload + rebalance --

    def apply_config(self, config: FleetConfig, rebalance: bool = True) -> bool:
        """Install a new fleet config (the `/fleet/reload` body). A
        stale generation (version < current) raises ValueError — the
        caller answers 400, so a racing old push cannot roll the ring
        back. Re-pushing the CURRENT config is "reconcile": no ring
        change, but the rebalance sweep still runs (idempotent — one
        scoped summary per peer when nothing moved), which is how a
        joining relay pulls its owners once the REST of the fleet has
        reloaded (peers scope summaries by THEIR ring, so a sweep
        before they reload sees nothing). Returns True when a
        rebalance was started."""
        with self._lock:
            changed = True
            if self.config is not None:
                if config.version < self.config.version:
                    raise ValueError(
                        f"stale fleet config version {config.version} "
                        f"< current {self.config.version}"
                    )
                if config == self.config:
                    changed = False
                elif config.version == self.config.version:
                    # Two DIFFERENT configs at one version would
                    # split-brain the ring (members install whichever
                    # push landed last). Content changes require a
                    # strictly newer generation; same-version re-push
                    # of the identical config (reconcile) is the only
                    # equal-version accept.
                    raise ValueError(
                        f"conflicting fleet config at version "
                        f"{config.version}: content changes need a "
                        "strictly newer version"
                    )
                else:
                    metrics.inc("evolu_fleet_reloads_total")
            if changed:
                self.config = config
                self.ring = HashRing(config)
                self._probe_cache.clear()
                metrics.set_gauge("evolu_fleet_ring_version", config.version)
                metrics.set_gauge("evolu_fleet_members", len(self.ring.relays))
        # New members become gossip peers (add_peer is idempotent
        # under the manager's own lock and gossips new ones
        # immediately); departed members' scoped summaries go empty on
        # their own, so stale peers are harmless.
        if changed and self.replication is not None:
            for url in self.ring.relays:
                if url != self.self_url:
                    self.replication.add_peer(url)
        if not rebalance:
            return False
        t = threading.Thread(
            target=self._rebalance, name="evolu-fleet-rebalance", daemon=True
        )
        with self._lock:
            if self._stopping:
                return False
            self._threads = [x for x in self._threads if x.is_alive()]
            self._threads.append(t)
        t.start()
        return True

    def stop(self) -> None:
        with self._lock:
            self._stopping = True
            threads = list(self._threads)
        for t in threads:
            t.join(timeout=35.0)

    # -- snapshot-driven owner moves --

    def rebalance_once(self) -> int:
        """Run one synchronous rebalance sweep on the calling thread
        (the unit-test / bench / operator surface — `run_once`'s
        analog). Serialized with any background reload-triggered sweep
        — two concurrent sweeps would share `_manifest_owners` and
        could unmark each other's mid-install owners. Returns the
        number of owners installed."""
        with self._rebalance_serial:
            return self._sweep()

    def _rebalance(self) -> None:
        with self._rebalance_serial:  # serialize racing reloads
            try:
                self._sweep()
            except Exception as e:  # noqa: BLE001 - a failed rebalance
                # degrades to incremental anti-entropy, never a crash.
                metrics.inc("evolu_fleet_rebalance_failures_total")
                log("server", "fleet rebalance failed", error=repr(e))

    def _sweep(self) -> int:
        """For each peer: ask for the owners it stores that are placed
        on US (the scoped summary), and snapshot-install the ones we
        lack entirely. Owners we already store heal through normal
        scoped gossip — the snapshot path is for whole-owner moves."""
        moved_total = 0
        for peer_url in list(self.ring.relays):
            if peer_url == self.self_url or self._stopping:
                continue
            try:
                moved_total += self._pull_moved_owners(peer_url)
            except Exception as e:  # noqa: BLE001 - per-peer isolation:
                # one unreachable loser must not block gains from the
                # others; its owners stay with it until it comes back.
                metrics.inc("evolu_fleet_rebalance_failures_total")
                log("server", "fleet rebalance peer failed",
                    peer=peer_url, error=repr(e))
        if self.replication is not None and moved_total:
            # Post-capture donor writes heal at debounce latency.
            self.replication.hint()
        return moved_total

    def _pull_moved_owners(self, peer_url: str) -> int:
        # 1. What does the peer store that belongs to me? An EMPTY
        # summary with our URL: the peer's scoped answer enumerates
        # exactly the owners placed on us — no full-store enumeration.
        body = protocol.encode_replica_summary(
            protocol.ReplicaSummary((), self._replica_id(), self.self_url)
        )
        resp = protocol.decode_replica_summary(
            self._post(peer_url + "/replicate/summary", body)
        )
        local = set(self.store.user_ids())
        gained = sorted(
            uid for uid, _tree in resp.trees
            if uid not in local and self.placed_on(uid, self.self_url)
        )
        if not gained:
            return 0
        with self._lock:
            if self._stopping:
                return 0
            self._installing.update(gained)
        t0 = time.perf_counter()
        try:
            from contextlib import nullcontext

            barrier = (
                self.write_behind.drain_barrier()
                if self.write_behind is not None else nullcontext()
            )
            with barrier:
                installed_msgs, shipped_trees = self._install_from_snapshot(
                    peer_url, set(gained)
                )
        except BaseException:
            # Nothing (or a prefix) landed — all of it through the
            # idempotent XOR gate, so partial installs are safe state.
            # Unmark: route() serves what we have; scoped gossip pulls
            # the rest incrementally.
            with self._lock:
                self._installing.difference_update(gained)
            raise
        # 2. Cutover at the per-owner Merkle watermark: an owner only
        # starts being served once its recomputed tree is byte-equal
        # to the donor's capture-time watermark. A concurrent gossip
        # ingest can only ADD rows (INSERT OR IGNORE), so a mismatch
        # here means a SUPERSET of the snapshot — safe to serve, but
        # counted separately (the bench asserts clean cutovers).
        by_owner = {uid: (root, crc) for uid, root, crc in
                    self._manifest_owners or []}
        import zlib as _z

        for uid in gained:
            shipped = shipped_trees.get(uid, "")
            now_tree = self.store.get_merkle_tree_string(uid)
            root_crc = by_owner.get(uid)
            exact = (
                shipped and now_tree == shipped and root_crc is not None
                and _z.crc32(shipped.encode("utf-8")) == root_crc[1]
            )
            metrics.inc(
                "evolu_fleet_cutover_verified_total" if exact
                else "evolu_fleet_cutover_superset_total"
            )
            with self._lock:
                self._installing.discard(uid)
        metrics.inc("evolu_fleet_rebalanced_owners_total", len(gained))
        metrics.inc("evolu_fleet_rebalanced_messages_total", installed_msgs)
        metrics.observe(
            "evolu_fleet_rebalance_ms", (time.perf_counter() - t0) * 1e3
        )
        log("server", "fleet rebalance installed owners", peer=peer_url,
            owners=len(gained), messages=installed_msgs)
        return len(gained)

    def _install_from_snapshot(self, peer_url: str, wanted: set):
        """Owner-scoped manifest → chunk fetches → owner-filtered
        ingest through `store.add_messages` (the changes==1 XOR gate —
        trees stay exact digests of the installed rows, and
        re-installs are idempotent). The request names the moved
        owners so the donor ships O(moved owners), not its whole
        store; the record filter below still applies — a pre-fleet
        donor ignores the owner field and ships everything. →
        (message_count, {owner: shipped tree text})."""
        from evolu_tpu.server import snapshot as snap

        manifest = protocol.decode_snapshot_manifest(
            self._post(
                peer_url + "/replicate/snapshot",
                protocol.encode_snapshot_request(
                    protocol.SnapshotRequest(
                        self._replica_id(), 0, tuple(sorted(wanted))
                    )
                ),
            )
        )
        self._manifest_owners = manifest.owners
        shipped_trees: Dict[str, str] = {}
        installed = 0
        for i in range(len(manifest.chunk_sizes)):
            if self._stopping:
                raise RuntimeError("fleet manager stopping mid-rebalance")
            raw = self._post(
                peer_url + "/replicate/snapshot/chunk",
                protocol.encode_snapshot_chunk_request(
                    protocol.SnapshotChunkRequest(
                        manifest.snapshot_id, i, self._replica_id()
                    )
                ),
            )
            chunk = protocol.decode_snapshot_chunk(raw)
            if (chunk.snapshot_id != manifest.snapshot_id
                    or chunk.index != i
                    or len(chunk.payload) != manifest.chunk_sizes[i]
                    or chunk.crc != manifest.chunk_crcs[i]):
                raise snap.SnapshotInstallError(
                    f"fleet rebalance chunk {i}: response does not match "
                    "the manifest (id/index/size/crc)"
                )
            by_owner: Dict[str, List[protocol.EncryptedCrdtMessage]] = {}
            for rec in snap.iter_records(chunk.payload):
                if rec[0] == "M" and rec[2] in wanted:
                    by_owner.setdefault(rec[2], []).append(
                        protocol.EncryptedCrdtMessage(rec[1], rec[3])
                    )
                elif rec[0] == "T" and rec[1] in wanted:
                    shipped_trees[rec[1]] = rec[2]
            for uid, msgs in by_owner.items():
                self.store.add_messages(uid, msgs)
                # Ledger ingress: rebalance-installed rows arrive as
                # snapshot chunks; add_messages above posted their
                # store terminals through its changes==1 gate.
                ledger.count(ledger.INGRESS_SNAPSHOT, len(msgs), owner=uid)
                installed += len(msgs)
        return installed, shipped_trees

    def _replica_id(self) -> str:
        if self.replication is not None:
            return self.replication.replica_id
        return f"fleet:{self.self_url}"


def _http_get_status(url: str, timeout: float = 2.0) -> int:
    """One readiness probe GET → the HTTP status (an ANSWERED non-200
    — e.g. 503 mid-install — is 'not serving', not 'unreachable')."""
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status
    except urllib.error.HTTPError as e:
        return e.code


# -- one fleet relay process (the benchmarks/fleet_scaling.py unit) --


def _worker_main(argv: Optional[Sequence[str]] = None) -> None:
    """Run ONE fleet relay as its own process: store + RelayServer +
    scoped replication + FleetManager. The bench spawns N of these —
    plain subprocesses like MultiprocessRelay's workers (no fork of
    jax state, no multiprocessing-spawn re-import of __main__).

    Without `--batching` a fleet worker serves the per-request store
    path and never initialises a JAX backend. With it, the worker's
    scheduler builds the device engine on its first batch — so it
    needs a device of its own: a chip belongs to ONE process, and N
    batching workers on one chip fail or hang. The launcher decides
    (benchmarks/fleet_scaling.py pins its children to the CPU)."""
    import argparse
    import json
    import signal

    # The relay sits above this module (it builds the FleetManager).
    from evolu_tpu.server.relay import RelayServer

    ap = argparse.ArgumentParser(description="one evolu fleet relay process")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--path", default=":memory:")
    ap.add_argument("--self-url", required=True)
    ap.add_argument("--config-json", required=True,
                    help="FleetConfig.to_json() of the shared fleet config")
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--replication-interval-s", type=float, default=1.0)
    ap.add_argument("--batching", action="store_true")
    args = ap.parse_args(argv)

    cfg = FleetConfig.from_json(json.loads(args.config_json))
    store = RelayStore(args.path, args.backend)
    peers = [u for u in cfg.relays if u != args.self_url.rstrip("/")]
    server = RelayServer(
        store, host=args.host, port=args.port, batching=args.batching,
        peers=peers, replication_interval_s=args.replication_interval_s,
    )
    # Fleet BEFORE start(): the replication loop's first round fires
    # immediately on start, and it must already be placement-scoped —
    # an unscoped first round against a big donor would pull owners
    # this member is not placed for.
    server.enable_fleet(cfg, self_url=args.self_url)
    server.start()
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_a: stop.set())
    print("READY", flush=True)  # the parent waits for listen()
    try:
        stop.wait()
    except KeyboardInterrupt:
        pass
    server.stop()


if __name__ == "__main__":  # pragma: no cover - subprocess entry
    _worker_main()
