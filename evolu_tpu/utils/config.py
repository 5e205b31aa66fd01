"""Runtime configuration (reference: packages/evolu/src/config.ts).

Unlike the reference's mutable module singleton, config is passed
explicitly to the runtime (`create_evolu(schema, config=Config(...))`);
a module-level default exists for parity with `setConfig`.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Tuple, Union


@dataclass
class Config:
    sync_url: str = "http://localhost:4000"
    log: Union[bool, str, List[str]] = False
    max_drift: int = 60000  # config.ts:9
    reload_url: str = "/"
    # TPU-native extensions (no reference equivalent):
    # Periodic pull interval in seconds (None = only explicit sync()).
    # The reference syncs on load/online/focus browser events
    # (db.ts:390-412); a headless process needs a timer instead.
    sync_interval: "float | None" = None
    backend: str = "auto"  # "cpu" | "tpu" | "auto" — merge kernel backend
    # Receive batches above this size apply blockwise (bounded device
    # and transaction memory; the Merkle tree and clock persist per
    # chunk, so a mid-sync crash resumes instead of replaying).
    # None = whole-batch transactions always (reference semantics).
    receive_chunk_size: "int | None" = 1 << 20
    min_device_batch: int = 1024  # below this, the CPU oracle path is faster than dispatch
    # A single-owner batch at/above this size shards by CELL RANGES over
    # every local device (parallel/hot_owner.py) instead of planning on
    # one device — the "hot owner" path (SURVEY.md §5). Only engages
    # when >1 device is visible. None disables.
    hot_owner_min_batch: "int | None" = 1 << 18
    # LWW plan formulation (ops/scatter_merge.py): "sort" = the r5
    # sort+scan pipeline, "scatter" = the dense scatter-argmax plan,
    # "auto" = by backend (scatter on CPU where it measured up to ~13×
    # faster at 1M rows; sort on TPU where the recorded cost model
    # prices serialized scatters/gathers far above one sort —
    # docs/BENCHMARKS.md r6). EVOLU_MERGE_PLAN overrides.
    merge_plan: str = "auto"
    # Keep per-cell stored winners HBM-resident across batches
    # (ops/winner_cache.py) instead of streaming them from SQLite per
    # batch — ~+30% steady-state end-to-end on the config-2 shape on
    # the CPU backend (benchmarks/winner_cache.py); on the attached
    # chip: not measured.
    # Ignored for backend "cpu".
    winner_cache: bool = True
    # Wire-protocol extension fields 6 (double) / 7 (int64) beyond the
    # reference's string|int32 value oneof (protobuf.proto:5-13).
    # False = strict interop: AUTHORING such a value raises at mutation
    # time (before it enters the log) instead of later producing a
    # field a reference TS peer would silently drop. Remote messages
    # always relay verbatim, and reference-range traffic is
    # byte-identical either way.
    wire_extensions: bool = True
    # Wire capabilities advertised in every sync request (field 5 —
    # sync/protocol.py capability extension, ISSUE 7). The relay echoes
    # the intersection with its own set; () sends the v1 wire
    # byte-identically. `crdt-types-v1` / `crdt-list-v1` /
    # `crdt-tensor-v1` (ISSUEs 7, 14, 20) are advisory (typed CRDT ops
    # are E2EE-opaque and relay through v1 peers unchanged; the echo
    # only SURFACES fleet support). `aead-batch-v1` (ISSUE 8, sync/aead.py)
    # GATES emission: only after a relay echoes it does the client send
    # session-keyed GCM records instead of per-message OpenPGP — the
    # ~10× crypto-ceiling lift (docs/WIRE_V2.md). Every client of this
    # framework DECODES v2 records unconditionally; drop the capability
    # here for owners shared with reference OpenPGP.js peers, which
    # cannot (the same interop dial as wire_extensions).
    # `sync-scope-v1` (ISSUE 18, sync/scope.py) likewise GATES
    # emission: a scope clause (Config.sync_scope) rides the wire only
    # after the relay echoes it — an unscoped or unnegotiated round
    # stays byte-identical to v1.
    sync_capabilities: Tuple[str, ...] = (
        "crdt-types-v1", "crdt-list-v1", "crdt-tensor-v1",
        "aead-batch-v1", "sync-scope-v1")
    # Partial replication (ISSUE 18, sync/scope.py::SyncScope): the
    # slice of the owner's log this client converges on — an HLC-millis
    # watermark ("recent history only") and/or a table filter (opaque
    # HMAC lanes on the wire). None = full replica (everything
    # unchanged). Out-of-scope rows land in the log but skip
    # materialization; queries touching them raise ScopeDeferred
    # (honest partial answers, runtime/worker.py); widen the scope to
    # escalate. Narrowing an established scope is unsupported.
    sync_scope: "object | None" = None
    # -- relay fleet knobs (no reference equivalent). These are LIVE
    # defaults: `RelayServer` / `ReplicationManager` resolve any
    # constructor arg left at None from the process `default_config`
    # (set_config before constructing relays), so embedders can tune a
    # fleet in one place without threading kwargs everywhere. --
    # serve_pull response budgets: at most this many messages per owner
    # and per response in one anti-entropy pull answer. None = the
    # server defaults (8192 / 65536, `replicate.PULL_MESSAGES_PER_*`).
    # Smaller values bound gossip-round latency; the snapshot-bootstrap
    # bench sweeps them honestly (benchmarks/snapshot_bootstrap.py).
    pull_messages_per_owner: "int | None" = None
    pull_messages_per_response: "int | None" = None
    # Snapshot bootstrap trigger (server/snapshot.py): a relay whose
    # store is empty — or lacking at least this many owners a peer
    # advertises — installs a full snapshot instead of crawling history
    # through capped pulls. None disables (incremental-only, the PR-3
    # behavior).
    bootstrap_lag_owners: "int | None" = None
    # Periodic local snapshot checkpoints for crash-consistent fast
    # restart (RelayServer(checkpoint_interval_s=...) →
    # snapshot.CheckpointWriter). None disables.
    checkpoint_interval_s: "float | None" = None
    # Changed-set-gated incremental query invalidation (ISSUE 9,
    # runtime/worker.py::_query × storage/deps.py × storage/changes.py):
    # subscribed queries whose read tables are disjoint from a
    # mutation's changed set skip re-execution entirely, and queries
    # with a static `"id" = ?` constraint skip row-disjoint writes.
    # Patch streams are byte-identical to the re-run-everything path
    # (conservative full invalidation on every "don't know"); False
    # restores the reference's unconditional re-execution.
    query_invalidation: bool = True
    # Bound on the worker's per-query caches (rows/raw bytes/dependency
    # index/seen-epoch): least-recently-executed entries are evicted
    # past this many distinct queries, so churned one-shot query
    # strings cannot grow the worker without bound. An evicted-but-
    # still-subscribed query self-heals on its next run via a
    # root-replace patch (correct against any client state). None =
    # unbounded (the pre-r9 behavior).
    query_cache_max: "int | None" = 32768
    # PR-11 storage inversion (storage/write_behind.py): serve sync
    # responses and Merkle answers from device-derived in-memory state
    # and demote SQLite to a bounded async write-behind materializer
    # drained off the serving path. Opt-in (default OFF — every
    # existing byte-identity pin stays on the synchronous path until
    # the torture bar is green in a deployment); EVOLU_WRITE_BEHIND=1
    # overrides at the relay. Durability floor: fsync'd record log +
    # exact idempotent replay (docs/WRITE_BEHIND.md).
    write_behind: bool = False
    # Admission bound for the write-behind queue (rows). Queue-full
    # stalls admission via the scheduler's 503 + Retry-After path —
    # never drops. ~150 bytes/row in-memory for typical ciphertexts.
    write_behind_max_rows: int = 1 << 20
    # Drain transaction sizing (rows per btree commit).
    write_behind_drain_rows: int = 1 << 16
    # PR-19 parallel owner-sharded drain: worker count for the
    # write-behind drain (0 = one worker per storage shard, the
    # default; clamped to the shard count; workers own shards
    # round-robin). Owners never share rows and LWW merge commutes, so
    # per-shard transactions need no cross-shard ordering — the end
    # state stays byte-identical at any worker count.
    # EVOLU_WB_DRAIN_WORKERS overrides at the relay.
    wb_drain_workers: int = 0
    # Delegate each drain worker's shard transactions to a child
    # process (storage/_wb_shard_proc.py) instead of running them on
    # the worker thread. Only honest for pure-Python FILE-BACKED
    # shards (the sqlite3 leg holds the GIL; the native C leg already
    # drops it, so threads scale there) — anything else falls back to
    # threads with a logged warning. EVOLU_WB_DRAIN_PROCESS=1
    # overrides at the relay.
    wb_drain_process: bool = False
    # PR-12 mesh-sharded engine (parallel/mesh.py::MeshContext): one
    # pjit/shard_map pass reconciles every owner across the device mesh
    # with STABLE owner->device placement (crc32, like the fleet ring)
    # instead of per-batch LPT, so device-resident per-owner state
    # (sharded winner-cache slot arrays, write-behind serving trees fed
    # from sharded deltas) stays placement-consistent across batches.
    # Default OFF until the parity gate (benchmarks/mesh_engine.py,
    # tests/test_mesh_engine.py: responses + SQLite end state
    # byte-identical to the single-device engine) is green in a
    # deployment; EVOLU_MESH_ENGINE=1 overrides at the relay.
    mesh_engine: bool = False
    # Cap the mesh at this many devices (None = all visible). The
    # placement hash is computed over the CAPPED size, so changing it
    # re-places owners (fine: the engine holds no per-owner device
    # state that outlives a batch without the cache-reset hooks).
    mesh_devices: "int | None" = None
    # After a swallowed offline sync failure, probe the relay's
    # GET /ping starting at this cadence in seconds (backing off 2x per
    # failure up to 30s); the first success fires the reconnect hook
    # and an immediate pull round — the headless analog of the
    # reference's online/focus re-sync listeners (db.ts:390-412).
    # None disables probing.
    reconnect_probe_interval: "float | None" = 1.0
    # PR-13 connection tier (server/conn.py): "threaded" = the
    # reference-shaped ThreadingHTTPServer (one thread per connection,
    # the default and every pin's baseline until event-loop parity is
    # proven in a deployment); "eventloop" = one selectors loop owns
    # every socket, complete requests run on a BOUNDED handler pool,
    # and push long-polls park the bare connection — 10^4-10^5 idle
    # subscriptions cost file descriptors, not threads.
    # EVOLU_CONN_TIER overrides at the relay.
    connection_tier: str = "threaded"
    # Event-tier bounds (flow control + slow-client hardening — see
    # docs/PUSH.md): handler-pool size (the only threads request
    # handling ever uses), in-flight dispatch bound past which the
    # loop sheds 503 + Retry-After itself, the ABSOLUTE budget a
    # request must fully arrive within (slowloris can't trickle past
    # it), the no-progress write stall budget, and the header cap
    # (431 past it).
    conn_handler_threads: int = 8
    conn_max_pending: int = 512
    conn_read_timeout_s: float = 30.0
    conn_write_timeout_s: float = 30.0
    conn_max_header_bytes: int = 16384
    # PR-13 push subscriptions (server/push.py): relay-held long-poll
    # subscriptions woken by a mutation's changed set at the
    # granularity E2EE exposes (owner + author-node row metadata) —
    # mutation→client-visible drops from the polling interval to the
    # push round trip. Relay default-on (a new GET endpoint, zero
    # effect on existing responses); push_subscribe wires the CLIENT
    # leg in connect(): wake-driven sync rounds instead of (or on top
    # of) the sync_interval timer.
    push_subscriptions: bool = True
    push_subscribe: bool = False
    push_poll_timeout_s: float = 25.0
    push_max_subscriptions: int = 1 << 17


default_config = Config()


def set_config(c: Config) -> None:
    global default_config
    default_config = c


@dataclass(frozen=True)
class FleetConfig:
    """Shared fleet placement configuration (server/fleet.py — no
    reference equivalent; the reference relay is a single node).

    Every relay in a fleet must hold the SAME FleetConfig: the
    owner→relay placement ring is a pure function of (relays,
    virtual_nodes, replication_factor, seed), so agreement on this
    object IS agreement on who serves whom. Distribution is static
    config (constructor arg or `POST /fleet/reload`), deliberately not
    a consensus protocol: a fleet is operated, membership changes are
    deploys. `version` is a monotonic operator counter so a relay can
    refuse a stale reload racing a newer one."""

    relays: Tuple[str, ...]  # member base URLs (the ring membership)
    replication_factor: int = 2  # R: replicas (incl. primary) per owner
    virtual_nodes: int = 64  # ring points per relay (placement smoothness)
    seed: int = 0  # shared hash seed — all members must agree
    version: int = 0  # monotonic config generation (reload ordering)
    # Routing mode for a request landing on a non-placed relay:
    # False = 307 redirect carrying the authoritative peer URL (the
    # client follows and caches the route — sync/client.py); True =
    # proxy-forward through the relay (one extra hop, but works for
    # clients that cannot follow redirects).
    forward: bool = False

    def __post_init__(self):
        object.__setattr__(
            self, "relays", tuple(u.rstrip("/") for u in self.relays)
        )

    def to_json(self) -> dict:
        return {
            "relays": list(self.relays),
            "replication_factor": self.replication_factor,
            "virtual_nodes": self.virtual_nodes,
            "seed": self.seed,
            "version": self.version,
            "forward": self.forward,
        }

    @classmethod
    def from_json(cls, d: dict) -> "FleetConfig":
        """Decode a `/fleet/reload` body. Raises ValueError on any
        malformed shape (the relay maps it to HTTP 400, matching the
        wire-decoder contract)."""
        try:
            raw = d["relays"]
            # A bare string iterates character-by-character into a ring
            # of one-character "URLs" — an easy templating mistake that
            # would 200 and then 307 every request to nonsense. Demand
            # a real list.
            if isinstance(raw, (str, bytes)) or not isinstance(raw, (list, tuple)):
                raise ValueError('fleet config "relays" must be a list of URLs')
            relays = tuple(str(u) for u in raw)
            if not relays:
                raise ValueError("fleet config needs at least one relay")
            if len(relays) > 1024:
                raise ValueError(f"fleet config lists {len(relays)} relays "
                                 "(max 1024)")
            vnodes = int(d.get("virtual_nodes", 64))
            if not 1 <= vnodes <= 4096:
                # The ring builds relays × vnodes hash points; an
                # absurd value from a reload body is a CPU/memory DoS,
                # not a tuning choice.
                raise ValueError(
                    f"virtual_nodes={vnodes} outside 1..4096")
            return cls(
                relays=relays,
                replication_factor=int(d.get("replication_factor", 2)),
                virtual_nodes=vnodes,
                seed=int(d.get("seed", 0)),
                version=int(d.get("version", 0)),
                forward=bool(d.get("forward", False)),
            )
        except (KeyError, TypeError) as e:
            raise ValueError(f"malformed fleet config: {e!r}") from e
