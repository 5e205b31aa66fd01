"""The relay's storage: message log + per-owner Merkle trees in SQLite.

Reference: apps/server/src/index.ts:60-216 — same storage shape
(index.ts:64-75), same sync pipeline (index.ts:204-216), same
own-message exclusion (`timestamp NOT LIKE '%' || nodeId`,
index.ts:100). The store is E2EE-blind: rows are (timestamp, userId,
ciphertext).

The lowest box of `server/`: `storage/native.py` ← this module ←
`engine.py` ← `scheduler.py` ← `relay.py` (HTTP, options, lifecycle),
each importing only what is left of it
(tests/test_import_hygiene.py holds the arrows). Nothing here needs a
socket, `http.server` or jax: a relay process that only stores and
serves never loads a backend.

`add_messages` keeps the reference's per-row insert (it needs per-row
rowcount for the changes==1 Merkle gate) but aggregates tree updates
into one delta pass; the batched many-owner path lives in
`evolu_tpu.server.engine.BatchReconciler`, which reads `packed` to
choose its ingest.
"""

from __future__ import annotations

import threading
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

from evolu_tpu.core.merkle import (
    apply_prefix_xors,
    diff_merkle_trees,
    merkle_tree_from_string,
    merkle_tree_to_string,
    minutes_base3,
)
from evolu_tpu.core.murmur import to_int32
from evolu_tpu.core.timestamp import (
    create_sync_timestamp,
    timestamp_from_string,
    timestamp_to_hash,
    timestamp_to_string,
)
from evolu_tpu.core.types import NonCanonicalStoreError
from evolu_tpu.obs import ledger
from evolu_tpu.storage.native import CppSqliteDatabase, open_database
from evolu_tpu.storage.sqlite import configure_shared_file_db
from evolu_tpu.sync import protocol



# Per-thread serve scope (see serve_single_request): one pending entry
# + a first-wins classification latch per request, so (a) a serve that
# commits the store but fails BEFORE answering posts NOTHING — the
# relay's reject.invalid stays the request's single terminal — and
# (b) the NonCanonicalStoreError object-path fallback, which re-runs
# add_messages idempotently, cannot classify the same messages twice.
_SERVE_SCOPE = threading.local()


def _ledger_store_apply(user_id, new_flags) -> None:
    """Conservation-ledger terminal classification for the OBJECT store
    path (`RelayStore.add_messages`): per-row was-new flags are the
    changes==1 truth — new rows terminate at store.inserted, the rest
    at store.duplicate. Inside a serve scope the counts ride the
    scope's pending entry (committed only when the serve answers,
    first classification wins); outside one (engine sharded-python
    fallback, fleet rebalance install, direct embedder calls) they
    post immediately. ONE seam on purpose: the ledger's negative test
    (tests/test_ledger.py) mis-wires exactly this function to prove the
    audit catches a route that forgets to count."""
    n_new = ledger.flag_sum(new_flags)
    scope = getattr(_SERVE_SCOPE, "scope", None)
    if scope is not None:
        if scope["classified"]:
            return  # fallback re-insert re-classifies; first wins
        scope["classified"] = True
        scope["entry"].count(ledger.STORE_INSERTED, n_new, owner=user_id)
        scope["entry"].count(ledger.STORE_DUPLICATE,
                             len(new_flags) - n_new, owner=user_id)
        return
    ledger.count(ledger.STORE_INSERTED, n_new, owner=user_id)
    ledger.count(ledger.STORE_DUPLICATE, len(new_flags) - n_new,
                 owner=user_id)


def response_since(server_tree, client_tree) -> Optional[str]:
    """The serve rule's first half: the trees' diff as the `since`
    timestamp string `eh_get_messages_wire` compares stored rows
    with, or None where the trees agree (nothing to fetch)."""
    diff = diff_merkle_trees(server_tree, client_tree)
    if diff is None:
        return None
    return timestamp_to_string(create_sync_timestamp(diff))


def fetch_response_stream(db, user_id, node_id, server_tree, client_tree) -> bytes:
    """The C-served SyncResponse `messages` stream for one request:
    tree diff → since timestamp (`response_since`) →
    `eh_get_messages_wire`. b"" when the trees agree; raises
    NonCanonicalStoreError for a malformed stored row (callers degrade
    that request to the object path). The serve rule must never drift
    between `RelayStore.sync_wire`, `_respond_deferred` and
    `BatchReconciler._respond_wire`, which times and counts the two
    halves itself (byte-identity with the object path is test-pinned
    at every call site)."""
    since = response_since(server_tree, client_tree)
    if since is None:
        return b""
    stream, _n = db.fetch_relay_messages_wire(user_id, since, node_id)
    return stream


def serve_single_request(store, request: "protocol.SyncRequest") -> bytes:
    """ONE copy of the per-request serve recipe: fused C wire path,
    object-path fallback (where non-canonical shapes reach the host
    oracle before any side effect). Shared by the non-batching do_POST
    branch and the scheduler's non-batchable/poison-retry fallbacks —
    the recipes must never drift (the scheduler's responses are pinned
    byte-identical to this path).

    Ledger: the whole serve runs under one scope (see _SERVE_SCOPE) so
    store terminals post exactly once per ANSWERED request — a serve
    that commits add_messages and then fails (e.g. a garbage client
    tree string) aborts the entry and the caller's reject.invalid is
    the single terminal; the NonCanonicalStoreError fallback's second
    add_messages run never double-classifies."""
    scope = {"entry": ledger.pending(), "classified": False}
    _SERVE_SCOPE.scope = scope
    try:
        if getattr(request, "scope", None) is not None:
            # Scoped serve (server/scope.py): ingest runs through the
            # same add_messages path (the ledger seam above fires
            # normally); only the RESPONSE is filtered. Never the fused
            # C wire path — per-row lane filtering can't ride it.
            from evolu_tpu.server import scope as scope_mod

            out = scope_mod.serve_scoped(store, request)
        else:
            out = store.sync_wire(request) if hasattr(store, "sync_wire") \
                else None
            if out is None:
                out = protocol.encode_sync_response(store.sync(request))
    except BaseException:
        scope["entry"].abort()
        raise
    finally:
        _SERVE_SCOPE.scope = None
    scope["entry"].commit()
    return out


class RelayStore:
    """Message + Merkle storage for many users (index.ts:60-105)."""

    def __init__(self, path: str = ":memory:", backend: str = "auto"):
        self.db = open_database(path, backend)
        # Packed-capable: the handle is the C++ backend's, which takes
        # flat timestamp/ciphertext buffers (`relay_insert_packed` and
        # the shard-set calls of `storage/native.py`). The engine and
        # the write-behind queue choose their route from this one
        # answer; a stand-in store without the attribute answers no.
        self.packed = isinstance(self.db, CppSqliteDatabase)
        # File-backed stores may be shared across PROCESSES (the
        # pre-forked MultiprocessRelay, the write-behind's
        # process-per-shard drain children): one shared pragma
        # discipline, see sqlite.configure_shared_file_db (no-op for
        # :memory:).
        configure_shared_file_db(self.db)
        # Uniqueness pair is the reference's (timestamp, userId)
        # (index.ts:64-75); the key ORDER is flipped and the table is
        # WITHOUT ROWID — a deliberate layout improvement: get_messages
        # becomes a pure PK range read (the reference scans), and the
        # batched ingest maintains ONE btree instead of three
        # (rowid table + PK index + the user index this replaced),
        # measured ~2.9× faster at 1M rows. Dedup semantics are
        # identical (INSERT OR IGNORE on the same pair).
        self.db.exec(
            'CREATE TABLE IF NOT EXISTS "message" ('
            '"timestamp" TEXT, "userId" TEXT, "content" BLOB, '
            'PRIMARY KEY ("userId", "timestamp")) WITHOUT ROWID'
        )
        self.db.exec(
            'CREATE TABLE IF NOT EXISTS "merkleTree" ('
            '"userId" TEXT PRIMARY KEY, "merkleTree" TEXT)'
        )

    def get_merkle_tree(self, user_id: str) -> dict:
        """index.ts:121-136 — a user's tree, empty if unseen.
        ('{}' parses to create_initial_merkle_tree(); ONE SELECT lives
        in get_merkle_tree_string — keep them from diverging.)"""
        return merkle_tree_from_string(self.get_merkle_tree_string(user_id))

    def add_messages(
        self, user_id: str, messages: Sequence[protocol.EncryptedCrdtMessage]
    ) -> dict:
        """index.ts:138-171 — INSERT OR IGNORE each message; XOR only
        *newly inserted* timestamps into the tree (the server gates on
        changes==1, unlike the client's always-XOR; index.ts:153-158).
        One transaction; returns the updated tree."""
        with self.db.transaction():
            tree = self.get_merkle_tree(user_id)
            deltas: Dict[str, int] = {}
            if hasattr(self.db, "relay_insert"):
                # C++ backend: bulk insert with per-row was-new flags.
                new_flags = self.db.relay_insert(
                    [(m.timestamp, user_id, m.content) for m in messages]
                )
            else:
                new_flags = [
                    self.db.run(
                        'INSERT OR IGNORE INTO "message" ("timestamp", "userId", "content") '
                        "VALUES (?, ?, ?)",
                        (m.timestamp, user_id, m.content),
                    )
                    == 1
                    for m in messages
                ]
            for m, was_new in zip(messages, new_flags):
                if was_new:
                    t = timestamp_from_string(m.timestamp)
                    key = minutes_base3(t.millis)
                    deltas[key] = to_int32(deltas.get(key, 0) ^ timestamp_to_hash(t))
            tree = apply_prefix_xors(tree, deltas)
            self.db.run(
                'INSERT OR REPLACE INTO "merkleTree" ("userId", "merkleTree") VALUES (?, ?)',
                (user_id, merkle_tree_to_string(tree)),
            )
        # After the transaction committed — a rolled-back batch must
        # post nothing (the scheduler's retry posts once instead).
        _ledger_store_apply(user_id, new_flags)
        return tree

    def get_messages(
        self, user_id: str, node_id: str, server_tree: dict, client_tree: dict
    ) -> Tuple[protocol.EncryptedCrdtMessage, ...]:
        """index.ts:173-202 — if the trees diverge, everything after the
        diff minute except the requester's own messages."""
        diff = diff_merkle_trees(server_tree, client_tree)
        if diff is None:
            return ()
        since = timestamp_to_string(create_sync_timestamp(diff))
        if hasattr(self.db, "fetch_relay_messages"):
            # C++ backend: packed single-call reader. NB the query text
            # lives in BOTH native/evolu_host.cpp::eh_get_messages and
            # the fallback below — change them together
            # (tests assert cross-backend equivalence).
            try:
                rows = self.db.fetch_relay_messages(user_id, since, node_id)
                return tuple(protocol.EncryptedCrdtMessage(t, c) for t, c in rows)
            except NonCanonicalStoreError:
                pass  # a malformed stored width degrades to the SQL path
        rows = self.db.exec_sql_query(
            'SELECT "timestamp", "content" FROM "message" '
            'WHERE "userId" = ? AND "timestamp" > ? AND "timestamp" NOT LIKE \'%\' || ? '
            'ORDER BY "timestamp"',
            (user_id, since, node_id),
        )
        return tuple(
            protocol.EncryptedCrdtMessage(r["timestamp"], r["content"]) for r in rows
        )

    def get_merkle_tree_string(self, user_id: str) -> str:
        """The stored tree TEXT verbatim — response paths reuse it
        instead of parse→re-dump (a ~25KB JSON round-trip per owner is
        the measured cold-sync respond wall, docs/BENCHMARKS.md r4)."""
        rows = self.db.exec_sql_query(
            'SELECT "merkleTree" FROM "merkleTree" WHERE "userId" = ?', (user_id,)
        )
        return rows[0]["merkleTree"] if rows else "{}"

    def owner_trees(self) -> List[Tuple[str, str]]:
        """Every (owner, stored tree TEXT) pair in ONE query — the
        replication summary map (server/replicate.py). Per-owner
        `get_merkle_tree_string` calls would be N+1 SELECTs per gossip
        round."""
        rows = self.db.exec_sql_query('SELECT "userId", "merkleTree" FROM "merkleTree"')
        return [(r["userId"], r["merkleTree"]) for r in rows]

    def replica_messages(
        self, user_id: str, since: str, limit: Optional[int] = None
    ) -> Tuple[protocol.EncryptedCrdtMessage, ...]:
        """Ranged replication read for a PEER RELAY: stored messages
        strictly after `since` in timestamp order — the EARLIEST
        `limit` of them when capped — WITHOUT the own-node exclusion of
        `get_messages` (a relay is not a message author, it needs all
        rows; server/replicate.py). Plain SQL on purpose: the C reader
        bakes in the `NOT LIKE` node filter, and replication volume is
        divergence-bounded, not the per-message hot path."""
        rows = self.db.exec_sql_query(
            'SELECT "timestamp", "content" FROM "message" '
            'WHERE "userId" = ? AND "timestamp" > ? ORDER BY "timestamp" LIMIT ?',
            (user_id, since, -1 if limit is None else int(limit)),
        )
        return tuple(
            protocol.EncryptedCrdtMessage(r["timestamp"], r["content"]) for r in rows
        )

    def sync(self, request: protocol.SyncRequest) -> protocol.SyncResponse:
        """The pure pipeline (index.ts:204-216)."""
        tree = self.add_messages(request.user_id, request.messages)
        client_tree = merkle_tree_from_string(request.merkle_tree)
        messages = self.get_messages(request.user_id, request.node_id, tree, client_tree)
        return protocol.SyncResponse(messages, merkle_tree_to_string(tree))

    def sync_wire(self, request: protocol.SyncRequest) -> Optional[bytes]:
        """`sync` + `encode_sync_response` fused: the response messages
        stream comes straight from ONE C call (zero per-row objects —
        the cold-sync response leg was object-bound, BENCHMARKS r4),
        byte-identical to the pure pipeline's encoding (test-pinned).
        None → caller takes the object path (python backend)."""
        if not hasattr(self.db, "fetch_relay_messages_wire"):
            return None
        tree = self.add_messages(request.user_id, request.messages)
        client_tree = merkle_tree_from_string(request.merkle_tree)
        try:
            stream = fetch_response_stream(
                self.db, request.user_id, request.node_id, tree, client_tree
            )
        except NonCanonicalStoreError:
            # A single malformed stored timestamp must not wedge this
            # owner's sync: serve via the object path, whose
            # get_messages degrades to generic SQL (advisor r4).
            # add_messages above was idempotent, so the caller's
            # sync() re-run is safe.
            return None
        # add_messages just dumped + stored this exact tree: read the
        # stored text back (one small SELECT) instead of a second
        # ~25KB JSON dump per request (review finding).
        return stream + protocol._string(2, self.get_merkle_tree_string(request.user_id))

    def user_ids(self) -> List[str]:
        return [r["userId"] for r in self.db.exec_sql_query('SELECT "userId" FROM "merkleTree"')]

    def stats(self) -> List[dict]:
        """Per-shard row counts for GET /stats (one-element list here;
        ShardedRelayStore returns one entry per shard). Read from the
        store itself, so in a MultiprocessRelay every worker reports
        the same shared-file truth regardless of which worker answers."""
        messages = self.db.exec_sql_query('SELECT COUNT(*) AS n FROM "message"')
        users = self.db.exec_sql_query('SELECT COUNT(*) AS n FROM "merkleTree"')
        return [{"index": 0, "messages": messages[0]["n"], "users": users[0]["n"]}]

    def close(self) -> None:
        self.db.close()


class ShardedRelayStore:
    """Owner-sharded relay storage: N independent SQLite stores, each
    its own single-writer — the storage twin of the owners-over-mesh
    device sharding (owners are independent, SURVEY.md §2.15), and the
    way past SQLite's one-writer throughput wall: the batch reconciler
    lands a pass on every shard in one native call, the shards one
    after the other on the caller's thread (`storage/native.py`, the
    shard-set calls; a thread a shard lost at every size on the chip's
    host, native/evolu_host.cpp).

    Same public surface as RelayStore; userId routes to a shard by a
    stable hash. Per-request semantics are unchanged — a request only
    ever touches its owner's shard."""

    def __init__(self, path: str = ":memory:", backend: str = "auto", shards: int = 8):
        paths = (
            [":memory:"] * shards
            if path == ":memory:"
            else [f"{path}.s{i:02d}" for i in range(shards)]
        )
        self.shards = [RelayStore(p, backend) for p in paths]
        self.packed = all(s.packed for s in self.shards)

    def shard_index(self, user_id: str) -> int:
        return zlib.crc32(user_id.encode("utf-8")) % len(self.shards)

    def shard_of(self, user_id: str) -> RelayStore:
        return self.shards[self.shard_index(user_id)]

    def get_merkle_tree(self, user_id: str) -> dict:
        return self.shard_of(user_id).get_merkle_tree(user_id)

    def get_merkle_tree_string(self, user_id: str) -> str:
        return self.shard_of(user_id).get_merkle_tree_string(user_id)

    def add_messages(self, user_id, messages) -> dict:
        return self.shard_of(user_id).add_messages(user_id, messages)

    def get_messages(self, user_id, node_id, server_tree, client_tree):
        return self.shard_of(user_id).get_messages(user_id, node_id, server_tree, client_tree)

    def sync(self, request: protocol.SyncRequest) -> protocol.SyncResponse:
        return self.shard_of(request.user_id).sync(request)

    def sync_wire(self, request: protocol.SyncRequest) -> Optional[bytes]:
        return self.shard_of(request.user_id).sync_wire(request)

    def owner_trees(self) -> List[Tuple[str, str]]:
        return [p for s in self.shards for p in s.owner_trees()]

    def replica_messages(self, user_id: str, since: str, limit: Optional[int] = None):
        return self.shard_of(user_id).replica_messages(user_id, since, limit)

    def user_ids(self) -> List[str]:
        return [u for s in self.shards for u in s.user_ids()]

    def stats(self) -> List[dict]:
        return [
            {**s.stats()[0], "index": i} for i, s in enumerate(self.shards)
        ]

    def close(self) -> None:
        for s in self.shards:
            s.close()



def _open_store(path: str, backend: str, shards: int):
    """The one store-construction rule shared by the relay parent (schema
    pre-creation) and its workers — they must agree on the layout."""
    if shards > 1:
        return ShardedRelayStore(path, backend, shards=shards)
    return RelayStore(path, backend)
