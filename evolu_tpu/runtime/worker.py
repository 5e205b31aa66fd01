"""DbWorker — the single-writer command engine.

Reference: packages/evolu/src/db.worker.ts. All state-changing work
funnels through one ordered queue processed by one thread; every
command runs inside one SQLite transaction and reports failures as an
`OnError` output instead of raising (db.worker.ts:50-75). Command
semantics live in methods named after the reference's command modules
(send.ts, receive.ts, query.ts, sync.ts, updateDbSchema.ts,
resetOwner.ts, restoreOwner.ts).

TPU-native twist: `Send`/`Receive` batches are applied through a
pluggable merge planner — the host oracle for small batches, the
device kernel (`evolu_tpu.ops.merge.plan_batch_device`) above
`config.min_device_batch` — with identical end state either way
(tests/test_apply.py property-tests the equivalence).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

from evolu_tpu.core.merkle import (
    MerkleText,
    diff_merkle_trees,
    merkle_tree_from_string,
    merkle_tree_to_string,
)
from evolu_tpu.core.timestamp import (
    receive_timestamps_batch,
    receive_timestamps_batch_packed,
    create_sync_timestamp,
    receive_timestamp,
    send_timestamp,
    timestamp_from_string,
    timestamp_to_string,
)
from evolu_tpu.core.types import CrdtClock, CrdtMessage, Owner, SyncError
from evolu_tpu.obs import anatomy, flight, metrics, trace
from evolu_tpu.runtime import messages as msg
from evolu_tpu.runtime.jsonpatch import create_patch
from evolu_tpu.runtime.synclock import SyncLock, get_sync_lock
from evolu_tpu.storage.apply import (
    _notify_plan_failure,
    apply_messages,
    apply_messages_chunked,
    plan_batch,
)
from evolu_tpu.storage.changes import ChangedSet
from evolu_tpu.storage.deps import query_dependencies
from evolu_tpu.storage.clock import read_clock, update_clock
from evolu_tpu.storage.schema import delete_all_tables, init_db_model, update_db_schema
from evolu_tpu.storage.sqlite import PySqliteDatabase
from evolu_tpu.sync.protocol import assert_wire_encodable
from evolu_tpu.utils.config import Config
from evolu_tpu.utils.log import logger


def _now_millis() -> int:
    return int(time.time() * 1000)


_MISSING = object()  # pop sentinel: a cached [] must still count


def select_planner(config: Config, db: Optional[PySqliteDatabase] = None) -> Callable:
    """Pick the merge planner per config.backend: the host oracle below
    `min_device_batch`, the device kernel at/above it ("auto"/"tpu"),
    and the cell-range-sharded hot-owner kernel for huge single-owner
    batches on multi-device hosts.

    With `db` and `config.winner_cache`, device-planned batches source
    stored winners from the HBM-resident cache (ops/winner_cache.py —
    measured faster than streaming them from SQLite per batch) — the
    returned planner then owns winner fetching (`fetches_winners =
    False`) and any batch planned OUTSIDE the cache (host oracle,
    hot-owner) invalidates its touched cells, keeping cache == SQLite."""
    if config.backend == "cpu":
        return plan_batch

    from evolu_tpu.ops.merge import plan_batch_device_full
    from evolu_tpu.ops.scatter_merge import set_plan_path

    set_plan_path(config.merge_plan)

    threshold = 0 if config.backend == "tpu" else config.min_device_batch
    hot_min = config.hot_owner_min_batch
    cache = None
    if db is not None and config.winner_cache:
        if config.mesh_engine and _multi_device():
            # PR-12: slot arrays sharded over the device mesh (stable
            # cell→device placement; one shard_map'd gather/plan/
            # scatter pass per batch). Same planner contract and
            # coherence hooks; plans are identical to the single-device
            # cache (parity-pinned in tests/test_mesh_engine.py).
            from evolu_tpu.ops.winner_cache import MeshShardedWinnerCache
            from evolu_tpu.parallel.mesh import get_mesh_context

            cache = MeshShardedWinnerCache(
                db, mesh_ctx=get_mesh_context(config.mesh_devices)
            )
        else:
            from evolu_tpu.ops.winner_cache import DeviceWinnerCache

            cache = DeviceWinnerCache(db)

    def planner(batch, existing):
        hot_route = (
            hot_min is not None and len(batch) >= hot_min and _multi_device()
        )
        touched = None
        if cache is not None:
            if not hot_route and len(batch) >= threshold and not existing:
                # The standard device route: winners live in HBM.
                return cache.plan_batch(batch)
            # A non-cache route plans this batch (hot-owner, host
            # oracle, or a caller handed explicit winners). It needs
            # real stored winners if apply gave us none, and afterwards
            # the cache entries for its cells are stale — the plan
            # bypasses the cache scatter — so invalidate them.
            touched = {(m.table, m.row, m.column) for m in batch}
            if not existing:
                from evolu_tpu.storage.apply import fetch_existing_winners

                existing = fetch_existing_winners(db, touched)
        cols = None
        if hot_route:
            plan, cols = _plan_hot_owner(batch, existing)
            if plan is not None:
                if touched is not None:
                    cache.invalidate(touched)
                return plan
        if touched is not None:
            cache.invalidate(touched)
        if len(batch) >= threshold:
            # `cols` reuses the hot path's columnarization when it
            # declined the batch (non-canonical hex case).
            return plan_batch_device_full(batch, existing, cols=cols)
        return plan_batch(batch, existing)

    def plan_packed(pb):
        """Packed-batch twin of the closure above for PackedReceive
        (the fused receive leg). None = materialize and route the
        object path (which owns invalidation for those shapes)."""
        n = len(pb)
        if n < threshold or (
            hot_min is not None and n >= hot_min and _multi_device()
        ):
            # Small batches take the host oracle; hot-owner batches
            # keep their multi-device shard route — both via objects.
            return None
        if cache is not None:
            return cache.plan_packed(pb)
        if db is None:
            return None
        return _plan_packed_streamed_nocache(db, pb)

    planner.plan_packed = plan_packed
    if cache is not None:
        planner.fetches_winners = False
        planner.on_transaction_failed = cache.on_transaction_failed
        planner.cache = cache
    return planner


def _plan_packed_streamed_nocache(db, pb):
    """Packed plan with winners streamed from SQLite (winner_cache
    off): the PackedReceive analog of `plan_batch_device_full`. None →
    object path (non-canonical batch or stored winner)."""
    from evolu_tpu.ops.merge import plan_packed_streamed

    millis, counter, node, case_ok = pb.parse_timestamps()
    if not bool(case_ok.all()):
        return None
    touched_ids, cells = pb.touched_cells()
    return plan_packed_streamed(db, pb, millis, counter, node, cells, touched_ids)


def _multi_device() -> bool:
    import jax

    return len(jax.devices()) >= 2


def _plan_hot_owner(batch, existing):
    """One client is one owner; a batch above hot_owner_min_batch
    shards by cell-id ranges over every local device (per-cell LWW
    merges are independent — SURVEY.md §5 "within one hot owner, by
    cell-id ranges"). Returns (plan, cols): the standard 3-tuple plan,
    or plan=None when the host should route normally (non-canonical hex
    case — the device order/hash contract doesn't hold there and
    plan_batch_device_full's own fallback takes over); `cols` carries
    the columnarization for reuse either way. Callers gate on
    `_multi_device()`."""
    from evolu_tpu.ops.merge import messages_to_columns
    from evolu_tpu.parallel.hot_owner import reconcile_hot_owner
    from evolu_tpu.parallel.mesh import create_mesh

    cols = messages_to_columns(batch, existing)
    cell_id, k1, k2, ex_k1, ex_k2, millis, counter, node, canonical = cols
    if not canonical:
        return None, cols
    xor_mask, upsert_mask, deltas, _digest = reconcile_hot_owner(
        create_mesh(), cell_id, k1, k2, ex_k1, ex_k2, millis, counter, node
    )
    upserts = [m for i, m in enumerate(batch) if upsert_mask[i]]
    return (list(map(bool, xor_mask)), upserts, deltas), cols


class DbWorker:
    """The engine. Post commands with `post`; outputs arrive on the
    `on_output` callback from the worker thread (or synchronously from
    `start` for `OnInit`)."""

    def __init__(
        self,
        db: PySqliteDatabase,
        config: Optional[Config] = None,
        on_output: Optional[Callable[[object], None]] = None,
        post_sync: Optional[Callable[[msg.SyncRequestInput], None]] = None,
        now: Callable[[], int] = _now_millis,
        sync_lock: Optional[SyncLock] = None,
    ):
        self.db = db
        self.config = config or Config()
        self.on_output = on_output or (lambda _o: None)
        self.post_sync = post_sync or (lambda _r: None)
        self.now = now
        self.sync_lock = sync_lock or get_sync_lock(db.path)
        self.owner: Optional[Owner] = None
        self.queries_rows_cache: Dict[str, List[dict]] = {}
        # (raw packed result bytes, per-row offsets) per query — the
        # change detector for the reactive loop (bytes) plus the r5
        # row-granular alignment key (offsets); lifecycle mirrors
        # queries_rows_cache exactly (staged per command, committed on
        # success, evicted and cleared together — a desynced pair would
        # suppress or duplicate patches).
        self.queries_raw_cache: Dict[str, tuple] = {}
        # r9 incremental invalidation (ISSUE 9). The change log is a
        # short list of (seq, ChangedSet) batches; each tracked query
        # remembers the seq it last executed at (`_query_seen`), so
        # gating = "did anything after my seq touch my read set?"
        # (`storage/deps.py` provides the read set). `_query_lru`
        # orders queries by last use for the Config.query_cache_max
        # bound; an execution with no cached baseline always emits a
        # root-replace (see `_query`), so eviction needs no tombstones.
        self._query_deps: Dict[str, object] = {}
        self._query_seen: Dict[str, int] = {}
        self._query_lru: Dict[str, None] = {}
        self._change_log: List[tuple] = []
        self._change_seq: int = 0
        self._planner = select_planner(self.config, self.db)
        self._staged_effects: List = []
        self._staged_cache: Dict[str, List[dict]] = {}
        self._staged_raw: Dict[str, tuple] = {}
        self._staged_changes: ChangedSet = ChangedSet()
        self._staged_seen: set = set()
        self._queue: "queue.Queue[object]" = queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self._stop = object()

    # -- lifecycle --

    def start(self, mnemonic: Optional[str] = None) -> Owner:
        """Init: bootstrap the db model in one transaction and emit
        OnInit with the owner (db.worker.ts:77-137). Applies the config's
        log setting to the module logger (setConfig, db.worker.ts:103)."""
        logger.configure(self.config.log)
        with self.db.transaction():
            self.owner = init_db_model(self.db, mnemonic)
        self.on_output(msg.OnInit(self.owner))
        self._thread = threading.Thread(target=self._loop, daemon=True, name="evolu-db-worker")
        self._thread.start()
        return self.owner

    def stop(self) -> None:
        self._queue.put(self._stop)
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def post(self, command: object) -> None:
        """Enqueue a DbWorkerInput (db.worker.ts:47-75)."""
        self._queue.put(command)

    def flush(self) -> None:
        """Block until every queued command has been processed (test/sync aid)."""
        done = threading.Event()
        self._queue.put(done)
        done.wait()

    def _loop(self) -> None:
        while True:
            command = self._queue.get()
            if command is self._stop:
                return
            if isinstance(command, threading.Event):
                command.set()
                continue
            self.handle(command)

    # Side effects (outputs, sync pushes, worker-cache writes) are staged
    # during a command and flushed only after its transaction commits —
    # otherwise a failure later in the command would roll back local
    # state that was already pushed to the relay (the relay's own-node
    # exclusion would then never return those messages: permanent
    # divergence), and the worker's query cache would desync from the
    # committed rows.

    def _emit(self, output: object) -> None:
        self._staged_effects.append(lambda: self.on_output(output))

    def _push(self, request: msg.SyncRequestInput) -> None:
        self._staged_effects.append(lambda: self.post_sync(request))

    def _manages_own_transactions(self, command: object) -> bool:
        """A Receive large enough to chunk commits per chunk (bounded
        transaction memory + resumable clock); every other command gets
        the reference's one-transaction-per-command wrapper. Nested
        transactions JOIN the outer one, so the chunked path must run
        without it or per-chunk commits would silently be no-ops."""
        chunk = self.config.receive_chunk_size
        return (
            isinstance(command, msg.Receive)
            and bool(chunk)
            and len(command.messages) > chunk
        )

    def handle(self, command: object) -> None:
        """Dispatch one command inside one transaction; errors roll back
        and surface as OnError (db.worker.ts:57-73). A Receive is tiled
        into `evolu_stage_ms{stage=recv_*}` (docs/OBSERVABILITY.md):
        `recv_clock` opens here, the seams to `recv_plan_host` and
        `recv_commit` are in `_receive`, and those of the device call,
        the pull and the apply are in the planners (`ops/winner_cache.py`,
        `ops/merge.py`)."""
        if isinstance(command, msg.Receive):
            with anatomy.tiles("recv_", whole="handle", first="clock"):
                self._handle(command)
        else:
            self._handle(command)

    def _handle(self, command: object) -> None:
        t0 = time.perf_counter()
        self._staged_effects = []
        self._staged_cache: Dict[str, List[dict]] = {}
        self._staged_raw: Dict[str, tuple] = {}
        self._staged_changes = ChangedSet()
        self._staged_seen = set()
        metrics.inc("evolu_worker_commands_total", command=type(command).__name__)
        try:
            self._handle_inner(command)
        finally:
            if isinstance(command, (msg.Send, msg.Receive, msg.Query)):
                # The mutation→notify latency surface (ISSUE 9): local
                # mutations notify within their Send; remote ones are a
                # Receive plus the follow-up Query sweep.
                metrics.observe(
                    "evolu_query_notify_latency_ms",
                    (time.perf_counter() - t0) * 1e3,
                    command=type(command).__name__,
                )

    def _handle_inner(self, command: object) -> None:
        try:
            from contextlib import nullcontext

            txn = (
                nullcontext()
                if self._manages_own_transactions(command)
                else self.db.transaction()
            )
            with txn:
                if isinstance(command, msg.Send):
                    self._send(command)
                elif isinstance(command, msg.Receive):
                    self._receive(command)
                elif isinstance(command, msg.Query):
                    # full=True = refresh whose trigger the change log
                    # cannot see (e.g. another process wrote the shared
                    # DB file): bypass gating.
                    self._query(command.queries,
                                gated=not getattr(command, "full", False))
                elif isinstance(command, msg.EvictQueries):
                    for q in command.queries:
                        self._evict_query_entry(q)
                elif isinstance(command, msg.Sync):
                    self._sync(command)
                elif isinstance(command, msg.UpdateDbSchema):
                    update_db_schema(self.db, command.table_definitions)
                    # DDL plus possible pre-declaration typed folds
                    # (crdt_types._fold_predeclaration_ops) touch app
                    # tables in ways no message batch describes: the
                    # "don't know" arm of the invalidation contract.
                    self._staged_changes.mark_unknown()
                elif isinstance(command, msg.ResetOwner):
                    self._reset_owner()
                elif isinstance(command, msg.RestoreOwner):
                    self._restore_owner(command.mnemonic)
                elif isinstance(command, msg.WidenSyncScope):
                    self._widen_scope(command)
                else:
                    raise ValueError(f"unknown command: {command!r}")
        except Exception as e:  # noqa: BLE001 - the Either-left channel
            # The flight recorder's dump rides the exception across the
            # worker boundary: OnError subscribers (and test failures)
            # see the last N structured events, not a bare traceback.
            flight.attach(e)
            metrics.inc("evolu_worker_errors_total",
                        command=type(command).__name__)
            if isinstance(command, (msg.Send, msg.Receive, msg.ResetOwner, msg.RestoreOwner)):
                # A planner-touching command's transaction rolled back,
                # but a stateful planner (the HBM winner cache) may have
                # advanced at plan time INSIDE it — e.g. apply_messages
                # succeeds, then the livelock SyncError aborts the whole
                # receive. Without this resync the cache keeps phantom
                # winners SQLite never committed: redelivered messages
                # get xor=False (their hash never enters the Merkle
                # tree — permanent digest divergence) and beats=False
                # (app rows never upserted). Found by
                # tests/test_model_check.py. Idempotent; the inner
                # apply-level hook may already have fired. Gated to
                # these commands so e.g. a failed Query cannot wipe a
                # warm cache.
                _notify_plan_failure(self._planner)
            # Commit the staged changed-set even on failure: for a
            # rolled-back transaction it is a harmless superset (extra
            # re-execution, never staleness); for a chunked receive it
            # covers the chunks that DID commit. Seen-epoch updates are
            # dropped — queries staged this command re-verify next time.
            self._commit_staged_changes()
            if self._manages_own_transactions(command):
                # Chunked receive: earlier chunks COMMITTED before the
                # failure — their staged effects (OnReceive, so query
                # subscribers re-render the committed rows) must still
                # fire; dropping them would hide committed state until
                # some later command happens to emit.
                self.queries_rows_cache.update(self._staged_cache)
                self.queries_raw_cache.update(self._staged_raw)
                self._flush_staged_effects()
            try:
                self.on_output(msg.OnError(e))
            except Exception:  # noqa: BLE001,S110 - a raising error
                # listener must not kill the worker thread (every later
                # flush would hang on a dead loop)
                pass
            return
        self._commit_staged_changes()
        # Seen-epochs commit with the caches: after _commit_staged_changes
        # the current seq covers this command's own writes, which every
        # query staged this command already observed (the sweep runs
        # after the apply inside _send) or was verified disjoint from.
        for q in self._staged_seen:
            self._query_seen[q] = self._change_seq
        self.queries_rows_cache.update(self._staged_cache)
        self.queries_raw_cache.update(self._staged_raw)
        self._enforce_query_cache_cap()
        if self._staged_seen or isinstance(command, msg.EvictQueries):
            metrics.set_gauge("evolu_query_subscriptions",
                              len(self.queries_rows_cache))
        self._flush_staged_effects()

    # -- incremental-invalidation bookkeeping (ISSUE 9) --

    def _commit_staged_changes(self) -> None:
        if not self._staged_changes:
            return
        self._change_seq += 1
        self._change_log.append((self._change_seq, self._staged_changes))
        self._staged_changes = ChangedSet()
        if len(self._change_log) > 64:
            self._compact_change_log()

    def _compact_change_log(self) -> None:
        """Drop entries every tracked query has seen; if stale one-shot
        seen-epochs still pin history, merge the oldest half into one
        cumulative entry whose seq is the max member seq — still
        greater than any seen value predating any member, so queries
        behind it observe the union (a superset: conservative)."""
        floor = min(self._query_seen.values(), default=self._change_seq)
        log = [(s, e) for s, e in self._change_log if s > floor]
        if len(log) > 64:
            half = len(log) // 2
            merged = ChangedSet()
            for _s, e in log[:half]:
                merged.merge(e)
            log = [(log[half - 1][0], merged)] + log[half:]
        self._change_log = log

    def _staged_changes_or_none(self):
        """The apply-layer recording target — None when invalidation is
        disabled, so the reference-fallback configuration pays zero
        per-message recording cost (record_batch no-ops on None)."""
        return self._staged_changes if self.config.query_invalidation else None

    def _evict_query_entry(self, q: str) -> None:
        """Unsubscribed (EvictQueries): drop every per-query structure."""
        self.queries_rows_cache.pop(q, None)
        self.queries_raw_cache.pop(q, None)
        self._query_deps.pop(q, None)
        self._query_seen.pop(q, None)
        self._query_lru.pop(q, None)

    def _enforce_query_cache_cap(self) -> None:
        """Bound the per-query caches to Config.query_cache_max by
        least-recently-executed eviction, so churned one-shot query
        strings cannot grow the worker without bound. A still-subscribed
        query that loses its entry self-heals on its next execution
        with a root-replace patch (emitted whenever there is no cached
        baseline — including an empty result, so a subscriber holding
        rows from before the eviction can never be left stale)."""
        cap = self.config.query_cache_max
        if not cap:
            return
        evicted = 0
        while len(self.queries_rows_cache) > cap and self._query_lru:
            q = next(iter(self._query_lru))
            del self._query_lru[q]
            had_entry = self.queries_rows_cache.pop(q, _MISSING)
            self.queries_raw_cache.pop(q, None)
            self._query_deps.pop(q, None)
            self._query_seen.pop(q, None)
            if had_entry is not _MISSING:
                evicted += 1  # LRU residue of failed queries don't count
        if evicted:
            metrics.inc("evolu_query_cache_evictions_total", evicted)
        if len(self._query_lru) > 2 * cap:
            # Failed/never-cached queries leave LRU-only residue; sweep
            # it on the rare overflow.
            for q in list(self._query_lru):
                if len(self._query_lru) <= 2 * cap:
                    break
                if q not in self.queries_rows_cache:
                    del self._query_lru[q]
                    self._query_deps.pop(q, None)
                    self._query_seen.pop(q, None)

    def _pending_since(self, seen: int, memo: Dict[int, object]):
        """Shared gate state for every query last verified at epoch
        `seen`: `"clean"` (nothing written since), `"conservative"`
        (an unattributable write — every gated query must re-execute),
        or `(tables, rows)` of the merged pending ChangedSet. Memoized
        per sweep so the change-log merge runs once per distinct
        epoch, not once per query."""
        pend = ChangedSet()
        for s, e in self._change_log:
            if s > seen:
                pend.merge(e)
        if self._staged_changes:
            pend.merge(self._staged_changes)
        if pend.conservative:
            state = "conservative"
        elif not pend.tables:
            state = "clean"
        else:
            state = (pend.tables, pend.rows)
        memo[seen] = state
        return state

    def _flush_staged_effects(self) -> None:
        for effect in self._staged_effects:
            try:
                effect()
            except Exception as e:  # noqa: BLE001 - listener raised: must
                # not kill the worker thread (the command already committed)
                try:
                    self.on_output(msg.OnError(e))
                except Exception:  # noqa: BLE001,S110 - error channel itself broken
                    pass

    # -- commands --

    def _send(self, command: msg.Send) -> None:
        """send.ts:82-122: stamp → apply → persist clock → push → re-query.

        One wall-clock sample per command, like the reference's
        per-command TimeEnv (types.ts:303-309). The mutation mints the
        distributed-trace root span (obs.trace, ISSUE 10): its context
        rides the staged SyncRequestInput into the sync transport and
        from there the HTTP traceparent header — the one id that ties
        client → relay → batch → engine → replica together."""
        # Refuse wire-unencodable values BEFORE they enter the log (the
        # whole command rolls back and surfaces as OnError): a committed
        # value the encoder cannot express (bytes always; float/int64 in
        # strict mode) would wedge every later resend batch permanently.
        # Remote messages are exempt — a replica relays what it received.
        for m in command.messages:
            assert_wire_encodable(m.value, self.config.wire_extensions)
        mspan = trace.start_span(
            "client.mutate", attrs={"messages": len(command.messages)}
        )
        with mspan, trace.use(mspan.context):
            clock = read_clock(self.db)
            t = clock.timestamp
            now = self.now()
            stamped: List[CrdtMessage] = []
            for m in command.messages:
                t = send_timestamp(t, now, self.config.max_drift)
                stamped.append(
                    CrdtMessage(timestamp_to_string(t), m.table, m.row, m.column, m.value)
                )
            tree = apply_messages(self.db, clock.merkle_tree, stamped,
                                  planner=self._planner,
                                  changes=self._staged_changes_or_none())
            next_clock = CrdtClock(t, tree)
            tree_string = update_clock(self.db, next_clock)
            self._push(
                msg.SyncRequestInput(
                    messages=tuple(stamped),
                    clock_timestamp=timestamp_to_string(t),
                    merkle_tree=tree_string,
                    owner=self.owner,
                    trace=mspan.context,
                )
            )
            self._query(command.queries, command.on_complete_ids)

    def _receive(self, command: msg.Receive) -> None:
        """receive.ts:144-199: merge remote messages, then anti-entropy."""
        clock = read_clock(self.db)
        if len(command.messages):
            # HLC merge folded over every remote timestamp
            # (receive.ts:45-66) — the reduced vectorized fold, with one
            # wall-clock sample per command like the reference's TimeEnv.
            # A parse failure re-runs the fold sequentially so the FIRST
            # failing message defines the surfaced error, exactly like
            # the reference's per-message traversal.
            from evolu_tpu.core.packed import PackedReceive
            from evolu_tpu.core.types import TimestampParseError
            from evolu_tpu.ops.host_parse import parse_timestamp_strings

            now = self.now()
            packed = isinstance(command.messages, PackedReceive)
            try:
                if packed:
                    # Fused receive: the 46-wide slab parses in one
                    # native call; node strings materialize only if a
                    # screen forces the exact sequential fold.
                    pb = command.messages
                    r_millis, r_counter, r_node, _case = pb.parse_timestamps()
                    t = receive_timestamps_batch_packed(
                        clock.timestamp, r_millis, r_counter, r_node,
                        lambda: [s[30:46] for s in pb.timestamp_strings()],
                        now=now, max_drift=self.config.max_drift,
                    )
                else:
                    r_millis, r_counter, _ = parse_timestamp_strings(
                        [m.timestamp for m in command.messages]
                    )
                    t = receive_timestamps_batch(
                        clock.timestamp, r_millis, r_counter,
                        [m.timestamp[30:46] for m in command.messages],
                        now=now, max_drift=self.config.max_drift,
                    )
            except TimestampParseError:
                ts_strings = (
                    command.messages.timestamp_strings() if packed
                    else [m.timestamp for m in command.messages]
                )
                t = clock.timestamp
                for s in ts_strings:
                    t = receive_timestamp(
                        t, timestamp_from_string(s), now, self.config.max_drift
                    )
            anatomy.seam("plan_host")
            messages = command.messages if packed else list(command.messages)
            deferred: List[CrdtMessage] = []
            scope = getattr(self.config, "sync_scope", None)
            if scope is not None and scope.tables:
                # Partial replication (ISSUE 18): only in-scope tables
                # materialize; out-of-scope messages still land in the
                # log and the Merkle tree (log-only apply below) so
                # anti-entropy and the digest never see the difference.
                # The packed slab cannot partition per-table — bounce
                # to the object path BEFORE any side effect (the same
                # stance as the r5 non-canonical bounce).
                if packed:
                    messages = list(messages.to_messages())
                in_scope: List[CrdtMessage] = []
                for m in messages:
                    (in_scope if scope.table_in_scope(m.table)
                     else deferred).append(m)
                messages = in_scope
            chunk = self.config.receive_chunk_size
            if chunk and len(messages) > chunk:
                # Huge history (e.g. initial sync of a restored device):
                # blockwise apply with the clock persisted per chunk —
                # the LWW contraction is associative, so the end state
                # equals one giant batch, but memory stays bounded and a
                # mid-sync failure resumes from the last chunk. The HLC
                # timestamp is already merged over the WHOLE batch above,
                # matching the reference's clock-then-apply order.
                receive_staged = False

                def persist(tree_so_far, _applied):
                    # Stage OnReceive as soon as the FIRST chunk commits:
                    # a mid-stream ChunkedApplyError flushes staged
                    # effects, so subscribers re-render the rows earlier
                    # chunks committed instead of them staying hidden
                    # until some later command emits.
                    nonlocal receive_staged
                    update_clock(self.db, CrdtClock(t, tree_so_far))
                    if not receive_staged:
                        receive_staged = True
                        self._emit(msg.OnReceive())

                tree = apply_messages_chunked(
                    self.db, clock.merkle_tree, messages, chunk_size=chunk,
                    planner=self._planner, on_chunk=persist,
                    changes=self._staged_changes_or_none(),
                )
                # persist() already wrote the final clock with this tree
                # and staged the OnReceive.
                anatomy.seam("commit")
                if deferred:
                    tree = self._apply_deferred(tree, deferred)
                    update_clock(self.db, CrdtClock(t, tree))
                clock = CrdtClock(t, tree)
            else:
                tree = apply_messages(
                    self.db, clock.merkle_tree, messages,
                    planner=self._planner, changes=self._staged_changes_or_none(),
                )
                anatomy.seam("commit")
                if deferred:
                    tree = self._apply_deferred(tree, deferred)
                clock = CrdtClock(t, tree)
                update_clock(self.db, clock)
                self._emit(msg.OnReceive())

        with anatomy.part("tree_diff"):
            # Equal texts are equal trees: nothing to parse, no diff.
            tree = clock.merkle_tree
            own_text = tree.text if isinstance(tree, MerkleText) else None
            same = own_text is not None and own_text == command.merkle_tree
            if same:
                diff = None
            else:
                server_tree = merkle_tree_from_string(command.merkle_tree)
                diff = diff_merkle_trees(server_tree, tree)
        metrics.inc_many((
            ("evolu_merkle_tree_bytes_total", len(command.merkle_tree), {"leg": "remote"}),
            ("evolu_merkle_tree_text_checks_total", int(own_text is not None),
             {"leg": "remote"}),
            ("evolu_merkle_tree_text_hits_total", int(same), {"leg": "remote"}),
        ))
        if diff is None:
            return
        # Livelock guard: the same diff twice in a row means the replicas
        # cannot converge (receive.ts:99-104).
        if command.previous_diff is not None and diff == command.previous_diff:
            raise SyncError()
        if self.sync_lock.is_pending_or_held():
            return
        since = timestamp_to_string(create_sync_timestamp(diff))
        rows = self.db.exec_sql_query(
            'SELECT * FROM "__message" WHERE "timestamp" > ? ORDER BY "timestamp"',
            (since,),
        )
        resend = tuple(
            CrdtMessage(r["timestamp"], r["table"], r["row"], r["column"], r["value"])
            for r in rows
        )
        self._push(
            msg.SyncRequestInput(
                messages=resend,
                clock_timestamp=timestamp_to_string(clock.timestamp),
                merkle_tree=merkle_tree_to_string(clock.merkle_tree),
                owner=self.owner,
                previous_diff=diff,
            )
        )

    # -- partial replication (ISSUE 18, sync/scope.py) --

    _SCOPE_DEFERRED_DDL = (
        'CREATE TABLE IF NOT EXISTS "__scope_deferred" '
        '("table" TEXT PRIMARY KEY, "rows" INTEGER NOT NULL) WITHOUT ROWID'
    )

    def _apply_deferred(self, tree: dict, deferred: List[CrdtMessage]) -> dict:
        """Out-of-scope leg of a scoped receive: log + Merkle tree only
        (`apply_messages_log_only`), no app-table rows, with the skipped
        materialization COUNTED in the `__scope_deferred` frontier so a
        query against one of these tables can answer a typed deferral
        instead of silently-empty rows."""
        from evolu_tpu.storage.apply import apply_messages_log_only

        # Frontier counts must be EXACT against the log: anti-entropy
        # re-serves whole minutes, so a batch can redeliver rows the
        # log already holds — screen them out before counting (the
        # insert below is ON CONFLICT DO NOTHING, so the log agrees).
        seen: set = set()
        stamps = [m.timestamp for m in deferred]
        for i in range(0, len(stamps), 500):
            chunk = stamps[i:i + 500]
            rows = self.db.exec_sql_query(
                'SELECT "timestamp" FROM "__message" WHERE "timestamp" '
                f'IN ({",".join("?" * len(chunk))})',
                tuple(chunk),
            )
            seen.update(r["timestamp"] for r in rows)
        tree = apply_messages_log_only(
            self.db, tree, deferred, changes=self._staged_changes_or_none()
        )
        cache = getattr(self._planner, "cache", None)
        if cache is not None:
            # The log's MAX(timestamp) for these cells just moved via a
            # plan the HBM cache never saw — the cache==SQLite invariant
            # demands invalidation, exactly like the host-oracle route.
            cache.invalidate({(m.table, m.row, m.column) for m in deferred})
        counts: Dict[str, int] = {}
        for m in deferred:
            if m.timestamp in seen:
                continue
            counts[m.table] = counts.get(m.table, 0) + 1
        self.db.exec(self._SCOPE_DEFERRED_DDL)
        for tbl, n in counts.items():
            self.db.run(
                'INSERT INTO "__scope_deferred" ("table", "rows") '
                'VALUES (?, ?) '
                'ON CONFLICT("table") DO UPDATE SET "rows" = "rows" + ?',
                (tbl, n, n),
            )
        n_new = sum(counts.values())
        if n_new:
            metrics.inc("evolu_scope_deferred_total", n_new)
        return tree

    def _deferred_frontier(self) -> Dict[str, int]:
        """table → deferred-message count, {} when nothing is deferred
        (including before the side table first exists)."""
        try:
            rows = self.db.exec_sql_query(
                'SELECT "table", "rows" FROM "__scope_deferred" '
                'WHERE "rows" > 0'
            )
        except Exception:  # noqa: BLE001 - no table yet = empty frontier
            return {}
        return {r["table"]: r["rows"] for r in rows}

    def _widen_scope(self, command: "msg.WidenSyncScope") -> None:
        """Escalation (widenSyncScope): relax the scope, re-materialize
        every newly-in-scope table from the LOCAL log in LWW order, and
        clear its frontier rows. History the relay withheld arrives via
        the next ordinary anti-entropy round — the scoped server
        subtree widens with the same clause, so the tree diff drives
        catch-up with no special protocol."""
        scope = getattr(self.config, "sync_scope", None)
        if scope is None:
            return  # already a full replica; nothing to widen
        if command.full:
            new = None
        else:
            new = scope.widen(command.watermark_millis,
                              tuple(command.tables))
            if new.is_noop:
                new = None
        n_remat = 0
        for tbl in sorted(self._deferred_frontier()):
            if new is None or new.table_in_scope(tbl):
                n_remat += self._rematerialize_table(tbl)
                self.db.run(
                    'DELETE FROM "__scope_deferred" WHERE "table" = ?',
                    (tbl,),
                )
        self.config.sync_scope = new
        if n_remat:
            # Whole tables appeared at once: unattributable to any
            # message batch — the conservative invalidation arm.
            self._staged_changes.mark_unknown()
            metrics.inc("evolu_scope_widen_materialized_total", n_remat)
        self._emit(msg.OnReceive())

    def _rematerialize_table(self, table: str) -> int:
        """Replay one table's app rows from the `__message` log: LWW
        winner per (row, column) upserted (ascending timestamp order,
        last write wins — byte-identical to having applied every batch
        unscoped), typed cells rebuilt via the order-free full-state
        fold. → messages replayed."""
        from evolu_tpu.core.crdt_types import load_schema, rebuild_state
        from evolu_tpu.storage.apply import _upsert_sql

        rows = self.db.exec_sql_query(
            'SELECT "timestamp", "row", "column", "value" FROM "__message" '
            'WHERE "table" = ? ORDER BY "timestamp"',
            (table,),
        )
        if not rows:
            return 0
        schema = load_schema(self.db)
        winners: Dict[tuple, dict] = {}
        has_typed = False
        for r in rows:
            if schema and schema.is_typed(table, r["column"]):
                has_typed = True
                continue
            winners[(r["row"], r["column"])] = r
        for r in winners.values():
            self.db.run(
                _upsert_sql(table, r["column"]),
                (r["row"], r["value"], r["value"]),
            )
        if has_typed:
            # Typed folds were skipped at defer time; the incremental
            # path can't replay them (its dedup screen reads __message,
            # where every one of these ops already lives) — the
            # order-free full rebuild is the exact route.
            rebuild_state(self.db, schema)
        cache = getattr(self._planner, "cache", None)
        if cache is not None:
            cache.invalidate({
                (table, r["row"], r["column"]) for r in rows
            })
        return len(rows)

    def _query(self, queries: Sequence[str], on_complete_ids: Sequence[str] = (),
               gated: bool = True) -> None:
        """query.ts:16-76: run, diff vs cache, post non-empty patches.

        r9 (ISSUE 9) gates the sweep on the changed-set: a query whose
        read tables (storage/deps.py, from SQLite's own compiled
        program) are disjoint from everything written since its last
        run skips WITHOUT a read or a byte compare; a query with a
        static `"id" = ?` constraint additionally skips row-disjoint
        writes. Every "don't know" — unknown deps, unknown rows,
        conservative change, no baseline — falls through to execution,
        so the emitted patch stream is byte-identical to re-running
        everything (bench-gated in benchmarks/query_sub_scaling.py).
        `gated=False` (explicit Sync refresh, Query(full=True))
        re-executes unconditionally.

        With the packed reader (C++ backend), the raw result bytes are
        the change detector for executed queries: a subscribed query
        whose bytes match the cached bytes skips dict materialization
        AND the rfc6902 diff entirely — the dominant cost of the
        reactive re-execution loop (SURVEY hot loop #4; measured r4:
        ~65 ms per 10k-row query on the per-cell path vs ~4 ms raw
        read + compare). Byte equality is EXACT here, not approximate:
        the only value whose deep-equality differs from bit-equality
        is REAL NaN, and SQLite converts NaN to NULL at bind time so
        no queried row can hold one (pinned in test_runtime.py;
        -0.0→0.0 rewrites emit a patch the deep-equal would skip — a
        real write happened, so the extra patch is harmless).

        A query with NO cached baseline (first run, or LRU-evicted
        under Config.query_cache_max) emits a ROOT-REPLACE patch
        (`{"op": "replace", "path": "", "value": rows}`) instead of
        index ops diffed against []: index ops are only correct when
        the subscriber also starts from [], which an evicted-but-live
        subscription does not."""
        patches = []
        # Partial replication (ISSUE 18): a query that reads a table
        # with deferred (log-only) rows must answer a TYPED deferral,
        # never silently-empty rows. One frontier read per sweep; {}
        # when no scope filter is active.
        _scope = getattr(self.config, "sync_scope", None)
        deferred_tables = (
            self._deferred_frontier()
            if _scope is not None and _scope.tables else {}
        )
        deferred_hits: set = set()
        raw_capable = hasattr(self.db, "exec_sql_query_packed_raw")
        if raw_capable:
            from evolu_tpu.storage.native import (
                unpack_changed_rows,
                unpack_packed_rows,
            )
        gate = gated and self.config.query_invalidation
        build_deps = self.config.query_invalidation
        pending_memo: Dict[int, object] = {}
        n_exec = n_clean = n_table = n_rows = n_cons = 0
        # The gate is INLINED in this loop with every dict hoisted to a
        # local: at 10^4 subscriptions per sweep the skip path's cost
        # IS the mutation→notify latency for disjoint writes, and a
        # per-query method call + attribute loads measurably dominate
        # it (profiled: ~2× the set ops). Verdict semantics — sound by
        # construction, any uncertainty re-executes: no baseline /
        # unknown deps ⇒ run; conservative epoch or unknown-table deps
        # ⇒ run (counted conservative); table-disjoint ⇒ skip;
        # table-overlap with a static id-filter disjoint from the
        # changed rows ⇒ skip; anything else ⇒ run.
        lru = self._query_lru
        staged_seen_add = self._staged_seen.add
        query_seen_get = self._query_seen.get
        deps_get = self._query_deps.get
        rows_cache, staged_cache = self.queries_rows_cache, self._staged_cache
        memo_get = pending_memo.get
        for q in queries:
            lru.pop(q, None)
            lru[q] = None
            if gate:
                run = True
                seen = query_seen_get(q)
                if seen is not None and (q in rows_cache or q in staged_cache):
                    state = memo_get(seen)
                    if state is None:
                        state = self._pending_since(seen, pending_memo)
                    if state == "clean":
                        n_clean += 1
                        run = False
                    elif state == "conservative":
                        n_cons += 1
                    else:
                        deps = deps_get(q)
                        read_tables = deps.tables if deps is not None else None
                        if read_tables is None:
                            if deps is not None:
                                n_cons += 1  # EXPLAIN walk gave up
                        else:
                            pend_tables, pend_rows = state
                            if pend_tables.isdisjoint(read_tables):
                                n_table += 1
                                run = False
                            else:
                                row_filters = deps.row_filters
                                for t in read_tables:
                                    if t not in pend_tables:
                                        continue
                                    changed = pend_rows.get(t)
                                    if changed is None:
                                        break  # unknown rows: run
                                    flt = row_filters.get(t)
                                    if flt is None or not changed.isdisjoint(flt):
                                        break  # true overlap: run
                                else:
                                    n_rows += 1
                                    run = False
                if not run:
                    staged_seen_add(q)
                    continue  # skipped: no read, no compare, no patch
            staged_seen_add(q)
            n_exec += 1
            sql, parameters = msg.deserialize_query(q)
            if deferred_tables:
                deps = deps_get(q)
                if deps is None:
                    # Built eagerly for the honesty check even when
                    # invalidation gating is off; never raises (its own
                    # failures degrade to unknown deps).
                    deps = query_dependencies(self.db, sql, parameters)
                    if build_deps:
                        self._query_deps[q] = deps
                read_tables = deps.tables
                if read_tables is not None:
                    hit = [t for t in read_tables if t in deferred_tables]
                else:
                    # EXPLAIN walk gave up: conservative text scan —
                    # over-matching defers a query it needn't (honest,
                    # recoverable by widening); under-matching would
                    # answer rows a full replica wouldn't.
                    import re as _re

                    hit = [
                        t for t in deferred_tables
                        if _re.search(r"\b" + _re.escape(t) + r"\b", sql)
                    ]
                if hit:
                    n_exec -= 1  # deferred, not executed
                    deferred_hits.update(hit)
                    continue
            if build_deps and q not in self._query_deps:
                # First execution builds the dependency index entry;
                # query_dependencies never raises (its own failures
                # degrade to unknown), so the statement's real error
                # surface stays with the execution below.
                self._query_deps[q] = query_dependencies(self.db, sql, parameters)
            entry = None
            cached = q in self._staged_cache or q in self.queries_rows_cache
            if raw_capable:
                raw, offs = self.db.exec_sql_query_packed_raw(
                    sql, parameters, with_offsets=True
                )
                entry = (raw, offs)
                prev_entry = self._staged_raw.get(q, self.queries_raw_cache.get(q))
                if cached and prev_entry is not None and prev_entry[0] == raw:
                    self._staged_raw[q] = prev_entry
                    continue  # unchanged — no parse, no diff, no patch
                prev = self._staged_cache.get(q, self.queries_rows_cache.get(q, []))
                if (
                    prev_entry is not None and prev
                    and offs is not None and prev_entry[1] is not None
                ):
                    # Row-granular: only changed row spans unpack; rows
                    # with unchanged bytes reuse prev's dict objects
                    # (identity-stable — create_patch shortcuts on
                    # `is`, and subscribers keep referential equality).
                    rows = unpack_changed_rows(
                        raw, offs, prev_entry[0], prev_entry[1], prev
                    )
                else:  # no prior entry, or a stale .so gave no offsets
                    rows = unpack_packed_rows(raw)
            else:
                rows = self.db.exec_sql_query(sql, parameters)
                prev = self._staged_cache.get(q, self.queries_rows_cache.get(q, []))
            if cached:
                ops = create_patch(prev, rows)
            else:
                # No cached baseline (first run, or LRU-evicted): emit
                # the whole result — EVEN an empty one. A subscriber
                # may hold non-empty rows from before the eviction,
                # and only a root-replace converges it from any state.
                ops = [{"op": "replace", "path": "", "value": rows}]
            # Stage rows BEFORE raw: an exception between unpack and here
            # leaves both staged caches at their old values — staging raw
            # first would let the OnError commit path pair NEW bytes with
            # OLD rows, suppressing the patch forever (advisor r4).
            self._staged_cache[q] = rows
            if entry is not None:
                self._staged_raw[q] = entry
            if ops:
                patches.append((q, ops))
        # Counters batched per sweep: at 10^4 subscriptions a per-query
        # metrics lock would cost more than the skips save.
        if n_exec:
            metrics.inc("evolu_query_executed_total", n_exec)
        if n_clean:
            metrics.inc("evolu_query_skipped_clean_total", n_clean)
        if n_table:
            metrics.inc("evolu_query_skipped_by_table_total", n_table)
        if n_rows:
            metrics.inc("evolu_query_skipped_by_rows_total", n_rows)
        if n_cons:
            metrics.inc("evolu_query_conservative_total", n_cons)
        if deferred_hits:
            from evolu_tpu.sync.scope import ScopeDeferred

            tables = tuple(sorted(deferred_hits))
            self._emit(msg.OnError(ScopeDeferred(
                tables, sum(deferred_tables[t] for t in tables)
            )))
            metrics.inc("evolu_scope_query_deferred_total",
                        len(deferred_hits))
        if patches or on_complete_ids:
            self._emit(msg.OnQuery(tuple(patches), tuple(on_complete_ids)))

    def _sync(self, command: msg.Sync) -> None:
        """sync.ts:20-69: optional query refresh, then a pull-only round."""
        if command.queries:
            # Ungated: an explicit sync refresh exists to pick up state
            # the worker did not write itself (another process on a
            # shared DB file; the reference's load/online/focus
            # re-runs). The byte compare still suppresses no-op patches.
            self._query(command.queries, gated=False)
        if self.sync_lock.is_pending_or_held():
            return
        clock = read_clock(self.db)
        self._push(
            msg.SyncRequestInput(
                messages=(),
                clock_timestamp=timestamp_to_string(clock.timestamp),
                merkle_tree=merkle_tree_to_string(clock.merkle_tree),
                owner=self.owner,
            )
        )

    def _drop_winner_cache(self) -> None:
        """Tables just got dropped; cached winner keys are meaningless."""
        cache = getattr(self._planner, "cache", None)
        if cache is not None:
            cache.reset()

    def verify_winner_cache(self, sample: "int | None" = None) -> int:
        """Audit the PR-11 "device state is truth" invariant on THIS
        worker's live cache: every HBM slot == SQLite MAX(timestamp)
        for its cell (`DeviceWinnerCache.verify_against_db`). → cells
        checked (0 when no cache is active — cpu backend, winner_cache
        off, or streaming mode). The torture episode and the ops
        surface both call through here so the audit always reads the
        worker's actual planner state, not a reconstructed twin."""
        cache = getattr(self._planner, "cache", None)
        if cache is None:
            return 0
        return cache.verify_against_db(sample=sample)

    def _clear_query_caches(self) -> None:
        self.queries_rows_cache.clear()
        self.queries_raw_cache.clear()
        self._query_deps.clear()
        self._query_seen.clear()
        self._query_lru.clear()
        # The change log only gates queries with a seen-epoch; all were
        # just cleared, so history is dead weight (seq stays monotonic).
        self._change_log.clear()

    def _drop_aead_sessions(self) -> None:
        """Owner identity changed: drop the cached aead-batch-v1
        session keys (sync/aead.py). Sessions are keyed by mnemonic so
        a stale entry could never decrypt wrongly — this is retention
        hygiene (no keys for retired identities) plus a fresh session
        salt for whatever identity syncs next, mirroring the
        winner-cache reset invariant on the same transitions."""
        from evolu_tpu.sync import aead

        aead.reset_sessions()

    def _reset_owner(self) -> None:
        """resetOwner.ts:7-21."""
        self._staged_changes.mark_unknown()  # DDL wipe: unattributable
        delete_all_tables(self.db)
        self._drop_winner_cache()
        self._drop_aead_sessions()
        self._staged_effects.append(self._clear_query_caches)
        self._emit(msg.ReloadAllTabs())

    def _restore_owner(self, mnemonic: str) -> None:
        """restoreOwner.ts:9-23 — wipe, re-seed identity; history returns
        via the first sync against the relay (SURVEY.md §3.5)."""
        self._staged_changes.mark_unknown()  # DDL wipe: unattributable
        delete_all_tables(self.db)
        self._drop_winner_cache()
        self._drop_aead_sessions()
        self._staged_effects.append(self._clear_query_caches)
        self.owner = init_db_model(self.db, mnemonic)
        self._emit(msg.ReloadAllTabs())
