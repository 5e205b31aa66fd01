"""LWW message application — the merge hot path.

`apply_messages_sequential` reproduces the reference's per-message loop
(applyMessages.ts:26-131) exactly and serves as the correctness oracle:

1. winner lookup: latest __message timestamp for the (table,row,column)
   cell (applyMessages.ts:34-40);
2. if absent or older than the message ⇒ upsert the app table
   (applyMessages.ts:92-103);
3. if the winner differs from the message timestamp ⇒ INSERT OR NOTHING
   into __message and XOR the timestamp hash into the Merkle tree
   (applyMessages.ts:104-122). NB the XOR is NOT gated on the insert
   actually inserting — a re-received non-winning duplicate XORs again
   (client semantics; the server gates on changes==1 instead,
   apps/server/src/index.ts:153-158).

`apply_messages` is the batched path with identical end state: one
winner query for all touched cells, decision masks computed batch-wise
(host here; `evolu_tpu.ops.merge` computes the same masks on device for
large batches), then bulk SQL. Equivalence is property-tested in
tests/test_apply.py.

Typed CRDT cells (counter/awset/list and the tensor family, ISSUEs 7/
14/20) ride the same transaction: `crdt_types.apply_typed_ops` folds
new ops into the `__crdt_*` state tables (tensor: the `__crdt_tensor`
op log) and materializes canonical bytes BEFORE the batch's __message
insert, while `strip_typed_upserts` removes their LWW upserts from the
plan. Packed batches containing ANY typed cell — tensor included —
bounce to this object path BEFORE any side effect (the r5 contract).
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from evolu_tpu.core.merkle import (
    MerkleText,
    MinuteDeltas,
    fold_key_deltas,
    fold_minute_deltas,
    insert_into_merkle_tree,
    minute_deltas_host,
)
from evolu_tpu.core.timestamp import timestamp_from_string
from evolu_tpu.core.types import CrdtMessage
from evolu_tpu.obs import anatomy, ledger, metrics
from evolu_tpu.storage.sqlite import PySqliteDatabase, quote_ident


# Mask counting shared with the relay's store seam (ONE copy).
_mask_sum = ledger.flag_sum

_SELECT_WINNER = (
    'SELECT "timestamp" FROM "__message" '
    'WHERE "table" = ? AND "row" = ? AND "column" = ? '
    'ORDER BY "timestamp" DESC LIMIT 1'
)
_INSERT_MESSAGE = (
    'INSERT INTO "__message" ("timestamp", "table", "row", "column", "value") '
    "VALUES (?, ?, ?, ?, ?) ON CONFLICT DO NOTHING"
)


def _fold_tree(merkle_tree: dict, deltas) -> dict:
    """A planned batch's per-minute deltas into the client's tree: ONE
    fold for every apply route, timed as the second half of a tiled
    Receive's `recv_tree_fold` (the first is the delta decode in
    `ops.merkle_ops`) and counted. The device planners hand over
    `MinuteDeltas` (sorted minutes), the host routes a
    {base3-minute-key: delta} dict; either way a tree that is text
    folds as text (`route=text`), and what the native pass refuses, or
    a tree that is not text, takes the dict route (`route=dict`)."""
    with anatomy.part("tree_fold"):
        if isinstance(deltas, MinuteDeltas):
            tree, nodes = fold_minute_deltas(merkle_tree, deltas)
        else:
            tree, nodes = fold_key_deltas(merkle_tree, deltas)
    route = "text" if isinstance(tree, MerkleText) else "dict"
    metrics.inc_many((("evolu_merkle_fold_minutes_total", len(deltas), {}),
                      ("evolu_merkle_fold_calls_total", 1, {}),
                      ("evolu_merkle_fold_nodes_total", nodes, {}),
                      ("evolu_merkle_fold_route_total", 1, {"route": route})))
    return tree


def _upsert_sql(table: str, column: str) -> str:
    """Hostile table/column names from the wire must not splice SQL:
    identifiers are quote-doubled (same as the C++ layer)."""
    t, c = quote_ident(table), quote_ident(column)
    # Explicit conflict target: targetless DO UPDATE needs SQLite >=
    # 3.35, the "id" PK spelling works on every 3.24+ (this container
    # runs 3.34). Same text in native/evolu_host.cpp::upsert_sql.
    return (
        f"INSERT INTO {t} (\"id\", {c}) VALUES (?, ?) "
        f"ON CONFLICT(\"id\") DO UPDATE SET {c} = ?"
    )


def apply_messages_sequential(
    db: PySqliteDatabase, merkle_tree: dict, messages: Sequence[CrdtMessage],
    changes=None,
) -> dict:
    """The reference loop, message by message.

    On the C++ backend the whole loop (winner check, upsert, insert)
    runs as one native call returning the XOR mask; on the Python
    backend it is O(n) SQL round trips. `changes` is an optional
    `storage.changes.ChangedSet` implementing the invalidation
    contract (ISSUE 9)."""
    from evolu_tpu.core.crdt_types import apply_typed_ops, load_schema
    from evolu_tpu.storage.changes import record_batch, record_typed_tables

    record_batch(changes, messages)
    schema = load_schema(db)
    typed = (
        [m for m in messages if schema.is_typed(m.table, m.column)]
        if schema else []
    )
    use_native = hasattr(db, "apply_sequential") and not typed and not any(
        "\x00" in m.timestamp or "\x00" in m.table or "\x00" in m.row
        or "\x00" in m.column
        for m in messages
    )  # the C path's char* ABI is NUL-terminated (binds AND winner
    # compares); NUL-bearing wire fields must take the Python loop to
    # bind full bytes like the reference (the batched production path
    # is NUL-exact natively). Typed batches take the Python loop too:
    # the native loop would LWW-upsert raw op values into app tables.
    entry = ledger.pending()
    entry.count(ledger.APPLY_INGRESS, len(messages))
    entry.count(ledger.ROUTE_SEQUENTIAL, len(messages))
    entry.count(ledger.ROUTE_TYPED, len(typed))
    try:
        if use_native:
            xor_mask = db.apply_sequential(messages)
            for m, flagged in zip(messages, xor_mask):
                if flagged:
                    merkle_tree = insert_into_merkle_tree(
                        timestamp_from_string(m.timestamp), merkle_tree
                    )
            # The native loop reports xor flags only: a row that XORed
            # but lost its cell is indistinguishable from a winner here,
            # so the sequential-route split is coarser (inserted = XORed)
            # than the batched routes'. The equation sums still balance.
            n_xor = _mask_sum(xor_mask)
            entry.count(ledger.APPLY_INSERTED, n_xor)
            entry.count(ledger.APPLY_DUPLICATE, len(messages) - n_xor)
            entry.commit()
            return merkle_tree
        if typed:
            # Fold + materialize BEFORE the loop inserts any __message
            # row: the dedup screen must observe pre-batch state (same
            # contract as the batched path). xor/insert semantics below
            # stay the reference's, timestamp-only.
            record_typed_tables(changes)
            apply_typed_ops(db, schema, typed)
        for m in messages:
            rows = db.exec_sql_query(_SELECT_WINNER, (m.table, m.row, m.column))
            t = rows[0]["timestamp"] if rows else None
            won = (t is None or t < m.timestamp)
            if won and not (schema and schema.is_typed(m.table, m.column)):
                db.run(_upsert_sql(m.table, m.column), (m.row, m.value, m.value))
            if t is None or t != m.timestamp:
                db.run(_INSERT_MESSAGE,
                       (m.timestamp, m.table, m.row, m.column, m.value))
                merkle_tree = insert_into_merkle_tree(
                    timestamp_from_string(m.timestamp), merkle_tree
                )
                entry.count(
                    ledger.APPLY_INSERTED if won else ledger.APPLY_LOSING
                )
            else:
                entry.count(ledger.APPLY_DUPLICATE)
        entry.commit()
        return merkle_tree
    except BaseException:
        # The oracle runs statement-at-a-time (no outer transaction
        # here): a mid-loop failure leaves the batch partially applied,
        # and the ledger counts the whole batch as rejected — the
        # conservative classification (route counted above never posts;
        # the pending entry dies with the abort).
        entry.abort()
        ledger.count(ledger.APPLY_INGRESS, len(messages))
        ledger.count(ledger.APPLY_REJECTED, len(messages))
        raise


def fetch_existing_winners(
    db: PySqliteDatabase, cells: Iterable[Tuple[str, str, str]]
) -> Dict[Tuple[str, str, str], str]:
    """Current winner timestamp per cell (cells with none left out),
    through the (table,row,column,timestamp) covering index
    (initDbModel.ts:51-56). The C++ backend probes it once a cell in
    one native call (`fetch_winners`), at any count of cells; the
    stdlib backend, which has no native call, joins the cells as a temp
    table. Counted in `evolu_winner_read_cells_total{route}` and timed
    as the `winner_read` part of a tiled Receive."""
    cells = list(cells)
    if not cells:
        return {}
    with anatomy.part("winner_read"):
        if hasattr(db, "fetch_winners"):
            winners = db.fetch_winners(cells)
            return {c: w for c, w in zip(cells, winners) if w is not None}
        metrics.inc("evolu_winner_read_cells_total", len(cells), route="join")
        with db.transaction():
            db.exec('CREATE TEMP TABLE IF NOT EXISTS "__cells" ("t" BLOB, "r" BLOB, "c" BLOB)')
            db.run('DELETE FROM "__cells"')
            db.run_many('INSERT INTO "__cells" VALUES (?, ?, ?)', cells)
            rows = db.exec_sql_query(
                'SELECT m."table" AS t, m."row" AS r, m."column" AS c, '
                'MAX(m."timestamp") AS w FROM "__message" m '
                'JOIN "__cells" x ON m."table" = x."t" AND m."row" = x."r" AND m."column" = x."c" '
                "GROUP BY m.\"table\", m.\"row\", m.\"column\""
            )
            db.run('DELETE FROM "__cells"')
        return {(r["t"], r["r"], r["c"]): r["w"] for r in rows}


def plan_batch(
    messages: Sequence[CrdtMessage],
    existing_winners: Dict[Tuple[str, str, str], str],
):
    """Compute merge decisions for a batch on host (pure, no SQL).

    Returns (xor_mask, upserts) where xor_mask[i] says message i's hash
    is XORed into the Merkle tree, and upserts maps cell -> (row, column,
    table, value) for cells whose final winner beats the stored one.
    Mirrors the sequential running-max semantics exactly; the device
    kernel (ops.merge.plan_batch_device) computes the same masks with a
    sort + segmented scan.
    """
    xor_mask: List[bool] = [False] * len(messages)
    running: Dict[Tuple[str, str, str], Optional[str]] = {}
    final: Dict[Tuple[str, str, str], CrdtMessage] = {}
    for i, m in enumerate(messages):
        cell = (m.table, m.row, m.column)
        w = running.get(cell, existing_winners.get(cell))
        xor_mask[i] = w is None or w != m.timestamp
        if w is None or w < m.timestamp:
            running[cell] = m.timestamp
            final[cell] = m
        else:
            running[cell] = w
    upserts = [
        m for cell, m in final.items()
        if (existing_winners.get(cell) is None or existing_winners[cell] < m.timestamp)
    ]
    return xor_mask, upserts


def apply_messages(
    db: PySqliteDatabase,
    merkle_tree: dict,
    messages: Sequence[CrdtMessage],
    planner=None,
    changes=None,
) -> dict:
    """Batched apply with end state identical to the sequential oracle.

    `planner` defaults to the host `plan_batch`; the TPU runtime passes
    a device planner with the same contract. `changes` (optional
    `storage.changes.ChangedSet`) collects the (table, rowId) pairs
    this apply touches — the query-invalidation contract (ISSUE 9):
    recording happens here at the apply level, so EVERY plan route
    (device kernel, winner cache, `merge._host_fallback`, hot-owner,
    host oracle, packed `eh_apply_planned_cells`) reports identically,
    and any unrecognizable batch escalates to conservative full
    invalidation inside `record_batch`.
    """
    if not len(messages):
        return merkle_tree
    planner = planner or plan_batch
    # Conservation ledger (obs/ledger.py): routing + terminal counts
    # accumulate into a pending entry and post ONLY when the
    # transaction commits — a rolled-back batch posts apply.rejected
    # instead, so a retry can never double-count.
    entry = ledger.pending()
    try:
        with db.transaction():  # whole-batch atomicity, like the reference's dbTransaction
            tree = _apply_in_txn(db, merkle_tree, messages, planner, changes,
                                 entry)
        entry.commit()
        return tree
    except BaseException:
        # A planner that mutates its own state at plan time (the HBM
        # winner cache) is now ahead of the rolled-back SQLite; let it
        # resynchronize.
        entry.abort()
        ledger.count(ledger.APPLY_INGRESS, len(messages))
        ledger.count(ledger.APPLY_REJECTED, len(messages))
        _notify_plan_failure(planner)
        raise


def _notify_plan_failure(planner) -> None:
    """Fire the planner's transaction-failure hook, if any. The hook
    may sit on the planner function (select_planner's closure) or on a
    bound method's instance (DeviceWinnerCache.plan_batch)."""
    on_failed = getattr(planner, "on_transaction_failed", None)
    if on_failed is None:
        owner = getattr(planner, "__self__", None)
        on_failed = getattr(owner, "on_transaction_failed", None)
    if on_failed is not None:
        on_failed()


def _apply_in_txn(db, merkle_tree, messages, planner, changes=None,
                  entry=None):
    """Dispatch inside the transaction: a PackedReceive batch (the
    fused receive leg) takes the columnar plan+apply when both the
    planner and the backend support it; everything else — and every
    packed batch the planner bounces (non-canonical case, host-oracle
    shapes, small batches) — materializes to CrdtMessage objects and
    runs the standard path, so behavior and error surfaces are
    identical either way (test-pinned)."""
    from evolu_tpu.core.packed import PackedReceive
    from evolu_tpu.core.crdt_types import load_schema
    from evolu_tpu.storage.changes import record_batch

    if entry is None:
        entry = ledger.pending()  # discarded; direct callers are tests
    entry.count(ledger.APPLY_INGRESS, len(messages))
    # Record BEFORE routing: the touched (table, row) set is the same
    # on every route, and recording first means a route that later
    # fails half-way still lands in a superset changed-set.
    record_batch(changes, messages)
    if isinstance(messages, PackedReceive):
        schema = load_schema(db)
        if schema and schema.has_typed(messages.cells):
            # Typed cells in a packed batch bounce to the object path
            # BEFORE any side effect (the r5 packed-receive contract,
            # extended): the packed C cell-apply would LWW-upsert raw
            # op values, and the typed fold needs message objects.
            metrics.inc("evolu_crdt_packed_bounces_total")
            metrics.inc("evolu_apply_packed_bounces_total")
            messages = messages.to_messages()
            metrics.inc("evolu_apply_batches_total", route="object")
            return _apply_messages_in_txn(db, merkle_tree, messages, planner,
                                          changes, entry)
        plan_packed = getattr(planner, "plan_packed", None)
        if plan_packed is not None and hasattr(db, "apply_planned_cells"):
            plan = plan_packed(messages)
            if plan is not None:
                metrics.inc("evolu_apply_batches_total", route="packed")
                xor_mask, upsert_mask, deltas = plan
                db.apply_planned_cells(messages, upsert_mask)
                # Packed terminals from the positional masks (already
                # host numpy — the plan was just applied to SQLite, so
                # no device pull happens here): winners are upserts,
                # XORed non-winners lost, the rest are duplicates.
                n, n_xor, n_win = (len(messages), _mask_sum(xor_mask),
                                   _mask_sum(upsert_mask))
                entry.count(ledger.ROUTE_PACKED, n)
                entry.count(ledger.APPLY_INSERTED, n_win)
                entry.count(ledger.APPLY_LOSING, n_xor - n_win)
                entry.count(ledger.APPLY_DUPLICATE, n - n_xor)
                return _fold_tree(merkle_tree, deltas)
        # The packed batch bounced (non-canonical shape, small batch,
        # hot-owner route, or a backend without the cell apply):
        # materialize and take the object path below.
        metrics.inc("evolu_apply_packed_bounces_total")
        messages = messages.to_messages()
    metrics.inc("evolu_apply_batches_total", route="object")
    return _apply_messages_in_txn(db, merkle_tree, messages, planner, changes,
                                  entry)


def _apply_messages_in_txn(db, merkle_tree, messages, planner, changes=None,
                           entry=None):
    if entry is None:
        entry = ledger.pending()  # discarded; direct callers are tests
    entry.count(ledger.ROUTE_OBJECT, len(messages))
    # `fetches_winners` may sit on the planner function or, for bound
    # methods (DeviceWinnerCache.plan_batch), on the owning instance.
    owner = getattr(planner, "__self__", None)
    fetches = getattr(planner, "fetches_winners",
                      getattr(owner, "fetches_winners", True))
    if fetches:
        cells = {(m.table, m.row, m.column) for m in messages}
        existing = fetch_existing_winners(db, cells)
    else:
        existing = {}  # the planner owns its winner source (HBM cache)
    plan = planner(messages, existing)
    from evolu_tpu.core.crdt_types import apply_typed_ops, load_schema

    schema = load_schema(db)
    typed = (
        [m for m in messages if schema.is_typed(m.table, m.column)]
        if schema else []
    )
    if typed:
        # Typed cells: fold new ops into merge state + materialize
        # (BEFORE the __message insert below — the dedup screen reads
        # pre-batch state), and strip their LWW upserts from whatever
        # planner produced the plan (ONE copy: ops.merge).
        from evolu_tpu.ops.merge import strip_typed_upserts
        from evolu_tpu.storage.changes import record_typed_tables

        record_typed_tables(changes)
        apply_typed_ops(db, schema, typed)
        plan = strip_typed_upserts(plan, messages, schema)
        # Tally station (outside the flow equations): typed messages
        # still ride the object route's __message insert below; their
        # LWW upserts were just stripped, so their terminal split leans
        # on the XOR flag alone.
        entry.count(ledger.ROUTE_TYPED, len(typed))
    if len(plan) == 3:
        # Device planner: masks AND per-minute Merkle deltas in one
        # dispatch (no per-message Python hashing).
        xor_mask, upserts, deltas = plan
    else:
        xor_mask, upserts = plan
        # Merkle deltas: the shared oracle-exact fold (verbatim node
        # case). Computed BEFORE any write so a malformed timestamp
        # rolls the whole batch back — committing messages whose
        # hashes never reach the tree would diverge the digest
        # permanently.
        deltas, _ = minute_deltas_host(
            m.timestamp for i, m in enumerate(messages) if xor_mask[i]
        )

    if hasattr(db, "apply_planned"):
        # C++ backend: upserts + bulk __message insert in one call.
        mask = getattr(plan, "upsert_mask", None)
        if mask is None:
            # Host planners return upserts only; rebuild the
            # positional mask keyed by cell+timestamp, flagging only
            # the FIRST occurrence of each winner key — a duplicate
            # timestamp with a different value must not upsert
            # twice, or the end state would diverge from the Python
            # path, which applies the planner's single chosen
            # winner. (Device planners carry the positional mask,
            # PlannedBatch, skipping this per-message pass.)
            pending = {(m.table, m.row, m.column, m.timestamp) for m in upserts}
            mask = []
            for m in messages:
                key = (m.table, m.row, m.column, m.timestamp)
                mask.append(key in pending)
                pending.discard(key)
        db.apply_planned(messages, mask)
        n_win = _mask_sum(mask)
    else:
        # App tables: only the final winner per cell touches the row.
        for m in upserts:
            db.run(_upsert_sql(m.table, m.column), (m.row, m.value, m.value))

        # __message: bulk insert, PK dedup handles duplicates.
        db.run_many(
            _INSERT_MESSAGE,
            [(m.timestamp, m.table, m.row, m.column, m.value) for m in messages],
        )
        n_win = len(upserts)

    # Terminal classification from masks already on host (never a
    # device pull — device planners return pulled numpy): winners
    # upserted, XORed non-winners lost LWW, the rest exact duplicates.
    n_xor = _mask_sum(xor_mask)
    entry.count(ledger.APPLY_INSERTED, n_win)
    entry.count(ledger.APPLY_LOSING, n_xor - n_win)
    entry.count(ledger.APPLY_DUPLICATE, len(messages) - n_xor)

    # One sparse-tree pass (pure, cannot fail after commit).
    return _fold_tree(merkle_tree, deltas)


def apply_messages_log_only(
    db: PySqliteDatabase,
    merkle_tree: dict,
    messages: Sequence[CrdtMessage],
    changes=None,
) -> dict:
    """Partial replication (ISSUE 18, sync/scope.py): land a batch in
    the __message log and the Merkle tree WITHOUT materializing
    app-table rows — the apply route for out-of-scope tables on a
    scoped client. The log rows and tree deltas are byte-identical to
    a full apply's (convergence and anti-entropy never see the
    difference); only the upsert step is skipped, with every skipped
    message tallied at `apply.deferred_mat` so the deferred frontier is
    counted, never silent. A later `widen()` re-materializes these
    rows from the log in LWW order (runtime/worker.py). Same pending-
    entry/transaction discipline as `apply_messages` — a rolled-back
    batch posts apply.rejected."""
    if not len(messages):
        return merkle_tree
    from evolu_tpu.storage.changes import record_batch

    entry = ledger.pending()
    try:
        with db.transaction():
            entry.count(ledger.APPLY_INGRESS, len(messages))
            entry.count(ledger.ROUTE_OBJECT, len(messages))
            # Recorded even though nothing materializes: invalidation
            # must stay conservative for queries that (wrongly) read a
            # deferred table — they re-run and hit the typed deferral.
            record_batch(changes, messages)
            cells = {(m.table, m.row, m.column) for m in messages}
            existing = fetch_existing_winners(db, cells)
            xor_mask, upserts = plan_batch(messages, existing)
            # Host fold only: deferred batches are out-of-scope tables
            # — rare relative to the hot path, never worth a dispatch.
            deltas, _ = minute_deltas_host(
                m.timestamp for i, m in enumerate(messages) if xor_mask[i]
            )
            db.run_many(
                _INSERT_MESSAGE,
                [(m.timestamp, m.table, m.row, m.column, m.value)
                 for m in messages],
            )
            n_xor = _mask_sum(xor_mask)
            entry.count(ledger.APPLY_INSERTED, len(upserts))
            entry.count(ledger.APPLY_LOSING, n_xor - len(upserts))
            entry.count(ledger.APPLY_DUPLICATE, len(messages) - n_xor)
            entry.count(ledger.APPLY_DEFERRED_MAT, len(messages))
            tree = _fold_tree(merkle_tree, deltas)
        entry.commit()
        return tree
    except BaseException:
        entry.abort()
        ledger.count(ledger.APPLY_INGRESS, len(messages))
        ledger.count(ledger.APPLY_REJECTED, len(messages))
        raise


class ChunkedApplyError(Exception):
    """A chunk failed after earlier chunks committed. `partial_tree`
    reflects every committed chunk and `applied` counts committed
    messages — the caller MUST persist `partial_tree` (e.g. to the
    clock) or the digest permanently diverges from the stored rows."""

    def __init__(self, partial_tree: dict, applied: int, cause: BaseException):
        super().__init__(f"chunked apply failed after {applied} messages: {cause}")
        self.partial_tree = partial_tree
        self.applied = applied
        self.__cause__ = cause


def apply_messages_chunked(
    db: PySqliteDatabase,
    merkle_tree: dict,
    messages: Sequence[CrdtMessage],
    chunk_size: int = 1 << 20,
    planner=None,
    on_chunk=None,
    changes=None,
) -> dict:
    """Blockwise apply for batches too large for one device dispatch.

    The LWW contraction is associative: each chunk's winners become the
    next chunk's stored winners (fetched fresh from SQLite), so folding
    chunks left-to-right is state-identical to one giant batch — the
    "blockwise accumulation over message chunks" strategy for batches
    exceeding HBM (SURVEY.md §5 long-context analog). Each chunk commits
    its own transaction, bounding both device and transaction memory.

    `on_chunk(tree, applied_count)` runs INSIDE the chunk's transaction,
    so the chunk's rows and whatever the callback persists (typically
    the clock with the updated tree) commit atomically — a crash can
    never leave committed __message rows whose hashes missed the
    persisted tree, which would be a permanent digest divergence (the
    re-received winner XORs with xor=false and its hash could never
    re-enter the tree). If a chunk or its callback fails, that whole
    chunk rolls back and `ChunkedApplyError` carries the tree and count
    covering the chunks that DID commit (unlike `apply_messages`,
    failure here is not all-or-nothing — earlier chunks stay committed).
    """
    applied = 0
    for i in range(0, len(messages), chunk_size):
        chunk = messages[i : i + chunk_size]
        try:
            with db.transaction():
                next_tree = apply_messages(db, merkle_tree, chunk, planner,
                                           changes=changes)
                if on_chunk is not None:
                    on_chunk(next_tree, applied + len(chunk))
        except Exception as e:
            # The inner apply_messages only fires the planner's failure
            # hook for exceptions raised inside itself; its transaction
            # JOINS this outer scope, so an `on_chunk` failure rolls
            # the chunk back here AFTER apply returned — the planner
            # (HBM winner cache) must still resynchronize or it keeps
            # phantom winners SQLite never committed (permanent digest
            # divergence on redelivery). Firing twice is harmless: the
            # hook is an idempotent reset.
            _notify_plan_failure(planner or plan_batch)
            raise ChunkedApplyError(merkle_tree, applied, e) from e
        merkle_tree = next_tree
        applied += len(chunk)
    return merkle_tree
