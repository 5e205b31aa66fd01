"""HBM-resident winner cache (ops/winner_cache.py) — state parity with
the streamed-winner production path across multi-batch steady state,
lazy seeding from a pre-populated store, non-canonical fallback with
invalidation, and the transaction-failure resync hook."""

import numpy as np
import pytest

from evolu_tpu.core.merkle import merkle_tree_to_string
from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.core.types import CrdtMessage
from evolu_tpu.ops.winner_cache import DeviceWinnerCache
from evolu_tpu.storage.apply import apply_messages
from evolu_tpu.storage.native import open_database
from evolu_tpu.storage.schema import init_db_model

BASE = 1_700_000_000_000


def _db():
    db = open_database(":memory:", "auto")
    init_db_model(db, mnemonic=None)
    db.exec('CREATE TABLE "todo" ("id" TEXT PRIMARY KEY, "title" BLOB, "done" BLOB)')
    return db


def _mk(i, node="a1b2c3d4e5f60718", row=None, col="title", value=None):
    return CrdtMessage(
        timestamp_to_string(Timestamp(BASE + i * 977, i % 4, node)),
        "todo", row or f"r{i % 23}", col, value if value is not None else f"v{i}",
    )


def _dump(db):
    return (
        db.exec('SELECT * FROM "__message" ORDER BY "timestamp"'),
        db.exec('SELECT * FROM "todo" ORDER BY "id"'),
    )


def test_cache_matches_streamed_path_across_batches():
    """Three successive batches with overlapping cells: the cached
    planner's SQLite end state and tree must equal the streamed-winner
    device planner's, batch by batch."""
    from evolu_tpu.ops.merge import plan_batch_device_full

    rng = np.random.default_rng(11)
    db_a, db_b = _db(), _db()
    cache = DeviceWinnerCache(db_b, capacity=64)  # force growth too
    tree_a, tree_b = {}, {}
    try:
        for batch_no in range(3):
            order = rng.permutation(120)
            batch = tuple(_mk(int(i) + batch_no * 40) for i in order)
            tree_a = apply_messages(db_a, tree_a, batch, planner=plan_batch_device_full)
            tree_b = apply_messages(db_b, tree_b, batch, planner=cache.plan_batch)
            assert _dump(db_a) == _dump(db_b), f"batch {batch_no}"
            assert merkle_tree_to_string(tree_a) == merkle_tree_to_string(tree_b)
    finally:
        db_a.close(), db_b.close()


def test_cache_seeds_from_prepopulated_store():
    """A cache created over a store that already has history must seed
    winners lazily from SQLite — a newer-than-stored message upserts, an
    older one does not."""
    db = _db()
    try:
        tree = apply_messages(db, {}, (_mk(50, row="rX"),))
        cache = DeviceWinnerCache(db, adaptive=False)  # pins lazy-seed behavior
        older = CrdtMessage(
            timestamp_to_string(Timestamp(BASE + 1, 0, "b" * 16)), "todo", "rX", "title", "OLD"
        )
        newer = CrdtMessage(
            timestamp_to_string(Timestamp(BASE + 10**9, 0, "b" * 16)), "todo", "rX", "title", "NEW"
        )
        tree = apply_messages(db, tree, (older,), planner=cache.plan_batch)
        assert db.exec_sql_query('SELECT "title" FROM "todo" WHERE "id" = ?', ("rX",)) == [{"title": "v50"}]
        tree = apply_messages(db, tree, (newer,), planner=cache.plan_batch)
        assert db.exec_sql_query('SELECT "title" FROM "todo" WHERE "id" = ?', ("rX",)) == [{"title": "NEW"}]
    finally:
        db.close()


def test_non_canonical_batch_falls_back_and_invalidates():
    """Uppercase node hex routes to the host oracle (raw-string order,
    verbatim hashing) and drops touched cells so the numeric cache never
    serves a non-canonical winner. End state equals the default path."""
    from evolu_tpu.storage.apply import plan_batch, fetch_existing_winners

    db_a, db_b = _db(), _db()
    cache = DeviceWinnerCache(db_b)
    weird = (
        CrdtMessage("2023-09-01T10:00:00.000Z-0000-ABCDEF0123456789", "todo", "rw", "title", "U"),
        CrdtMessage("2023-09-01T10:00:00.000Z-0000-abcdef0123456789", "todo", "rw", "title", "L"),
    )
    clean_then = (_mk(900, row="rw"),)
    try:
        tree_a = apply_messages(db_a, {}, weird)
        tree_b = apply_messages(db_b, {}, weird, planner=cache.plan_batch)
        assert ("todo", "rw", "title") not in cache._slots  # invalidated
        tree_a = apply_messages(db_a, tree_a, clean_then)
        tree_b = apply_messages(db_b, tree_b, clean_then, planner=cache.plan_batch)
        assert _dump(db_a) == _dump(db_b)
        assert merkle_tree_to_string(tree_a) == merkle_tree_to_string(tree_b)
    finally:
        db_a.close(), db_b.close()


def test_production_routing_through_worker():
    """backend="tpu" + winner_cache (the default) routes client
    receives through the HBM cache: the planner advertises
    fetches_winners=False, the cache fills, end state matches a
    cpu-backend client, and reset_owner drops the cache."""
    from evolu_tpu.core.merkle import merkle_tree_to_string as tree_str
    from evolu_tpu.runtime.client import create_evolu
    from evolu_tpu.storage.clock import read_clock
    from evolu_tpu.utils.config import Config

    schema = {"todo": ("title", "isCompleted")}
    hot = create_evolu(schema, config=Config(backend="tpu", winner_cache=True))
    cpu = create_evolu(schema, config=Config(backend="cpu"), mnemonic=hot.owner.mnemonic)
    try:
        cache = hot.worker._planner.cache
        assert cache is not None and not hot.worker._planner.fetches_winners
        cache.adaptive = False  # pin cached mode: this test asserts slot state
        messages = tuple(_mk(i, node=f"{(i % 5) + 1:016x}") for i in range(300))
        for c in (hot, cpu):
            c.receive(messages, "{}", None)
            c.worker.flush()
        assert cache._slots, "cache never engaged"
        assert (
            hot.db.exec('SELECT * FROM "__message" ORDER BY "timestamp"')
            == cpu.db.exec('SELECT * FROM "__message" ORDER BY "timestamp"')
        )
        assert tree_str(read_clock(hot.db).merkle_tree) == tree_str(
            read_clock(cpu.db).merkle_tree
        )
        hot.reset_owner()
        hot.worker.flush()
        assert not cache._slots  # dropped with the tables
    finally:
        hot.dispose(), cpu.dispose()


def test_slot_reuse_never_leaks_stale_keys():
    """An invalidated cell's slot goes to the free list; when a NEW
    cell (with no SQLite history) reuses it, the slot must read as
    no-winner — not the previous cell's keys, which would wrongly
    suppress the new cell's first upsert."""
    db = _db()
    cache = DeviceWinnerCache(db, adaptive=False)  # slot-state test
    try:
        # Occupy a slot with a large winner for cell rA.
        tree = apply_messages(db, {}, (_mk(10**6, row="rA"),), planner=cache.plan_batch)
        slot_a = cache._slots[("todo", "rA", "title")]
        cache.invalidate([("todo", "rA", "title")])
        assert slot_a in cache._free
        # A brand-new cell reuses the slot; its (small) first message
        # must still upsert.
        small = CrdtMessage(
            timestamp_to_string(Timestamp(BASE, 0, "c" * 16)), "todo", "rNEW", "title", "first"
        )
        tree = apply_messages(db, tree, (small,), planner=cache.plan_batch)
        assert cache._slots[("todo", "rNEW", "title")] == slot_a  # reused
        assert db.exec_sql_query(
            'SELECT "title" FROM "todo" WHERE "id" = ?', ("rNEW",)
        ) == [{"title": "first"}]
        # And the free list does not grow without bound across cycles.
        assert len(cache._free) == 0
    finally:
        db.close()


def test_chunked_on_chunk_failure_fires_cache_resync(tmp_path):
    """apply_messages_chunked: an `on_chunk` failure rolls the chunk
    back AFTER apply_messages returned — the winner cache (already
    scatter-advanced) must still resync, or redelivery sees phantom
    winners (xor=False forever: permanent digest divergence)."""
    from evolu_tpu.core.merkle import merkle_tree_to_string
    from evolu_tpu.storage.apply import ChunkedApplyError, apply_messages_chunked

    db = _db()
    cache = DeviceWinnerCache(db, adaptive=False)  # scatter-ahead state must exist
    msgs = tuple(_mk(i, row=f"c{i}") for i in range(6))
    try:
        with pytest.raises(ChunkedApplyError):
            apply_messages_chunked(
                db, {}, msgs, chunk_size=3, planner=cache.plan_batch,
                on_chunk=lambda tree, n: (_ for _ in ()).throw(RuntimeError("persist failed")),
            )
        assert not cache._slots, "cache kept phantom winners after rollback"
        # Redelivery must fully apply: rows upserted, hashes in tree.
        tree = apply_messages(db, {}, msgs, planner=cache.plan_batch)
        rows = db.exec_sql_query('SELECT COUNT(*) AS n FROM "todo"')
        assert rows == [{"n": 6}]
        db_cmp = _db()
        expect = apply_messages(db_cmp, {}, msgs)
        assert merkle_tree_to_string(tree) == merkle_tree_to_string(expect)
        db_cmp.close()
    finally:
        db.close()


def test_chunked_receive_through_worker_with_cache():
    """Chunked receive with the cache engaged on EVERY chunk
    (backend="tpu" → threshold 0): chunk N+1's stored winners come from
    the HBM scatter of chunk N, not a SQLite re-read — end state must
    equal a cpu-backend whole-batch client, including cross-chunk cell
    overlap where a later chunk carries an OLDER timestamp for a cell
    an earlier chunk already won."""
    from evolu_tpu.core.merkle import merkle_tree_to_string
    from evolu_tpu.runtime.client import create_evolu
    from evolu_tpu.storage.clock import read_clock
    from evolu_tpu.utils.config import Config

    schema = {"todo": ("title", "isCompleted")}
    chunked = create_evolu(
        schema, config=Config(backend="tpu", receive_chunk_size=50)
    )
    whole = create_evolu(
        schema, config=Config(backend="cpu", receive_chunk_size=None),
        mnemonic=chunked.owner.mnemonic,
    )
    # 180 messages over 30 cells: chunks overlap cells, and message
    # order is descending within some cells so later chunks lose.
    messages = tuple(
        _mk((37 * i) % 180, node=f"{(i % 7) + 1:016x}", row=f"r{i % 30}")
        for i in range(180)
    )
    try:
        cache = chunked.worker._planner.cache
        assert cache is not None
        cache.adaptive = False  # pin the HBM scatter chain this test exercises
        for c in (chunked, whole):
            c.receive(messages, "{}", None)
            c.worker.flush()
        assert cache._slots  # engaged across chunks
        assert (
            chunked.db.exec('SELECT * FROM "__message" ORDER BY "timestamp"')
            == whole.db.exec('SELECT * FROM "__message" ORDER BY "timestamp"')
        )
        assert (
            chunked.db.exec('SELECT * FROM "todo" ORDER BY "id"')
            == whole.db.exec('SELECT * FROM "todo" ORDER BY "id"')
        )
        assert merkle_tree_to_string(read_clock(chunked.db).merkle_tree) == \
            merkle_tree_to_string(read_clock(whole.db).merkle_tree)
    finally:
        chunked.dispose(), whole.dispose()


def test_command_level_rollback_resyncs_cache():
    """The livelock SyncError is raised AFTER apply_messages returns,
    inside the worker's one-transaction-per-command scope: the command
    rolls back but the cache already scattered forward. Without the
    command-boundary resync hook, redelivery sees phantom winners —
    xor=False forever (hashes never enter the tree) and beats=False
    (app rows never upserted). Found by tests/test_model_check.py."""
    from evolu_tpu.core.merkle import (
        create_initial_merkle_tree,
        diff_merkle_trees,
        insert_into_merkle_tree,
        merkle_tree_to_string,
    )
    from evolu_tpu.core.types import SyncError
    from evolu_tpu.runtime.client import create_evolu
    from evolu_tpu.storage.clock import read_clock
    from evolu_tpu.utils.config import Config

    schema = {"todo": ("title",)}
    hot = create_evolu(schema, config=Config(backend="tpu"))
    # Pin the static cached path: the adaptive gate would stream a
    # fresh cache's first batches and the scatter-ahead state this
    # regression test exists to exercise would never form.
    hot.worker._planner.cache.adaptive = False
    cpu = create_evolu(schema, config=Config(backend="cpu"), mnemonic=hot.owner.mnemonic)
    msgs = tuple(_mk(i, node="9" * 16, row=f"rl{i}") for i in range(8))
    try:
        # Server tree = post-apply local tree + one phantom hash the
        # client never receives: diff(server, local_after) == phantom's
        # minute. Passing that minute as previous_diff makes _receive
        # apply the batch and THEN raise the livelock SyncError.
        expect_local = create_initial_merkle_tree()
        for m in msgs:
            from evolu_tpu.core.timestamp import timestamp_from_string

            expect_local = insert_into_merkle_tree(
                timestamp_from_string(m.timestamp), expect_local
            )
        phantom = Timestamp(BASE + 10**9, 0, "8" * 16)
        server_tree = insert_into_merkle_tree(phantom, expect_local)
        prev = diff_merkle_trees(server_tree, expect_local)
        assert prev is not None

        errors = []
        hot.subscribe_error(lambda e: errors.append(e))
        for client in (hot, cpu):
            client.receive(msgs, merkle_tree_to_string(server_tree), prev)
            client.worker.flush()
        assert errors and isinstance(errors[-1], SyncError)
        assert hot.db.exec('SELECT COUNT(*) FROM "__message"') == [(0,)]  # rolled back

        # Redelivery must fully apply on BOTH backends identically.
        for client in (hot, cpu):
            client.receive(msgs, "{}", None)
            client.worker.flush()
        assert (
            hot.db.exec('SELECT * FROM "__message" ORDER BY "timestamp"')
            == cpu.db.exec('SELECT * FROM "__message" ORDER BY "timestamp"')
        )
        assert hot.db.exec('SELECT COUNT(*) FROM "todo"') == [(8,)]
        th = merkle_tree_to_string(read_clock(hot.db).merkle_tree)
        tc = merkle_tree_to_string(read_clock(cpu.db).merkle_tree)
        assert th == tc == merkle_tree_to_string(expect_local)
    finally:
        hot.dispose(), cpu.dispose()


def test_transaction_failure_resets_cache():
    """If the transaction rolls back after planning, the cache (already
    scattered forward) must resync — the same message applied again
    must still XOR/upsert correctly."""
    db = _db()
    cache = DeviceWinnerCache(db, adaptive=False)  # scatter-ahead state must exist
    msg = _mk(7, row="rF")
    try:
        real_apply = db.apply_planned
        calls = {"n": 0}

        def exploding(messages, mask):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("disk full")
            return real_apply(messages, mask)

        db.apply_planned = exploding
        with pytest.raises(RuntimeError):
            apply_messages(db, {}, (msg,), planner=cache.plan_batch)
        assert not cache._slots  # reset
        tree = apply_messages(db, {}, (msg,), planner=cache.plan_batch)
        assert db.exec_sql_query('SELECT "title" FROM "todo" WHERE "id" = ?', ("rF",)) == [{"title": "v7"}]
        rows = db.exec_sql_query('SELECT COUNT(*) AS n FROM "__message"')
        assert rows == [{"n": 1}]
        assert tree  # hash entered the tree exactly once
    finally:
        db.apply_planned = real_apply
        db.close()


def test_foreign_write_resets_cache(tmp_path):
    """A SECOND connection writing the same database file moves SQLite's
    data_version; the next plan_batch must drop the cache and re-seed
    from SQLite instead of serving a stale winner (advisor r2: a foreign
    apply could otherwise upsert losers over newer committed winners)."""
    path = str(tmp_path / "shared.db")
    db = open_database(path, "auto")
    init_db_model(db, mnemonic=None)
    db.exec('CREATE TABLE "todo" ("id" TEXT PRIMARY KEY, "title" BLOB, "done" BLOB)')
    cache = DeviceWinnerCache(db, adaptive=False)  # slot-state test
    try:
        tree = apply_messages(db, {}, (_mk(5, row="rF"),), planner=cache.plan_batch)
        assert ("todo", "rF", "title") in cache._slots

        # A foreign connection commits a NEWER winner for the same cell,
        # bypassing this worker (and so the cache) entirely.
        foreign = open_database(path, "auto")
        newer = CrdtMessage(
            timestamp_to_string(Timestamp(BASE + 10**9, 0, "f" * 16)),
            "todo", "rF", "title", "FOREIGN",
        )
        apply_messages(foreign, {}, (newer,))
        foreign.close()

        # An older-than-foreign (but newer-than-local) message must LOSE:
        # with a stale cache it would have won and clobbered "FOREIGN".
        loser = CrdtMessage(
            timestamp_to_string(Timestamp(BASE + 10**6, 0, "c" * 16)),
            "todo", "rF", "title", "LOSER",
        )
        apply_messages(db, tree, (loser,), planner=cache.plan_batch)
        assert db.exec_sql_query(
            'SELECT "title" FROM "todo" WHERE "id" = ?', ("rF",)
        ) == [{"title": "FOREIGN"}]
    finally:
        db.close()


def test_adaptive_gating_crosses_modes_with_identical_state():
    """Hysteresis (VERDICT r2 #3): a churn burst (every batch all-new
    cells) flips the planner to streaming; a steady phase decays the
    EWMA and warms the cache back up; a second burst flips it again.
    End state must equal the static streamed planner throughout."""
    from evolu_tpu.ops.merge import plan_batch_device_full

    rng = np.random.default_rng(21)
    db_a, db_b = _db(), _db()
    cache = DeviceWinnerCache(db_b, capacity=64)
    tree_a, tree_b = {}, {}
    modes = []
    try:
        def batches():
            # burst: 3 batches of brand-new cells each
            for b in range(3):
                yield [_mk(b * 200 + j, row=f"burst{b}_{j % 40}") for j in range(120)]
            # steady: 5 batches over one fixed population
            for b in range(5):
                order = rng.permutation(120)
                yield [_mk(1000 + b * 40 + int(i), row=f"s{int(i) % 23}") for i in order]
            # second burst
            for b in range(3):
                yield [_mk(3000 + b * 200 + j, row=f"b2_{b}_{j % 40}") for j in range(120)]

        for batch in batches():
            batch = tuple(batch)
            tree_a = apply_messages(db_a, tree_a, batch, planner=plan_batch_device_full)
            tree_b = apply_messages(db_b, tree_b, batch, planner=cache.plan_batch)
            modes.append(cache._streaming)
            assert _dump(db_a) == _dump(db_b)
            assert merkle_tree_to_string(tree_a) == merkle_tree_to_string(tree_b)
        # Burst 1 must have triggered streaming; the steady phase must
        # have returned to cached; burst 2 must stream again.
        assert any(modes[:3]), modes
        assert not modes[7], modes  # cached again by the end of steady
        assert any(modes[8:]), modes
    finally:
        db_a.close(), db_b.close()


def test_reset_reseed_batch_does_not_count_as_churn():
    """The first batch after reset() re-seeds every cell it touches;
    that 1.0 new-cell rate is recovery, not churn, and must not flip a
    steady workload into streamed mode (advisor r3: each unrelated
    rollback cost ~3 streamed batches before this fix)."""
    from evolu_tpu.ops.merge import plan_batch_device_full

    rng = np.random.default_rng(33)
    db_a, db_b = _db(), _db()
    cache = DeviceWinnerCache(db_b, capacity=64)
    tree_a, tree_b = {}, {}
    try:
        def steady(base):
            order = rng.permutation(120)
            return tuple(_mk(base + int(i), row=f"s{int(i) % 23}") for i in order)

        for b in range(4):  # settle into cached mode
            batch = steady(b * 40)
            tree_a = apply_messages(db_a, tree_a, batch, planner=plan_batch_device_full)
            tree_b = apply_messages(db_b, tree_b, batch, planner=cache.plan_batch)
        assert not cache._streaming
        ewma_before = cache._seed_ewma

        cache.on_transaction_failed()  # e.g. an unrelated rollback

        for b in range(4, 6):
            batch = steady(b * 40)
            tree_a = apply_messages(db_a, tree_a, batch, planner=plan_batch_device_full)
            tree_b = apply_messages(db_b, tree_b, batch, planner=cache.plan_batch)
            assert not cache._streaming, "re-seed batch was scored as churn"
            assert _dump(db_a) == _dump(db_b)
            assert merkle_tree_to_string(tree_a) == merkle_tree_to_string(tree_b)
        assert cache._seed_ewma <= ewma_before + 1e-9
    finally:
        db_a.close(), db_b.close()


def test_per_batch_foreign_writes_still_reach_streaming(tmp_path):
    """A foreign writer touching the DB between EVERY batch resets the
    cache each time; the skip-once rule must not swallow every 1.0
    re-seed rate or the gate starves and re-seeds the whole cache from
    SQLite forever (r4 review finding) — sustained resets ARE the
    churn signal, so streaming must engage."""
    db = open_database(str(tmp_path / "wc.db"), "auto")
    init_db_model(db, mnemonic=None)
    db.exec('CREATE TABLE "todo" ("id" TEXT PRIMARY KEY, "title" BLOB, "done" BLOB)')
    foreign = open_database(str(tmp_path / "wc.db"), "auto")
    cache = DeviceWinnerCache(db, capacity=64)
    rng = np.random.default_rng(5)
    tree = {}
    try:
        streamed = []
        for b in range(6):
            foreign.exec("CREATE TABLE IF NOT EXISTS _poke (x)")
            foreign.exec("INSERT INTO _poke VALUES (1)")  # moves data_version
            order = rng.permutation(120)
            batch = tuple(_mk(b * 40 + int(i), row=f"s{int(i) % 23}") for i in order)
            tree = apply_messages(db, tree, batch, planner=cache.plan_batch)
            streamed.append(cache._streaming)
        assert any(streamed), (
            f"gate starved: every re-seed rate was suppressed {streamed}"
        )
    finally:
        db.close(), foreign.close()


def test_disable_adaptive_while_streaming_reseeds_safely():
    """Flipping adaptive=False on a cache that is ALREADY streaming
    must fall back to the cached path with a full reseed — not look up
    previously-streamed cells in the (empty) slot table (regression:
    KeyError aborting the apply transaction)."""
    db = _db()
    cache = DeviceWinnerCache(db)
    try:
        first = tuple(_mk(i, row=f"s{i}") for i in range(6))
        tree = apply_messages(db, {}, first, planner=cache.plan_batch)
        assert cache._streaming  # fresh cache streams its first batch
        cache.adaptive = False
        again = tuple(_mk(100 + i, row=f"s{i}") for i in range(6))
        apply_messages(db, tree, again, planner=cache.plan_batch)
        assert not cache._streaming and cache._slots
        assert db.exec_sql_query('SELECT COUNT(*) AS n FROM "__message"') == [{"n": 12}]
    finally:
        db.close()


class _PbStub:
    """The minimal PackedReceive surface `plan_packed` touches before
    the seed branch (n, parse_timestamps, touched_cells, cells,
    cell_id) — enough to drive the adaptive gate without native
    crypto."""

    def __init__(self, messages):
        from evolu_tpu.ops.host_parse import intern_cells

        self.n = len(messages)
        self._ts = [m.timestamp for m in messages]
        self.cell_id, self.cells = intern_cells(
            [m.table for m in messages], [m.row for m in messages],
            [m.column for m in messages],
        )

    def parse_timestamps(self):
        from evolu_tpu.ops.host_parse import parse_timestamp_strings

        return parse_timestamp_strings(self._ts, with_case=True)

    def touched_cells(self):
        ids = np.unique(self.cell_id)
        return ids, [self.cells[int(i)] for i in ids]


def test_plan_packed_seed_failure_samples_ewma_once(monkeypatch):
    """A non-canonical stored-winner seed bounces `plan_packed` to the
    object path, which re-enters the adaptive gate via `plan_batch` for
    the SAME batch — the bounce must arm `_skip_ewma_once` so the gate
    samples the EWMA exactly once per batch (ADVICE r5)."""
    db = _db()
    # adaptive=False pins the gate to the cached route (a fresh
    # adaptive cache's first all-new batch would stream instead of
    # seeding); the EWMA is still sampled on every gate entry, which is
    # exactly the behavior under test.
    cache = DeviceWinnerCache(db, capacity=64, adaptive=False)
    msgs = tuple(_mk(i) for i in range(40))
    try:
        monkeypatch.setattr(
            DeviceWinnerCache, "_seed_new_cells", lambda self, cells, pb=None, ids=None: False
        )
        assert cache.plan_packed(_PbStub(msgs)) is None
        assert cache._skip_ewma_once, "bounce did not arm the one-shot skip"
        ewma_after_packed = cache._seed_ewma
        monkeypatch.setattr(
            DeviceWinnerCache, "_host_fallback", lambda self, m, c: "HOST"
        )
        assert cache.plan_batch(msgs) == "HOST"
        assert cache._seed_ewma == ewma_after_packed, (
            "object-path re-route sampled the EWMA a second time"
        )
    finally:
        db.close()


def test_capacity_cap_drop_and_reseed_eviction():
    """Bounded cache (VERDICT #3): driving the cache past `max_slots`
    with ever-new cells must evict (drop-and-reseed), never grow past
    the cap, and keep the SQLite end state + tree byte-equal to the
    streamed-winner planner's. One batch bigger than the cap itself
    plans streamed (no cache state) — same end state."""
    from evolu_tpu.obs import metrics
    from evolu_tpu.ops.merge import plan_batch_device_full

    db_a, db_b = _db(), _db()
    cache = DeviceWinnerCache(db_b, capacity=16, adaptive=False, max_slots=40)
    tree_a, tree_b = {}, {}
    metrics.reset()
    try:
        # 6 batches × 23 distinct rows (cells rotate via the row key),
        # crossing the 40-slot cap repeatedly.
        for batch_no in range(6):
            batch = tuple(
                _mk(i + batch_no * 23, row=f"cap{batch_no}-{i}") for i in range(23)
            )
            tree_a = apply_messages(db_a, tree_a, batch, planner=plan_batch_device_full)
            tree_b = apply_messages(db_b, tree_b, batch, planner=cache.plan_batch)
            assert len(cache._slots) <= 40, f"batch {batch_no} exceeded the cap"
            assert cache._next_slot <= 64  # device slots stay bounded too
            assert _dump(db_a) == _dump(db_b), f"batch {batch_no}"
            assert merkle_tree_to_string(tree_a) == merkle_tree_to_string(tree_b)
        assert metrics.get_counter("evolu_winner_cache_evictions_total") >= 1

        # A single batch larger than the cap: streamed, still byte-equal.
        big = tuple(_mk(500 + i, row=f"big{i}") for i in range(50))
        tree_a = apply_messages(db_a, tree_a, big, planner=plan_batch_device_full)
        tree_b = apply_messages(db_b, tree_b, big, planner=cache.plan_batch)
        assert _dump(db_a) == _dump(db_b)
        assert merkle_tree_to_string(tree_a) == merkle_tree_to_string(tree_b)
        assert len(cache._slots) <= 40
    finally:
        db_a.close(), db_b.close()


# The stored winners the packed route reads (`read_winner_keys` with the
# batch's interned blob) against the temp-table join.

_INSERT = ('INSERT INTO "__message" ("timestamp", "table", "row", "column", "value") '
           "VALUES (?, ?, ?, ?, ?) ON CONFLICT DO NOTHING")


def _stored_cells(n_cells, extra=()):
    """A native store and a stdlib mirror (whose `fetch_existing_winners`
    is the temp-table join) holding the winners of `n_cells` cells: none
    for every fourth, one or two messages for the rest, every fifth
    replaying its last, cell 2 only a NULL timestamp; `extra` rows
    besides."""
    from evolu_tpu.storage.sqlite import PySqliteDatabase

    cells = [("todo", f"r{i}", "title") for i in range(n_cells)]
    rows = []
    for i, cell in enumerate(cells):
        if i == 2:
            rows.append((None, *cell, "ghost"))
        elif i % 4:
            rows += [(_mk(3 * i + j).timestamp, *cell, f"v{j}") for j in range(1 + i % 2)]
            if i % 5 == 1:
                rows.append(rows[-1])
    rows += list(extra)
    db, py = _db(), PySqliteDatabase()
    init_db_model(py, mnemonic=None)
    for d in (db, py):
        with d.transaction():
            d.run_many(_INSERT, rows)
    return cells, db, py


def _cells_pb(cells, cell_ids):
    """A PackedReceive of one message a row, row i on `cells[cell_ids[i]]`,
    with canonical timestamps newer than any stored."""
    from evolu_tpu.core.packed import PackedReceive
    from evolu_tpu.storage.native import cell_spans

    n = len(cell_ids)
    blob, lens = cell_spans(cells)
    vals = [b"new%d" % i for i in range(n)]
    vlens = np.fromiter(map(len, vals), np.int32, n)
    return PackedReceive(
        n, "".join(_mk(10**6 + i).timestamp for i in range(n)).encode(), cells,
        np.asarray(cell_ids, np.int32), np.full(n, 3, np.uint8), np.zeros(n, np.int64),
        np.zeros(n, np.float64), vlens, (np.cumsum(vlens) - vlens).astype(np.int64),
        b"".join(vals), blob, lens)


@pytest.mark.parametrize("n_cells", [50, 5000])
def test_packed_seeds_read_what_the_join_reads(n_cells):
    """A third of the cells seeded the string way, then every touched
    cell through `plan_packed`'s seed read from the batch's blob: each
    slot holds `winner_key_columns` of the join's dict, at 50 cells and
    above the 4,096 where the read used to switch to the join."""
    from evolu_tpu.ops.merge import winner_key_columns
    from evolu_tpu.ops.winner_cache import _new_cell_ids
    from evolu_tpu.storage.apply import fetch_existing_winners

    cells, db, py = _stored_cells(n_cells)
    cache = DeviceWinnerCache(db, capacity=64, adaptive=False)
    try:
        join = {c: w for c, w in fetch_existing_winners(py, cells).items() if w is not None}
        assert cells[2] not in join and len(join) == n_cells * 3 // 4 - 1
        assert cache._seed_new_cells(cells[::3])
        pb = _cells_pb(cells, np.arange(n_cells)[::-1])
        touched_ids, touched = pb.touched_cells()
        new_cells = [c for c in touched if c not in cache._slots]
        ids = _new_cell_ids(touched, new_cells, touched_ids)
        assert [cells[i] for i in ids] == new_cells
        assert cache._seed_new_cells(new_cells, pb, ids)
        v1, v2, canonical = winner_key_columns(cells, join)
        w1, w2 = cache._gather_slot_values(np.array([cache._slots[c] for c in cells]))
        assert canonical and np.array_equal(w1, v1) and np.array_equal(w2, v2)
    finally:
        db.close(), py.close()


def test_a_non_canonical_stored_winner_bounces_the_packed_routes_as_before():
    """Upper-case hex in a stored winner: the cached route's seed and
    the streamed plan both give the batch back to the object path, with
    no slot for it, as the string read did."""
    from evolu_tpu.obs import metrics
    from evolu_tpu.ops.merge import plan_packed_streamed

    upper = "2023-09-01T10:00:00.000Z-0000-ABCDEF0123456789"
    cells, db, py = _stored_cells(50, [(upper, "todo", "r8", "title", "U")])
    cache = DeviceWinnerCache(db, capacity=64, adaptive=False)
    try:
        pb = _cells_pb(cells, np.arange(50))
        bounced = metrics.get_counter("evolu_winner_cache_noncanonical_seeds_total")
        assert cache.plan_packed(pb) is None
        assert metrics.get_counter("evolu_winner_cache_noncanonical_seeds_total") == bounced + 1
        assert cache._skip_ewma_once and not cache._slots
        millis, counter, node, _ok = pb.parse_timestamps()
        touched_ids, touched = pb.touched_cells()
        assert plan_packed_streamed(db, pb, millis, counter, node, touched, touched_ids) is None
    finally:
        db.close(), py.close()
