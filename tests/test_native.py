"""C++ SQLite host layer: build, interface parity, byte-identical end
state vs the Python backend (SURVEY.md §2.14 "real SQLite via the C API
behind a C++ host layer" + the byte-identical north star)."""

import ctypes
import json
import os
import random
import subprocess
import sys
import threading

import numpy as np
import pytest

from evolu_tpu.core.ids import create_node_id
from evolu_tpu.core.merkle import merkle_tree_to_string
from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.core.types import CrdtMessage
from evolu_tpu.obs import metrics
from evolu_tpu.server import engine as engine_mod
from evolu_tpu.server.engine import BatchReconciler
from evolu_tpu.server.relay import ShardedRelayStore
from evolu_tpu.storage import native
from evolu_tpu.storage.apply import apply_messages, apply_messages_sequential
from evolu_tpu.storage.native import (
    CppSqliteDatabase,
    native_available,
    open_database,
)
from evolu_tpu.storage.schema import init_db_model
from evolu_tpu.storage.sqlite import PySqliteDatabase
from evolu_tpu.sync import protocol

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native host library unavailable"
)


def ts(millis, counter=0, node=None):
    return timestamp_to_string(Timestamp(millis, counter, node or "a" * 16))


def make_messages(n=200, seed=1):
    rng = random.Random(seed)
    nodes = [create_node_id() for _ in range(4)]
    tables = ["todo", "todoCategory"]
    msgs = []
    for i in range(n):
        table = rng.choice(tables)
        row = f"row{rng.randrange(20)}"
        col = rng.choice(["title", "isCompleted", "categoryId"])
        value = rng.choice(["x", "y", 1, 0, None, 3.5, f"v{i}"])
        t = Timestamp(1_700_000_000_000 + rng.randrange(0, 120_000), rng.randrange(4), rng.choice(nodes))
        msgs.append(CrdtMessage(timestamp_to_string(t), table, row, col, value))
    return msgs


def bootstrap(db):
    init_db_model(db, mnemonic=None)
    for table in ("todo", "todoCategory"):
        db.exec(
            f'CREATE TABLE IF NOT EXISTS "{table}" ('
            '"id" TEXT PRIMARY KEY, "title" BLOB, "isCompleted" BLOB, "categoryId" BLOB)'
        )


def dump(db):
    rows = {}
    for table in ("todo", "todoCategory", "__message"):
        rows[table] = db.exec(f'SELECT * FROM "{table}" ORDER BY 1, 2')
    return rows


def test_basic_interface_parity():
    cpp = CppSqliteDatabase()
    py = PySqliteDatabase()
    for db in (cpp, py):
        db.exec('CREATE TABLE "t" ("a", "b")')
        db.run('INSERT INTO "t" VALUES (?, ?)', (1, "x"))
        db.run_many('INSERT INTO "t" VALUES (?, ?)', [(2, None), (3, 2.5), (4, b"\x00\xff")])
    assert cpp.exec('SELECT * FROM "t"') == py.exec('SELECT * FROM "t"')
    assert cpp.exec_sql_query('SELECT "a", "b" FROM "t" WHERE "a" > ?', (1,)) == (
        py.exec_sql_query('SELECT "a", "b" FROM "t" WHERE "a" > ?', (1,))
    )
    assert cpp.run('UPDATE "t" SET "b" = ? WHERE "a" < ?', ("z", 3)) == 2
    cpp.close()
    py.close()


def test_transaction_rollback_and_reentrancy():
    db = CppSqliteDatabase()
    db.exec('CREATE TABLE "t" ("x")')
    with pytest.raises(RuntimeError):
        with db.transaction():
            db.run('INSERT INTO "t" VALUES (1)')
            with db.transaction():  # joins the outer txn
                db.run('INSERT INTO "t" VALUES (2)')
            raise RuntimeError("boom")
    assert db.exec('SELECT COUNT(*) FROM "t"') == [(0,)]
    with db.transaction():
        db.run('INSERT INTO "t" VALUES (3)')
    assert db.exec('SELECT * FROM "t"') == [(3,)]
    db.close()


def test_error_surface():
    from evolu_tpu.core.types import UnknownError

    db = CppSqliteDatabase()
    with pytest.raises(UnknownError):
        db.exec("SELECT nonsense FROM nowhere")
    db.close()


def test_apply_sequential_matches_python_backend():
    msgs = make_messages()
    cpp, py = CppSqliteDatabase(), PySqliteDatabase()
    bootstrap(cpp), bootstrap(py)
    tree_c, tree_p = {}, {}
    with cpp.transaction():
        tree_c = apply_messages_sequential(cpp, tree_c, msgs)
    with py.transaction():
        tree_p = apply_messages_sequential(py, tree_p, msgs)
    assert dump(cpp) == dump(py)
    assert merkle_tree_to_string(tree_c) == merkle_tree_to_string(tree_p)
    cpp.close(), py.close()


def test_apply_batched_matches_python_backend():
    msgs = make_messages(seed=7)
    cpp, py = CppSqliteDatabase(), PySqliteDatabase()
    bootstrap(cpp), bootstrap(py)
    tree_c = apply_messages(cpp, {}, msgs)
    tree_p = apply_messages(py, {}, msgs)
    assert dump(cpp) == dump(py)
    assert merkle_tree_to_string(tree_c) == merkle_tree_to_string(tree_p)
    # Re-applying the same batch is idempotent on state.
    state = dump(cpp)
    apply_messages(cpp, tree_c, msgs)
    assert dump(cpp) == state
    cpp.close(), py.close()


def test_fetch_winners_and_relay_insert():
    db = CppSqliteDatabase()
    bootstrap(db)
    msgs = [
        CrdtMessage(ts(1_700_000_000_000), "todo", "r1", "title", "a"),
        CrdtMessage(ts(1_700_000_060_000), "todo", "r1", "title", "b"),
        CrdtMessage(ts(1_700_000_120_000), "todo", "r2", "title", "c"),
    ]
    with db.transaction():
        apply_messages_sequential(db, {}, msgs)
    winners = db.fetch_winners(
        [("todo", "r1", "title"), ("todo", "r2", "title"), ("todo", "rX", "title")]
    )
    assert winners == [ts(1_700_000_060_000), ts(1_700_000_120_000), None]

    db.exec(
        'CREATE TABLE "message" ("timestamp" TEXT, "userId" TEXT, "content" BLOB, '
        'PRIMARY KEY ("timestamp", "userId"))'
    )
    rows = [(ts(1), "u1", b"\x01\x02"), (ts(2), "u1", b"\x03"), (ts(1), "u1", b"dup")]
    flags = db.relay_insert(rows)
    assert flags == [True, True, False]
    assert db.exec('SELECT COUNT(*) FROM "message"') == [(2,)]
    db.close()


def test_open_database_auto_prefers_native():
    db = open_database(backend="auto")
    assert isinstance(db, CppSqliteDatabase)
    db.close()


def test_end_to_end_client_on_native_backend(tmp_path):
    from evolu_tpu.runtime.client import Evolu

    e = Evolu(db_path=str(tmp_path / "n.db"), backend="native")
    try:
        assert isinstance(e.db, CppSqliteDatabase)
        e.update_db_schema({"todo": ("title",)})
        rid = e.create("todo", {"title": "native"})
        e.worker.flush()
        rows = e.query_once('SELECT "id", "title" FROM "todo"')
        assert rows == [{"id": rid, "title": "native"}]
    finally:
        e.dispose()


def test_closed_database_raises_not_crashes():
    from evolu_tpu.core.types import UnknownError

    db = CppSqliteDatabase()
    db.close()
    with pytest.raises(UnknownError, match="closed"):
        db.exec("SELECT 1")
    with pytest.raises(UnknownError, match="closed"):
        with db.transaction():
            pass
    db.close()  # double close is a no-op


def test_multi_statement_exec_raises_like_python():
    db = CppSqliteDatabase()
    db.exec('CREATE TABLE "a" ("x")')
    db.exec('CREATE TABLE "b" ("x")')
    with pytest.raises(Exception, match="one statement"):
        db.exec('DELETE FROM "a"; DELETE FROM "b"')
    # trailing whitespace/semicolons are fine
    assert db.exec("SELECT 1 ;  ") == [(1,)]
    db.close()


def test_duplicate_timestamp_distinct_values_backend_parity():
    # A hostile peer sends two messages with the SAME timestamp for the
    # same cell but different values: both backends must end identically.
    t = ts(1_700_000_000_000)
    msgs = [
        CrdtMessage(t, "todo", "r1", "title", "A"),
        CrdtMessage(t, "todo", "r1", "title", "B"),
    ]
    cpp, py = CppSqliteDatabase(), PySqliteDatabase()
    bootstrap(cpp), bootstrap(py)
    apply_messages(cpp, {}, msgs)
    apply_messages(py, {}, msgs)
    assert dump(cpp) == dump(py)
    cpp.close(), py.close()


def test_run_on_closed_database_raises():
    from evolu_tpu.core.types import UnknownError

    db = CppSqliteDatabase()
    db.close()
    with pytest.raises(UnknownError, match="closed"):
        db.run("SELECT 1")


def test_trailing_comments_accepted_like_python():
    db = CppSqliteDatabase()
    assert db.exec("SELECT 1; -- done") == [(1,)]
    assert db.exec("SELECT 2; /* trailing\n block */ ;") == [(2,)]
    db.close()


def test_embedded_nul_in_wire_fields_backend_parity():
    """Hostile wire data: table/row/column strings carrying embedded
    NUL bytes must produce byte-identical __message rows on both
    backends (the packed C path binds with explicit byte lengths; a
    NUL-terminated bind would silently truncate). A NUL inside an
    UPSERTED identifier aborts on both backends instead."""
    from evolu_tpu.core.types import UnknownError

    msgs = [
        CrdtMessage(ts(1_700_000_000_000 + i), "todo", f"r\x00ow{i}", "title", f"v\x00al{i}")
        for i in range(5)
    ]
    dumps = []
    for backend in ("python", "native"):
        db = open_database(backend=backend)
        bootstrap(db)
        # No upserts planned (mask all False via planner contract):
        # messages land in __message only, full bytes preserved.
        if hasattr(db, "apply_planned"):
            with db.transaction():
                db.apply_planned(msgs, [False] * len(msgs))
        else:
            with db.transaction():
                db.run_many(
                    'INSERT INTO "__message" ("timestamp", "table", "row", "column", "value") '
                    "VALUES (?, ?, ?, ?, ?) ON CONFLICT DO NOTHING",
                    [(m.timestamp, m.table, m.row, m.column, m.value) for m in msgs],
                )
        dumps.append(db.exec('SELECT * FROM "__message" ORDER BY "timestamp"'))
        db.close()
    assert dumps[0] == dumps[1]
    assert "r\x00ow0" in {r[2] for r in dumps[0]}  # NUL survived, not truncated

    # Upsert with a NUL identifier: Python's quote_ident raises; the C
    # path must refuse too (rc 3), not truncate into a different table.
    db = open_database(backend="native")
    bootstrap(db)
    bad = CrdtMessage(ts(1_700_000_000_001), "to\x00do", "r", "title", "x")
    with pytest.raises(UnknownError):
        with db.transaction():
            db.apply_planned([bad], [True])
    db.close()


def test_null_timestamp_row_does_not_crash_native_backend():
    """SQLite's legacy quirk lets a non-INTEGER BLOB PRIMARY KEY hold
    NULL; a tampered DB must yield defined behavior (NULL = no winner),
    not a null-pointer read, on both the fetch_winners and
    apply_sequential hot paths (ADVICE r1 low)."""
    db = open_database(backend="native")
    bootstrap(db)
    db.run(
        'INSERT INTO "__message" ("timestamp", "table", "row", "column", "value") '
        "VALUES (NULL, 'todo', 'r1', 'title', 'ghost')"
    )
    # fetch_winners: the NULL row is the only row for the cell. MAX/
    # ORDER BY DESC places NULL last, so it is also what the scan sees.
    winners = db.fetch_winners([("todo", "r1", "title")])
    assert winners == [None] or winners == [""] or winners[0] is None
    # apply_sequential: NULL winner treated as absent -> message wins.
    m = CrdtMessage(ts(1_700_000_000_000), "todo", "r1", "title", "real")
    mask = db.apply_sequential([m])
    assert list(mask) == [True]
    rows = db.exec('SELECT "title" FROM "todo" WHERE "id" = \'r1\'')
    assert rows == [("real",)]
    db.close()


def test_packed_query_reader_full_type_matrix():
    """`eh_exec_packed` + `unpack_packed_rows` (SURVEY hot loop #4)
    must reproduce the per-cell path exactly for every SQLite storage
    class — ints at 64-bit extremes, floats incl. inf/-0.0, unicode
    and NUL-bearing text, NUL-bearing blobs, nulls — and the raw bytes
    must be deterministic for an unchanged result set (they are the
    reactive loop's change detector)."""
    from evolu_tpu.storage.native import unpack_packed_rows

    cpp = CppSqliteDatabase()
    py = PySqliteDatabase()
    rows = [
        (1, "plain"), (2, None), (3, 2.5), (4, b"\x00\xff\x00"),
        (2**63 - 1, "max"), (-(2**63), "min"), (6, float("inf")),
        (7, -0.0), (8, "uni ✓ café"), (9, "nul\x00in\x00text"),
        (10, b""), (11, ""),
    ]
    for db in (cpp, py):
        db.exec('CREATE TABLE "t" ("a", "b")')
        db.run_many('INSERT INTO "t" VALUES (?, ?)', rows)
    sql = 'SELECT "a", "b" FROM "t" ORDER BY "a"'
    want = py.exec_sql_query(sql)
    got = cpp.exec_sql_query(sql)  # routes through the packed reader
    assert got == want
    raw1 = cpp.exec_sql_query_packed_raw(sql)
    raw2 = cpp.exec_sql_query_packed_raw(sql)
    assert raw1 == raw2
    assert unpack_packed_rows(raw1) == want
    # Empty result set: header only, parses to [].
    raw_empty = cpp.exec_sql_query_packed_raw('SELECT "a" FROM "t" WHERE "a" = -42')
    assert unpack_packed_rows(raw_empty) == []
    cpp.close(), py.close()


def test_unpack_changed_rows_matches_full_unpack():
    """The r5 row-granular unpack (`unpack_changed_rows`) must produce
    EXACTLY `unpack_packed_rows(raw)` for any pair of consecutive
    result sets — in-place edits (same and different encoded length),
    appends, deletions, reorders, type changes, NULL/blob values, and
    empty↔nonempty transitions — while reusing the previous result's
    dict OBJECTS for rows whose packed bytes are unchanged."""
    import random

    from evolu_tpu.storage.native import (
        native_available,
        open_database,
        unpack_changed_rows,
        unpack_packed_rows,
    )

    if not native_available():
        pytest.skip("native backend unavailable")
    db = open_database(backend="auto")
    db.exec('CREATE TABLE "t" ("id" TEXT PRIMARY KEY, "a" BLOB, "b" BLOB)')
    rng = random.Random(5)
    SQL = 'SELECT * FROM "t" ORDER BY "id"'

    def populate(n, mutate=None):
        db.run('DELETE FROM "t"', ())
        for i in range(n):
            v = (mutate or {}).get(i, f"val{i}")
            db.run('INSERT INTO "t" VALUES (?, ?, ?)',
                   (f"id{i:05d}", v, i * (1.5 if i % 3 else 1)))

    populate(300)
    prev_raw, prev_offs = db.exec_sql_query_packed_raw(SQL, (), with_offsets=True)
    prev_rows = unpack_packed_rows(prev_raw)

    # In-place same-length edit: exactly one fresh dict, rest reused.
    populate(300, {50: "VAL50"})
    raw, offs = db.exec_sql_query_packed_raw(SQL, (), with_offsets=True)
    got = unpack_changed_rows(raw, offs, prev_raw, prev_offs, prev_rows)
    assert got == unpack_packed_rows(raw)
    assert sum(g is p for g, p in zip(got, prev_rows)) == 299

    # Append keeps the whole previous prefix by identity.
    populate(310)
    raw, offs = db.exec_sql_query_packed_raw(SQL, (), with_offsets=True)
    got = unpack_changed_rows(raw, offs, prev_raw, prev_offs, prev_rows)
    assert got == unpack_packed_rows(raw)

    # Random mutation chains (incl. NULL/blob/length changes/shrink).
    for trial in range(40):
        n = rng.randrange(0, 40)
        mutate = {
            i: rng.choice([None, b"\x00\xffbin", "m" * rng.randrange(1, 9), 7, 2.5])
            for i in rng.sample(range(max(n, 1)), min(n, rng.randrange(0, 6)))
        }
        populate(n, mutate)
        raw, offs = db.exec_sql_query_packed_raw(SQL, (), with_offsets=True)
        got = unpack_changed_rows(raw, offs, prev_raw, prev_offs, prev_rows)
        assert got == unpack_packed_rows(raw), trial
        prev_raw, prev_offs, prev_rows = raw, offs, got
    db.close()


# --- heap reservation per native batch (ISSUE 30) ---
#
# `reserve_heap` in native/evolu_host.cpp: before a batched call steps
# its first statement it takes 96 KiB blocks from malloc until they
# cover the batch's input bytes times a constant (8 for `__message` and
# its two indexes, 4 for a single B-tree), at most 32 MiB, and frees
# them in the order taken. What is pinned: the arithmetic (whole blocks,
# nothing under one, exactly the cap above it); on a fresh thread the
# heap's writable extent afterwards, and that a batch under the estimate
# no longer moves it; the end state of every reserving call against the
# Python backend on both sides of one block and of the cap; and the two
# counters, once a call, in the acquisition the call already made.

BLOCK = 96 * 1024
CAP = 32 * 1024 * 1024 // BLOCK * BLOCK  # the whole blocks in half of one glibc heap
INDEXED, ROW = 8, 4  # kHeapPerByteIndexed, kHeapPerByteRow
RESERVED = "evolu_native_heap_reserved_bytes_total"
RESERVE_CALLS = "evolu_native_heap_reserve_calls_total"


def _reserved():
    return metrics.get_counter(RESERVED), metrics.get_counter(RESERVE_CALLS)


def _expected(input_bytes: int, per_byte: int) -> int:
    return min(input_bytes * per_byte, CAP) // BLOCK * BLOCK


@pytest.mark.parametrize("per_byte", [INDEXED, ROW])
def test_reserve_heap_holds_whole_blocks_up_to_the_cap(per_byte):
    lib = native.load_library()
    one = BLOCK // per_byte  # input bytes that ask for exactly one block
    assert lib.eh_reserve_heap(0, per_byte) == 0
    assert lib.eh_reserve_heap(-7, per_byte) == 0
    assert lib.eh_reserve_heap(one - 1, per_byte) == 0
    assert lib.eh_reserve_heap(one, per_byte) == BLOCK
    assert lib.eh_reserve_heap(3 * one + one // 2, per_byte) == 3 * BLOCK
    assert lib.eh_reserve_heap(CAP // per_byte - 1, per_byte) == CAP - BLOCK
    assert lib.eh_reserve_heap(CAP // per_byte, per_byte) == CAP
    assert lib.eh_reserve_heap(1 << 40, per_byte) == CAP
    assert lib.eh_reserve_heap(1 << 62, per_byte) == CAP  # no overflow on the way to the cap


_HEAP_EXTENT_SCRIPT = r"""
import ctypes, json, sys, threading
import numpy as np
from evolu_tpu.core.packed import PackedReceive
from evolu_tpu.storage.native import CppSqliteDatabase, load_library
from evolu_tpu.storage.schema import init_db_model

HEAP = 64 << 20  # glibc's HEAP_MAX_SIZE: a thread arena's heaps are aligned to it
libc = ctypes.CDLL(None)
libc.malloc.restype = ctypes.c_void_p
libc.malloc.argtypes = [ctypes.c_size_t]
libc.free.argtypes = [ctypes.c_void_p]
lib = load_library()


def extent():
    # (heap base, bytes of it that are rw-p) around a pointer malloc just returned
    p = libc.malloc(48)
    libc.free(p)
    base = p & ~(HEAP - 1)
    with open("/proc/self/maps") as maps:
        for line in maps:
            span, perm = line.split()[:2]
            lo, hi = (int(x, 16) for x in span.split("-"))
            if lo <= p < hi:
                assert perm.startswith("rw"), line
                return base, hi - max(lo, base), "[heap]" in line


def batch(n, value_bytes):
    # n messages of 100 cells, each value `value_bytes` of text
    cells = [("todo", "row%04d" % c, "title") for c in range(100)]
    enc = [x.encode() for cell in cells for x in cell]
    ts = b"".join(b"2023-11-14T22:13:20.%03dZ-%04X-a1b2c3d4e5f60718" % (i % 1000, i // 1000)
                  for i in range(n))
    vlens = np.full(n, value_bytes, np.int32)
    return PackedReceive(
        n, ts, cells, (np.arange(n) % 100).astype(np.int32), np.full(n, 3, np.uint8),
        np.zeros(n, np.int64), np.zeros(n, np.float64), vlens,
        np.arange(n, dtype=np.int64) * value_bytes, b"v" * (n * value_bytes),
        b"".join(enc), np.fromiter(map(len, enc), np.int32, len(enc)))


out = {}


def on_a_fresh_thread():
    db = CppSqliteDatabase()
    init_db_model(db, mnemonic=None)
    db.exec('CREATE TABLE "todo" ("id" TEXT PRIMARY KEY, "title" BLOB)')
    pb = batch(2000, 200)  # 0.5 MB of input: an estimate of 4 MB
    out["before"] = extent()
    out["reserved"] = lib.eh_reserve_heap(16 << 20, 1)
    out["after_reserve"] = extent()
    with db.transaction():
        db.apply_planned_cells(pb, np.ones(pb.n, np.uint8))
    out["after_apply"] = extent()
    out["rows"] = db.exec('SELECT COUNT(*) FROM "__message"')[0][0]
    db.close()


t = threading.Thread(target=on_a_fresh_thread)
t.start()
t.join()
print(json.dumps(out))
"""


def test_reserved_heap_is_writable_and_the_batch_no_longer_extends_it():
    """The scratch check of ISSUE 30, kept as a test: a process of its
    own (no other thread shares the arena), a fresh thread, and the
    address space read from /proc/self/maps."""
    if not os.path.exists("/proc/self/maps"):
        pytest.skip("/proc/self/maps is absent: no view of the heap's extent")
    if not hasattr(ctypes.CDLL(None), "gnu_get_libc_version"):
        pytest.skip("not glibc (no gnu_get_libc_version): other allocators grow otherwise")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("MALLOC_", "GLIBC_TUNABLES"))}
    proc = subprocess.run([sys.executable, "-c", _HEAP_EXTENT_SCRIPT], cwd=root, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    base, writable, main_arena = out["after_reserve"]
    assert not main_arena, "a fresh thread drew the main arena"
    assert out["reserved"] == (16 << 20) // BLOCK * BLOCK
    assert writable >= out["reserved"], out
    assert out["before"][1] < 4 << 20, out  # the reservation is what grew it
    assert out["rows"] == 2000
    base2, writable2, _ = out["after_apply"]
    assert base2 == base
    # 2,000 messages of 0.5 MB asked malloc for several MB of pages and
    # journal; the extent moved by less than one block.
    assert 0 <= writable2 - writable < BLOCK, out


def _client_db(backend):
    db = open_database(backend=backend)
    bootstrap(db)
    return db


def _client_dump(db):
    return {t: db.exec_sql_query(f'SELECT * FROM "{t}" ORDER BY 1, 2')
            for t in ("todo", "todoCategory", "__message")}


def _sized_messages(n, value_bytes, seed=30):
    """n messages over 40 cells; `value_bytes` of text each (0: the
    mixed small values of `make_messages`)."""
    rng = random.Random(seed)
    base = make_messages(n, seed)
    if not value_bytes:
        return base
    return [CrdtMessage(m.timestamp, m.table, m.row, m.column,
                        ("%06d" % rng.randrange(10**6)) * (value_bytes // 6))
            for m in base]


def _packed_input_bytes(pb):
    cell_bytes = np.add.reduceat(np.asarray(pb.cell_lens, np.int64),
                                 np.arange(0, len(pb.cell_lens), 3))
    return pb.n * 46 + int(cell_bytes[pb.cell_id].sum()) + int(pb.vlens.sum())


# (messages, value bytes): input under one block's worth (8 KiB at 8 a
# byte), a few blocks, just under the cap (4 MiB of input), over it.
CLIENT_SIZES = {"sub_block": (60, 0), "blocks": (400, 0),
                "under_cap": (3600, 1000), "over_cap": (4400, 1000)}


@pytest.mark.parametrize("size", sorted(CLIENT_SIZES))
def test_apply_planned_cells_end_state_on_both_sides_of_a_block_and_the_cap(size):
    from evolu_tpu.runtime.worker import select_planner
    from evolu_tpu.sync import native_crypto
    from evolu_tpu.sync.client import encrypt_messages
    from evolu_tpu.utils.config import Config

    if not native_crypto.native_available():
        pytest.skip("the PackedReceive comes from the native decrypt")
    mnemonic = "legal winner thank year wave sausage worth useful legal winner thank yellow"
    msgs = _sized_messages(*CLIENT_SIZES[size])
    wire = protocol.encode_sync_response(
        protocol.SyncResponse(tuple(encrypt_messages(msgs, mnemonic)), "{}"))
    pb, _tree = native_crypto.decrypt_response_columns(wire, mnemonic)
    want = _expected(_packed_input_bytes(pb), INDEXED)
    assert {"sub_block": want == 0, "blocks": 0 < want < CAP // 4,
            "under_cap": CAP - 64 * BLOCK < want < CAP, "over_cap": want == CAP}[size], want

    cpp, py = _client_db("native"), _client_db("python")
    packed0 = metrics.get_counter("evolu_apply_batches_total", route="packed")
    bytes0, calls0 = _reserved()
    tree_c = apply_messages(cpp, {}, pb, planner=select_planner(Config(backend="tpu"), cpp))
    bytes1, calls1 = _reserved()
    tree_p = apply_messages(py, {}, msgs)
    assert metrics.get_counter("evolu_apply_batches_total", route="packed") == packed0 + 1
    assert _client_dump(cpp) == _client_dump(py)
    assert merkle_tree_to_string(tree_c) == merkle_tree_to_string(tree_p)
    # The planner's own reads (a temp table of cells) may reserve too.
    assert bytes1 - bytes0 >= want and (calls1 - calls0 >= 1) == (bytes1 > bytes0)
    cpp.close(), py.close()



# --- the multi-row __message insert ---
#
# `MessageInserter` in native/evolu_host.cpp: the planned applies bind
# `__message` rows CHUNK at a time into one multi-row statement, stepped
# once a chunk, and take the single-row statement for a tail under
# CHUNK. What is pinned: on both sides of a chunk, into an empty store
# and into one that holds rows, with timestamps repeated inside the
# batch and against stored rows, the end state equals the Python
# backend's down to the rowids and the index's entries, and the rows
# split between the two statements as `evolu_apply_rows_total` says; a
# call that fails after chunks were stepped leaves `__message` as it
# was; the sequential loop still looks each winner up after the
# previous message's insert.

CHUNK = 64  # kInsertChunk
ROWS = "evolu_apply_rows_total"


def _rows_split():
    return tuple(metrics.get_counter(ROWS, stmt=s) for s in ("multi", "single"))


def _packed_receive(msgs):
    from evolu_tpu.sync import native_crypto
    from evolu_tpu.sync.client import encrypt_messages

    mnemonic = "legal winner thank year wave sausage worth useful legal winner thank yellow"
    wire = protocol.encode_sync_response(
        protocol.SyncResponse(tuple(encrypt_messages(msgs, mnemonic)), "{}"))
    return native_crypto.decrypt_response_columns(wire, mnemonic)[0]


def _full_dump(db):
    """`__message` by rowid, the entries of `index__message` in its
    order with the rowid each points at, the app tables by id (the
    Python backend upserts in its planner's order, so their rowids are
    not its to match)."""
    q = db.exec_sql_query
    return {
        "__message": q('SELECT rowid, * FROM "__message" ORDER BY rowid'),
        "index__message": q('SELECT rowid, "table", "row", "column", "timestamp" '
                            'FROM "__message" INDEXED BY "index__message" '
                            'ORDER BY "table", "row", "column", "timestamp"'),
        **{t: q(f'SELECT * FROM "{t}" ORDER BY "id"') for t in ("todo", "todoCategory")},
    }


def _with_repeats(msgs, stored):
    """`msgs` with timestamps repeated: every fifth replays the message
    before it and every eleventh takes its timestamp and cell with
    another value; with `stored`, every third replays a stored message
    and every thirteenth takes a stored one's timestamp and cell."""
    out = list(msgs)
    for j in range(len(out)):
        if stored and j % 3 == 0:
            out[j] = stored[7 * j % len(stored)]
        elif stored and j % 13 == 6:
            s = stored[j % len(stored)]
            out[j] = CrdtMessage(s.timestamp, s.table, s.row, s.column, f"other{j}")
        elif j % 5 == 2:
            out[j] = out[j - 1]
        elif j % 11 == 4:
            p = out[j - 1]
            out[j] = CrdtMessage(p.timestamp, p.table, p.row, p.column, f"other{j}")
    return out


@pytest.mark.parametrize("stored", [False, True], ids=["empty", "stored"])
@pytest.mark.parametrize("n", [1, CHUNK - 1, CHUNK, CHUNK + 1, 3 * CHUNK + 5])
def test_apply_planned_cells_lands_chunked_rows_as_the_python_backend(n, stored):
    from evolu_tpu.runtime.worker import select_planner
    from evolu_tpu.sync import native_crypto
    from evolu_tpu.utils.config import Config

    if not native_crypto.native_available():
        pytest.skip("the PackedReceive comes from the native decrypt")
    held = make_messages(150, seed=48) if stored else []
    msgs = _with_repeats(make_messages(n, seed=n), held)
    cpp, py = _client_db("native"), _client_db("python")
    planner = select_planner(Config(backend="tpu"), cpp)
    tree_c = apply_messages(cpp, {}, _packed_receive(held), planner=planner) if held else {}
    tree_p = apply_messages(py, {}, held) if held else {}
    assert len(_full_dump(cpp)["__message"]) == len({m.timestamp for m in held})
    packed0 = metrics.get_counter("evolu_apply_batches_total", route="packed")
    multi0, single0 = _rows_split()
    tree_c = apply_messages(cpp, tree_c, _packed_receive(msgs), planner=planner)
    tree_p = apply_messages(py, tree_p, msgs)
    assert metrics.get_counter("evolu_apply_batches_total", route="packed") == packed0 + 1
    multi1, single1 = _rows_split()
    assert (multi1 - multi0, single1 - single0) == (n // CHUNK * CHUNK, n % CHUNK)
    assert _full_dump(cpp) == _full_dump(py)
    assert merkle_tree_to_string(tree_c) == merkle_tree_to_string(tree_p)
    cpp.close(), py.close()


def _hand_packed(cells, cell_ids):
    """A PackedReceive of one text value a row, row i on
    `cells[cell_ids[i]]` (an index past the cells is kept as given)."""
    from evolu_tpu.core.packed import PackedReceive

    n = len(cell_ids)
    enc = [x.encode() for cell in cells for x in cell]
    vals = [b"v%d" % i for i in range(n)]
    vlens = np.fromiter(map(len, vals), np.int32, n)
    return PackedReceive(
        n, "".join(ts(1_700_000_000_000 + 1000 * i) for i in range(n)).encode(), cells,
        np.asarray(cell_ids, np.int32), np.full(n, 3, np.uint8), np.zeros(n, np.int64),
        np.zeros(n, np.float64), vlens, (np.cumsum(vlens) - vlens).astype(np.int64),
        b"".join(vals), b"".join(enc), np.fromiter(map(len, enc), np.int32, len(enc)))


@pytest.mark.parametrize("fault", ["cell_index", "nul_identifier"])
def test_a_failing_planned_apply_leaves_the_message_table_as_it_was(fault):
    """Return code 2 (a cell index past the cells, found before any
    statement steps) and 3 (a NUL in an upserted table's name, met after
    two chunks were stepped) raise, and the caller's transaction takes
    every row back; no row is counted."""
    from evolu_tpu.core.types import UnknownError

    db = _client_db("native")
    apply_messages(db, {}, make_messages(100, seed=3))
    before = _full_dump(db)
    cells = [("todo", "r0", "title"), ("todoCategory", "r1", "title"), ("to\x00do", "r2", "title")]
    n = 3 * CHUNK + 5
    ids = [i % 2 for i in range(n)]
    if fault == "cell_index":
        ids[-1], want = len(cells), "cell index out of range"
    else:
        ids[2 * CHUNK + 3], want = 2, "identifier contains NUL"
    split = _rows_split()
    with pytest.raises(UnknownError, match=want):
        with db.transaction():
            db.apply_planned_cells(_hand_packed(cells, ids), np.ones(n, np.uint8))
    assert _full_dump(db) == before
    assert _rows_split() == split
    # The same batch with every cell index in range lands whole.
    ids = [i % 2 for i in range(n)]
    with db.transaction():
        db.apply_planned_cells(_hand_packed(cells, ids), np.ones(n, np.uint8))
    assert len(_full_dump(db)["__message"]) == len(before["__message"]) + n
    assert _rows_split() == (split[0] + 3 * CHUNK, split[1] + 5)
    db.close()


def test_apply_sequential_looks_each_winner_up_after_the_previous_insert():
    """`eh_apply_sequential` keeps one insert a message, stepped before
    the next message's winner lookup. Each cell gets a message, then an
    older one, then a replay of the first: the older loses to a winner
    that only this batch stored, and the replay XORs nothing. Over
    three chunks, where an insert held back for a chunk would not yet be
    visible to the lookups after it."""
    msgs = []
    for c in range(CHUNK):
        newer = CrdtMessage(ts(1_700_000_000_000 + 1000 * c + 500), "todo", f"r{c}", "title",
                            f"new{c}")
        older = CrdtMessage(ts(1_700_000_000_000 + 1000 * c), "todo", f"r{c}", "title",
                            f"old{c}")
        msgs += [newer, older, newer]
    cpp, py = _client_db("native"), _client_db("python")
    with cpp.transaction():
        flags = cpp.apply_sequential(msgs)
    assert flags == [True, True, False] * CHUNK
    assert [r["title"] for r in cpp.exec_sql_query('SELECT "title" FROM "todo" ORDER BY rowid')] \
        == [f"new{c}" for c in range(CHUNK)]
    with py.transaction():
        apply_messages_sequential(py, {}, msgs)
    assert _full_dump(cpp) == _full_dump(py)
    assert len(_full_dump(cpp)["__message"]) == 2 * CHUNK
    cpp.close(), py.close()


SHARDS = 4
# (rows a shard, content bytes) over 3 live shards of 4: input under one
# block's worth (16 KiB at 4 a byte), a few blocks, under the cap
# (8 MiB of input), over it.
RELAY_SIZES = {"sub_block": (24, 20), "blocks": (600, 120),
               "under_cap": (600, 4100), "over_cap": (600, 5000)}
INSERT_MESSAGE = ('INSERT OR IGNORE INTO "message" ("timestamp", "userId", "content") '
                  'VALUES (?, ?, ?)')
UPSERT_TREE = 'INSERT OR REPLACE INTO "merkleTree" ("userId", "merkleTree") VALUES (?, ?)'


def _relay_pass(rows_per_shard, content_bytes, round_=0):
    """→ (live shards, per-shard [(owner, [(ts, content)])]): two owners
    a shard, the second pushing its first five rows twice."""
    import zlib

    live, groups = (0, 2, 3), []
    rng = random.Random(rows_per_shard * 31 + round_)
    for si in live:
        owners, i = [], 0
        while len(owners) < 2:
            u = f"owner-{si}-{i}"
            if zlib.crc32(u.encode()) % SHARDS == si:
                owners.append(u)
            i += 1
        shard_groups = []
        for o, n in zip(owners, (rows_per_shard // 2, rows_per_shard - rows_per_shard // 2)):
            rows = [(ts(1_700_000_000_000 + (round_ * 10_000 + j) * 977, j % 4,
                        "%016x" % zlib.crc32(o.encode())),
                     bytes(rng.randrange(256) for _ in range(8)) * (content_bytes // 8))
                    for j in range(n)]
            shard_groups.append((o, rows))
        shard_groups.append((owners[1], shard_groups[1][1][:5]))
        groups.append(shard_groups)
    return live, groups


def _native_batch(shard_groups):
    rows = [r for _o, rs in shard_groups for r in rs]
    return ([o for o, _rs in shard_groups], [len(rs) for _o, rs in shard_groups],
            *engine_mod._pack_rows([t for t, _c in rows], [c for _t, c in rows]))


def _relay_input_bytes(groups):
    return sum(46 + len(o.encode()) + len(c)
               for shard_groups in groups for o, rs in shard_groups for _t, c in rs)


def _fold(stored: str, new_ts) -> str:
    from evolu_tpu.core.merkle import (
        apply_prefix_xors, merkle_tree_from_string, minute_deltas_host)

    deltas, _ = minute_deltas_host(iter(new_ts))
    return merkle_tree_to_string(apply_prefix_xors(merkle_tree_from_string(stored), deltas))


@pytest.mark.parametrize("size", sorted(RELAY_SIZES))
def test_shard_set_end_state_on_both_sides_of_a_block_and_the_cap(size):
    from conftest import relay_store_dump

    live, groups = _relay_pass(*RELAY_SIZES[size])
    want = _expected(_relay_input_bytes(groups), ROW)
    assert {"sub_block": want == 0, "blocks": 0 < want < CAP // 4,
            "under_cap": CAP - 64 * BLOCK < want < CAP, "over_cap": want == CAP}[size], want
    cpp = ShardedRelayStore(":memory:", "native", shards=SHARDS)
    py = ShardedRelayStore(":memory:", "python", shards=SHARDS)
    try:
        for round_ in range(2):  # the second on top of stored rows and trees
            if round_:
                live, groups = _relay_pass(*RELAY_SIZES[size], round_=1)
            dbs = [cpp.shards[si].db for si in live]
            bytes0, calls0 = _reserved()
            flags, stored = native.relay_insert_packed_shards(
                dbs, [_native_batch(g) for g in groups])
            assert _reserved() == (bytes0 + want, calls0 + (1 if want else 0))
            tree_rows = []
            for shard_groups, was_new in zip(groups, flags):
                new_ts, pos = {}, 0
                for o, rs in shard_groups:
                    new_ts.setdefault(o, []).extend(
                        t for (t, _c), f in zip(rs, was_new[pos:pos + len(rs)]) if f)
                    pos += len(rs)
                tree_rows.append([(o, _fold(stored[o], tss)) for o, tss in new_ts.items()])
            native.relay_commit_shards(dbs, tree_rows)
            assert _reserved() == (bytes0 + want, calls0 + (1 if want else 0))
            # The Python backend, statement by statement.
            for si, shard_groups in zip(live, groups):
                db = py.shards[si].db
                with db.transaction():
                    new_ts = {}
                    for o, rs in shard_groups:
                        for t, c in rs:
                            if db.run(INSERT_MESSAGE, (t, o, c)) == 1:
                                new_ts.setdefault(o, []).append(t)
                    for o, tss in new_ts.items():
                        db.run(UPSERT_TREE, (o, _fold(py.shards[si].get_merkle_tree_string(o), tss)))
            assert relay_store_dump(cpp) == relay_store_dump(py)
        assert sum(s.stats()[0]["messages"] for s in cpp.shards) == \
            2 * sum(len(rs) for g in groups[:] for _o, rs in g[:2])
    finally:
        cpp.close(), py.close()


# (rows, bytes a text cell): 3 cells a row, one of them NULL.
RUN_MANY_SIZES = {"sub_block": (100, 40), "blocks": (2000, 40),
                  "under_cap": (2000, 2900), "over_cap": (2000, 3400)}


@pytest.mark.parametrize("size", sorted(RUN_MANY_SIZES))
def test_run_many_end_state_on_both_sides_of_a_block_and_the_cap(size):
    n, width = RUN_MANY_SIZES[size]
    rng = random.Random(n + width)
    rows = [(f"k{i:06d}", ("%08d" % rng.randrange(10**8)) * (width // 8),
             None if i % 3 else bytes([i % 256]) * width) for i in range(n)]
    rows += rows[:7]  # OR IGNORE on the primary key
    want = _expected(sum(len(v) for r in rows for v in r if v is not None), ROW)
    assert {"sub_block": want == 0, "blocks": 0 < want < CAP // 4,
            "under_cap": CAP - 64 * BLOCK < want < CAP, "over_cap": want == CAP}[size], want
    cpp, py = CppSqliteDatabase(), PySqliteDatabase()
    bytes0, calls0 = _reserved()
    for db in (cpp, py):
        db.exec('CREATE TABLE "t" ("k" TEXT PRIMARY KEY, "a" TEXT, "b" BLOB)')
        with db.transaction():
            assert db.run_many('INSERT OR IGNORE INTO "t" VALUES (?, ?, ?)', rows) == n
    assert _reserved() == (bytes0 + want, calls0 + (1 if want else 0))
    assert cpp.exec('SELECT * FROM "t" ORDER BY "k"') == py.exec('SELECT * FROM "t" ORDER BY "k"')
    cpp.close(), py.close()


def test_every_reserving_call_posts_its_bytes_once():
    """Each batched entry point: the two counters move by what
    `out_reserved` said, once a call, and not at all under one block or
    for an empty batch."""
    db = CppSqliteDatabase()
    bootstrap(db)
    db.exec('CREATE TABLE "message" ("timestamp" TEXT, "userId" TEXT, "content" BLOB, '
            'PRIMARY KEY ("userId", "timestamp")) WITHOUT ROWID')
    big = "v" * 500
    msgs = [CrdtMessage(ts(1_700_000_000_000 + i), "todo", f"row{i % 9}", "title", big)
            for i in range(300)]
    msg_bytes = sum(46 + 4 + len(m.row) + 5 + 500 for m in msgs)
    relay_rows = [(ts(1_700_000_000_000 + i), "owner-a", b"c" * 300) for i in range(200)]
    relay_bytes = sum(46 + 7 + 300 for _ in relay_rows)
    calls = [
        (lambda: db.apply_sequential(msgs), _expected(msg_bytes, INDEXED)),
        (lambda: db.apply_planned(msgs, [True] * len(msgs)), _expected(msg_bytes, INDEXED)),
        (lambda: db.relay_insert(relay_rows), _expected(relay_bytes, ROW)),
        (lambda: db.relay_insert_packed(
            ["owner-b"], [200], *engine_mod._pack_rows(
                [t for t, _u, _c in relay_rows], [c for _t, _u, c in relay_rows])),
         _expected(relay_bytes, ROW)),
        (lambda: db.run_many('INSERT INTO "todoCategory" ("id", "title") VALUES (?, ?)',
                             [(f"id{i}", big) for i in range(100)]),
         _expected(100 * 505 - 10 - 90, ROW)),
        # Under one block, and empty: nothing is written to the registry.
        (lambda: db.apply_sequential(msgs[:3]), 0),
        (lambda: db.apply_planned(msgs[:3], [True] * 3), 0),
        (lambda: db.relay_insert(relay_rows[:5]), 0),
        (lambda: db.run_many('INSERT INTO "todo" ("id", "title") VALUES (?, ?)', [("a", "b")]), 0),
        (lambda: db.apply_sequential([]), 0),
        (lambda: db.relay_insert([]), 0),
    ]
    with db.transaction():
        for call, want in calls:
            bytes0, calls0 = _reserved()
            call()
            assert _reserved() == (bytes0 + want, calls0 + (1 if want else 0)), want
    assert [want for _c, want in calls[:5]] == sorted(
        [want for _c, want in calls[:5]], reverse=True) and calls[4][1] > 0
    db.close()


class _CountingLock:
    """The registry's lock, counting the acquisitions of one thread."""

    def __init__(self, lock):
        self.lock, self.count, self.thread = lock, 0, threading.get_ident()

    def __enter__(self):
        if threading.get_ident() == self.thread:
            self.count += 1
        return self.lock.__enter__()

    def __exit__(self, *exc):
        return self.lock.__exit__(*exc)


def test_a_pass_storage_leg_still_takes_the_registry_lock_once_a_native_call(monkeypatch):
    """`_store_pass`: BEGIN + insert + stored trees, then upsert +
    COMMIT. Each native call posted one counter before this issue and
    posts one `inc_many` now, the reservation's counters riding with
    `evolu_engine_store_calls_total`."""
    live, groups = _relay_pass(*RELAY_SIZES["blocks"])
    want = _expected(_relay_input_bytes(groups), ROW)
    store = ShardedRelayStore(":memory:", "native", shards=SHARDS)
    engine = BatchReconciler(store)
    lock = _CountingLock(metrics.registry._lock)
    try:
        before = {op: metrics.get_counter("evolu_engine_store_calls_total", op=op)
                  for op in ("insert", "commit")}
        bytes0, calls0 = _reserved()
        monkeypatch.setattr(metrics.registry, "_lock", lock)
        with engine._store_pass(store.shards, list(live),
                                [_native_batch(g) for g in groups]) as (flags, stored, tree_rows):
            assert lock.count == 1
            for si, shard_groups in zip(live, groups):
                tree_rows[si].extend((o, "{}") for o in dict(shard_groups))
        assert lock.count == 2
        monkeypatch.undo()
        assert _reserved() == (bytes0 + want, calls0 + 1) and want > 0
        for op in ("insert", "commit"):
            assert metrics.get_counter("evolu_engine_store_calls_total", op=op) == before[op] + 1
        assert sum(s.stats()[0]["messages"] for s in store.shards) == \
            sum(len(rs) for g in groups for _o, rs in g[:2])
    finally:
        engine.close()
        store.close()


# The stored winners read by one native call (`eh_fetch_winner_keys`):
# a probe of the covering index a cell, at every count of cells.

_NONCANON = "2023-09-01T10:00:00.000Z-0000-ABCDEF0123456789"
_INSERT = ('INSERT INTO "__message" ("timestamp", "table", "row", "column", "value") '
           "VALUES (?, ?, ?, ?, ?) ON CONFLICT DO NOTHING")


def _winner_cells(n_cells):
    """`n_cells` cells, one in four with no stored row, and the rows
    that store the rest: one to three messages a cell, every seventh
    cell's last message replayed (the insert's ON CONFLICT is the
    apply's), cell 2 holding only a NULL timestamp and cell 3 a NULL one
    besides its messages. Identifiers include a NUL byte and non-ASCII
    text, which bind by length."""
    cells = [("todo", f"r{i}", "title") for i in range(n_cells)]
    cells[5] = ("todo", "r\x005", "title")
    cells[6] = ("todoCategory", "ré6", "title")
    rows = []
    for i, cell in enumerate(cells):
        if i % 4 == 0:
            continue
        if i in (2, 3):
            rows.append((None, *cell, "ghost"))
        if i == 2:
            continue
        for j in range(1 + i % 3):
            rows.append((ts(1_700_000_000_000 + 977 * i + 13 * j, j), *cell, f"v{j}"))
        if i % 7 == 1:
            rows.append(rows[-1])
    return cells, rows


def _stored_pair(rows):
    """The same rows in a native store and a stdlib one (whose
    `fetch_existing_winners` is the temp-table join)."""
    cpp, py = CppSqliteDatabase(), PySqliteDatabase()
    for db in (cpp, py):
        bootstrap(db)
        with db.transaction():
            db.run_many(_INSERT, rows)
    return cpp, py


def _cells_pb(cells):
    from evolu_tpu.core.packed import PackedReceive

    blob, lens = native.cell_spans(cells)
    return PackedReceive(0, b"", cells, np.zeros(0, np.int32), *([None] * 6), blob, lens)


def _same_keys(got, want):
    assert got[2] == want[2]
    assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])


@pytest.mark.parametrize("n_cells", [50, 5000])
def test_native_winner_key_read_equals_the_temp_table_join(n_cells):
    """Above and below the 4,096 cells where the C++ backend used to
    switch to the join: the dict, the key columns from the strings, and
    the key columns straight from the native slab agree key for key
    with the join, cells with no winner, replays and NULL timestamps
    included (a NULL-only cell has no winner: MAX ignores it, ORDER BY
    ... DESC puts it last)."""
    from evolu_tpu.ops.merge import read_winner_keys, winner_key_columns
    from evolu_tpu.storage.apply import fetch_existing_winners

    cells, rows = _winner_cells(n_cells)
    cpp, py = _stored_pair(rows)
    join = fetch_existing_winners(py, cells)
    assert join[cells[2]] is None and join[cells[3]] == ts(1_700_000_000_000 + 977 * 3)
    want = {c: w for c, w in join.items() if w is not None}
    native0 = metrics.get_counter("evolu_winner_read_cells_total", route="native")
    assert fetch_existing_winners(cpp, cells) == want
    assert cpp.fetch_winners(cells) == [want.get(c) for c in cells]
    keys = winner_key_columns(cells, want)
    assert keys[2] is True and np.count_nonzero(keys[0]) == len(want)
    _same_keys(read_winner_keys(cpp, cells), keys)
    pb = _cells_pb(cells)
    ids = np.arange(n_cells)[::-1][::3]  # any subset, in any order
    picked = [cells[i] for i in ids]
    _same_keys(read_winner_keys(cpp, picked, pb, ids), winner_key_columns(picked, want))
    assert metrics.get_counter("evolu_winner_read_cells_total", route="native") == \
        native0 + 3 * n_cells + len(ids)
    cpp.close(), py.close()


def test_a_non_canonical_stored_winner_reads_as_not_canonical():
    from evolu_tpu.ops.merge import read_winner_keys, winner_key_columns
    from evolu_tpu.storage.apply import fetch_existing_winners

    cells, rows = _winner_cells(50)
    rows.append((_NONCANON, *cells[8], "upper"))
    cpp, py = _stored_pair(rows)
    join = {c: w for c, w in fetch_existing_winners(py, cells).items() if w is not None}
    assert join[cells[8]] == _NONCANON and fetch_existing_winners(cpp, cells) == join
    keys = winner_key_columns(cells, join)
    assert keys[2] is False
    _same_keys(read_winner_keys(cpp, cells), keys)
    _same_keys(read_winner_keys(cpp, cells, _cells_pb(cells), np.arange(50)), keys)
    cpp.close(), py.close()


@pytest.mark.parametrize("stored", ["x" * 45, "2023-09-01T10:00:00.000Z-0000-abcdef0123456789ff",
                                    "2023-13-01T10:00:00.000Z-0000-abcdef0123456789", 7,
                                    b"2023-09-01T10:00:00.000Z-0000-abcdef0123456789"],
                         ids=["narrow", "wide", "malformed", "integer", "blob"])
def test_a_stored_winner_of_another_form_takes_the_string_path(stored):
    """The native read reports a winner that is not 46 bytes of text
    (and the parse refuses a malformed one): the batch takes the string
    path, so the dict holds the winner exactly as stored and the key
    read fails exactly as `winner_key_columns` does on the join's dict."""
    from evolu_tpu.ops.merge import read_winner_keys, winner_key_columns
    from evolu_tpu.storage.apply import fetch_existing_winners

    cells, rows = _winner_cells(50)
    rows.append((stored, *cells[8], "odd"))
    cpp, py = _stored_pair(rows)
    join = {c: w for c, w in fetch_existing_winners(py, cells).items() if w is not None}
    assert join[cells[8]] == stored and fetch_existing_winners(cpp, cells) == join
    present, _slab = cpp.fetch_winner_keys(*native.cell_spans(cells))
    assert present[8] == (1 if stored == "2023-13-01T10:00:00.000Z-0000-abcdef0123456789" else 2)
    with pytest.raises(Exception) as want:
        winner_key_columns(cells, join)
    for args in ((), (_cells_pb(cells), np.arange(50))):
        with pytest.raises(want.type, match=str(want.value)):
            read_winner_keys(cpp, cells, *args)
    cpp.close(), py.close()


@pytest.mark.parametrize("fault", ["id_past_the_cells", "negative_id", "span_past_the_blob"])
def test_fetch_winner_keys_refuses_what_it_cannot_bind(fault):
    from evolu_tpu.core.types import UnknownError

    db = CppSqliteDatabase()
    bootstrap(db)
    blob, lens = native.cell_spans([("todo", "r1", "title"), ("todo", "r2", "title")])
    ids = {"id_past_the_cells": [0, 2], "negative_id": [-1]}.get(fault)
    if fault == "span_past_the_blob":
        blob = blob[:-1]
    with pytest.raises(ValueError if ids is None else UnknownError):
        db.fetch_winner_keys(blob, lens, ids)
    db.close()


def test_fetch_winners_decodes_in_linear_time():
    """The answer buffer is copied once, not once a cell: 20,000 cells
    read in well under a second, and in under 30 times what 2,000 take
    (a copy a cell makes it ~100 times: each copy grows with the cells
    too). Best of three at each size."""
    import time

    def best_read(n):
        cells, rows = _winner_cells(n)
        db = CppSqliteDatabase()
        bootstrap(db)
        with db.transaction():
            db.run_many(_INSERT, rows)
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            winners = db.fetch_winners(cells)
            times.append(time.perf_counter() - t0)
        assert sum(w is not None for w in winners) == len({r[1:4] for r in rows if r[0]})
        db.close()
        return min(times)

    small, large = best_read(2_000), best_read(20_000)
    assert large < 1.0 and large < 30 * small, (small, large)
