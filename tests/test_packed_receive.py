"""The fused receive leg (r5): `ehc_decrypt_response_columns` →
PackedReceive → packed plan (`plan_packed`) → `eh_apply_planned_cells`.

Reference path being replaced, as ONE leg:
packages/evolu/src/sync.worker.ts:135-173 → receive.ts:144 →
applyMessages.ts:78. The invariant throughout: the packed path either
produces EXACTLY the object path's outcome (state, clock, errors) or
bounces to the object path before any side effect.
"""

import random

import numpy as np
import pytest

from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.core.types import CrdtMessage
from evolu_tpu.storage.apply import apply_messages
from evolu_tpu.storage.native import native_available, open_database
from evolu_tpu.storage.schema import init_db_model
from evolu_tpu.sync import native_crypto, protocol
from evolu_tpu.sync.client import encrypt_messages
from evolu_tpu.utils.config import Config

MN = "legal winner thank year wave sausage worth useful legal winner thank yellow"

pytestmark = pytest.mark.skipif(
    not native_crypto.native_available(), reason="native crypto unavailable"
)


def _mk_msgs(n=400, seed=11, nodes=("a1b2c3d4e5f60718", "ffeeddccbbaa9988")):
    rng = random.Random(seed)
    vals = [
        lambda i: f"título {i} ✓",
        lambda i: i % 2,
        lambda i: None,
        lambda i: i * 0.25,
        lambda i: "x\x00y",  # NUL-bearing value must round-trip
        lambda i: -(2**63) if i % 2 else 2**63 - 1,
        lambda i: "",
    ]
    out = []
    for i in range(n):
        out.append(
            CrdtMessage(
                timestamp_to_string(
                    Timestamp(
                        1_700_000_000_000 + (i // 3) * 977, i % 3, rng.choice(nodes)
                    )
                ),
                rng.choice(["todo", "todoCategory"]),
                f"row{rng.randrange(n // 5 or 1)}",
                rng.choice(["title", "isCompleted"]),
                vals[i % len(vals)](i),
            )
        )
    rng.shuffle(out)
    return out


def _response_bytes(msgs, tree='{"m":1}'):
    enc = encrypt_messages(msgs, MN)
    return protocol.encode_sync_response(protocol.SyncResponse(tuple(enc), tree))


def test_columns_materialization_matches_object_path():
    """decrypt_response_columns must reproduce the object path exactly:
    same messages (incl. NUL/unicode/int64-extreme values), same tree,
    and interning must preserve first-appearance semantics."""
    msgs = _mk_msgs(120)
    resp = _response_bytes(msgs)
    out = native_crypto.decrypt_response_columns(resp, MN)
    assert out is not None
    pb, tree = out
    obj = native_crypto.decrypt_response(resp, MN)
    assert pb.to_messages() == obj[0] == tuple(msgs)
    assert tree == obj[1] == '{"m":1}'
    # Cell interning matches the host interner (first appearance).
    from evolu_tpu.ops.host_parse import intern_cells

    cid, cells = intern_cells(
        [m.table for m in msgs], [m.row for m in msgs], [m.column for m in msgs]
    )
    assert cells == pb.cells
    assert np.array_equal(cid, pb.cell_id)
    # Slices materialize their exact row range.
    assert pb[10:37].to_messages() == tuple(msgs[10:37])


def test_columns_fallbacks_to_object_path():
    """Every non-canonical shape returns None BEFORE any output: a
    demoted ciphertext (gpg-compressed), wrong password, truncated
    wire, a non-46-byte timestamp, and invalid UTF-8 inside decrypted
    content. The object/pure chain then owns the exact error."""
    from pathlib import Path

    msgs = _mk_msgs(8)
    enc = list(native_crypto.encrypt_batch(msgs, MN))
    fixtures = Path(__file__).parent / "fixtures"
    gpg_ct = (fixtures / "gpg_aes256_s2k1024_zip.pgp").read_bytes()
    ts46 = msgs[0].timestamp
    spliced = list(enc)
    spliced.insert(3, protocol.EncryptedCrdtMessage(ts46, gpg_ct))
    resp = protocol.encode_sync_response(protocol.SyncResponse(tuple(spliced), "{}"))
    assert native_crypto.decrypt_response_columns(resp, MN) is None
    # ...but the object path still serves it (oracle demotion).
    assert native_crypto.decrypt_response(resp, MN) is not None

    ok = protocol.encode_sync_response(protocol.SyncResponse(tuple(enc), "{}"))
    assert native_crypto.decrypt_response_columns(ok, "wrong-pw") is None
    assert native_crypto.decrypt_response_columns(ok[:-1], MN) is None

    short_ts = list(enc)
    short_ts[2] = protocol.EncryptedCrdtMessage("short-ts", short_ts[2].content)
    resp = protocol.encode_sync_response(protocol.SyncResponse(tuple(short_ts), "{}"))
    assert native_crypto.decrypt_response_columns(resp, MN) is None
    assert native_crypto.decrypt_response(resp, MN) is not None

    # Invalid UTF-8 inside a decrypted string field: the pure path
    # raises (ValueError family); columns must bounce, not emit bytes
    # Python would reject.
    from evolu_tpu.sync.crypto import encrypt_symmetric

    bad_content = b"\x0a\x02t\xff" + b"\x12\x01r" + b"\x1a\x01c"
    bad = protocol.EncryptedCrdtMessage(ts46, encrypt_symmetric(bad_content, MN))
    resp = protocol.encode_sync_response(protocol.SyncResponse((bad,), "{}"))
    assert native_crypto.decrypt_response_columns(resp, MN) is None
    with pytest.raises(ValueError):
        msgs_out = native_crypto.decrypt_response(resp, MN)
        if msgs_out is None:  # pure-path ownership
            from evolu_tpu.sync.client import decrypt_messages

            decrypt_messages(
                protocol.decode_sync_response(resp).messages, MN
            )


@pytest.mark.skipif(not native_available(), reason="native host unavailable")
def test_packed_apply_state_equals_object_apply():
    """The full fused leg vs the object leg, same response bytes, two
    fresh databases: identical __message rows, app-table rows, and
    Merkle tree — including a second wave on top of stored winners and
    chunked slices."""
    from evolu_tpu.runtime.worker import select_planner

    msgs = _mk_msgs(2000, seed=3)
    resp = _response_bytes(msgs)
    pb, _tree = native_crypto.decrypt_response_columns(resp, MN)

    def mkdb():
        db = open_database(backend="auto")
        init_db_model(db, mnemonic=None)
        for t in ("todo", "todoCategory"):
            db.exec(
                f'CREATE TABLE "{t}" ("id" TEXT PRIMARY KEY, "title" BLOB, '
                '"isCompleted" BLOB)'
            )
        return db

    def dump(db):
        return (
            db.exec_sql_query(
                'SELECT * FROM "__message" ORDER BY "timestamp","table","row","column"',
                (),
            ),
            db.exec_sql_query('SELECT * FROM "todo" ORDER BY "id"', ()),
            db.exec_sql_query('SELECT * FROM "todoCategory" ORDER BY "id"', ()),
        )

    results = {}
    for mode in ("objects", "packed"):
        db = mkdb()
        planner = select_planner(Config(min_device_batch=64), db)
        half = len(msgs) // 2
        b1 = tuple(msgs[:half]) if mode == "objects" else pb[:half]
        b2 = tuple(msgs[half:]) if mode == "objects" else pb[half:]
        t1 = apply_messages(db, {}, b1, planner=planner)
        t2 = apply_messages(db, t1, b2, planner=planner)
        results[mode] = (dump(db), t2)
        db.close()
    assert results["objects"] == results["packed"]


@pytest.mark.skipif(not native_available(), reason="native host unavailable")
def test_packed_noncanonical_case_routes_to_host_oracle():
    """Uppercase node hex is non-canonical: the packed planner must
    bounce (None) and the materialized object path's host oracle must
    produce the reference's raw-string-order state — equal to the
    pure-Python backend applying the same messages."""
    from evolu_tpu.runtime.worker import select_planner

    msgs = _mk_msgs(1500, seed=9, nodes=("A1B2C3D4E5F60718", "ffeeddccbbaa9988"))
    resp = _response_bytes(msgs)
    pb, _tree = native_crypto.decrypt_response_columns(resp, MN)
    assert pb is not None  # ASCII case parses; canonicality is a PLAN concern
    _m, _c, _n, case_ok = pb.parse_timestamps()
    assert not bool(case_ok.all())

    def mk(backend):
        db = open_database(backend=backend)
        init_db_model(db, mnemonic=None)
        for t in ("todo", "todoCategory"):
            db.exec(
                f'CREATE TABLE "{t}" ("id" TEXT PRIMARY KEY, "title" BLOB, '
                '"isCompleted" BLOB)'
            )
        return db

    db_packed = mk("auto")
    planner = select_planner(Config(min_device_batch=64), db_packed)
    assert planner.plan_packed(pb) is None
    tree_packed = apply_messages(db_packed, {}, pb, planner=planner)

    db_pure = mk("python")
    tree_pure = apply_messages(db_pure, {}, tuple(msgs))
    q = 'SELECT * FROM "__message" ORDER BY "timestamp","table","row","column"'
    assert db_packed.exec_sql_query(q, ()) == db_pure.exec_sql_query(q, ())
    assert tree_packed == tree_pure
    db_packed.close(), db_pure.close()


def test_fuzz_columns_never_diverges_from_oracle():
    """Mutation fuzz over response bytes: whenever the columns walker
    accepts the wire, its materialization must equal the pure
    decode+decrypt value exactly. (Columns never accepts an erroring
    wire — any demotion is a None — so an accepted wire implies the
    oracle succeeds too.)"""
    from evolu_tpu.sync.client import decrypt_messages
    from evolu_tpu.sync.crypto import PgpError

    rng = random.Random(29)
    base = _response_bytes(_mk_msgs(6), tree='{"x":1}')
    accepted = 0
    for trial in range(200):
        b = bytearray(base)
        for _ in range(rng.randint(1, 5)):
            op = rng.random()
            if op < 0.6 and b:
                b[rng.randrange(len(b))] ^= 1 << rng.randrange(8)
            elif op < 0.8 and len(b) > 2:
                del b[rng.randrange(len(b))]
            else:
                b.insert(rng.randrange(len(b) + 1), rng.randrange(256))
        data = bytes(b)
        out = native_crypto.decrypt_response_columns(data, MN)
        if out is None:
            continue  # production falls through to the object/pure chain
        accepted += 1
        pb, tree = out
        try:
            resp = protocol.decode_sync_response(data)
            oracle = (decrypt_messages(resp.messages, MN), resp.merkle_tree)
        except (PgpError, ValueError) as e:  # pragma: no cover - divergence
            raise AssertionError(
                f"columns accepted a wire the oracle rejects ({e!r}), trial {trial}"
            )
        assert (pb.to_messages(), tree) == oracle, f"trial {trial}"
    assert accepted  # the fuzz must exercise the accept path at least once


@pytest.mark.skipif(not native_available(), reason="native host unavailable")
def test_packed_streaming_and_nocache_routes_match_oracle():
    """The two packed plan routes that do NOT use HBM-cached winners —
    the adaptive gate's STREAMING mode and a `winner_cache=False`
    deployment — are production-routed and must equal the pure-Python
    oracle's state exactly (they share `plan_packed_streamed`, but each
    entry point is exercised here on purpose)."""
    from evolu_tpu.runtime.worker import select_planner

    msgs = _mk_msgs(1500, seed=31)
    resp = _response_bytes(msgs)
    pb, _tree = native_crypto.decrypt_response_columns(resp, MN)
    q = 'SELECT * FROM "__message" ORDER BY "timestamp","table","row","column"'

    def mk(backend):
        db = open_database(backend=backend)
        init_db_model(db, mnemonic=None)
        for t in ("todo", "todoCategory"):
            db.exec(
                f'CREATE TABLE "{t}" ("id" TEXT PRIMARY KEY, "title" BLOB, '
                '"isCompleted" BLOB)'
            )
        return db

    db_oracle = mk("python")
    tree_oracle = apply_messages(db_oracle, {}, tuple(msgs))
    want = db_oracle.exec_sql_query(q, ())

    # (a) winner_cache off → worker._plan_packed_streamed_nocache.
    db_a = mk("auto")
    planner_a = select_planner(
        Config(min_device_batch=64, winner_cache=False), db_a
    )
    assert getattr(planner_a, "cache", None) is None
    tree_a = apply_messages(db_a, {}, pb, planner=planner_a)
    assert db_a.exec_sql_query(q, ()) == want and tree_a == tree_oracle

    # (b) adaptive streaming mode → DeviceWinnerCache._plan_packed_streamed.
    db_b = mk("auto")
    planner_b = select_planner(Config(min_device_batch=64), db_b)
    cache = planner_b.cache
    cache._streaming = True
    cache._known = set()
    cache._seed_ewma = 1.0  # above seed_lo: the gate stays streaming
    tree_b = apply_messages(db_b, {}, pb, planner=planner_b)
    assert cache._streaming, "the gate left streaming mode unexpectedly"
    assert db_b.exec_sql_query(q, ()) == want and tree_b == tree_oracle
    db_oracle.close(), db_a.close(), db_b.close()


@pytest.mark.skipif(not native_available(), reason="native host unavailable")
def test_worker_receive_packed_equals_objects():
    """DbWorker._receive fed the SAME response as PackedReceive vs
    CrdtMessage tuple: identical database state, clock, and outputs —
    and identical HLC error surfaces (duplicate node)."""
    from evolu_tpu.runtime import messages as rmsg
    from evolu_tpu.runtime.worker import DbWorker

    msgs = _mk_msgs(1600, seed=21)
    resp = _response_bytes(msgs, tree="{}")
    pb, tree = native_crypto.decrypt_response_columns(resp, MN)

    def run(batch):
        db = open_database(backend="auto")
        outputs = []
        worker = DbWorker(
            db,
            Config(min_device_batch=64),
            on_output=outputs.append,
            now=lambda: 1_700_001_000_000,  # past every message: no drift error
        )
        worker.start(mnemonic=MN)
        for t in ("todo", "todoCategory"):
            db.exec(
                f'CREATE TABLE IF NOT EXISTS "{t}" ("id" TEXT PRIMARY KEY, '
                '"title" BLOB, "isCompleted" BLOB)'
            )
        worker.post(rmsg.Receive(batch, tree, None))
        worker.flush()
        state = (
            db.exec_sql_query(
                'SELECT * FROM "__message" ORDER BY "timestamp","table","row","column"',
                (),
            ),
            db.exec_sql_query('SELECT * FROM "todo" ORDER BY "id"', ()),
            # Clock WITHOUT the node suffix: the node id is random per
            # device, so only millis/counter and the tree must match.
            [
                (r["timestamp"][:29], r["merkleTree"])
                for r in db.exec_sql_query(
                    'SELECT "timestamp", "merkleTree" FROM "__clock"', ()
                )
            ],
        )
        kinds = [type(o).__name__ for o in outputs]
        worker.stop()
        db.close()
        return state, kinds

    s_obj, k_obj = run(tuple(msgs))
    s_pk, k_pk = run(pb)
    assert s_obj == s_pk
    assert s_obj[0], "no rows applied — the receive leg never ran"
    assert k_obj == k_pk


@pytest.mark.skipif(not native_available(), reason="native host unavailable")
def test_packed_typed_cells_bounce_before_side_effects():
    """ISSUE 7 satellite: ANY typed cell in a packed batch routes to
    the object path BEFORE side effects (the r5 packed-receive
    contract extended to CRDT column types) — the packed C cell-apply
    would LWW-upsert raw op values, and the typed fold needs message
    objects. Pinned: plan_packed is NEVER consulted, the bounce
    counter moves, and the end state equals the pure object path."""
    from evolu_tpu.core import crdt_list as cl
    from evolu_tpu.core import crdt_types as ct
    from evolu_tpu.obs import metrics
    from evolu_tpu.runtime.worker import select_planner
    from evolu_tpu.storage.schema import update_db_schema
    from evolu_tpu.core.types import TableDefinition

    rng = random.Random(21)
    base = 1_700_000_000_000
    msgs = []
    elem_pool = []
    for i in range(300):
        ts = timestamp_to_string(
            Timestamp(base + i * 977, i % 3, "a1b2c3d4e5f60718"))
        roll = rng.random()
        row = f"row{rng.randrange(20)}"
        if roll < 0.3:
            msgs.append(CrdtMessage(ts, "todo", row, "votes",
                                    rng.randrange(-9, 10)))
        elif roll < 0.5:
            msgs.append(CrdtMessage(ts, "todo", row, "labels",
                                    ct.set_add_value(rng.choice("xyz"))))
        elif roll < 0.65:
            after = rng.choice(elem_pool) if elem_pool and rng.random() < 0.7 \
                else None
            msgs.append(CrdtMessage(ts, "todo", row, "notes",
                                    cl.list_insert_value(f"n{i}", after=after)))
            elem_pool.append(ts)
        elif roll < 0.72 and elem_pool:
            msgs.append(CrdtMessage(ts, "todo", row, "notes",
                                    cl.list_delete_value(rng.choice(elem_pool))))
        else:
            msgs.append(CrdtMessage(ts, "todo", row, "title", f"t{i}"))
    resp = _response_bytes(msgs)
    pb, _tree = native_crypto.decrypt_response_columns(resp, MN)
    assert pb is not None

    def mkdb():
        db = open_database(backend="auto")
        init_db_model(db, mnemonic=None)
        update_db_schema(db, [TableDefinition.of(
            "todo", ("title", "votes:counter", "labels:awset", "notes:list"))])
        return db

    def dump(db):
        return (
            db.exec_sql_query(
                'SELECT * FROM "__message" ORDER BY "timestamp","table","row","column"',
                (),
            ),
            db.exec_sql_query('SELECT * FROM "todo" ORDER BY "id"', ()),
            db.exec_sql_query('SELECT * FROM "__crdt_counter" ORDER BY "row","column"', ()),
            db.exec_sql_query('SELECT * FROM "__crdt_set" ORDER BY "tag"', ()),
            db.exec_sql_query('SELECT * FROM "__crdt_list" ORDER BY "tag"', ()),
            db.exec_sql_query('SELECT * FROM "__crdt_list_kill" ORDER BY "tag"', ()),
        )

    results = {}
    for mode in ("objects", "packed"):
        db = mkdb()
        planner = select_planner(Config(min_device_batch=64), db)
        calls = []
        orig = planner.plan_packed
        planner.plan_packed = lambda p: (calls.append(1), orig(p))[1]
        before = metrics.get_counter("evolu_crdt_packed_bounces_total")
        batch = tuple(msgs) if mode == "objects" else pb
        tree = apply_messages(db, {}, batch, planner=planner)
        if mode == "packed":
            assert not calls, "plan_packed ran on a typed batch"
            assert metrics.get_counter(
                "evolu_crdt_packed_bounces_total") == before + 1
        results[mode] = (dump(db), tree)
        db.close()
    assert results["objects"] == results["packed"]


def test_packed_tensor_cells_bounce_before_side_effects():
    """ISSUE 20 satellite: tensor cells in a packed batch take the
    SAME pre-side-effect bounce as the other typed families — the
    packed C cell-apply would LWW-upsert the raw op JSON where the
    semidirect fold needs message objects. Pinned exactly like the
    ISSUE 7 leg: plan_packed never consulted, the bounce counter
    moves, end state equals the pure object path bit-for-bit."""
    from evolu_tpu.core import crdt_tensor as tz
    from evolu_tpu.core.types import TableDefinition
    from evolu_tpu.obs import metrics
    from evolu_tpu.runtime.worker import select_planner
    from evolu_tpu.storage.schema import update_db_schema

    cfg_sum = tz.parse_tensor_type("tensor:sum:f32:2")
    cfg_max = tz.parse_tensor_type("tensor:max:bf16:3")
    rng = random.Random(20)
    base = 1_700_000_000_000
    msgs = []
    for i in range(200):
        ts = timestamp_to_string(
            Timestamp(base + i * 977, i % 3, "a1b2c3d4e5f60718"))
        roll = rng.random()
        row = f"row{rng.randrange(8)}"
        if roll < 0.35:
            vals = [rng.uniform(-20, 20), rng.uniform(-20, 20)]
            mk = tz.tensor_set_value if rng.random() < 0.3 \
                else tz.tensor_delta_value
            msgs.append(CrdtMessage(ts, "todo", row, "weights",
                                    mk(cfg_sum, vals)))
        elif roll < 0.55:
            vals = [rng.uniform(-8, 8) for _ in range(3)]
            msgs.append(CrdtMessage(ts, "todo", row, "peak",
                                    tz.tensor_delta_value(cfg_max, vals)))
        elif roll < 0.62:  # malformed tensor traffic rides along
            msgs.append(CrdtMessage(ts, "todo", row, "weights",
                                    rng.choice(["junk", '["d","x!"]'])))
        else:
            msgs.append(CrdtMessage(ts, "todo", row, "title", f"t{i}"))
    resp = _response_bytes(msgs)
    pb, _tree = native_crypto.decrypt_response_columns(resp, MN)
    assert pb is not None

    def mkdb():
        db = open_database(backend="auto")
        init_db_model(db, mnemonic=None)
        update_db_schema(db, [TableDefinition.of(
            "todo",
            ("title", "weights:tensor:sum:f32:2", "peak:tensor:max:bf16:3"))])
        return db

    def dump(db):
        return (
            db.exec_sql_query(
                'SELECT * FROM "__message" '
                'ORDER BY "timestamp","table","row","column"', ()),
            db.exec_sql_query('SELECT * FROM "todo" ORDER BY "id"', ()),
            db.exec_sql_query(
                'SELECT * FROM "__crdt_tensor" ORDER BY "tag","column"', ()),
        )

    results = {}
    for mode in ("objects", "packed"):
        db = mkdb()
        planner = select_planner(Config(min_device_batch=64), db)
        calls = []
        orig = planner.plan_packed
        planner.plan_packed = lambda p: (calls.append(1), orig(p))[1]
        before = metrics.get_counter("evolu_crdt_packed_bounces_total")
        batch = tuple(msgs) if mode == "objects" else pb
        tree = apply_messages(db, {}, batch, planner=planner)
        if mode == "packed":
            assert not calls, "plan_packed ran on a tensor batch"
            assert metrics.get_counter(
                "evolu_crdt_packed_bounces_total") == before + 1
        results[mode] = (dump(db), tree)
        db.close()
    assert results["objects"] == results["packed"]


# --- decrypt lanes (ISSUE 32) ---------------------------------------
#
# `ehc_decrypt_response_columns` walks the wire on the caller's thread,
# runs decrypt_one in L lanes and columnarizes in wire order. L is the
# call's own (cores granted, 8, messages / 4,096);
# `ehc_decrypt_response_columns_lanes` takes it as an argument, for
# these tests only. The contract: the blob does not depend on L, and
# neither does the return code.

import ctypes
import functools
import hashlib
import os
import threading

# sha256 of the blob the PARENT commit's library (eeeb05a: one loop, one
# thread) gave for `_response_bytes(_mk_msgs(n))`. The blob holds only
# plaintext, so the random salts of a fresh encryption do not move it.
# Never update: a digest that changes means the blob changed.
PARENT_BLOB_SHA256 = {
    0: "6aa018afa1b1b6dbaf7c42ece2e23558bda166a03579417005d8fad1cc49f6b9",
    1: "161171e7d5e9181ebdabe55e03c779167574815e01819ba040f97e11b0f7ba8d",
    4095: "afbcd3e65a76fc7898bc5f0a9234e7e83c8cbbe94db946a5e0100c8ea3bd2f76",
    8192: "e22903b8d83d063db856ffb4c0b4fd10428e272ffd499f98acfa5d65d1e18b48",
    25000: "b38a2b7a58efb7a4fbc668f81edfc75ae204c2c3b16119ec97a3a34080f4cda4",
}


def _columns_lanes(wire, lanes, password=MN):
    """→ (rc, blob or None, lanes that ran) of the test-only entry."""
    lib = native_crypto.load_library()
    pw = password.encode("utf-8")
    out_p, out_len, ran = ctypes.c_void_p(), ctypes.c_int64(), ctypes.c_int32(-1)
    rc = lib.ehc_decrypt_response_columns_lanes(
        wire, len(wire), pw, len(pw), lanes,
        ctypes.byref(out_p), ctypes.byref(out_len), ctypes.byref(ran))
    if rc != 0:
        return rc, None, ran.value
    try:
        return rc, ctypes.string_at(out_p.value, out_len.value), ran.value
    finally:
        lib.ehc_free(out_p)


@functools.lru_cache(maxsize=None)
def _wire_of(n):
    return _response_bytes(_mk_msgs(n))


@pytest.mark.parametrize("n", sorted(PARENT_BLOB_SHA256))
@pytest.mark.parametrize("lanes", [1, 2, 3, 8])
def test_blob_is_the_parents_at_every_lane_count(lanes, n):
    rc, blob, ran = _columns_lanes(_wire_of(n), lanes)
    assert rc == 0
    # More lanes than messages is fewer lanes, never an empty one.
    assert ran == max(1, min(lanes, n))
    assert hashlib.sha256(blob).hexdigest() == PARENT_BLOB_SHA256[n]
    assert blob == _columns_lanes(_wire_of(n), 1)[1]


@pytest.mark.parametrize("n", [0, 1, 4095, 8191, 8192, 25000])
def test_the_call_chooses_its_lanes_from_the_batch_and_counts_them(n):
    """Under 8,192 messages one lane and no thread (lanes that ran = 1 +
    threads started); above, one for every 4,096, capped by the cores
    this process may run on. Python counts lanes and calls together."""
    from evolu_tpu.obs import metrics

    def counted():
        return (metrics.get_counter("evolu_recv_decrypt_lanes_total"),
                metrics.get_counter("evolu_recv_decrypt_calls_total"))

    want = 1 if n < 8192 else min(len(os.sched_getaffinity(0)), 8, n // 4096)
    lanes0, calls0 = counted()
    pb, tree = native_crypto.decrypt_response_columns(_wire_of(n), MN)
    assert pb.n == n and tree == '{"m":1}'
    assert counted() == (lanes0 + want, calls0 + 1)
    if n:  # a call that gives no batch counts nothing
        assert native_crypto.decrypt_response_columns(_wire_of(n), "wrong-pw") is None
        assert counted() == (lanes0 + want, calls0 + 1)


def test_lanes_entry_refuses_a_lane_count_out_of_range():
    for lanes in (0, -1, 65):
        assert _columns_lanes(_wire_of(1), lanes)[0] == 1


def _mixed_records(n, seed):
    """n messages, each sealed as v1 OpenPGP or as an aead-batch-v1
    record under one of three session salts by `seed`, so both formats
    and a change of session key fall on every lane boundary of 2, 3 and
    8 lanes."""
    from evolu_tpu.sync import aead
    from evolu_tpu.sync.client import encrypt_messages_v2

    msgs = _mk_msgs(n, seed=seed)
    rng = random.Random(seed)
    v1 = encrypt_messages(msgs, MN)
    v2 = []
    for _ in range(3):
        aead.reset_sessions()  # a new session: a new salt, a new key
        v2.append(encrypt_messages_v2(msgs, MN))
    aead.reset_sessions()
    picks = [rng.randrange(4) for _ in range(n)]
    assert len(set(picks)) == 4
    return msgs, tuple((v1, *v2)[k][i] for i, k in enumerate(picks))


@pytest.mark.parametrize("lanes", [2, 3, 8])
def test_mixed_v1_and_v2_records_across_lane_boundaries(lanes):
    msgs, enc = _mixed_records(96, seed=lanes)
    wire = protocol.encode_sync_response(protocol.SyncResponse(enc, "{}"))
    rc1, one, _ = _columns_lanes(wire, 1)
    rc, blob, ran = _columns_lanes(wire, lanes)
    assert (rc1, rc, ran) == (0, 0, lanes) and blob == one
    from evolu_tpu.core.packed import PackedReceive

    pb, _tree = PackedReceive.from_blob(blob)
    assert pb.to_messages() == tuple(msgs)


def _bad_utf8_record(ts):
    from evolu_tpu.sync.crypto import encrypt_symmetric

    return protocol.EncryptedCrdtMessage(
        ts, encrypt_symmetric(b"\x0a\x02t\xff" + b"\x12\x01r" + b"\x1a\x01c", MN))


def _flip_last_byte(record):
    return protocol.EncryptedCrdtMessage(
        record.timestamp, record.content[:-1] + bytes([record.content[-1] ^ 1]))


# 12 messages in 3 lanes of 4: the first, a middle and the last message
# of a lane, and of the batch.
@pytest.mark.parametrize("at", [0, 3, 4, 6, 7, 11])
@pytest.mark.parametrize("fault", ["ciphertext", "timestamp", "utf8"])
def test_a_bad_message_gives_the_one_lane_code_wherever_it_falls(fault, at):
    enc = list(encrypt_messages(_mk_msgs(12), MN))
    ts = enc[at].timestamp
    enc[at] = {
        "ciphertext": lambda: _flip_last_byte(enc[at]),  # the MDC no longer matches
        "timestamp": lambda: protocol.EncryptedCrdtMessage(ts[:-1], enc[at].content),
        "utf8": lambda: _bad_utf8_record(ts),
    }[fault]()
    wire = protocol.encode_sync_response(protocol.SyncResponse(tuple(enc), "{}"))
    codes = {lanes: _columns_lanes(wire, lanes)[0] for lanes in (1, 2, 3, 8)}
    assert set(codes.values()) == {3}, codes
    assert native_crypto.decrypt_response_columns(wire, MN) is None


def test_the_first_failure_in_wire_order_names_the_code():
    """A non-canonical wire shape answers 2, a message that needs the
    object path 3, and as in one loop over the messages the earlier of
    the two decides: at every lane count."""
    enc = list(encrypt_messages(_mk_msgs(12), MN))
    good = protocol.encode_sync_response(protocol.SyncResponse(tuple(enc), "{}"))
    noncanonical = b"\x08\x01"  # a top-level varint field: not wt-2
    enc[9] = _flip_last_byte(enc[9])
    bad = protocol.encode_sync_response(protocol.SyncResponse(tuple(enc), "{}"))
    for lanes in (1, 2, 3, 8):
        assert _columns_lanes(good + noncanonical, lanes)[0] == 2
        assert _columns_lanes(noncanonical + bad, lanes)[0] == 2
        assert _columns_lanes(bad + noncanonical, lanes)[0] == 3
        assert _columns_lanes(good[:-1], lanes)[0] == 2  # truncated tree field


def test_a_bad_message_on_a_real_lane_boundary_bounces_from_python():
    """8,192 messages are two lanes of 4,096 where two cores are
    granted: corrupt the last message of lane 0 or the first of lane 1
    and the Python entry gives None; the object path still serves it."""
    enc = list(encrypt_messages(_mk_msgs(8192), MN))
    for at in (4095, 4096):
        broken = list(enc)
        broken[at] = protocol.EncryptedCrdtMessage("short-ts", enc[at].content)
        wire = protocol.encode_sync_response(protocol.SyncResponse(tuple(broken), "{}"))
        assert native_crypto.decrypt_response_columns(wire, MN) is None
        assert native_crypto.decrypt_response(wire, MN) is not None


def test_two_threads_decrypting_at_once_each_get_their_batch():
    """Each call owns its lanes, contexts and buffers: two Python
    threads in `decrypt_response_columns` at once (ctypes has released
    the interpreter lock) both get their own batch, several times over."""
    batches = {"a": _mk_msgs(8192, seed=5), "b": _mk_msgs(9000, seed=6)}
    wires = {k: _response_bytes(v, tree='{"%s":1}' % k) for k, v in batches.items()}
    want = {k: _columns_lanes(w, 1)[1] for k, w in wires.items()}
    start, failures = threading.Barrier(2), []

    from evolu_tpu.core.packed import PackedReceive

    def worker(k):
        ref, ref_tree = PackedReceive.from_blob(want[k])
        start.wait(timeout=60)
        for _ in range(6):
            pb, tree = native_crypto.decrypt_response_columns(wires[k], MN)
            if (tree, bytes(pb.ts_slab), pb.vblob, pb.cell_blob, pb.cell_id.tolist(),
                    pb.ivals.tolist()) != (ref_tree, bytes(ref.ts_slab), ref.vblob,
                                           ref.cell_blob, ref.cell_id.tolist(),
                                           ref.ivals.tolist()):
                failures.append(k)

    threads = [threading.Thread(target=worker, args=(k,)) for k in wires]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads) and not failures
