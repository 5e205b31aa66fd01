"""CRDT column types beyond the LWW register (ISSUE 7).

Layers under test, host-oracle-first:
1. op codecs + hand-model golden fixtures (tests/fixtures/crdt_golden.json
   — computed BY HAND, pinned, never updated);
2. device kernels (`ops/crdt_merge.py`) bit-identical to the host folds
   on property-sampled op logs (permutation + partition invariance);
3. apply routing: typed cells never LWW-upsert, fold+materialize inside
   the apply transaction, batched == sequential-oracle end state on both
   storage backends, redelivery idempotence;
4. winner-cache contract per type (slot == MAX(timestamp); app value ==
   merge-state fold);
5. end-to-end: 2-relay anti-entropy + snapshot checkpoint carrying
   typed ops crc-identically, capability negotiated.
"""

import json
import random
import time
import zlib
from pathlib import Path

import numpy as np
import pytest

from evolu_tpu.core import crdt_types as ct
from evolu_tpu.core.merkle import create_initial_merkle_tree
from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.core.types import CrdtMessage, TableDefinition
from evolu_tpu.obs import metrics
from evolu_tpu.ops import crdt_merge as cm
from evolu_tpu.storage.apply import apply_messages, apply_messages_sequential
from evolu_tpu.storage.native import native_available, open_database
from evolu_tpu.storage.schema import init_db_model, update_db_schema
from evolu_tpu.utils.config import Config

MN = "legal winner thank year wave sausage worth useful legal winner thank yellow"
GOLDEN = json.loads((Path(__file__).parent / "fixtures" / "crdt_golden.json").read_text())

SCHEMA_DEF = TableDefinition.of(
    "metrics", ("name", "clicks:counter", "tags:awset", "items:list"))


def _mk_db(backend="python"):
    db = open_database(":memory:", backend)
    init_db_model(db, MN)
    update_db_schema(db, [SCHEMA_DEF])
    return db


def _golden_msgs(section):
    cell = section.get("cell")
    out = []
    for op in section["ops"]:
        t, r, c = (op.get("table"), op.get("row"), op.get("column")) if cell is None \
            else cell
        out.append(CrdtMessage(op["timestamp"], op.get("table", t), op.get("row", r),
                               op.get("column", c), op["value"]))
    return out


def _app_value(db, column, row="r1"):
    rows = db.exec_sql_query(
        f'SELECT "{column}" AS v FROM "metrics" WHERE "id" = ?', (row,)
    )
    return rows[0]["v"] if rows else None


# --- 1. codecs ---


def test_column_spec_parsing():
    assert ct.parse_column_spec("title") == ("title", "lww")
    assert ct.parse_column_spec("clicks:counter") == ("clicks", "counter")
    assert ct.parse_column_spec("tags:awset") == ("tags", "awset")
    for bad in ("clicks:bogus", ":counter", "a:b:c"):
        with pytest.raises(ValueError):
            ct.parse_column_spec(bad)


def test_op_codecs_valueerror_only():
    """Typed-op codec fuzz (ISSUE 7 satellite): anything malformed
    raises ValueError and nothing else — mirroring the wire decoder
    contract, so a hostile peer's garbage is always classifiable."""
    assert ct.counter_delta(-5) == -5
    for bad in (True, False, None, "5", 1.5, 2**31, -(2**31), [], {}):
        with pytest.raises(ValueError):
            ct.counter_delta(bad)
    v = ct.set_add_value("red")
    assert ct.decode_set_op(v) == ("a", '"red"', ())
    rv = ct.set_remove_value(7, ["t2", "t1", "t1"])
    assert ct.decode_set_op(rv) == ("r", "7", ("t1", "t2"))
    rng = random.Random(5)
    corpus = [
        None, 5, 1.5, b"x", "", "{", "[]", '["x",1]', '["a"]', '["a",1,2]',
        '["r","e"]', '["r","e","x"]', '["r","e",[5]]', '["a",true]',
        '["a",[1]]', '["a",{"k":1}]', '["r",null,[]]' ,
    ]
    corpus += ["".join(chr(rng.randrange(32, 127)) for _ in range(rng.randrange(0, 40)))
               for _ in range(200)]
    for c in corpus:
        try:
            ct.decode_set_op(c)
        except ValueError:
            pass  # the ONLY permitted error type
    with pytest.raises(ValueError):
        ct.set_add_value(object())
    with pytest.raises(ValueError):
        ct.set_remove_value("e", [1])


def test_schema_registry_persistence_and_conflict():
    db = _mk_db()
    schema = ct.load_schema(db)
    assert schema.column_type("metrics", "clicks") == "counter"
    assert schema.column_type("metrics", "tags") == "awset"
    assert schema.column_type("metrics", "name") == "lww"
    assert schema.has_typed([("metrics", "rX", "clicks")])
    assert not schema.has_typed([("metrics", "rX", "name")])
    # Redeclaration with the same type is idempotent; a DIFFERENT type raises.
    ct.declare_column_types(db, [("metrics", "clicks", "counter")])
    with pytest.raises(ValueError):
        ct.declare_column_types(db, [("metrics", "clicks", "awset")])
    # Cache invalidation: a new declaration is visible immediately.
    ct.declare_column_types(db, [("metrics", "votes", "counter")])
    assert ct.load_schema(db).column_type("metrics", "votes") == "counter"


# --- 2. golden fixtures (hand model; never update) ---


@pytest.mark.parametrize("backend", ["python"] + (["native"] if native_available() else []))
@pytest.mark.parametrize("seed", [0, 7, 23])
def test_golden_counter_any_order_any_partition(backend, seed):
    g = GOLDEN["counter"]
    msgs = _golden_msgs(g)
    msgs += [msgs[i] for i in g["redeliver"]]
    rng = random.Random(seed)
    rng.shuffle(msgs)
    db = _mk_db(backend)
    tree = create_initial_merkle_tree()
    i = 0
    while i < len(msgs):  # random partition into batches
        j = i + rng.randrange(1, len(msgs) - i + 1)
        tree = apply_messages(db, tree, msgs[i:j])
        i = j
    assert _app_value(db, "clicks") == g["expected_value"]
    state = db.exec_sql_query('SELECT "pos", "neg" FROM "__crdt_counter"')
    assert (state[0]["pos"], state[0]["neg"]) == (g["expected_pos"], g["expected_neg"])
    # Redelivering EVERYTHING changes nothing (op-set semantics).
    tree = apply_messages(db, tree, msgs)
    assert _app_value(db, "clicks") == g["expected_value"]


@pytest.mark.parametrize("backend", ["python"] + (["native"] if native_available() else []))
@pytest.mark.parametrize("seed", [1, 13, 99])
def test_golden_awset_any_order_any_partition(backend, seed):
    g = GOLDEN["awset"]
    msgs = _golden_msgs(g)
    msgs += [msgs[i] for i in g["redeliver"]]
    rng = random.Random(seed)
    rng.shuffle(msgs)
    db = _mk_db(backend)
    tree = create_initial_merkle_tree()
    i = 0
    while i < len(msgs):
        j = i + rng.randrange(1, len(msgs) - i + 1)
        tree = apply_messages(db, tree, msgs[i:j])
        i = j
    assert _app_value(db, "tags") == g["expected_value"]
    alive = {r["tag"] for r in db.exec_sql_query(
        'SELECT "tag" FROM "__crdt_set" WHERE "alive" = 1')}
    assert alive == set(g["expected_alive_tags"])
    dead_known = {r["tag"] for r in db.exec_sql_query(
        'SELECT "tag" FROM "__crdt_set" WHERE "alive" = 0')}
    # Every hand-model dead tag is either a dead stored add or a
    # tombstone-only kill (the not-yet-seen-add case).
    kills = {r["tag"] for r in db.exec_sql_query('SELECT "tag" FROM "__crdt_kill"')}
    for t in g["expected_dead_tags"]:
        assert t in dead_known or t in kills


def test_golden_mixed_lww_untouched():
    """LWW columns in a table WITH typed columns keep exact reference
    semantics (winner upsert, raw value)."""
    g = GOLDEN["mixed_lww"]
    msgs = _golden_msgs(g) + _golden_msgs(GOLDEN["counter"])
    db = _mk_db()
    apply_messages(db, create_initial_merkle_tree(), msgs)
    assert _app_value(db, "name") == g["expected_value"]
    assert _app_value(db, "clicks") == GOLDEN["counter"]["expected_value"]


# --- 3. device twins: bit-identical, permutation/partition invariant ---


@pytest.mark.parametrize("seed", [2, 17, 4040])
def test_counter_kernel_matches_oracle_and_invariances(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 20_000))
    k = int(rng.integers(1, 200))
    cell = rng.integers(0, k, n).astype(np.int32)
    delta = rng.integers(-(2**31) + 1, 2**31, n).astype(np.int64)
    pos, neg = cm.pn_counter_sums(cell, delta, k)
    hp = np.zeros(k, np.int64)
    hn = np.zeros(k, np.int64)
    np.add.at(hp, cell, np.where(delta > 0, delta, 0))
    np.add.at(hn, cell, np.where(delta < 0, -delta, 0))
    assert np.array_equal(pos, hp) and np.array_equal(neg, hn)
    # Permutation invariance.
    perm = rng.permutation(n)
    pos_p, neg_p = cm.pn_counter_sums(cell[perm], delta[perm], k)
    assert np.array_equal(pos_p, pos) and np.array_equal(neg_p, neg)
    # Partition invariance (chunked accumulation == one batch).
    cut = n // 3
    p1, n1 = cm.pn_counter_sums(cell[:cut], delta[:cut], k)
    p2, n2 = cm.pn_counter_sums(cell[cut:], delta[cut:], k)
    assert np.array_equal(p1 + p2, pos) and np.array_equal(n1 + n2, neg)


@pytest.mark.parametrize("seed", [3, 31])
def test_awset_kernel_matches_oracle(seed):
    rng = random.Random(seed)
    tags = [f"tag{i:05d}" for i in range(rng.randrange(1, 3000))]
    kills = {t for t in tags if rng.random() < 0.3} | {f"phantom{i}" for i in range(7)}
    state_killed = {t for t in tags if rng.random() < 0.1} | {"elsewhere"}
    host = ct.alive_add_flags(tags, kills, state_killed)
    dev = cm.awset_alive_flags(tags, kills, state_killed)
    assert host == dev
    # Membership fold: order-free, duplicate-safe scatter-OR.
    pairs = np.array([rng.randrange(40) for _ in tags], np.int32)
    alive = np.array(host, bool)
    member = cm.awset_membership(pairs, alive, 40)
    expect = np.zeros(40, np.int32)
    np.maximum.at(expect, pairs, alive.astype(np.int32))
    assert np.array_equal(member, expect)
    perm = np.array(rng.sample(range(len(tags)), len(tags)))
    assert np.array_equal(cm.awset_membership(pairs[perm], alive[perm], 40), expect)


@pytest.mark.parametrize("n", [1, 255, 256, 8192, 40_000])
def test_segmented_sum_scan_formulations_agree(n):
    """Blocked two-level == associative_scan reference == Pallas
    (interpret mode) for the sum monoid — same pinning discipline as
    the lex-max scan (tests/test_pallas.py)."""
    import jax

    rng = np.random.default_rng(n)
    flags = rng.random(n) < 0.1
    flags[0] = True
    vals = rng.integers(0, 2**33, n).astype(np.uint64)
    with jax.enable_x64(True):
        ref = np.asarray(cm._segmented_sum_scan_reference(
            np.asarray(flags), np.asarray(vals)))
        blocked = np.asarray(cm.segmented_sum_scan(np.asarray(flags), np.asarray(vals)))
    assert np.array_equal(ref, blocked)
    from evolu_tpu.ops.pallas_scan import segmented_sum_scan_pallas

    if n <= 8192:  # interpret mode is slow; bound it
        with jax.enable_x64(True):
            pal = np.asarray(segmented_sum_scan_pallas(
                np.asarray(flags), np.asarray(vals), interpret=True))
        assert np.array_equal(ref, pal)


def test_counter_shard_sums_core_groups_by_owner_cell():
    """The reconcile-shaped sharded fold: (owner, cell) segments via
    the SHARED pack_owner_cell_key layout — totals at seg-end rows
    equal the per-(owner, cell) oracle sums."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(8)
    n = 4096
    owner = rng.integers(0, 5, n).astype(np.int64)
    cell = rng.integers(0, 50, n).astype(np.int32)
    delta = rng.integers(-100, 100, n).astype(np.int64)
    with jax.enable_x64(True):
        grp, seg_end, pos_sum, neg_sum = jax.jit(cm.counter_shard_sums_core)(
            jnp.asarray(owner), jnp.asarray(cell), jnp.asarray(delta)
        )
    grp, seg_end = np.asarray(grp), np.asarray(seg_end)
    pos_sum, neg_sum = np.asarray(pos_sum), np.asarray(neg_sum)
    got = {}
    for g, e, p, q in zip(grp, seg_end, pos_sum, neg_sum):
        if e:
            got[int(g)] = (int(p), int(q))
    expect = {}
    for o, c, d in zip(owner, cell, delta):
        key = (int(o) << 25 | int(c))
        p, q = expect.get(key, (0, 0))
        expect[key] = (p + max(d, 0), q + max(-d, 0))
    assert got == {k: v for k, v in expect.items()}


# --- 4. apply routing: batched == sequential oracle, both backends ---


def _random_mixed_log(seed, n=300):
    from evolu_tpu.core import crdt_list as cl

    rng = random.Random(seed)
    nodes = ["aaaaaaaaaaaaaaa1", "bbbbbbbbbbbbbbb2"]
    msgs = []
    tag_pool = []
    elem_pool = []  # list element tags (insert op timestamps)
    for i in range(n):
        ts = timestamp_to_string(
            Timestamp(1_700_000_000_000 + i * 977, i % 3, rng.choice(nodes))
        )
        roll = rng.random()
        row = f"r{rng.randrange(6)}"
        if roll < 0.25:
            msgs.append(CrdtMessage(ts, "metrics", row, "clicks",
                                    rng.randrange(-50, 50)))
        elif roll < 0.38:
            msgs.append(CrdtMessage(ts, "metrics", row, "tags",
                                    ct.set_add_value(rng.choice("abcde"))))
            tag_pool.append(ts)
        elif roll < 0.46 and tag_pool:
            observed = rng.sample(tag_pool, min(len(tag_pool), rng.randrange(0, 4)))
            msgs.append(CrdtMessage(ts, "metrics", row, "tags",
                                    ct.set_remove_value(rng.choice("abcde"), observed)))
        elif roll < 0.58:
            after = rng.choice(elem_pool) if elem_pool and rng.random() < 0.7 \
                else None
            msgs.append(CrdtMessage(ts, "metrics", row, "items",
                                    cl.list_insert_value(f"e{i}", after=after)))
            elem_pool.append(ts)
        elif roll < 0.64 and elem_pool:
            msgs.append(CrdtMessage(ts, "metrics", row, "items",
                                    cl.list_delete_value(rng.choice(elem_pool))))
        elif roll < 0.72:
            # Malformed typed ops: must be ignored identically everywhere.
            col, val = rng.choice([("clicks", "oops"), ("clicks", 2**40),
                                   ("tags", "{not json"), ("tags", 5),
                                   ("items", "nope"), ("items", '["i"]')])
            msgs.append(CrdtMessage(ts, "metrics", row, col, val))
        else:
            msgs.append(CrdtMessage(ts, "metrics", row, "name", f"n{i}"))
    # Redeliver a sample (dedup must hold).
    msgs += rng.sample(msgs, min(len(msgs), 40))
    return msgs


def _dump_all(db):
    return (
        db.exec_sql_query('SELECT * FROM "__message" ORDER BY "timestamp"'),
        db.exec_sql_query('SELECT * FROM "metrics" ORDER BY "id"'),
        db.exec_sql_query('SELECT * FROM "__crdt_counter" ORDER BY "table", "row", "column"'),
        db.exec_sql_query('SELECT * FROM "__crdt_set" ORDER BY "tag"'),
        db.exec_sql_query('SELECT * FROM "__crdt_kill" ORDER BY "tag"'),
        db.exec_sql_query('SELECT * FROM "__crdt_list" ORDER BY "tag"'),
        db.exec_sql_query('SELECT * FROM "__crdt_list_kill" ORDER BY "tag"'),
    )


@pytest.mark.parametrize("backend", ["python"] + (["native"] if native_available() else []))
@pytest.mark.parametrize("seed", [5, 42])
def test_batched_equals_sequential_oracle_mixed(backend, seed):
    msgs = _random_mixed_log(seed)
    db_a, db_b = _mk_db(backend), _mk_db(backend)
    with db_a.transaction():
        apply_messages_sequential(db_a, create_initial_merkle_tree(), msgs)
    apply_messages(db_b, create_initial_merkle_tree(), msgs)
    assert _dump_all(db_a) == _dump_all(db_b)


@pytest.mark.parametrize("backend", ["python"] + (["native"] if native_available() else []))
def test_device_planner_equals_host_for_typed(backend):
    """The device full-plan (and its typed upsert strip) produces the
    same end state as the host planner on a typed batch."""
    from evolu_tpu.ops.merge import plan_batch_device_full

    msgs = _random_mixed_log(77, n=400)
    db_a, db_b = _mk_db(backend), _mk_db(backend)
    apply_messages(db_a, create_initial_merkle_tree(), msgs)
    apply_messages(db_b, create_initial_merkle_tree(), msgs,
                   planner=plan_batch_device_full)
    assert _dump_all(db_a) == _dump_all(db_b)


def test_typed_cells_never_lww_upsert():
    """A counter cell's app value is NEVER the raw winning op value:
    the largest-timestamp op here carries delta -1, and the cell must
    read the SUM, not -1."""
    base = 1_700_000_000_000
    msgs = [
        CrdtMessage(timestamp_to_string(Timestamp(base + i * 1000, 0,
                                                  "aaaaaaaaaaaaaaa1")),
                    "metrics", "r1", "clicks", d)
        for i, d in enumerate([10, 20, -1])
    ]
    db = _mk_db()
    apply_messages(db, create_initial_merkle_tree(), msgs)
    assert _app_value(db, "clicks") == 29


def test_malformed_ops_counted_and_ignored():
    metrics.reset()
    base = 1_700_000_000_000
    mk = lambda i, col, v: CrdtMessage(  # noqa: E731
        timestamp_to_string(Timestamp(base + i * 1000, 0, "aaaaaaaaaaaaaaa1")),
        "metrics", "r1", col, v)
    msgs = [mk(0, "clicks", 5), mk(1, "clicks", "garbage"),
            mk(2, "tags", ct.set_add_value("x")), mk(3, "tags", "not-json")]
    db = _mk_db()
    apply_messages(db, create_initial_merkle_tree(), msgs)
    assert _app_value(db, "clicks") == 5
    assert _app_value(db, "tags") == '["x"]'
    assert metrics.get_counter("evolu_crdt_malformed_ops_total", type="counter") == 1
    assert metrics.get_counter("evolu_crdt_malformed_ops_total", type="awset") == 1
    # All four ops are in the log (transport semantics untouched).
    assert len(db.exec_sql_query('SELECT * FROM "__message"')) == 4


# --- 5. winner-cache contract per type ---


def test_winner_cache_contract_typed_cells():
    """Typed cells keep slot == MAX(timestamp) (the xor gate) while the
    app value is the merge-state fold — the per-type cache contract."""
    from evolu_tpu.runtime.client import create_evolu

    e = create_evolu({"metrics": ("name", "clicks:counter", "tags:awset")},
                     config=Config(backend="tpu", min_device_batch=1))
    try:
        # Pin the static cached path: the adaptive gate would stream
        # these all-new-cell micro-batches (dropping the slots this
        # test reads); the contract under test is the slot invariant,
        # not the gating policy (tests/test_winner_cache.py owns that).
        e.worker._planner.cache.adaptive = False
        row = e.create("metrics", {"name": "n"})
        e.worker.flush()
        for d in (4, -1, 9):
            e.increment("metrics", row, "clicks", d)
        e.set_add("metrics", row, "tags", "t1")
        e.worker.flush()
        cache = e.worker._planner.cache
        assert cache is not None and cache._slots
        w1 = np.asarray(cache._w1)
        w2 = np.asarray(cache._w2)
        checked_typed = 0
        schema = ct.load_schema(e.db)
        for (table, r, col), slot in cache._slots.items():
            got = e.db.exec_sql_query(
                'SELECT MAX("timestamp") AS m FROM "__message" '
                'WHERE "table" = ? AND "row" = ? AND "column" = ?',
                (table, r, col))[0]["m"]
            k1, k2 = int(w1[slot]), int(w2[slot])
            cached_ts = timestamp_to_string(
                Timestamp(k1 >> 16, k1 & 0xFFFF, f"{k2:016x}"))
            assert cached_ts == got, (table, r, col)
            if schema.is_typed(table, col):
                checked_typed += 1
        assert checked_typed >= 2  # clicks + tags slots were exercised
        assert _app_value(e.db, "clicks", row) == 12
        assert _app_value(e.db, "tags", row) == '["t1"]'
    finally:
        e.dispose()


# --- 6. end-to-end: anti-entropy + snapshot carry typed state ---


def _converge(replicas, deadline_s=30.0):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        for r in replicas:
            r.sync()
            r.worker.flush()
        dumps = [r.db.exec('SELECT * FROM "__message" ORDER BY "timestamp"')
                 for r in replicas]
        if all(d == dumps[0] for d in dumps):
            return
        time.sleep(0.05)
    raise AssertionError("replicas did not converge in time")


def test_two_relay_antientropy_and_snapshot_carry_typed_state(tmp_path):
    """Typed ops ride replication + snapshot unchanged: relay B pulls
    relay A's typed traffic through Merkle anti-entropy; a checkpoint
    of A restores into a fresh relay byte-identically (crc-pinned);
    clients hanging off EVERY relay materialize identical typed values;
    and the capability is negotiated along the way."""
    from evolu_tpu.runtime.client import create_evolu
    from evolu_tpu.server import snapshot
    from evolu_tpu.server.relay import RelayServer, RelayStore
    from evolu_tpu.sync import protocol
    from evolu_tpu.sync.client import connect

    schema = {"metrics": ("name", "clicks:counter", "tags:awset")}
    a = RelayServer(RelayStore(), peers=[]).start()
    b = None
    c = None
    e1 = e2 = e3 = None
    try:
        e1 = create_evolu(schema, config=Config(sync_url=a.url))
        connect(e1)
        row = e1.create("metrics", {"name": "page"})
        for d in (5, -2, 7):
            e1.increment("metrics", row, "clicks", d)
        e1.set_add("metrics", row, "tags", "red")
        e1.set_add("metrics", row, "tags", "blue")
        e1.worker.flush()
        e1.set_remove("metrics", row, "tags", "blue")
        e1.worker.flush()
        e1.sync()
        e1.worker.flush()
        e1._transport.flush()
        # Capability negotiated with the live relay.
        caps = e1._transport.negotiated_capabilities
        assert any(protocol.CAP_CRDT_TYPES in v for v in caps.values()), caps

        # Relay B converges through anti-entropy (byte-level replica
        # state: stored tree text + every (timestamp, content) row).
        owner = e1.owner.id
        state = lambda store: (  # noqa: E731
            store.get_merkle_tree_string(owner),
            store.replica_messages(owner, ""),
        )
        b = RelayServer(RelayStore(), peers=[a.url],
                        replication_interval_s=0.1).start()
        deadline = time.time() + 20
        while time.time() < deadline:
            if state(b.store) == state(a.store) and state(a.store)[1]:
                break
            time.sleep(0.05)
        assert state(b.store) == state(a.store)

        # Snapshot checkpoint of A restores crc-identically into C.
        path = str(tmp_path / "a.checkpoint")
        snapshot.write_checkpoint(a.store, path)
        fresh = RelayStore()
        snapshot.restore_checkpoint(fresh, path)
        crc = lambda store: zlib.crc32(repr(state(store)).encode())  # noqa: E731
        assert crc(fresh) == crc(a.store)
        c = RelayServer(fresh).start()

        # A fresh client against EACH relay materializes the same values.
        e2 = create_evolu(schema, config=Config(sync_url=b.url),
                          mnemonic=e1.owner.mnemonic)
        e3 = create_evolu(schema, config=Config(sync_url=c.url),
                          mnemonic=e1.owner.mnemonic)
        connect(e2)
        connect(e3)
        _converge([e1, e2])
        _converge([e1, e3])
        for e in (e1, e2, e3):
            rows = e.db.exec_sql_query(
                'SELECT "clicks", "tags" FROM "metrics"')
            assert (rows[0]["clicks"], rows[0]["tags"]) == (10, '["red"]')
        # Typed state tables converge byte-identically too.
        dumps = [_dump_all(e.db) for e in (e1, e2, e3)]
        assert dumps[0] == dumps[1] == dumps[2]
    finally:
        for e in (e1, e2, e3):
            if e is not None:
                e.dispose()
        for s in (a, b, c):
            if s is not None:
                s.stop()


def test_rebuild_state_matches_incremental():
    """The order-free fold rebuilt from the full log equals the
    incrementally maintained state (the integrity-check invariant)."""
    msgs = _random_mixed_log(123, n=250)
    db = _mk_db()
    apply_messages(db, create_initial_merkle_tree(), msgs)
    before = _dump_all(db)
    ct.rebuild_state(db, ct.load_schema(db))
    assert _dump_all(db) == before


def test_reset_owner_drops_typed_state():
    from evolu_tpu.runtime.client import create_evolu

    e = create_evolu({"metrics": ("clicks:counter",)}, config=Config(backend="cpu"))
    try:
        row = e.create("metrics", {})
        e.increment("metrics", row, "clicks", 3)
        e.worker.flush()
        assert e.db.exec_sql_query('SELECT * FROM "__crdt_counter"')
        e.reset_owner()
        e.worker.flush()
        # Schema cache dropped with the tables: a fresh declare works.
        e.update_db_schema({"metrics": ("clicks:counter",)})
        e.worker.flush()
        assert ct.load_schema(e.db).column_type("metrics", "clicks") == "counter"
        assert e.db.exec_sql_query('SELECT * FROM "__crdt_counter"') == []
    finally:
        e.dispose()


def test_late_declaration_folds_predeclaration_ops():
    """Review finding: ops that reached __message BEFORE the column was
    declared typed (rolling upgrade) must fold at declaration time —
    otherwise this replica materializes a different value than a
    replica that declared before syncing, forever (anti-entropy is
    timestamp-only and cannot heal it)."""
    base = 1_700_000_000_000
    mk = lambda i, col, v: CrdtMessage(  # noqa: E731
        timestamp_to_string(Timestamp(base + i * 1000, 0, "aaaaaaaaaaaaaaa1")),
        "metrics", "r1", col, v)
    ops = [mk(0, "clicks", 5), mk(1, "clicks", 7),
           mk(2, "tags", ct.set_add_value("x"))]

    # Replica L: receives the ops while the columns are still UNDECLARED
    # (plain LWW schema), then upgrades.
    late = open_database(":memory:", "python")
    init_db_model(late, MN)
    update_db_schema(late, [TableDefinition.of("metrics", ("name", "clicks", "tags"))])
    apply_messages(late, create_initial_merkle_tree(), ops)
    assert _app_value(late, "clicks") == 7  # LWW winner, pre-upgrade
    folds = metrics.get_counter("evolu_crdt_predeclaration_folds_total")
    update_db_schema(late, [SCHEMA_DEF])  # the upgrade declares the types
    # the declaration folded the column logs it found: three ops
    assert metrics.get_counter("evolu_crdt_predeclaration_folds_total") == folds + 3

    # Replica E: declared first, then synced.
    early = _mk_db()
    apply_messages(early, create_initial_merkle_tree(), ops)
    assert metrics.get_counter("evolu_crdt_predeclaration_folds_total") == folds + 3

    for db in (late, early):
        assert _app_value(db, "clicks") == 12, "fold must cover pre-declaration ops"
        assert _app_value(db, "tags") == '["x"]'
    assert _dump_all(late)[2:] == _dump_all(early)[2:]  # identical __crdt_* state

    # Later ops keep folding incrementally on both.
    more = [mk(10, "clicks", -2)]
    for db in (late, early):
        apply_messages(db, create_initial_merkle_tree(), more)
        assert _app_value(db, "clicks") == 10


def test_set_remove_covers_just_queued_add():
    """Review finding: add-then-remove on ONE replica without an
    explicit flush must still remove the element — set_remove drains
    the worker before reading its observation."""
    from evolu_tpu.runtime.client import create_evolu

    e = create_evolu({"metrics": ("tags:awset",)}, config=Config(backend="cpu"))
    try:
        row = e.create("metrics", {})
        e.set_add("metrics", row, "tags", "ghost")
        e.set_remove("metrics", row, "tags", "ghost")  # no flush between
        e.worker.flush()
        assert _app_value(e.db, "tags", row) == "[]"
    finally:
        e.dispose()


def test_load_schema_raises_on_transient_error_instead_of_caching_empty():
    """Review finding: a transient load error must FAIL the apply (safe
    rollback), never cache an empty schema that would route typed cells
    through the LWW path forever."""
    db = _mk_db()
    ct.invalidate_schema_cache(db)
    orig = db.exec_sql_query

    def flaky(sql, params=()):
        if "__crdt_schema" in sql:
            raise RuntimeError("database is locked")
        return orig(sql, params)

    db.exec_sql_query = flaky
    with pytest.raises(RuntimeError):
        ct.load_schema(db)
    db.exec_sql_query = orig
    assert ct.load_schema(db).column_type("metrics", "clicks") == "counter"
    # Missing table (pure-LWW db) still caches the empty schema.
    plain = open_database(":memory:", "python")
    init_db_model(plain, MN)
    assert not ct.load_schema(plain)
    assert getattr(plain, "_crdt_schema_cache", None) is not None
