"""Mesh-sharded reconcile tests on the virtual 8-device CPU mesh.

Config-5 shape (SURVEY.md §6): owners sharded over a mesh, per-owner
results identical to the host oracle, digests XOR-combined across
devices.
"""

import numpy as np
import pytest

import jax

from evolu_tpu.core.merkle import create_initial_merkle_tree, apply_prefix_xors
from evolu_tpu.core.timestamp import (
    create_initial_timestamp,
    send_timestamp,
    timestamp_to_hash,
    timestamp_from_string,
    timestamp_to_string,
)
from evolu_tpu.core.murmur import to_int32
from evolu_tpu.core.types import CrdtMessage
from evolu_tpu.parallel import (
    assign_owners_to_shards,
    create_mesh,
    reconcile_owner_batches,
)
from evolu_tpu.storage.apply import plan_batch


def _mk_messages(node, n, start_millis=1_700_000_000_000, table="todo", rows=8):
    t = create_initial_timestamp(node)
    out = []
    for i in range(n):
        t = send_timestamp(t, start_millis + i * 7)
        out.append(
            CrdtMessage(
                timestamp_to_string(t), table, f"row{i % rows}", "title", f"v{i}"
            )
        )
    return out


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8


def test_assign_owners_balanced():
    sizes = {f"o{i}": (i + 1) * 10 for i in range(20)}
    shards = assign_owners_to_shards(sizes, 4)
    assert sorted(o for s in shards for o in s) == sorted(sizes)
    loads = [sum(sizes[o] for o in s) for s in shards]
    assert max(loads) - min(loads) <= max(sizes.values())


def test_sharded_reconcile_matches_host_oracle():
    mesh = create_mesh()
    owner_batches = {
        f"owner{i}": _mk_messages(f"{i:016x}", 50 + 17 * i) for i in range(12)
    }
    existing = {o: {} for o in owner_batches}
    results, digest = reconcile_owner_batches(mesh, owner_batches, existing)

    expected_digest = 0
    for owner, msgs in owner_batches.items():
        xor_mask, upserts, deltas = results[owner]
        exp_xor, exp_upserts = plan_batch(msgs, {})
        assert xor_mask == exp_xor, owner
        # Upsert ORDER differs (host: cell-first-seen; device: batch
        # position of the winning message) but each upsert hits a
        # distinct cell, so order carries no semantics.
        assert set(upserts) == set(exp_upserts), owner
        # Per-owner deltas reproduce the sequential tree exactly.
        exp_deltas = {}
        from evolu_tpu.core.merkle import minutes_base3

        for i, m in enumerate(msgs):
            if exp_xor[i]:
                ts = timestamp_from_string(m.timestamp)
                k = minutes_base3(ts.millis)
                exp_deltas[k] = to_int32(exp_deltas.get(k, 0) ^ timestamp_to_hash(ts))
                expected_digest ^= timestamp_to_hash(ts) & 0xFFFFFFFF
        assert deltas == exp_deltas, owner
    assert digest == expected_digest


def test_sharded_reconcile_respects_existing_winners():
    mesh = create_mesh()
    msgs = _mk_messages("a" * 16, 10)
    # Existing winner newer than everything: no upserts for that cell.
    cells = {(m.table, m.row, m.column) for m in msgs}
    winner = "2099-01-01T00:00:00.000Z-0000-ffffffffffffffff"
    existing = {"o1": {c: winner for c in cells}}
    results, _ = reconcile_owner_batches(mesh, {"o1": msgs}, existing)
    xor_mask, upserts, _deltas = results["o1"]
    assert upserts == []
    assert xor_mask == [True] * len(msgs)  # hashes still enter the tree


def test_hot_owner_client_receive_end_to_end():
    """A single client IS one owner: a receive batch at/above
    hot_owner_min_batch routes through the cell-range-sharded kernel
    spanning the 8-device mesh, with SQLite end state and persisted
    clock byte-identical to the CPU-oracle client."""
    import sys
    sys.path.insert(0, __file__.rsplit("/", 1)[0])
    from test_runtime import TODO_SCHEMA, create_evolu

    from evolu_tpu.core.merkle import merkle_tree_to_string
    from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
    from evolu_tpu.storage.clock import read_clock
    from evolu_tpu.utils.config import Config

    base = 1_700_000_000_000
    messages = tuple(
        CrdtMessage(
            timestamp_to_string(Timestamp(base + i, i % 3, f"{(i % 5):016x}")),
            "todo", f"r{i % 97}", "title", f"v{i}",
        )
        for i in range(600)
    )
    hot = create_evolu(TODO_SCHEMA, config=Config(backend="tpu", hot_owner_min_batch=64))
    cpu = create_evolu(TODO_SCHEMA, config=Config(backend="cpu"),
                       mnemonic=hot.owner.mnemonic)
    # Pin the routing: the receive must actually go through the
    # cell-range-sharded kernel, not silently fall back.
    import evolu_tpu.parallel.hot_owner as hot_mod
    calls = []
    orig = hot_mod.reconcile_hot_owner
    hot_mod.reconcile_hot_owner = lambda *a, **k: (calls.append(1), orig(*a, **k))[1]
    try:
        for c in (hot, cpu):
            c.receive(messages, "{}", None)
            c.worker.flush()
        assert calls, "hot-owner kernel was never invoked"
        dump_hot = hot.db.exec('SELECT * FROM "__message" ORDER BY "timestamp"')
        dump_cpu = cpu.db.exec('SELECT * FROM "__message" ORDER BY "timestamp"')
        assert len(dump_hot) == len(messages) and dump_hot == dump_cpu
        rows_hot = hot.db.exec('SELECT * FROM "todo" ORDER BY "id"')
        rows_cpu = cpu.db.exec('SELECT * FROM "todo" ORDER BY "id"')
        assert rows_hot == rows_cpu
        th = merkle_tree_to_string(read_clock(hot.db).merkle_tree)
        tc = merkle_tree_to_string(read_clock(cpu.db).merkle_tree)
        assert th == tc
    finally:
        hot_mod.reconcile_hot_owner = orig
        hot.dispose(), cpu.dispose()


def test_server_hot_owner_rows_split_across_shards():
    """An owner whose rows exceed an even shard's worth splits row-wise
    across the mesh (hashing needs no cell locality; XOR merges the
    per-shard per-minute partials exactly) — deltas and digest must
    equal the reference fold."""
    from evolu_tpu.core.merkle import minute_deltas_host
    from evolu_tpu.server.engine import owner_minute_deltas

    mesh = create_mesh()
    hot = [m.timestamp for m in _mk_messages("a" * 16, 5000)]
    small = [m.timestamp for m in _mk_messages("b" * 16, 40)]
    rows = {"hot": hot, "small": small}
    # Pin that the row-split path actually engages: the hot owner must
    # exceed an even shard's worth (engine splits when len > ceil(n/D)),
    # otherwise this test silently degrades to the unsplit path.
    even_share = -(-(len(hot) + len(small)) // mesh.devices.size)
    assert mesh.devices.size > 1 and len(hot) > even_share
    deltas, digest = owner_minute_deltas(mesh, rows)
    expect_digest = 0
    for o, ts_list in rows.items():
        expect, d = minute_deltas_host(ts_list)
        assert deltas[o] == expect, o
        expect_digest ^= d
    assert digest == expect_digest


def test_non_canonical_owner_quarantined_to_host_path():
    """An owner whose batch carries non-canonical hex case (uppercase
    node) is planned on the host with raw-string order and verbatim-case
    hashing; canonical owners stay on device; the combined digest covers
    both."""
    from evolu_tpu.core.merkle import minutes_base3

    mesh = create_mesh()
    clean = _mk_messages("c" * 16, 23)
    weird = [
        CrdtMessage("2022-07-03T18:41:40.000Z-0000-ABCDEF0123456789", "todo", "r", "title", "U"),
        CrdtMessage("2022-07-03T18:41:40.000Z-0000-abcdef0123456789", "todo", "r", "title", "L"),
        CrdtMessage("2022-07-03T18:41:41.000Z-0000-" + "b" * 16, "todo", "r2", "title", "x"),
    ]
    batches = {"clean": clean, "weird": weird}
    results, digest = reconcile_owner_batches(mesh, batches, {o: {} for o in batches})

    expected_digest = 0
    for owner, msgs in batches.items():
        xor_mask, upserts, deltas = results[owner]
        exp_xor, exp_upserts = plan_batch(msgs, {})
        assert xor_mask == exp_xor, owner
        assert set(upserts) == set(exp_upserts), owner
        exp_deltas = {}
        for i, m in enumerate(msgs):
            if exp_xor[i]:
                ts = timestamp_from_string(m.timestamp)
                k = minutes_base3(ts.millis)
                exp_deltas[k] = to_int32(exp_deltas.get(k, 0) ^ timestamp_to_hash(ts))
                expected_digest ^= timestamp_to_hash(ts) & 0xFFFFFFFF
        assert deltas == exp_deltas, owner
    assert digest == expected_digest


def test_single_owner_many_devices_and_empty():
    mesh = create_mesh()
    results, digest = reconcile_owner_batches(mesh, {}, {})
    assert results == {} and digest == 0
    msgs = _mk_messages("b" * 16, 3)
    results, _ = reconcile_owner_batches(mesh, {"only": msgs}, {"only": {}})
    assert len(results["only"][1]) == len(plan_batch(msgs, {})[1])


def test_high_contention_tiebreak_across_owners():
    """Config 4 analog: every owner's replicas write the same cells; the
    device tiebreak must match the string-order oracle exactly."""
    mesh = create_mesh()
    owner_batches = {}
    for o in range(4):
        msgs = []
        # 8 "replicas" stamp the same 5 rows at identical millis values:
        # order decided by (counter, node) alone.
        for r in range(8):
            node = f"{r:x}" * 16
            t = create_initial_timestamp(node[:16])
            for i in range(25):
                t = send_timestamp(t, 1_700_000_000_000)  # frozen clock
                msgs.append(
                    CrdtMessage(
                        timestamp_to_string(t), "todo", f"row{i % 5}", "title", f"{o}/{r}/{i}"
                    )
                )
        owner_batches[f"own{o}"] = msgs
    existing = {o: {} for o in owner_batches}
    results, _ = reconcile_owner_batches(mesh, owner_batches, existing)
    for o, msgs in owner_batches.items():
        exp_xor, exp_upserts = plan_batch(msgs, {})
        assert results[o][0] == exp_xor
        assert set(results[o][1]) == set(exp_upserts)


def test_tree_equivalence_after_delta_apply():
    """Applying the sharded deltas to an empty tree gives the identical
    tree to sequential inserts (whole-pipeline equivalence)."""
    from evolu_tpu.core.merkle import insert_into_merkle_tree

    mesh = create_mesh()
    msgs = _mk_messages("c" * 16, 200)
    results, _ = reconcile_owner_batches(mesh, {"o": msgs}, {"o": {}})
    xor_mask, _, deltas = results["o"]
    tree = apply_prefix_xors(create_initial_merkle_tree(), deltas)
    expected = create_initial_merkle_tree()
    for i, m in enumerate(msgs):
        if xor_mask[i]:
            expected = insert_into_merkle_tree(timestamp_from_string(m.timestamp), expected)
    assert tree == expected


# --- server batch reconcile engine ---


def _sync_req(user, node, messages=(), tree="{}"):
    from evolu_tpu.sync import protocol

    return protocol.SyncRequest(tuple(messages), user, node, tree)


def test_batch_reconciler_matches_sequential_store():
    """Engine end state == per-request store.sync end state (config 3)."""
    from evolu_tpu.server.engine import BatchReconciler
    from evolu_tpu.server.relay import RelayStore
    from evolu_tpu.sync import protocol

    def enc(msgs):
        return tuple(protocol.EncryptedCrdtMessage(m.timestamp, b"ct-" + m.timestamp.encode()) for m in msgs)

    owners = {f"u{i:03d}": _mk_messages(f"{i:016x}", 30 + i * 5) for i in range(10)}
    requests = [
        _sync_req(o, msgs[0].timestamp[30:46], enc(msgs)) for o, msgs in owners.items()
    ]

    seq = RelayStore()
    for r in requests:
        seq.sync(r)

    batch_store = RelayStore()
    engine = BatchReconciler(batch_store, create_mesh())
    responses = engine.reconcile(requests)

    for o in owners:
        assert batch_store.get_merkle_tree(o) == seq.get_merkle_tree(o), o
    n_seq = seq.db.exec_sql_query('SELECT COUNT(*) AS n FROM "message"')[0]["n"]
    n_batch = batch_store.db.exec_sql_query('SELECT COUNT(*) AS n FROM "message"')[0]["n"]
    assert n_seq == n_batch
    # Each response excludes the requester's own messages; with one node
    # per owner and nothing else stored, responses are empty.
    assert all(r.messages == () for r in responses)


def test_batch_reconciler_idempotent_and_cross_device_fetch():
    from evolu_tpu.server.engine import BatchReconciler
    from evolu_tpu.server.relay import RelayStore
    from evolu_tpu.sync import protocol

    store = RelayStore()
    engine = BatchReconciler(store, create_mesh())
    msgs = _mk_messages("d" * 16, 40)
    enc = tuple(protocol.EncryptedCrdtMessage(m.timestamp, b"x") for m in msgs)
    node = msgs[0].timestamp[30:46]
    r1 = _sync_req("u1", node, enc)
    engine.reconcile([r1])
    tree1 = store.get_merkle_tree("u1")
    engine.reconcile([r1])  # resend: no changes
    assert store.get_merkle_tree("u1") == tree1
    # A second device (different node, empty tree) gets the full history.
    r2 = _sync_req("u1", "e" * 16)
    (resp,) = engine.reconcile([r2])
    assert len(resp.messages) == len(msgs)


def test_reconcile_wire_byte_identical_to_object_respond():
    """`BatchReconciler.reconcile_wire` (r5: bytes-mode respond over
    `eh_get_messages_wire`) must be BYTE-identical to
    `encode_sync_response(reconcile(...)[i])` across push, cold pull,
    steady state, NUL-bearing ids, a sharded store, and the
    python-backend fallback — and a malformed stored timestamp must
    degrade that request to the object path, not wedge it."""
    from evolu_tpu.server.engine import BatchReconciler
    from evolu_tpu.server.relay import RelayStore, ShardedRelayStore
    from evolu_tpu.sync import protocol

    def enc(msgs):
        return tuple(
            protocol.EncryptedCrdtMessage(m.timestamp, b"ct\x00-" + m.timestamp.encode())
            for m in msgs
        )

    owners = {f"w{i:03d}": _mk_messages(f"{i + 7:016x}", 25 + i * 3) for i in range(6)}
    owners["u\x00evil"] = _mk_messages("a" * 16, 10)  # NUL-bearing id
    push = [
        _sync_req(o, msgs[0].timestamp[30:46], enc(msgs)) for o, msgs in owners.items()
    ]
    cold = [_sync_req(o, "e" * 16) for o in owners]  # other-device pulls

    for mk in (lambda: RelayStore(), lambda: ShardedRelayStore(shards=3),
               lambda: RelayStore(backend="python")):
        obj_store, wire_store = mk(), mk()
        obj_eng = BatchReconciler(obj_store, create_mesh())
        wire_eng = BatchReconciler(wire_store, create_mesh())
        for batch in (push, cold, cold):  # cold twice = steady-state repeat
            want = [protocol.encode_sync_response(r) for r in obj_eng.reconcile(batch)]
            got = wire_eng.reconcile_wire(batch)
            assert got == want
        obj_eng.close(), wire_eng.close()
        obj_store.close(), wire_store.close()

    # Malformed stored width: rc 2 must degrade that request to the
    # object path (both engines serve the same bytes, no exception).
    from evolu_tpu.storage.native import native_available

    if native_available():
        obj_store, wire_store = RelayStore(), RelayStore()
        for s in (obj_store, wire_store):
            s.add_messages("u1", enc(owners["w000"]))
            s.db.run(
                'INSERT INTO "message" ("timestamp", "userId", "content") '
                "VALUES (?, ?, ?)",
                ("2099-01-01T00:00:00.000Z-00ff", "u1", b"bad"),
            )
        obj_eng = BatchReconciler(obj_store, create_mesh())
        wire_eng = BatchReconciler(wire_store, create_mesh())
        (want,) = obj_eng.reconcile([_sync_req("u1", "e" * 16)])
        (got,) = wire_eng.reconcile_wire([_sync_req("u1", "e" * 16)])
        assert got == protocol.encode_sync_response(want)
        obj_eng.close(), wire_eng.close()
        obj_store.close(), wire_store.close()


def test_hot_owner_cell_sharding_matches_single_device():
    """One hot owner's batch sharded by cell ranges over 8 devices must
    produce the single-device planner's exact masks, minute deltas, and
    digest (SURVEY.md §5 hot-owner strategy)."""
    import numpy as np

    from evolu_tpu.core.merkle import minutes_base3
    from evolu_tpu.core.murmur import to_int32
    from evolu_tpu.ops.encode import timestamp_hashes
    from evolu_tpu.ops.merge import plan_merge_core
    from evolu_tpu.ops.merkle_ops import merkle_minute_deltas, minute_deltas_to_dict
    from evolu_tpu.parallel.hot_owner import reconcile_hot_owner
    from evolu_tpu.parallel.mesh import create_mesh

    rng = np.random.default_rng(13)
    n = 3000
    base = 1_700_000_000_000
    cell_id = rng.integers(0, 400, n).astype(np.int32)
    millis = base + rng.integers(0, 10 * 60_000, n).astype(np.int64)
    counter = rng.integers(0, 16, n).astype(np.int32)
    node = rng.integers(1, 2**63, n).astype(np.uint64)
    k1 = (millis.astype(np.uint64) << np.uint64(16)) | counter.astype(np.uint64)
    k2 = node.copy()
    ex_k1 = np.zeros(n, np.uint64)
    ex_k2 = np.zeros(n, np.uint64)
    # Some cells have a stored winner mid-range.
    with_winner = cell_id % 3 == 0
    ex_k1[with_winner] = ((base + 5 * 60_000) << 16)
    ex_k2[with_winner] = 7

    mesh = create_mesh(8)
    got_xor, got_upsert, got_deltas, got_digest = reconcile_hot_owner(
        mesh, cell_id, k1, k2, ex_k1, ex_k2, millis, counter, node
    )

    import jax

    import jax.numpy as jnp

    with jax.enable_x64(True):
        args = tuple(jnp.asarray(a) for a in (cell_id, k1, k2, ex_k1, ex_k2))
        exp_xor, exp_upsert = (
            np.asarray(a) for a in plan_merge_core(*args, num_segments=n)
        )
        exp_deltas = minute_deltas_to_dict(
            *merkle_minute_deltas(millis, counter, node, exp_xor)
        )
        hashes = np.asarray(timestamp_hashes(millis, counter, node))
    np.testing.assert_array_equal(got_xor, exp_xor)
    np.testing.assert_array_equal(got_upsert, exp_upsert)
    assert got_deltas == exp_deltas
    exp_digest = 0
    for i in np.nonzero(exp_xor)[0]:
        exp_digest ^= int(hashes[i])
    assert got_digest == exp_digest


def test_multihost_helpers_single_process():
    """Single process hosts every shard; local_owners respects the
    actual LPT shard assignment. (jax.distributed.initialize itself
    must run before any backend exists, so it is not callable from
    inside the suite — the helpers are the testable surface.)"""
    import jax

    from evolu_tpu.parallel import multihost
    from evolu_tpu.parallel.mesh import assign_owners_to_shards, create_mesh

    mesh = create_mesh()
    assert not multihost.is_multihost()
    assert multihost.local_shard_indices(mesh) == list(range(mesh.devices.size))
    sizes = {f"o{i}": (i * 37) % 101 + 1 for i in range(10)}
    shards = assign_owners_to_shards(sizes, mesh.devices.size)
    assert sorted(multihost.local_owners(mesh, shards)) == sorted(sizes)


@pytest.mark.parametrize("n", [2, 4, 8])
def test_dryrun_multichip_any_mesh_size(n):
    """The driver artifact must not be shape-specialized to n=8: the
    full sharded reconcile step compiles, runs, and digest-matches the
    host oracle at several mesh sizes (VERDICT r2 weak #7)."""
    import __graft_entry__ as graft

    graft.dryrun_multichip(n)


def test_reconcile_stream_matches_sequential_batches():
    """Pipelined streaming reconcile (device leg of batch k+1 in flight
    while batch k commits) and sequential `reconcile` calls must both
    end byte-identical to the sequential server (a per-request replay
    on the python backend) — across cross-batch duplicates, in-batch
    duplicates, owners spanning batches, a non-canonical-hex owner, and
    an all-duplicate replay batch (VERDICT r2 #1)."""
    from evolu_tpu.server.engine import BatchReconciler
    from evolu_tpu.server.relay import RelayStore, ShardedRelayStore
    from evolu_tpu.sync import protocol

    def enc(msgs):
        return tuple(
            protocol.EncryptedCrdtMessage(m.timestamp, b"ct-" + m.timestamp.encode())
            for m in msgs
        )

    def req(owner, msgs, node="f" * 16):
        return _sync_req(owner, node, enc(msgs))

    a = _mk_messages("a" * 16, 40)
    b = _mk_messages("b" * 16, 35)
    c = _mk_messages("c" * 16, 30)
    weird = [
        CrdtMessage("2023-09-01T10:00:00.000Z-0000-ABCDEF0123456789",
                    "todo", "r", "title", "U"),
        CrdtMessage("2023-09-01T10:01:00.000Z-0001-ABCDEF0123456789",
                    "todo", "r", "title", "U2"),
    ]
    batches = [
        # batch 0: two owners, an in-batch duplicate for uA
        [req("uA", a[:20] + a[10:12]), req("uB", b[:15])],
        # batch 1: cross-batch duplicates (uA rows 10-19 again) + new
        # rows; owner uC and the non-canonical owner join
        [req("uA", a[10:30]), req("uC", c), req("uW", weird)],
        # batch 2: all-duplicate replay for uA and uW, fresh tail for uB
        [req("uA", a[:30]), req("uW", weird), req("uB", b[15:])],
    ]

    def dump(store):
        shards = getattr(store, "shards", [store])
        return (
            sorted(r for s in shards for r in s.db.exec(
                'SELECT "timestamp","userId","content" FROM "message"')),
            sorted(r for s in shards for r in s.db.exec(
                'SELECT "userId","merkleTree" FROM "merkleTree"')),
        )

    # One request an owner a batch, so the sequential server answers
    # each request as the batched pass does.
    oracle = RelayStore(":memory:", "python")
    want = [[oracle.sync(r) for r in batch] for batch in batches]

    seq_store = ShardedRelayStore(shards=4)
    seq_engine = BatchReconciler(seq_store, create_mesh())
    seq_responses = [seq_engine.reconcile(batch) for batch in batches]

    pipe_store = ShardedRelayStore(shards=4)
    pipe_engine = BatchReconciler(pipe_store, create_mesh())
    pipe_responses = pipe_engine.reconcile_stream(batches)

    try:
        assert dump(seq_store) == dump(oracle)
        assert dump(pipe_store) == dump(oracle)
        assert seq_responses == want
        assert pipe_responses == want
    finally:
        seq_engine.close(), pipe_engine.close()
        seq_store.close(), pipe_store.close(), oracle.close()


def test_compact_segment_overflow_falls_back_to_full_pull():
    """A batch whose distinct (owner, minute) pairs exceed the device
    compaction cap must detect the overflow and decode via the
    full-width pull, bit-identical to the host fold."""
    from evolu_tpu.core.merkle import minute_deltas_host
    from evolu_tpu.core.timestamp import Timestamp
    from evolu_tpu.server.engine import deltas_from_columns
    from evolu_tpu.ops.host_parse import parse_timestamp_strings

    base = 1_700_000_000_000
    owners = {}
    ts_all = []
    for o in range(64):
        # Every row its own minute: segments == rows, far above cap.
        msgs = [
            timestamp_to_string(Timestamp(base + (o * 97 + i) * 60_000, 0, "a" * 16))
            for i in range(64)
        ]
        owners[f"u{o:02d}"] = msgs
        ts_all.extend(msgs)
    all_m, all_c, all_n, case_ok = parse_timestamp_strings(ts_all, with_case=True)
    owner_index, pos = {}, 0
    for o, msgs in owners.items():
        owner_index[o] = np.arange(pos, pos + len(msgs))
        pos += len(msgs)

    deltas, digest = deltas_from_columns(
        create_mesh(), owner_index, all_m, all_c, all_n, case_ok, ts_all
    )
    expect_digest = 0
    for o, msgs in owners.items():
        exp, d = minute_deltas_host(msgs)
        assert deltas[o] == exp, o
        expect_digest ^= d
    assert digest == expect_digest


def test_reconcile_stream_bad_batch_lands_prior_batch():
    """A malformed batch k+1 raising in start_batch must not drop the
    already-dispatched batch k: the stream finishes it (matching
    sequential reconcile, which would commit k before raising), and
    the store remains serviceable afterwards."""
    from evolu_tpu.server.engine import BatchReconciler
    from evolu_tpu.server.relay import ShardedRelayStore
    from evolu_tpu.sync import protocol

    def req(owner, msgs):
        return _sync_req(owner, "f" * 16, tuple(
            protocol.EncryptedCrdtMessage(m.timestamp, b"c") for m in msgs
        ))

    good = [req("uA", _mk_messages("a" * 16, 20))]
    bad = [protocol.SyncRequest(
        (protocol.EncryptedCrdtMessage("not-46-chars", b"c"),), "uB", "f" * 16, "{}"
    )]
    store = ShardedRelayStore(shards=2)
    engine = BatchReconciler(store, create_mesh())
    with pytest.raises(ValueError):
        engine.reconcile_stream([good, bad])
    stored = sum(
        s.db.exec('SELECT COUNT(*) FROM "message"')[0][0] for s in store.shards
    )
    assert stored == 20, "batch 1 must have committed despite batch 2 raising"
    # The engine keeps working after the error.
    engine.reconcile([req("uC", _mk_messages("c" * 16, 5))])
    stored = sum(
        s.db.exec('SELECT COUNT(*) FROM "message"')[0][0] for s in store.shards
    )
    assert stored == 25


def test_packed_owner_kernel_matches_wide_kernel():
    """The r5 packed-owner shard kernel (owner in the sort key's top
    bits, zero extra payloads) must produce BIT-identical outputs to
    the wide fallback on owner-consistent inputs — ties, stored-winner
    equal/greater flags, padding rows, multiple owners — and the
    host router must pick the wide kernel when ids exceed the packed
    bounds."""
    import jax
    import jax.numpy as jnp

    from evolu_tpu.ops.merge import _PAD_CELL
    from evolu_tpu.parallel.reconcile import (
        _shard_kernel,
        _shard_kernel_wide,
        shard_kernel_for,
    )

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    rng = np.random.default_rng(23)
    N = 1024  # 8 shards × 128
    mesh = create_mesh()

    def mapped(kern):
        spec = P("owners")
        return jax.jit(shard_map(
            kern, mesh=mesh, in_specs=(spec,) * 6,
            out_specs=(spec,) * 8 + (P(),), check_vma=False,
        ))

    with jax.enable_x64(True):
        packed = mapped(_shard_kernel)
        wide = mapped(_shard_kernel_wide)
        for trial in range(10):
            n = int(rng.integers(8, N))
            cells = int(rng.integers(1, n))
            cell = np.full(N, int(_PAD_CELL), np.int32)
            cell[:n] = rng.integers(0, cells, n)
            owner_of_cell = rng.integers(0, 16, cells)  # owner = f(cell)
            owner = np.zeros(N, np.int64)
            owner[:n] = owner_of_cell[cell[:n]]
            k1 = np.zeros(N, np.uint64); k2 = np.zeros(N, np.uint64)
            k1[:n] = rng.integers(1, 9, n); k2[:n] = rng.integers(0, 5, n)
            ex1 = np.zeros(N, np.uint64); ex2 = np.zeros(N, np.uint64)
            ex1_c = rng.integers(0, 9, cells).astype(np.uint64)
            ex2_c = rng.integers(0, 5, cells).astype(np.uint64)
            ex1[:n] = ex1_c[cell[:n]]; ex2[:n] = ex2_c[cell[:n]]
            args = tuple(map(jnp.asarray, (cell, k1, k2, ex1, ex2, owner)))
            a = packed(*args)
            b = wide(*args)
            # Sort orders differ (owner-major vs cell-major): compare
            # the masks in BATCH order via the shard-local permutation.
            from evolu_tpu.ops.merge import unpermute_masks

            block = N // mesh.devices.size
            xa, ua = unpermute_masks(
                np.asarray(a[0]), np.asarray(a[1]), np.asarray(a[2]),
                block_size=block,
            )
            xb, ub = unpermute_masks(
                np.asarray(b[0]), np.asarray(b[1]), np.asarray(b[2]),
                block_size=block,
            )
            assert np.array_equal(xa, xb), (trial, "xor")
            assert np.array_equal(ua, ub), (trial, "upsert")
            assert int(a[8]) == int(b[8]), (trial, "digest")
            # The (owner, minute) Merkle feed too — sorted orders
            # differ, so compare the order-insensitive decode.
            from evolu_tpu.ops.merkle_ops import decode_owner_minute_deltas

            da = decode_owner_minute_deltas(*(np.asarray(o) for o in a[3:8]))
            db_ = decode_owner_minute_deltas(*(np.asarray(o) for o in b[3:8]))
            assert da == db_, (trial, "minute deltas")

    # Router: in-bounds → packed; oversized cell ids or owners → wide
    # (plan path pinned to "sort" — the scatter route has its own
    # router pins in tests/test_scatter_merge.py).
    from evolu_tpu.ops.scatter_merge import set_plan_path

    set_plan_path("sort")
    try:
        small = {"cell_id": np.array([1, int(_PAD_CELL)], np.int32),
                 "owner_ix": np.array([3, 0], np.int64)}
        assert shard_kernel_for(small) is _shard_kernel
        big_cell = {"cell_id": np.array([1 << 25], np.int32),
                    "owner_ix": np.array([0], np.int64)}
        assert shard_kernel_for(big_cell) is _shard_kernel_wide
        big_owner = {"cell_id": np.array([1], np.int32),
                     "owner_ix": np.array([4095], np.int64)}
        assert shard_kernel_for(big_owner) is _shard_kernel_wide
    finally:
        set_plan_path("auto")


def test_run_batch_wire_on_generic_store_without_db_handle():
    """A store exposing only the RelayStore METHOD surface (no `.db`
    SQL handle at all) must route through the object-respond fallback
    instead of raising AttributeError (ADVICE r5) — byte-identical to
    a real RelayStore served the same batch."""
    from evolu_tpu.server.engine import BatchReconciler
    from evolu_tpu.server.relay import RelayStore
    from evolu_tpu.sync import protocol

    class GenericStore:
        """Method-only facade over a private RelayStore."""

        def __init__(self):
            self._inner = RelayStore()

        def add_messages(self, user_id, messages):
            return self._inner.add_messages(user_id, messages)

        def get_messages(self, user_id, node_id, server_tree, client_tree):
            return self._inner.get_messages(user_id, node_id, server_tree, client_tree)

        def get_merkle_tree(self, user_id):
            return self._inner.get_merkle_tree(user_id)

        def close(self):
            self._inner.close()

    def enc(msgs):
        return tuple(
            protocol.EncryptedCrdtMessage(m.timestamp, b"ct-" + m.timestamp.encode())
            for m in msgs
        )

    owners = {f"g{i}": _mk_messages(f"{i + 3:016x}", 15 + i) for i in range(4)}
    push = [
        _sync_req(o, msgs[0].timestamp[30:46], enc(msgs)) for o, msgs in owners.items()
    ]
    cold = [_sync_req(o, "e" * 16) for o in owners]

    ref_store, gen_store = RelayStore(), GenericStore()
    ref_eng = BatchReconciler(ref_store, create_mesh())
    gen_eng = BatchReconciler(gen_store, create_mesh())
    try:
        for batch in (push, cold):
            want = ref_eng.run_batch_wire(batch)
            got = gen_eng.run_batch_wire(batch)
            assert got == want
    finally:
        ref_eng.close(), gen_eng.close()
        ref_store.close(), gen_store.close()


def test_delta_compact_transfer_matches_full_key_kernel():
    """The 16 B/row delta-encoded compact upload (VERDICT #9) must
    produce identical deltas + digest to the 20 B/row packed-HLC-key
    kernel run on the same layout, and batches outside its admission
    bounds (millis span ≥ 2^32 ms) must silently keep the full-key
    kernel — same results either way, and the host fold's."""
    from evolu_tpu.core.merkle import minute_deltas_host
    from evolu_tpu.core.timestamp import Timestamp
    from evolu_tpu.obs import metrics
    from evolu_tpu.ops import to_host_many, with_x64
    from evolu_tpu.ops.host_parse import parse_timestamp_strings
    from evolu_tpu.server import engine

    base = 1_700_000_000_000
    mesh = create_mesh()

    @with_x64
    def full_key(cols):
        """The full-key program on the layout `_deltas_layout` returns
        (its words rebuilt by `_full_key_upload` where the layout
        admitted the delta variant)."""
        deltas, digest, good, layout = engine._deltas_layout(mesh, *cols, None)
        buf, k1, oix, cap, delta, _rows = layout
        if delta:
            buf = engine._full_key_upload(
                buf.reshape(mesh.devices.size, -1), k1, oix).reshape(-1)
        out = engine._compiled_packed_kernel(mesh, cap, False)(buf)
        state = (deltas, digest, good, None, (buf, k1, oix, mesh, cap))
        return engine.deltas_decode(state, to_host_many(out)), delta

    def uploaded(variant):
        return metrics.get_counter(
            "evolu_engine_compact_upload_bytes_total", variant=variant)

    def run(spread, admitted):
        owners, ts_all = {}, []
        for o in range(5):
            msgs = [
                timestamp_to_string(
                    Timestamp(base + o * 60_000 + i * spread, i % 3, f"{o + 1:016x}")
                )
                for i in range(40)
            ]
            owners[f"u{o}"] = msgs
            ts_all.extend(msgs)
        all_m, all_c, all_n, case_ok = parse_timestamp_strings(ts_all, with_case=True)
        owner_index, pos = {}, 0
        for o, msgs in owners.items():
            owner_index[o] = np.arange(pos, pos + len(msgs))
            pos += len(msgs)
        cols = (owner_index, all_m, all_c, all_n, case_ok, ts_all)
        before = uploaded("delta"), uploaded("full")
        routed = engine.deltas_from_columns(mesh, *cols)
        grew = uploaded("delta") > before[0], uploaded("full") > before[1]
        assert grew == (admitted, not admitted)
        full, layout_admits = full_key(cols)
        assert layout_admits == admitted
        # Host oracle cross-check, not just self-consistency.
        expect_digest = 0
        for o, msgs in owners.items():
            exp, d = minute_deltas_host(msgs)
            assert routed[0][o] == exp, o
            expect_digest ^= d
        assert routed == full
        assert routed[1] == expect_digest

    run(spread=977, admitted=True)  # in-bounds: the delta kernel serves it
    run(spread=120_000_000_00, admitted=False)  # 1.2e10 ms × 40 rows ≫ 2^32: full-key
