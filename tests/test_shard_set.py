"""The shard-set storage calls (ISSUE 27): `relay_insert_packed_shards`
and `relay_commit_shards` land the storage leg of an engine pass on
every live shard in two native calls on the caller's thread.

What is pinned: on twin stores the two calls leave exactly what the
per-shard sequence they replaced leaves (BEGIN, `relay_insert_packed`,
the owners' tree SELECTs, the `merkleTree` upsert, COMMIT, shard by
shard): the same was-new flags row for row, the same stored trees read,
the same dumps; through the engine the same wire bytes as the same
batches through a `RelayStore(":memory:", "python")`; a failure in the
middle of the set leaves no shard with a row or an open transaction, in
Python's checks and in C, and through the scheduler the poisoned batch
is retried as singletons with the sequential server's answers and a
clean ledger; a served pass is exactly two such calls, and no
`evolu-ingest` thread exists any more.
"""

import threading
import urllib.request
import zlib

import numpy as np
import pytest

from conftest import relay_store_dump
from evolu_tpu.core.merkle import (
    apply_prefix_xors,
    merkle_tree_from_string,
    merkle_tree_to_string,
    minute_deltas_host,
)
from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.core.types import UnknownError
from evolu_tpu.obs import ledger as ledger_mod
from evolu_tpu.obs import metrics
from evolu_tpu.server import engine as engine_mod
from evolu_tpu.server.engine import BatchReconciler
from evolu_tpu.server.relay import RelayServer, RelayStore, ShardedRelayStore
from evolu_tpu.server.scheduler import SyncScheduler
from evolu_tpu.storage import native
from evolu_tpu.sync import protocol

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="native host library unavailable")

BASE = 1_700_000_000_000
SHARDS = 8
SUBSETS = {1: (5,), 3: (1, 4, 6), 8: tuple(range(SHARDS))}
SCENARIOS = ("fresh", "in_batch_duplicates", "already_stored", "owner_in_two_groups")
UPSERT = 'INSERT OR REPLACE INTO "merkleTree" ("userId", "merkleTree") VALUES (?, ?)'


ROWS_PER_SHARD = {"served": 24, "bulk": 600}  # a served pass's size; thousands of rows a call


def _owners_on(shard: int, n: int):
    out, i = [], 0
    while len(out) < n:
        u = f"owner-{shard}-{i}"
        if zlib.crc32(u.encode()) % SHARDS == shard:
            out.append(u)
        i += 1
    return out


def _ts(owner: str, i: int) -> str:
    node = f"{zlib.crc32(owner.encode()):016x}"
    return timestamp_to_string(Timestamp(BASE + i * 7_000, i % 4, node))


def _groups(shard: int, rows: int, scenario: str, round_: int):
    """One shard's request groups [(owner, [timestamps])] for a round.
    Round 1 of `already_stored` re-sends half of round 0."""
    a, b, c = _owners_on(shard, 3)
    third = rows // 3
    lo = round_ * rows
    if scenario == "already_stored" and round_ == 1:
        lo = third // 2
    groups = [
        (a, [_ts(a, lo + i) for i in range(third)]),
        (b, [_ts(b, lo + i) for i in range(third)]),
        (c, [_ts(c, lo + i) for i in range(rows - 2 * third)]),
    ]
    if scenario == "in_batch_duplicates":
        # Within a group and across two groups of one owner.
        groups[0][1].extend(groups[0][1][:5])
        groups.append((b, groups[1][1][-4:] + [_ts(b, lo + rows + 1)]))
    if scenario == "owner_in_two_groups":
        groups.append((a, [_ts(a, lo + rows + i) for i in range(6)]))
    return groups


def _batch(groups):
    """→ the argument tuple of `relay_insert_packed`."""
    ts = [t for _o, tss in groups for t in tss]
    contents = [b"ct:" + t[-20:].encode() for t in ts]
    return ([o for o, _t in groups], [len(t) for _o, t in groups],
            *engine_mod._pack_rows(ts, contents))


def _tree_rows(batch, was_new, stored):
    """The pass's (owner, tree TEXT) rows: each owner's stored tree
    folded with its NEW rows, first-appearance order."""
    gu, gc, ts_packed, _cp, _lens = batch
    new_ts = {}
    pos = 0
    for u, n in zip(gu, gc):
        for i in range(pos, pos + n):
            if was_new[i]:
                new_ts.setdefault(u, []).append(ts_packed[i * 46:(i + 1) * 46].decode())
        pos += n
    rows = []
    for u, tss in new_ts.items():
        deltas, _ = minute_deltas_host(iter(tss))
        rows.append((u, merkle_tree_to_string(
            apply_prefix_xors(merkle_tree_from_string(stored[u]), deltas))))
    return rows


def _per_shard_pass(store, live, batches):
    """The sequence the shard-set calls replaced, shard by shard."""
    flags, stored = [], {}
    for si in live:
        store.shards[si].db.begin()
    for si, batch in zip(live, batches):
        flags.append(store.shards[si].db.relay_insert_packed(*batch))
        for u in batch[0]:
            stored[u] = store.shards[si].get_merkle_tree_string(u)
    for si, batch, f in zip(live, batches, flags):
        rows = _tree_rows(batch, f, stored)
        if rows:
            store.shards[si].db.run_many(UPSERT, rows)
    for si in live:
        store.shards[si].db.commit()
    return flags, stored


def _shard_set_pass(store, live, batches):
    dbs = [store.shards[si].db for si in live]
    flags, stored = native.relay_insert_packed_shards(dbs, batches)
    assert all(db._in_txn for db in dbs)
    native.relay_commit_shards(
        dbs, [_tree_rows(b, f, stored) for b, f in zip(batches, flags)])
    assert not any(db._in_txn for db in dbs)
    return flags, stored


def _dump(store):
    """`relay_store_dump` plus the merkleTree rows in rowid order: an
    upsert order that differed would show there and nowhere else."""
    return relay_store_dump(store), [
        s.db.exec('SELECT rowid, * FROM "merkleTree" ORDER BY rowid') for s in store.shards]


def _assert_clean(store, rows_expected=0):
    """No shard holds a row or a transaction (Python's flag AND SQLite's
    own state: a handle inside a transaction refuses BEGIN)."""
    for i, s in enumerate(store.shards):
        if not s.db._db:
            continue
        assert not s.db._in_txn, f"shard {i} still flagged inside a transaction"
        s.db.begin()
        s.db.rollback()
        assert s.db.exec('SELECT COUNT(*) FROM "message"') == [(rows_expected,)], i
        assert s.db.exec('SELECT COUNT(*) FROM "merkleTree"') == [(0,)], i


@pytest.mark.parametrize("size", sorted(ROWS_PER_SHARD))
@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("k", sorted(SUBSETS))
def test_shard_set_calls_match_the_per_shard_sequence(k, scenario, size):
    live = SUBSETS[k]
    rows = ROWS_PER_SHARD[size]
    a = ShardedRelayStore(":memory:", "native", shards=SHARDS)
    b = ShardedRelayStore(":memory:", "native", shards=SHARDS)
    try:
        for round_ in (0, 1):
            batches = [_batch(_groups(si, rows, scenario, round_)) for si in live]
            want_flags, want_stored = _per_shard_pass(a, live, batches)
            got_flags, got_stored = _shard_set_pass(b, live, batches)
            for si, w, g in zip(live, want_flags, got_flags):
                assert g.dtype == np.bool_ and np.array_equal(w, g), (round_, si)
            assert got_stored == want_stored, round_
            assert _dump(a) == _dump(b), round_
        if scenario in ("in_batch_duplicates", "already_stored"):
            assert not all(f.all() for f in got_flags), "scenario made no duplicate"
    finally:
        a.close(), b.close()


def _requests(live, rows, scenario, round_):
    reqs = []
    for si in live:
        for owner, tss in _groups(si, rows, scenario, round_):
            msgs = tuple(
                protocol.EncryptedCrdtMessage(t, b"ct:" + t[-20:].encode()) for t in tss)
            reqs.append(protocol.SyncRequest(msgs, owner, "f" * 16, "{}"))
    return reqs


def _replay_per_request(reqs):
    """→ (python store, its answers): the sequential server's replay."""
    oracle = RelayStore(":memory:", "python")
    with ledger_mod.quarantine():
        return oracle, [oracle.sync(r) for r in reqs]


def _assert_equals_replay(store, oracle):
    dump = relay_store_dump(store)
    assert sorted(r for msgs, _t in dump for r in msgs) == oracle.db.exec(
        'SELECT * FROM "message" ORDER BY "timestamp", "userId"')
    assert sorted(r for _m, ts in dump for r in ts) == oracle.db.exec(
        'SELECT * FROM "merkleTree" ORDER BY "userId"')


@pytest.mark.parametrize("size", sorted(ROWS_PER_SHARD))
@pytest.mark.parametrize("entry", ("run_batch_wire", "reconcile_wire"))
@pytest.mark.parametrize("k", sorted(SUBSETS))
def test_engine_wire_bytes_match_the_python_replay(k, entry, size):
    """Every scenario as consecutive batches through the served entry
    (`run_batch_wire`) and the offline one (`reconcile_wire`) on the
    native sharded store, both `start_batch` + `_land`, against the
    same batches through the python backend (`_ingest_generic`)."""
    live = SUBSETS[k]
    rows = ROWS_PER_SHARD[size]
    store = ShardedRelayStore(":memory:", "native", shards=SHARDS)
    oracle = RelayStore(":memory:", "python")
    eng, oracle_eng = BatchReconciler(store), BatchReconciler(oracle)
    calls0 = metrics.get_counter("evolu_engine_store_calls_total", op="insert")
    try:
        n = 0
        for scenario in SCENARIOS[1:]:
            for round_ in (0, 1):
                reqs = _requests(live, rows, scenario, round_)
                with ledger_mod.quarantine():
                    got = getattr(eng, entry)(reqs)
                    want = oracle_eng.run_batch_wire(reqs)
                assert got == want, (scenario, round_)
                n += 1
        assert metrics.get_counter(
            "evolu_engine_store_calls_total", op="insert") == calls0 + n
        _assert_equals_replay(store, oracle)
    finally:
        eng.close(), oracle_eng.close(), store.close(), oracle.close()


def test_reconcile_lands_through_the_stream_route_in_two_store_calls():
    """`reconcile` on a packed-capable store is the scheduler's route:
    one pass counted under `path="stream"`, two native store calls, the
    `pass_*` stages of a served pass; answers, rows and trees are the
    per-request replay's on the python backend."""
    store = ShardedRelayStore(":memory:", "native", shards=SHARDS)
    eng = BatchReconciler(store)
    # One request an owner a batch (a batched pass answers every request
    # of an owner from the tree after all of them); the second batch
    # re-sends half of the first and repeats rows inside one request.
    first = _requests(SUBSETS[8], 24, "already_stored", 0)
    reqs = _requests(SUBSETS[8], 24, "already_stored", 1)
    reqs[0] = protocol.SyncRequest(
        reqs[0].messages + reqs[0].messages[:3], reqs[0].user_id, "f" * 16, "{}")
    oracle, want = _replay_per_request(first + reqs)
    try:
        with ledger_mod.quarantine():
            assert eng.reconcile(first) == want[:len(first)]
        passes0 = {p: metrics.get_counter("evolu_engine_store_passes_total", path=p)
                   for p in ("stream", "generic", "write_behind", "oneshot")}
        calls0 = [metrics.get_counter("evolu_engine_store_calls_total", op=op)
                  for op in ("insert", "commit")]
        tiles0 = metrics.registry.get_histogram("evolu_stage_ms", stage="pass_insert")[3]
        with ledger_mod.quarantine():
            assert eng.reconcile(reqs) == want[len(first):]
        passes = {p: metrics.get_counter("evolu_engine_store_passes_total", path=p)
                  for p in passes0}
        assert passes == {**passes0, "stream": passes0["stream"] + 1}
        assert [metrics.get_counter("evolu_engine_store_calls_total", op=op)
                for op in ("insert", "commit")] == [calls0[0] + 1, calls0[1] + 1]
        assert metrics.registry.get_histogram(
            "evolu_stage_ms", stage="pass_insert")[3] == tiles0 + 1
        _assert_equals_replay(store, oracle)
    finally:
        eng.close(), store.close(), oracle.close()


def test_a_batch_replayed_whole_inserts_nothing_and_counts_duplicates():
    """Every row a duplicate: the device hashes them all, every owner is
    recomputed on the host to an empty delta, no tree TEXT moves, and
    the ledger files every row under store.duplicate."""
    ledger_mod.reset()
    ledger_mod.set_enabled(True)
    store = ShardedRelayStore(":memory:", "native", shards=SHARDS)
    eng = BatchReconciler(store)
    reqs = _requests(SUBSETS[8], 24, "in_batch_duplicates", 0)
    n = sum(len(r.messages) for r in reqs)
    try:
        first = eng.reconcile(reqs)
        before = _dump(store)
        t0 = ledger_mod.totals()
        again = eng.reconcile(reqs)
        assert _dump(store) == before
        assert [r.merkle_tree for r in again] == [r.merkle_tree for r in first]
        t1 = ledger_mod.totals()
        assert t1[ledger_mod.STORE_INSERTED] == t0[ledger_mod.STORE_INSERTED]
        assert t1[ledger_mod.STORE_DUPLICATE] == t0[ledger_mod.STORE_DUPLICATE] + n
    finally:
        eng.close(), store.close()
        ledger_mod.reset()


class _StandIn:
    """The RelayStore surface over a real store, without `.db` and
    without the store's own answer."""

    def __init__(self, inner):
        for name in ("add_messages", "get_messages", "get_merkle_tree",
                     "get_merkle_tree_string", "sync"):
            setattr(self, name, getattr(inner, name))


@pytest.mark.parametrize("shape, packed, route", [
    ("native", True, "stream"),
    ("python", False, "generic"),
    ("sharded-native", True, "stream"),
    ("sharded-python", False, "generic"),
    ("stand-in", False, "generic"),
])
def test_the_store_answers_packed_capable_and_the_engine_routes_by_it(shape, packed, route):
    backend = "python" if shape.endswith("python") else "native"
    inner = (ShardedRelayStore(":memory:", backend, shards=2) if shape.startswith("sharded")
             else RelayStore(":memory:", backend))
    store = _StandIn(inner) if shape == "stand-in" else inner
    eng = BatchReconciler(store)
    try:
        assert getattr(store, "packed", False) is packed
        for s in getattr(store, "shards", ()):
            assert s.packed is packed
        assert eng._route(live=False) == eng._route(live=True) == route
        passes0 = metrics.get_counter("evolu_engine_store_passes_total", path=route)
        reqs = _requests((0, 1), 12, "fresh", 0)
        oracle, want = _replay_per_request(reqs)
        with ledger_mod.quarantine():
            assert eng.reconcile(reqs) == want
        assert metrics.get_counter(
            "evolu_engine_store_passes_total", path=route) == passes0 + 1
        oracle.close()
    finally:
        eng.close(), inner.close()


def _poison_closed(store, si):
    store.shards[si].db.close()


def _poison_in_txn(store, si):
    store.shards[si].db.begin()  # Python's own check refuses the set


def _poison_raw_begin(store, si):
    store.shards[si].db.exec("BEGIN")  # only C's BEGIN finds out


def _poison_no_table(store, si):
    store.shards[si].db.exec('DROP TABLE "message"')  # fails after C's BEGIN


@pytest.mark.parametrize("size", sorted(ROWS_PER_SHARD))
@pytest.mark.parametrize(
    "poison", (_poison_closed, _poison_in_txn, _poison_raw_begin, _poison_no_table))
def test_insert_failure_mid_set_leaves_no_row_and_no_transaction(poison, size):
    live = SUBSETS[8]
    bad = 3
    store = ShardedRelayStore(":memory:", "native", shards=SHARDS)
    try:
        batches = [_batch(_groups(si, ROWS_PER_SHARD[size], "fresh", 0)) for si in live]
        poison(store, bad)
        with pytest.raises(UnknownError):
            native.relay_insert_packed_shards([s.db for s in store.shards], batches)
        db = store.shards[bad].db
        if poison is _poison_in_txn:
            assert db._in_txn  # the transaction that was there is its owner's
            db.rollback()
        elif poison is _poison_raw_begin:
            assert not db._in_txn
            db.exec("ROLLBACK")
        elif poison is _poison_no_table:
            db.exec('CREATE TABLE "message" ("x")')
        _assert_clean(store)
        # The set is usable again at once.
        if poison is not _poison_closed and poison is not _poison_no_table:
            _shard_set_pass(store, live, batches)
    finally:
        store.close()


@pytest.mark.parametrize("failure", ("upsert_in_c", "body_raises", "not_in_txn"))
def test_commit_failure_rolls_every_shard_back(failure):
    live = SUBSETS[8]
    store = ShardedRelayStore(":memory:", "native", shards=SHARDS)
    eng = BatchReconciler(store)
    try:
        batches = [_batch(_groups(si, 24, "fresh", 0)) for si in live]
        if failure == "body_raises":
            with pytest.raises(RuntimeError, match="between the calls"):
                with eng._store_pass(store.shards, live, batches) as (flags, stored, rows):
                    assert all(f.all() for f in flags.values())
                    raise RuntimeError("between the calls")
        else:
            dbs = [s.db for s in store.shards]
            flags, stored = native.relay_insert_packed_shards(dbs, batches)
            rows = [_tree_rows(b, f, stored) for b, f in zip(batches, flags)]
            if failure == "upsert_in_c":
                # Inside shard 3's transaction: the roll-back restores it.
                dbs[3].exec('DROP TABLE "merkleTree"')
            else:
                dbs[3].rollback()
            with pytest.raises(UnknownError):
                native.relay_commit_shards(dbs, rows)
        _assert_clean(store)
    finally:
        eng.close(), store.close()


def _post(url, req):
    with urllib.request.urlopen(urllib.request.Request(
            url, data=protocol.encode_sync_request(req), method="POST"), timeout=120) as r:
        return r.read()


@pytest.mark.parametrize("poison", ("shard_in_txn", "pull_fails_after_insert"))
def test_poisoned_pass_is_retried_as_singletons_with_exact_answers(poison, monkeypatch):
    """A pass that fails in the middle of the set, before anything was
    begun (`shard_in_txn`) or with every shard's rows inserted and its
    transaction open (`pull_fails_after_insert`), leaves nothing behind,
    so the scheduler's singleton retry answers what a sequential server
    answers and the ledger balances."""
    ledger_mod.reset()
    ledger_mod.set_enabled(True)
    store = ShardedRelayStore(":memory:", "native", shards=SHARDS)
    sched = SyncScheduler(store, max_batch=16, max_wait_s=0.3)
    server = RelayServer(store, scheduler=sched).start()
    poisoned0 = metrics.get_counter("evolu_sched_poisoned_batches_total")
    owners = [_owners_on(si, 1)[0] for si in range(SHARDS)]
    reqs = {
        o: protocol.SyncRequest(
            tuple(protocol.EncryptedCrdtMessage(_ts(o, i), b"ct-%d" % i) for i in range(5)),
            o, "f" * 16, "{}")
        for o in owners
    }
    bad = store.shards[3].db
    if poison == "shard_in_txn":
        bad.begin()
    else:
        real, state = engine_mod.deltas_pull, {"armed": True}

        def pull_once(dev):
            if state.pop("armed", False):
                raise RuntimeError("injected pull failure")
            return real(dev)

        monkeypatch.setattr(engine_mod, "deltas_pull", pull_once)
    got, errors = {}, []
    try:
        def client(o):
            try:
                got[o] = _post(server.url, reqs[o])
            except Exception as e:  # noqa: BLE001 - collected and re-raised
                errors.append(e)

        threads = [threading.Thread(target=client, args=(o,)) for o in owners]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
        if poison == "shard_in_txn":
            # The singleton of that shard's owner joined the open
            # transaction; its owner commits it.
            assert bad._in_txn
            bad.commit()
        assert metrics.get_counter("evolu_sched_poisoned_batches_total") > poisoned0
        assert not any(s.db._in_txn for s in store.shards)
        oracle = RelayStore(":memory:", "python")
        try:
            with ledger_mod.quarantine():
                for o in owners:
                    assert got[o] == protocol.encode_sync_response(oracle.sync(reqs[o])), o
        finally:
            oracle.close()
        assert sum(s.stats()[0]["messages"] for s in store.shards) == 5 * len(owners)
        assert ledger_mod.audit() == [], ledger_mod.audit()
        t = ledger_mod.totals()
        assert t[ledger_mod.INGRESS_SYNC] == t[ledger_mod.STORE_INSERTED] == 5 * len(owners)
    finally:
        server.stop()
        store.close()


def test_a_served_pass_is_two_store_calls_and_no_ingest_thread():
    store = ShardedRelayStore(":memory:", "native", shards=SHARDS)
    server = RelayServer(store, batching=True).start()

    def calls(op):
        return metrics.get_counter("evolu_engine_store_calls_total", op=op)

    try:
        owners = [_owners_on(si, 1)[0] for si in range(SHARDS)]
        for round_ in range(20):
            passes0 = metrics.get_counter("evolu_engine_store_passes_total", path="stream")
            before = calls("insert"), calls("commit")
            o = owners[round_ % SHARDS]
            _post(server.url, protocol.SyncRequest(
                tuple(protocol.EncryptedCrdtMessage(_ts(o, round_ * 10 + i), b"ct")
                      for i in range(10)),
                o, "f" * 16, "{}"))
            assert metrics.get_counter(
                "evolu_engine_store_passes_total", path="stream") == passes0 + 1
            assert (calls("insert"), calls("commit")) == (before[0] + 1, before[1] + 1)
        names = [t.name for t in threading.enumerate()]
        assert not [n for n in names if n.startswith("evolu-ingest")], names
        assert any(n.startswith("evolu-pull") for n in names), names
        assert not hasattr(BatchReconciler, "_map_shards")
        assert not hasattr(BatchReconciler, "_pool")
    finally:
        server.stop()
        store.close()
