"""Partial replication (ISSUE 18): scoped sync filters.

Covers the whole slice pipeline: the client scope model + HMAC lane
tags (sync/scope.py), the ScopeClause wire codec under the
ValueError-only contract with v1 byte-identity when the capability is
absent, relay-side lane tracking with the cardinality cap + overflow
lane, scoped Merkle subtree derivation (device/host fold equivalence,
tree cache coherence), the scoped serve (watermark + lane filtering,
own-node livelock avoidance), push-hub lane gating, the
capability-gated client emission + fleet-failover downgrade
(the PR-8 retarget lesson applied to scope), worker-side deferred
materialization with the counted frontier + typed query deferral +
widen re-materialization, and the scoped snapshot capture.
"""

import random
import urllib.error

import pytest

from evolu_tpu.api import model
from evolu_tpu.api.query import table
from evolu_tpu.core.merkle import (
    apply_prefix_xors,
    diff_merkle_trees,
    merkle_tree_from_string,
    merkle_tree_to_string,
    minute_deltas_host,
)
from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.obs import ledger, metrics
from evolu_tpu.runtime import messages as msg
from evolu_tpu.runtime.client import create_evolu
from evolu_tpu.server import scope as server_scope
from evolu_tpu.server.relay import RelayServer, RelayStore, serve_single_request
from evolu_tpu.sync import protocol
from evolu_tpu.sync.client import connect
from evolu_tpu.sync.scope import ScopeDeferred, SyncScope, derive_scope_tag
from evolu_tpu.utils.config import Config

BASE = 1_700_000_000_000
MINUTE = 60_000
NODE_A = "a1b2c3d4e5f60718"
NODE_B = "0f1e2d3c4b5a6978"

SCHEMA = {
    "todo": ("title", "isCompleted", *model.COMMON_COLUMNS),
    "note": ("body", *model.COMMON_COLUMNS),
}


def _ts(millis, counter=0, node=NODE_A):
    return timestamp_to_string(Timestamp(millis, counter, node))


def _emsgs(node, minute, n, start=0):
    return tuple(
        protocol.EncryptedCrdtMessage(
            _ts(BASE + minute * MINUTE + (start + i) * 500, 0, node),
            b"ct-%d-%d" % (minute, start + i),
        )
        for i in range(n)
    )


def _client_tree(timestamps):
    deltas, _ = minute_deltas_host(timestamps)
    return merkle_tree_to_string(apply_prefix_xors({}, deltas))


@pytest.fixture(autouse=True)
def _fresh_scope_state():
    server_scope.tree_cache.reset()
    yield
    server_scope.tree_cache.reset()


# --- scope model + lane tags (sync/scope.py) ---


def test_derive_scope_tag_shape_and_determinism():
    t1 = derive_scope_tag("alpha mnemonic", "todo")
    t2 = derive_scope_tag("alpha mnemonic", "todo")
    assert t1 == t2
    assert len(t1) == 16 and all(c in "0123456789abcdef" for c in t1)
    assert derive_scope_tag("alpha mnemonic", "note") != t1
    assert derive_scope_tag("beta mnemonic", "todo") != t1


def test_sync_scope_model():
    assert SyncScope().is_noop
    s = SyncScope(tables=("todo",))
    assert not s.is_noop
    assert s.table_in_scope("todo")
    assert not s.table_in_scope("note")
    # System tables are always in scope — the substrate stays whole.
    assert s.table_in_scope("__message")
    # No table filter = everything in scope.
    assert SyncScope(watermark_millis=5).table_in_scope("anything")
    with pytest.raises(ValueError):
        SyncScope(watermark_millis=-1)
    with pytest.raises(ValueError):
        SyncScope(tables=tuple(f"t{i}" for i in range(
            protocol._MAX_SCOPE_TAGS + 1)))


def test_widen_semantics():
    s = SyncScope(watermark_millis=100, tables=("todo",))
    w = s.widen(50, ("note",))
    assert w.watermark_millis == 50 and w.tables == ("todo", "note")
    assert s.widen() == s  # no-arg widen is the identity
    with pytest.raises(ValueError):
        s.widen(200)  # raising the watermark narrows
    with pytest.raises(ValueError):
        SyncScope(watermark_millis=100).widen(tables=("todo",))
    # Adding an already-present table is idempotent.
    assert s.widen(tables=("todo",)).tables == ("todo",)


def test_wire_clause():
    assert SyncScope().wire_clause("m") is None
    s = SyncScope(watermark_millis=7, tables=("todo",))
    c = s.wire_clause("m", push_tables=("todo", "note"))
    assert c.watermark_millis == 7
    assert c.tags == (derive_scope_tag("m", "todo"),)
    # Pushed messages are tagged even for OUT-of-scope tables — the
    # relay's lanes must stay truthful for other scoped clients.
    assert c.push_tags == (
        derive_scope_tag("m", "todo"), derive_scope_tag("m", "note"))
    # Watermark-only scope: no lanes requested, no push assignment.
    c2 = SyncScope(watermark_millis=7).wire_clause("m", push_tables=("todo",))
    assert c2.tags == () and c2.push_tags == ()


# --- wire codec (satellite: fuzz + downgrade) ---


def test_scope_clause_roundtrip():
    clause = protocol.ScopeClause(12345, ("aa" * 8, "bb" * 8), ("cc" * 8, ""))
    req = protocol.SyncRequest(
        (protocol.EncryptedCrdtMessage(_ts(BASE), b"x"),
         protocol.EncryptedCrdtMessage(_ts(BASE + 1), b"y")),
        "user1", NODE_A, "{}", ("sync-scope-v1",), clause,
    )
    out = protocol.decode_sync_request(protocol.encode_sync_request(req))
    assert out == req
    assert out.scope.watermark_millis == 12345


def test_unscoped_request_stays_byte_identical():
    """The v1 wire pin: scope=None emits NO field 6 — byte-for-byte
    what the pre-scope encoder produced (the golden protoc fixture in
    test_sync.py pins the same property against reference bytes)."""
    req = protocol.SyncRequest((), "u", NODE_A, "{}")
    base = protocol.encode_sync_request(req)
    assert protocol.encode_request_scope(None) == b""
    assert b"".join((
        protocol._string(2, "u"), protocol._string(3, NODE_A),
        protocol._string(4, "{}"),
    )) == base
    # A no-op scope never reaches the wire (wire_clause → None).
    assert SyncScope().wire_clause("m") is None


def test_scope_decode_bounds():
    too_many = protocol.ScopeClause(
        0, tuple("t%02d" % i for i in range(protocol._MAX_SCOPE_TAGS + 4)))
    with pytest.raises(ValueError):
        protocol.decode_scope_clause(protocol.encode_scope_clause(too_many))
    long_tag = protocol.ScopeClause(0, ("x" * (protocol._MAX_SCOPE_TAG_LEN + 1),))
    with pytest.raises(ValueError):
        protocol.decode_scope_clause(protocol.encode_scope_clause(long_tag))
    # push_tags count must equal the message count.
    bad = protocol.encode_sync_request(
        protocol.SyncRequest((), "u", NODE_A, "{}")
    ) + protocol.encode_request_scope(protocol.ScopeClause(0, (), ("t1",)))
    with pytest.raises(ValueError):
        protocol.decode_sync_request(bad)
    # Negative watermark (10-byte two's-complement varint) rejects.
    neg = protocol._tag(1, 0) + protocol._varint((1 << 64) - 5)
    with pytest.raises(ValueError):
        protocol.decode_scope_clause(neg)
    # Wrong wire type for a tag field rejects.
    with pytest.raises(ValueError):
        protocol.decode_scope_clause(protocol._tag(2, 0) + protocol._varint(7))


def test_scope_codec_fuzz_valueerror_only():
    """Malformed scope bytes — standalone and embedded as field 6 —
    raise ValueError and nothing else (the wire-decoder contract)."""
    rng = random.Random(18)
    prefix = protocol.encode_sync_request(
        protocol.SyncRequest((), "u", NODE_A, "{}"))
    for _ in range(1500):
        blob = rng.randbytes(rng.randrange(0, 80))
        for data in (blob, prefix + protocol._len_delimited(6, blob)):
            try:
                protocol.decode_scope_clause(blob)
            except ValueError:
                pass
            try:
                protocol.decode_sync_request(data)
            except ValueError:
                pass


def test_snapshot_request_scope_roundtrip():
    req = protocol.SnapshotRequest("r1", 4096, ("o1",), BASE, ("aa" * 8,))
    out = protocol.decode_snapshot_request(
        protocol.encode_snapshot_request(req))
    assert out == req
    # Unscoped stays byte-identical (no fields 4/5 emitted).
    plain = protocol.SnapshotRequest("r1", 0, ())
    assert protocol.encode_snapshot_request(plain) == \
        protocol._string(1, "r1")
    with pytest.raises(ValueError):
        protocol.decode_snapshot_request(
            protocol._string(1, "r") + protocol._tag(5, 0) +
            protocol._varint(3))


# --- relay lane tracking + cardinality hardening ---


def test_record_push_lanes_and_overflow_cap():
    store = RelayStore()
    try:
        db = store.db
        before = metrics.get_counter("evolu_scope_overflow_total")
        # Distinct lanes up to the cap record verbatim...
        n = server_scope.MAX_OWNER_LANES
        ts = [_ts(BASE + i) for i in range(n + 10)]
        tags = ["%016x" % i for i in range(n)] + ["%016x" % (n + i) for i in range(10)]
        server_scope.record_push_lanes(db, "u1", ts, tags)
        rows = db.exec_sql_query(
            'SELECT DISTINCT "tag" FROM "scopeLane" WHERE "userId" = ?',
            ("u1",))
        lanes = {r["tag"] for r in rows}
        # ...and the 10 past-cap tags collapsed into the overflow lane.
        assert server_scope.OVERFLOW_TAG in lanes
        assert len(lanes) == server_scope.MAX_OWNER_LANES + 1
        assert metrics.get_counter("evolu_scope_overflow_total") == before + 10
        # Overflow rows are never excluded, whatever lanes a request
        # asks for — the conservative always-served lane.
        excl = server_scope.excluded_timestamps(
            db, "u1", frozenset({"%016x" % 0}))
        assert set(ts[n:]).isdisjoint(excl)
        assert ts[1] in excl  # a known foreign lane IS excludable
        # Untagged pushes ("" per message) record nothing.
        server_scope.record_push_lanes(db, "u2", [_ts(BASE)], [""])
        assert db.exec_sql_query(
            'SELECT * FROM "scopeLane" WHERE "userId" = ?', ("u2",)) == []
    finally:
        store.close()


def test_record_push_lanes_author_only():
    """A resend relays foreign rows; tagging those would let a device
    censor another's rows out of scoped views AND open the
    retroactive-exclusion livelock — with `node_id`, only rows the
    pusher authored get a lane."""
    store = RelayStore()
    try:
        own = _ts(BASE, 0, NODE_A)
        foreign = _ts(BASE + 1, 0, NODE_B)
        server_scope.record_push_lanes(
            store.db, "u1", [own, foreign], ["aa" * 8, "bb" * 8],
            node_id=NODE_A)
        rows = store.db.exec_sql_query(
            'SELECT "timestamp", "tag" FROM "scopeLane" WHERE "userId"=?',
            ("u1",))
        assert {(r["timestamp"], r["tag"]) for r in rows} == {(own, "aa" * 8)}
    finally:
        store.close()


# --- scoped subtree: fold routes + cache ---


def test_scoped_fold_device_host_equivalence(monkeypatch):
    """The masked device minute-fold must equal the host oracle on
    canonical batches; non-canonical hex case must route to the host
    oracle (the r5 contract)."""
    monkeypatch.setattr(server_scope, "SCOPE_DEVICE_FOLD_MIN", 4)
    ts = [_ts(BASE + i * 700, i % 3, NODE_A if i % 2 else NODE_B)
          for i in range(64)]
    mask = [i % 3 != 1 for i in range(64)]
    before_dev = metrics.get_counter("evolu_scope_fold_total", route="device")
    got = server_scope.scoped_minute_deltas(ts, mask)
    assert metrics.get_counter(
        "evolu_scope_fold_total", route="device") == before_dev + 1
    want, _ = minute_deltas_host(t for t, keep in zip(ts, mask) if keep)
    assert got == want
    # Non-canonical case (uppercase node hex): host route, same result.
    bad = [t[:30] + t[30:].upper() for t in ts]
    before_host = metrics.get_counter("evolu_scope_fold_total", route="host")
    got_bad = server_scope.scoped_minute_deltas(bad, mask)
    assert metrics.get_counter(
        "evolu_scope_fold_total", route="host") == before_host + 1
    want_bad, _ = minute_deltas_host(
        t for t, keep in zip(bad, mask) if keep)
    assert got_bad == want_bad


def test_scoped_tree_cache_coherent_by_construction():
    store = RelayStore()
    try:
        store.add_messages("u1", _emsgs(NODE_A, 0, 8))
        clause = protocol.ScopeClause(BASE, (), ())
        full = store.get_merkle_tree_string("u1")
        misses = metrics.get_counter("evolu_scope_tree_cache_misses_total")
        t1, r1 = server_scope.scoped_tree_for(store, "u1", NODE_B, clause, full)
        assert metrics.get_counter("evolu_scope_tree_cache_misses_total") == misses + 1
        hits = metrics.get_counter("evolu_scope_tree_cache_hits_total")
        t2, r2 = server_scope.scoped_tree_for(store, "u1", NODE_B, clause, full)
        assert (t2, r2) == (t1, r1)
        assert metrics.get_counter("evolu_scope_tree_cache_hits_total") == hits + 1
        # Any ingest rewrites the full-tree text → the entry self-invalidates.
        store.add_messages("u1", _emsgs(NODE_A, 1, 4))
        full2 = store.get_merkle_tree_string("u1")
        assert full2 != full
        t3, _r3 = server_scope.scoped_tree_for(store, "u1", NODE_B, clause, full2)
        assert t3 != t1
        # ... which reads as a miss; misses track the owner's write rate.
        assert metrics.get_counter("evolu_scope_tree_cache_misses_total") == misses + 2
        assert metrics.get_counter("evolu_scope_tree_cache_hits_total") == hits + 1
        # LRU past the cap: the oldest entry goes, and is counted.
        evictions = metrics.get_counter("evolu_scope_tree_cache_evictions_total")
        lru = server_scope._ScopedTreeCache(cap=1)
        lru.put(("u1", NODE_A), full, t1, r1)
        lru.put(("u1", NODE_B), full, t1, r1)
        assert lru.get(("u1", NODE_A), full) is None
        assert lru.get(("u1", NODE_B), full) == (t1, r1)
        assert metrics.get_counter("evolu_scope_tree_cache_evictions_total") == evictions + 1
    finally:
        store.close()


# --- the scoped serve ---


def test_scoped_response_watermark_filter():
    store = RelayStore()
    try:
        old = _emsgs(NODE_A, 0, 6)
        new = _emsgs(NODE_A, 2, 6)
        store.add_messages("u1", old + new)
        wm = BASE + 2 * MINUTE
        req = protocol.SyncRequest(
            (), "u1", NODE_B, "{}", ("sync-scope-v1",),
            protocol.ScopeClause(wm, (), ()))
        resp = server_scope.scoped_response(store, req)
        got = [m.timestamp for m in resp.messages]
        assert got == [m.timestamp for m in new]
        # The scoped tree covers exactly the slice.
        assert resp.merkle_tree == _client_tree(got)
        # An unscoped request still serves everything (full tree).
        full = store.sync(protocol.SyncRequest((), "u1", NODE_B, "{}"))
        assert len(full.messages) == 12
        # Convergence within the slice: a client holding the slice
        # diffs to None — served nothing, no livelock.
        req2 = protocol.SyncRequest(
            (), "u1", NODE_B, resp.merkle_tree, ("sync-scope-v1",),
            protocol.ScopeClause(wm, (), ()))
        resp2 = server_scope.scoped_response(store, req2)
        assert resp2.messages == ()
    finally:
        store.close()


def test_scoped_response_lane_filter_and_unknown_conservative():
    store = RelayStore()
    try:
        tag_todo = derive_scope_tag("m", "todo")
        tag_note = derive_scope_tag("m", "note")
        todo_rows = _emsgs(NODE_A, 0, 4)
        note_rows = _emsgs(NODE_A, 1, 4)
        untagged = _emsgs(NODE_A, 2, 3)
        # A pushes with lane assignments for the first two batches.
        push = protocol.SyncRequest(
            todo_rows + note_rows, "u1", NODE_A, "{}", ("sync-scope-v1",),
            protocol.ScopeClause(0, (tag_todo,),
                                 (tag_todo,) * 4 + (tag_note,) * 4))
        serve_single_request(store, push)
        # ...and a v1 device pushes rows with no lane attribution.
        serve_single_request(
            store, protocol.SyncRequest(untagged, "u1", NODE_A, "{}"))
        # B pulls the todo lane only: known-note rows withheld, the
        # unknown-lane rows served conservatively.
        pull = protocol.SyncRequest(
            (), "u1", NODE_B, "{}", ("sync-scope-v1",),
            protocol.ScopeClause(0, (tag_todo,), ()))
        resp = server_scope.scoped_response(store, pull)
        got = {m.timestamp for m in resp.messages}
        assert got == {m.timestamp for m in todo_rows + untagged}
        assert resp.merkle_tree == _client_tree(sorted(got))
        # Slice convergence: holding the slice → nothing more.
        again = protocol.SyncRequest(
            (), "u1", NODE_B, resp.merkle_tree, ("sync-scope-v1",),
            protocol.ScopeClause(0, (tag_todo,), ()))
        assert server_scope.scoped_response(store, again).messages == ()
    finally:
        store.close()


def test_scoped_serve_own_rows_no_livelock():
    """The membership rule's own-node arm: a client whose OWN writes
    fall outside its scope must not livelock — its rows stay in the
    scoped tree (XOR-cancel against its local copies) while the
    response excludes them as always."""
    store = RelayStore()
    try:
        tag_todo = derive_scope_tag("m", "todo")
        tag_note = derive_scope_tag("m", "note")
        own_note = _emsgs(NODE_B, 0, 5)  # B's own out-of-scope rows
        push = protocol.SyncRequest(
            own_note, "u1", NODE_B, "{}", ("sync-scope-v1",),
            protocol.ScopeClause(0, (tag_todo,), (tag_note,) * 5))
        serve_single_request(store, push)
        # B's local tree holds its own rows; the scoped serve's tree
        # must equal it exactly → diff None, empty response, no loop.
        local = _client_tree([m.timestamp for m in own_note])
        pull = protocol.SyncRequest(
            (), "u1", NODE_B, local, ("sync-scope-v1",),
            protocol.ScopeClause(0, (tag_todo,), ()))
        resp = server_scope.scoped_response(store, pull)
        assert resp.messages == ()
        assert diff_merkle_trees(
            merkle_tree_from_string(resp.merkle_tree),
            merkle_tree_from_string(local)) is None
    finally:
        store.close()


def test_serve_single_request_scoped_ledger_clean():
    """A scoped serve is egress classification, not flow: the
    conservation ledger must stay balanced (`audit() == []`)."""
    ledger.reset()
    store = RelayStore()
    try:
        push = protocol.SyncRequest(
            _emsgs(NODE_A, 0, 10), "u1", NODE_A, "{}", ("sync-scope-v1",),
            protocol.ScopeClause(0, (derive_scope_tag("m", "todo"),),
                                 (derive_scope_tag("m", "todo"),) * 10))
        # The HTTP handler tallies ingress at its decode boundary;
        # calling the serve recipe directly, we mirror that here.
        ledger.count(ledger.INGRESS_SYNC, len(push.messages), owner="u1")
        serve_single_request(store, push)
        pull = protocol.SyncRequest(
            (), "u1", NODE_B, "{}", ("sync-scope-v1",),
            protocol.ScopeClause(BASE, (), ()))
        out = protocol.decode_sync_response(serve_single_request(store, pull))
        assert len(out.messages) == 10
        assert ledger.audit() == []
        stations = ledger.snapshot()["stations"]
        assert stations.get(ledger.SERVE_SCOPED, 0) == 10
    finally:
        store.close()
        ledger.reset()


# --- push hub lane gating ---


def test_event_wakes_truth_table():
    from evolu_tpu.server.push import _event_wakes

    fs = frozenset
    # Own-write exclusion unchanged.
    assert not _event_wakes(fs({NODE_A}), None, NODE_A, None)
    assert _event_wakes(fs({NODE_B}), None, NODE_A, None)
    # Both sides known and disjoint → skip; overlapping → wake.
    assert not _event_wakes(fs({NODE_B}), fs({"t1"}), NODE_A, fs({"t2"}))
    assert _event_wakes(fs({NODE_B}), fs({"t1", "t2"}), NODE_A, fs({"t2"}))
    # Either side unknown → conservative wake.
    assert _event_wakes(fs({NODE_B}), None, NODE_A, fs({"t2"}))
    assert _event_wakes(fs({NODE_B}), fs({"t1"}), NODE_A, None)
    assert _event_wakes(None, None, NODE_A, fs({"t2"}))
    # The gates are independent: unknown authorship doesn't bypass a
    # known-disjoint lane gate.
    assert not _event_wakes(None, fs({"t1"}), NODE_A, fs({"t2"}))


def test_parse_poll_query_tags():
    from evolu_tpu.server.push import parse_poll_query

    owner, node, cursor, timeout, tags = parse_poll_query(
        f"owner=u1&node={NODE_A}&cursor=0&tags=aa,bb")
    assert tags == frozenset({"aa", "bb"})
    assert parse_poll_query(f"owner=u1&node={NODE_A}&cursor=0")[4] is None
    with pytest.raises(ValueError):
        parse_poll_query(
            f"owner=u1&node={NODE_A}&cursor=0&tags="
            + ",".join("t%d" % i for i in range(protocol._MAX_SCOPE_TAGS + 1)))
    with pytest.raises(ValueError):
        parse_poll_query(
            f"owner=u1&node={NODE_A}&cursor=0&tags="
            + "x" * (protocol._MAX_SCOPE_TAG_LEN + 1))


def test_hub_lane_gated_wakeups():
    from evolu_tpu.server.push import PushHub

    hub = PushHub()
    try:
        # Prime the channel so cursors have a floor.
        hub.notify("u1", [_ts(BASE, 0, NODE_B)], tags=None)
        cursor = 1
        kind, val = hub.park("u1", NODE_A, cursor + 0, None, token="tok1",
                             tags=frozenset({"t1"}))
        # The mint event has unknown tags → immediate wake is possible;
        # park from the current seq instead.
        if kind == "now":
            kind, val = hub.park("u1", NODE_A, 2, None, token="tok1",
                                 tags=frozenset({"t1"}))
        assert kind == "parked"
        # A foreign write in a DIFFERENT lane must not wake.
        woken = hub.notify("u1", [_ts(BASE + 1, 0, NODE_B)],
                           tags=frozenset({"t2"}))
        assert woken == 0
        # Same lane → wakes.
        woken = hub.notify("u1", [_ts(BASE + 2, 0, NODE_B)],
                           tags=frozenset({"t1"}))
        assert woken == 1
        # Unknown event tags → conservative wake for a scoped waiter.
        kind, _ = hub.park("u1", NODE_A, 3, None, token="tok2",
                           tags=frozenset({"t1"}))
        assert kind == "parked"
        assert hub.notify("u1", [_ts(BASE + 3, 0, NODE_B)], tags=None) == 1
    finally:
        hub.close()


# --- client emission gate + failover downgrade (satellite) ---


def test_scope_clause_capability_gated_end_to_end():
    """Round 1 (nothing negotiated): no clause on the wire — no lane
    state at the relay. Round 2 (echo landed): the clause rides and
    lanes record."""
    server = RelayServer().start()
    try:
        cfg = Config(sync_url=server.url,
                     sync_scope=SyncScope(tables=("todo",)))
        ev = create_evolu(SCHEMA, config=cfg)
        tr = connect(ev)
        try:
            def round_trip():
                ev.worker.flush(); tr.flush(); ev.worker.flush()

            ev.create("todo", {"title": "r1"})
            round_trip()
            assert protocol.CAP_SYNC_SCOPE in \
                tr.negotiated_capabilities[server.url]
            # Round 1 was unnegotiated: the push carried no clause.
            assert server.store.db.exec_sql_query(
                "SELECT name FROM sqlite_schema WHERE name='scopeLane'") == []
            ev.create("todo", {"title": "r2"})
            round_trip()
            rows = server.store.db.exec_sql_query(
                'SELECT "tag" FROM "scopeLane"')
            assert {r["tag"] for r in rows} == {
                derive_scope_tag(ev.owner.mnemonic, "todo")}
            assert ev.get_error() is None
        finally:
            ev.dispose()
    finally:
        server.stop()


def test_scope_failover_reencodes_without_clause():
    """The PR-8 retarget lesson: a failover target that never
    advertised sync-scope-v1 must never receive a scope clause."""
    from evolu_tpu.utils.config import FleetConfig

    a = RelayServer(RelayStore(), peers=[], replication_interval_s=30).start()
    b = RelayServer(RelayStore(), capabilities=(), peers=[],
                    replication_interval_s=30).start()
    cfg = FleetConfig(relays=(a.url, b.url), replication_factor=2, version=1)
    a.enable_fleet(cfg)
    b.enable_fleet(cfg)
    ev = None
    try:
        ev = create_evolu(SCHEMA, config=Config(
            sync_url=b.url, sync_scope=SyncScope(tables=("todo",))))
        tr = connect(ev)
        tr._routes[ev.owner.id] = a.url + "/"

        def round_trip():
            ev.worker.flush(); tr.flush(); ev.worker.flush()

        ev.create("todo", {"title": "r1"})
        round_trip()
        assert protocol.CAP_SYNC_SCOPE in \
            tr.negotiated_capabilities[a.url + "/"]
        ev.create("todo", {"title": "r2"})
        round_trip()
        assert a.store.db.exec_sql_query(
            "SELECT name FROM sqlite_schema WHERE name='scopeLane'")
        # A dies; the round fails over to B, which never advertised —
        # the clause must be dropped in the re-encode.
        a.stop()
        errors = []
        ev.subscribe_error(errors.append)
        before = metrics.get_counter("evolu_scope_downgrades_total",
                                     reason="failover")
        ev.create("todo", {"title": "r3"})
        round_trip()
        assert not errors
        assert metrics.get_counter(
            "evolu_scope_downgrades_total", reason="failover") == before + 1
        assert b.store.user_ids() == [ev.owner.id]
        assert b.store.db.exec_sql_query(
            "SELECT name FROM sqlite_schema WHERE name='scopeLane'") == []
    finally:
        if ev is not None:
            ev.dispose()
        b.stop()


def test_unadvertising_relay_strips_hostile_clause():
    """A relay with the capability OFF answers a scoped request with
    the full serve (over-approximation), never an error."""
    server = RelayServer(RelayStore(), capabilities=()).start()
    try:
        from evolu_tpu.sync.client import _http_post

        serve_single_request(server.store,
                             protocol.SyncRequest(_emsgs(NODE_A, 0, 4),
                                                  "u1", NODE_A, "{}"))
        body = protocol.encode_sync_request(protocol.SyncRequest(
            (), "u1", NODE_B, "{}", ("sync-scope-v1",),
            protocol.ScopeClause(BASE + MINUTE, (), ())))
        out = protocol.decode_sync_response(_http_post(server.url, body))
        assert len(out.messages) == 4  # full serve, watermark ignored
    finally:
        server.stop()


# --- worker: deferred materialization + typed deferral + widen ---


def _drain(src, dst):
    from evolu_tpu.core.types import CrdtMessage
    from evolu_tpu.storage.clock import read_clock

    node = read_clock(dst.db).timestamp.node
    rows = src.db.exec_sql_query(
        'SELECT * FROM "__message" WHERE "timestamp" NOT LIKE \'%\' || ? '
        'ORDER BY "timestamp"', (node,))
    return tuple(
        CrdtMessage(r["timestamp"], r["table"], r["row"], r["column"],
                    r["value"]) for r in rows)


def _tree_str(ev):
    from evolu_tpu.storage.clock import read_clock

    return merkle_tree_to_string(read_clock(ev.db).merkle_tree)


def test_worker_defers_out_of_scope_then_widens():
    full = create_evolu(SCHEMA)
    thin = create_evolu(
        SCHEMA, config=Config(sync_scope=SyncScope(tables=("todo",))))
    try:
        full.create("todo", {"title": "t1"})
        full.create("note", {"body": "n1"})
        full.create("note", {"body": "n2"})
        full.worker.flush()
        drained = _drain(full, thin)
        n_note = sum(1 for m in drained if m.table == "note")
        thin.receive(drained, _tree_str(full))
        thin.worker.flush()
        q_todo = table("todo").select("title").serialize()
        q_note = table("note").select("body").serialize()
        assert [r["title"] for r in thin.query_once(q_todo)] == ["t1"]
        # The out-of-scope table has NO materialized rows...
        assert thin.db.exec_sql_query('SELECT * FROM "note"') == []
        # ...but its messages are in the log and the tree: the thin
        # replica is byte-identical to the full one at the substrate.
        assert _tree_str(thin) == _tree_str(full)
        # The deferral is counted, never silent.
        frontier = thin.db.exec_sql_query(
            'SELECT "table", "rows" FROM "__scope_deferred"')
        assert {(r["table"], r["rows"]) for r in frontier} == {("note", n_note)}
        # A query against the deferred table answers a TYPED marker.
        thin.query_once(q_note)
        thin.worker.flush()
        err = thin.get_error()
        assert isinstance(err, ScopeDeferred)
        assert err.tables == ("note",) and err.deferred_rows == n_note
        # Widen to full: re-materializes from the local log in LWW
        # order and clears the frontier.
        thin.worker.post(msg.WidenSyncScope(full=True))
        thin.worker.flush()
        assert thin.worker.config.sync_scope is None
        assert [r["body"] for r in sorted(
            thin.db.exec_sql_query('SELECT "body" FROM "note"'),
            key=lambda r: r["body"])] == ["n1", "n2"]
        assert thin.db.exec_sql_query(
            'SELECT * FROM "__scope_deferred"') == []
        # And the re-materialized rows answer queries normally.
        bodies = sorted(r["body"] for r in thin.query_once(q_note))
        assert bodies == ["n1", "n2"]
        assert _tree_str(thin) == _tree_str(full)
    finally:
        full.dispose()
        thin.dispose()


def test_worker_widen_rematerializes_lww_winner():
    """Conflicting edits inside the deferred window: the widen replay
    must land the LWW winner, byte-identical to an unscoped apply."""
    full = create_evolu(SCHEMA)
    thin = create_evolu(
        SCHEMA, config=Config(sync_scope=SyncScope(tables=("todo",))))
    try:
        rid = full.create("note", {"body": "v1"})
        full.worker.flush()
        full.update("note", rid, {"body": "v2"})
        full.worker.flush()
        thin.receive(_drain(full, thin), _tree_str(full))
        thin.worker.flush()
        assert thin.db.exec_sql_query('SELECT * FROM "note"') == []
        thin.worker.post(msg.WidenSyncScope(full=True))
        thin.worker.flush()
        rows = thin.db.exec_sql_query('SELECT "id", "body" FROM "note"')
        assert [(r["id"], r["body"]) for r in rows] == [(rid, "v2")]
    finally:
        full.dispose()
        thin.dispose()


def test_worker_widen_narrowing_surfaces_error():
    thin = create_evolu(
        SCHEMA, config=Config(sync_scope=SyncScope(
            watermark_millis=100, tables=("todo",))))
    try:
        thin.worker.post(msg.WidenSyncScope(watermark_millis=200))
        thin.worker.flush()
        assert isinstance(thin.get_error(), ValueError)
        # The scope is untouched after the failed command.
        assert thin.worker.config.sync_scope.watermark_millis == 100
    finally:
        thin.dispose()


def test_scoped_clients_converge_within_slice_through_relay():
    """End-to-end through a live relay: a full and a thin device of
    one owner; the thin device converges byte-identically WITHIN its
    slice and defers the rest with an exact counter."""
    server = RelayServer().start()
    try:
        full = create_evolu(SCHEMA, config=Config(sync_url=server.url))
        thin = create_evolu(
            SCHEMA, mnemonic=full.owner.mnemonic,
            config=Config(sync_url=server.url,
                          sync_scope=SyncScope(tables=("todo",))))
        tf, tt = connect(full), connect(thin)
        try:
            q = table("todo").select("title").order_by("title").serialize()
            full.create("todo", {"title": "a"})
            full.create("note", {"body": "hidden"})
            thin.create("todo", {"title": "b"})
            for _ in range(6):
                full.worker.flush(); tf.flush(); full.worker.flush()
                thin.worker.flush(); tt.flush(); thin.worker.flush()
                full.sync(refresh_queries=False)
                thin.sync(refresh_queries=False)
            assert [r["title"] for r in full.query_once(q)] == ["a", "b"]
            assert [r["title"] for r in thin.query_once(q)] == ["a", "b"]
            assert full.get_error() is None
            # The slice boundary: thin materialized no note rows. (The
            # relay serves them conservatively — full's pushes carry
            # lane tags only once ITS scope clause would; full has no
            # scope, so note rows ride in unknown lanes — and the
            # worker's filter defers them client-side, counted.)
            assert thin.db.exec_sql_query('SELECT * FROM "note"') == []
            front = thin.db.exec_sql_query(
                'SELECT "rows" FROM "__scope_deferred" WHERE "table"=?',
                ("note",))
            assert front and front[0]["rows"] > 0
        finally:
            full.dispose()
            thin.dispose()
    finally:
        server.stop()


# --- scoped snapshot capture ---


def test_scoped_snapshot_capture_regenerates_trees():
    from evolu_tpu.server import snapshot

    store = RelayStore()
    try:
        old = _emsgs(NODE_A, 0, 10)
        new = _emsgs(NODE_A, 3, 10)
        store.add_messages("u1", old + new)
        wm = BASE + 3 * MINUTE
        manifest, chunks = snapshot.capture_snapshot(
            store, watermark_millis=wm)
        recs = [r for c in chunks for r in snapshot.iter_records(c)]
        kept = [r[1] for r in recs if r[0] == "M"]
        assert kept == [m.timestamp for m in new]
        # The shipped tree is recomputed from the kept rows — the
        # installer's recompute-from-rows verify passes unchanged.
        trees = {r[1]: r[2] for r in recs if r[0] == "T"}
        assert trees["u1"] == _client_tree(kept)
        dest = RelayStore()
        try:
            snapshot.install_stream(dest, manifest, chunks)
            assert dest.get_merkle_tree_string("u1") == _client_tree(kept)
        finally:
            dest.close()
        # Unscoped capture is untouched (no scope filter applied).
        m2, c2 = snapshot.capture_snapshot(store)
        recs2 = [r for c in c2 for r in snapshot.iter_records(c)]
        assert sum(1 for r in recs2 if r[0] == "M") == 20
    finally:
        store.close()
