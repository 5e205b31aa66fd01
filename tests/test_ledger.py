"""Conservation-ledger accounting plane (ISSUE 15).

Unit semantics (stations, equations, pending entries, the owner
cardinality cap), the relay/engine wiring driven by REAL HTTP traffic,
the deliberately mis-wired-route negative test (the audit must catch a
route that forgets to count), the scheduler poison-retry
no-double-count pin, the write-behind queued==drained balance, the
recompile/bandwidth sentinels, and the GET /ledger read surface.
"""

import json
import re
import urllib.error
import urllib.request

import pytest

from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.obs import ledger as ledger_mod
from evolu_tpu.obs import metrics
from evolu_tpu.obs.ledger import Ledger
from evolu_tpu.server.relay import RelayServer, RelayStore, ShardedRelayStore
from evolu_tpu.sync import protocol

BASE = 1700000000000


def setup_function(_fn):
    ledger_mod.reset()
    ledger_mod.set_enabled(True)


def _ts(i, node="89e3b4f11a2c5d70"):
    return timestamp_to_string(Timestamp(BASE + i * 1000, 0, node))


def _sync_req(user, node, n_msgs, start=0, ts_list=None):
    msgs = tuple(
        protocol.EncryptedCrdtMessage(t, b"ct-%d" % i)
        for i, t in enumerate(
            ts_list
            if ts_list is not None
            else [_ts(start + i, node) for i in range(n_msgs)]
        )
    )
    return protocol.SyncRequest(msgs, user, node, "{}")


def _post(url, req, expect_error=None):
    body = protocol.encode_sync_request(req)
    try:
        r = urllib.request.urlopen(
            urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/octet-stream"},
            ),
            timeout=30,
        )
        return protocol.decode_sync_response(r.read())
    except urllib.error.HTTPError as e:
        if expect_error is not None and e.code == expect_error:
            return None
        raise


def _get_json(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read().decode("utf-8"))


# --- unit semantics ---


def test_counts_totals_and_owner_subledgers():
    led = Ledger()
    led.count(ledger_mod.INGRESS_SYNC, 5, owner="alice")
    led.count(ledger_mod.INGRESS_SYNC, 2, owner="bob")
    led.count(ledger_mod.STORE_INSERTED, 7)
    assert led.total(ledger_mod.INGRESS_SYNC) == 7
    assert led.owner_totals("alice") == {ledger_mod.INGRESS_SYNC: 5}
    assert led.audit() == []  # 7 in, 7 out
    led.count(ledger_mod.STORE_DUPLICATE, 1)
    v = led.audit()
    assert len(v) == 1 and v[0]["equation"] == "server-flow"
    assert v[0]["delta"] == -1
    assert v[0]["rhs"][ledger_mod.STORE_DUPLICATE] == 1


def test_audit_reports_per_station_deltas_and_barrier_scoping():
    led = Ledger()
    led.count(ledger_mod.WB_QUEUED, 10)
    # Mid-stream: the wb balance only holds at a drain barrier.
    assert led.audit(at_barrier=False) == []
    v = led.audit(at_barrier=True)
    names = {x["equation"] for x in v}
    assert "write-behind-balance" in names
    led.count(ledger_mod.WB_DRAINED, 10)
    led.count(ledger_mod.INGRESS_SYNC, 10)
    led.count(ledger_mod.STORE_INSERTED, 10)
    assert led.audit(at_barrier=True) == []


def test_apply_plane_equations():
    led = Ledger()
    led.count(ledger_mod.APPLY_INGRESS, 10)
    led.count(ledger_mod.ROUTE_PACKED, 6)
    led.count(ledger_mod.ROUTE_OBJECT, 4)
    led.count(ledger_mod.APPLY_INSERTED, 5)
    led.count(ledger_mod.APPLY_LOSING, 2)
    led.count(ledger_mod.APPLY_DUPLICATE, 3)
    assert led.audit() == []
    led.count(ledger_mod.APPLY_INGRESS, 1)  # unrouted message
    assert [v["equation"] for v in led.audit()] == ["apply-routing"]


def test_pending_entry_commit_abort_and_single_shot():
    led = Ledger()
    e = led.pending()
    e.count(ledger_mod.INGRESS_SYNC, 3, owner="o")
    assert led.total(ledger_mod.INGRESS_SYNC) == 0  # not yet posted
    e.commit()
    e.commit()  # idempotent
    assert led.total(ledger_mod.INGRESS_SYNC) == 3
    a = led.pending()
    a.count(ledger_mod.INGRESS_SYNC, 99)
    a.abort()
    a.commit()  # after abort: nothing
    assert led.total(ledger_mod.INGRESS_SYNC) == 3


def test_owner_cardinality_cap_folds_into_overflow():
    led = Ledger(owner_cardinality_cap=4)
    for i in range(10):
        led.count(ledger_mod.INGRESS_SYNC, 1, owner=f"owner-{i}")
    owners = led.owners()
    assert len(owners) == 5  # 4 real + __overflow__
    assert led.owner_totals(ledger_mod.OWNER_OVERFLOW) == {
        ledger_mod.INGRESS_SYNC: 6
    }
    # The GLOBAL station total is never lost to the fold.
    assert led.total(ledger_mod.INGRESS_SYNC) == 10


def test_snapshot_shape_and_reset():
    led = Ledger()
    led.count(ledger_mod.INGRESS_SYNC, 2, owner="a")
    snap = led.snapshot()
    assert snap["stations"][ledger_mod.INGRESS_SYNC] == 2
    assert snap["owners"]["a"][ledger_mod.INGRESS_SYNC] == 2
    assert {e["name"] for e in snap["equations"]} >= {
        "server-flow", "write-behind-balance", "apply-routing",
        "apply-outcomes",
    }
    led.reset()
    assert led.totals() == {}
    assert led.owners() == []
    # Equations persist across reset (configuration, not data).
    led.count(ledger_mod.INGRESS_SYNC, 1)
    assert led.audit(at_barrier=True) != []


def test_disabled_ledger_records_nothing():
    led = Ledger()
    led.enabled = False
    led.count(ledger_mod.INGRESS_SYNC, 5)
    e = led.pending()
    e.count(ledger_mod.STORE_INSERTED, 5)
    e.commit()
    assert led.totals() == {}


# --- relay wiring, driven by real HTTP traffic ---


def test_per_request_relay_conserves_and_classifies():
    server = RelayServer(ShardedRelayStore(shards=2)).start()
    try:
        _post(server.url, _sync_req("alice", "a" * 16, 3))
        _post(server.url, _sync_req("alice", "a" * 16, 3))  # exact redelivery
        _post(server.url, _sync_req("bob", "b" * 16, 2, start=50))
        _post(server.url, _sync_req("carol", "c" * 16, 0))  # pull-only
        t = ledger_mod.totals()
        assert t[ledger_mod.INGRESS_SYNC] == 8
        assert t[ledger_mod.STORE_INSERTED] == 5
        assert t[ledger_mod.STORE_DUPLICATE] == 3
        assert ledger_mod.audit() == [], ledger_mod.audit()
        # Owner sub-ledgers track the same flows.
        assert ledger_mod.ledger.owner_totals("alice") == {
            ledger_mod.INGRESS_SYNC: 6,
            ledger_mod.STORE_INSERTED: 3,
            ledger_mod.STORE_DUPLICATE: 3,
        }
    finally:
        server.stop()


def test_batching_relay_conserves_across_engine_pass():
    server = RelayServer(ShardedRelayStore(shards=2), batching=True).start()
    try:
        _post(server.url, _sync_req("alice", "a" * 16, 4))
        _post(server.url, _sync_req("bob", "b" * 16, 3, start=50))
        _post(server.url, _sync_req("alice", "a" * 16, 4))  # redelivery
        t = ledger_mod.totals()
        assert t[ledger_mod.INGRESS_SYNC] == 11
        assert t[ledger_mod.STORE_INSERTED] == 7
        assert t[ledger_mod.STORE_DUPLICATE] == 4
        assert ledger_mod.audit() == [], ledger_mod.audit()
    finally:
        server.stop()


def test_non_canonical_batch_routes_singleton_and_conserves():
    server = RelayServer(RelayStore(), batching=True).start()
    try:
        # A non-canonical-width timestamp (45 chars, 3-digit counter):
        # the scheduler must dispatch the request as a singleton (never
        # a packed batch), the bounce tally must record it, and the
        # singleton path's host-oracle error surface (500 — the
        # transaction rolls the whole request back) must classify every
        # message as reject.invalid: conservation holds on the error
        # path too.
        req = _sync_req("nc-owner", "d" * 16, 0,
                        ts_list=[_ts(1, "d" * 16),
                                 "1970-01-01T00:00:00.001Z-001-deadbeefdeadbeef"])
        assert _post(server.url, req, expect_error=500) is None
        assert ledger_mod.ledger.total(ledger_mod.BOUNCE_NON_CANONICAL) == 2
        t = ledger_mod.totals()
        assert t[ledger_mod.INGRESS_SYNC] == 2
        assert t[ledger_mod.REJECT_INVALID] == 2
        assert t.get(ledger_mod.STORE_INSERTED, 0) == 0
        assert ledger_mod.audit() == [], ledger_mod.audit()
    finally:
        server.stop()


def test_scheduler_poison_retry_does_not_double_count(monkeypatch):
    from evolu_tpu.server.engine import BatchReconciler

    orig = BatchReconciler.run_batch_wire
    state = {"fails": 0}

    def flaky(self, requests, *stage):
        if state["fails"] == 0:
            state["fails"] += 1
            raise RuntimeError("injected poison")
        return orig(self, requests, *stage)

    monkeypatch.setattr(BatchReconciler, "run_batch_wire", flaky)
    server = RelayServer(RelayStore(), batching=True).start()
    try:
        _post(server.url, _sync_req("alice", "a" * 16, 3))
        assert state["fails"] == 1, "injected poison never fired"
        assert metrics.get_counter("evolu_sched_poisoned_batches_total") >= 1
        t = ledger_mod.totals()
        # Exactly once despite the failed engine pass + singleton retry.
        assert t[ledger_mod.INGRESS_SYNC] == 3
        assert t[ledger_mod.STORE_INSERTED] == 3
        assert t.get(ledger_mod.STORE_DUPLICATE, 0) == 0
        assert ledger_mod.audit() == [], ledger_mod.audit()
    finally:
        server.stop()


def test_backpressure_shed_is_a_terminal():
    from evolu_tpu.server.scheduler import SyncScheduler

    store = RelayStore()
    sched = SyncScheduler(store, max_queue=0)  # every submit sheds
    server = RelayServer(store, scheduler=sched).start()
    try:
        assert _post(server.url, _sync_req("alice", "a" * 16, 4),
                     expect_error=503) is None
        t = ledger_mod.totals()
        assert t[ledger_mod.INGRESS_SYNC] == 4
        assert t[ledger_mod.SHED_BACKPRESSURE] == 4
        assert ledger_mod.audit() == [], ledger_mod.audit()
    finally:
        server.stop()


def test_relay_500_is_a_reject_terminal(monkeypatch):
    store = RelayStore()

    def boom(request):
        raise RuntimeError("injected serve failure")

    server = RelayServer(store).start()
    monkeypatch.setattr(store, "sync_wire", boom)
    monkeypatch.setattr(store, "sync", boom)
    try:
        assert _post(server.url, _sync_req("alice", "a" * 16, 2),
                     expect_error=500) is None
        t = ledger_mod.totals()
        assert t[ledger_mod.INGRESS_SYNC] == 2
        assert t[ledger_mod.REJECT_INVALID] == 2
        assert ledger_mod.audit() == [], ledger_mod.audit()
    finally:
        server.stop()


def test_commit_then_raise_serve_posts_single_terminal():
    """Review regression: a serve that COMMITS add_messages and then
    fails before answering (here: a garbage client merkle-tree string
    parsed after the insert) must post exactly ONE terminal — the 500's
    reject.invalid — not store terminals AND a reject. The serve scope
    aborts the store classification on the error path."""
    server = RelayServer(RelayStore()).start()
    try:
        req = protocol.SyncRequest(
            (protocol.EncryptedCrdtMessage(_ts(0, "a" * 16), b"ct"),),
            "ctr-owner", "a" * 16, "not-a-merkle-tree",
        )
        assert _post(server.url, req, expect_error=500) is None
        t = ledger_mod.totals()
        assert t[ledger_mod.INGRESS_SYNC] == 1
        assert t[ledger_mod.REJECT_INVALID] == 1
        assert t.get(ledger_mod.STORE_INSERTED, 0) == 0
        assert ledger_mod.audit() == [], ledger_mod.audit()
        # The retry (valid tree) classifies the committed row once.
        _post(server.url, _sync_req("ctr-owner", "a" * 16, 1))
        t = ledger_mod.totals()
        assert t[ledger_mod.STORE_DUPLICATE] == 1
        assert ledger_mod.audit() == [], ledger_mod.audit()
    finally:
        server.stop()


def test_non_canonical_store_fallback_classifies_once():
    """Review regression: a malformed STORED timestamp makes sync_wire
    bounce to the object path, which re-runs add_messages idempotently
    — the serve scope's first-wins latch must keep the classification
    at exactly one set of terminals per request."""
    store = RelayStore()
    server = RelayServer(store).start()
    try:
        _post(server.url, _sync_req("fb-owner", "a" * 16, 2))
        # Poison the owner's stored history with a non-canonical width
        # row so the C response reader raises NonCanonicalStoreError.
        store.db.run(
            'INSERT INTO "message" ("timestamp", "userId", "content") '
            "VALUES (?, ?, ?)",
            ("1970-01-01T00:00:00.009Z-001-aaaaaaaaaaaaaaaa", "fb-owner",
             b"bad"),
        )
        base = ledger_mod.totals()
        # A diverging request (client tree "{}") must read stored rows:
        # the wire path bounces, the object path serves.
        _post(server.url, _sync_req("fb-owner", "b" * 16, 1, start=90))
        t = ledger_mod.totals()
        new_terms = (
            t.get(ledger_mod.STORE_INSERTED, 0)
            + t.get(ledger_mod.STORE_DUPLICATE, 0)
            - base.get(ledger_mod.STORE_INSERTED, 0)
            - base.get(ledger_mod.STORE_DUPLICATE, 0)
        )
        assert new_terms == 1, f"fallback double-classified: {new_terms}"
        assert ledger_mod.audit() == [], ledger_mod.audit()
    finally:
        server.stop()


def test_miswired_route_is_caught_by_the_audit(monkeypatch):
    """THE negative test: silence one route's terminal counting (the
    object store path) and the conservation audit must name the broken
    equation with a positive ingress-side delta — a ledger that cannot
    catch a mis-wired route is worse than none."""
    from evolu_tpu.server import store as store_mod

    monkeypatch.setattr(store_mod, "_ledger_store_apply",
                        lambda *_a, **_kw: None)
    server = RelayServer(RelayStore()).start()
    try:
        _post(server.url, _sync_req("alice", "a" * 16, 3))
        violations = ledger_mod.audit()
        assert violations, "audit missed the silenced store route"
        v = violations[0]
        assert v["equation"] == "server-flow"
        assert v["delta"] == 3  # 3 ingressed, 0 reached a terminal
        assert v["lhs"][ledger_mod.INGRESS_SYNC] == 3
    finally:
        server.stop()


# --- write-behind: the queued == drained balance ---


def test_write_behind_queue_balances_at_drain_barrier(tmp_path):
    server = RelayServer(
        ShardedRelayStore(str(tmp_path / "wb.db"), shards=2),
        write_behind=True,
        write_behind_log=str(tmp_path / "wb.wblog"),
    ).start()
    try:
        _post(server.url, _sync_req("alice", "a" * 16, 5))
        _post(server.url, _sync_req("bob", "b" * 16, 3, start=50))
        _post(server.url, _sync_req("alice", "a" * 16, 5))  # redelivery
        server.write_behind.flush()
        t = ledger_mod.totals()
        assert t[ledger_mod.WB_QUEUED] == t[ledger_mod.WB_DRAINED]
        assert t[ledger_mod.INGRESS_SYNC] == 13
        assert (t[ledger_mod.STORE_INSERTED]
                + t[ledger_mod.STORE_DUPLICATE]) == 13
        assert t[ledger_mod.STORE_INSERTED] == 8
        assert ledger_mod.audit(at_barrier=True) == [], ledger_mod.audit()
        # GET /ledger runs the audit under the drain barrier itself.
        payload = _get_json(server.url + "/ledger")
        assert payload["violations"] == []
        assert payload["stations"][ledger_mod.WB_QUEUED] == 13
    finally:
        server.stop()


# --- GET /ledger + /stats section ---


def test_ledger_endpoint_and_stats_section():
    server = RelayServer(RelayStore()).start()
    try:
        _post(server.url, _sync_req("alice", "a" * 16, 2))
        payload = _get_json(server.url + "/ledger")
        assert payload["stations"][ledger_mod.INGRESS_SYNC] == 2
        assert payload["owners"]["alice"][ledger_mod.STORE_INSERTED] == 2
        assert payload["violations"] == []
        assert any(e["name"] == "server-flow" for e in payload["equations"])
        stats = _get_json(server.url + "/stats")
        assert stats["ledger"]["stations"][ledger_mod.INGRESS_SYNC] == 2
        assert stats["ledger"]["violations"] == []
    finally:
        server.stop()


# --- apply plane, driven through the real client worker ---


def test_client_apply_plane_conserves():
    from evolu_tpu.runtime.client import create_evolu

    evolu = create_evolu({"todo": ("title", "isCompleted")})
    try:
        for i in range(5):
            evolu.create("todo", {"title": f"t{i}", "isCompleted": False})
        evolu.worker.flush()
        t = ledger_mod.totals()
        assert t[ledger_mod.APPLY_INGRESS] >= 10  # 2 cols x 5 rows
        routed = (t.get(ledger_mod.ROUTE_PACKED, 0)
                  + t.get(ledger_mod.ROUTE_OBJECT, 0)
                  + t.get(ledger_mod.ROUTE_SEQUENTIAL, 0))
        assert routed == t[ledger_mod.APPLY_INGRESS]
        assert ledger_mod.audit() == [], ledger_mod.audit()
    finally:
        evolu.dispose()


def test_apply_rollback_counts_rejected():
    from evolu_tpu.core.types import CrdtMessage, TableDefinition
    from evolu_tpu.storage import (
        apply_messages, init_db_model, open_database, update_db_schema,
    )

    db = open_database()
    init_db_model(db, "legal winner thank year wave sausage worth useful "
                      "legal winner thank yellow")
    update_db_schema(db, [TableDefinition.of("todo", ["title"])])
    bad = [CrdtMessage(_ts(1), "todo", "r1", "title", "x"),
           CrdtMessage("not-a-timestamp", "todo", "r1", "title", "y")]
    with pytest.raises(Exception):
        apply_messages(db, {}, bad)
    t = ledger_mod.totals()
    assert t[ledger_mod.APPLY_INGRESS] == 2
    assert t[ledger_mod.APPLY_REJECTED] == 2
    assert ledger_mod.audit() == [], ledger_mod.audit()


# --- recompile sentinel (satellite) ---


def test_recompile_sentinel_flat_within_buckets():
    from evolu_tpu.server import engine as eng_mod

    server = RelayServer(ShardedRelayStore(shards=2), batching=True).start()
    try:
        _post(server.url, _sync_req("alice", "a" * 16, 8))  # warm-up
        assert metrics.get_gauge("evolu_jit_cache_size", cache="merkle") == (
            eng_mod.merkle_jit_cache_size()
        )
        recompiles = metrics.get_counter(
            "evolu_jit_recompiles_total", cache="merkle"
        )
        # Same bucket (8 and 5 rows both pad to the 64-row bucket):
        # the counter must stay flat.
        _post(server.url, _sync_req("bob", "b" * 16, 5, start=100))
        _post(server.url, _sync_req("carol", "c" * 16, 8, start=200))
        assert metrics.get_counter(
            "evolu_jit_recompiles_total", cache="merkle"
        ) == recompiles, "recompile sentinel moved within one bucket"
    finally:
        server.stop()


def test_recompile_sentinel_counts_growth_and_flight_event():
    from evolu_tpu.obs import flight
    from evolu_tpu.server import engine as eng_mod

    eng_mod._JIT_SENTINEL_SIZES.clear()
    before = metrics.get_counter("evolu_jit_recompiles_total", cache="merkle")
    eng_mod.observe_jit_caches(0)  # baseline observation
    real = eng_mod.merkle_jit_cache_size()
    # Simulate growth without compiling anything: shrink the recorded
    # baseline so the next diff is positive.
    eng_mod._JIT_SENTINEL_SIZES["merkle"] = real - 2 if real >= 2 else 0
    flight.clear()
    eng_mod.observe_jit_caches(batch_rows=777)
    grown = metrics.get_counter("evolu_jit_recompiles_total", cache="merkle")
    assert grown >= before + (2 if real >= 2 else real)
    if real:
        evs = [e for e in flight.dump() if e.target == "kernel:jit"]
        assert evs and evs[-1].fields["bucket_rows"] >= 777
    eng_mod._JIT_SENTINEL_SIZES.clear()


# --- pull-bandwidth plane (satellite) ---


def test_pull_instrumentation_counts_waves():
    import numpy as np

    import jax

    from evolu_tpu.ops import to_host_many

    before = metrics.get_counter("evolu_pull_bytes_total")
    arrs = to_host_many(jax.numpy.arange(1024, dtype=jax.numpy.int32),
                        np.arange(256, dtype=np.int64))
    wave = sum(a.nbytes for a in arrs)
    assert metrics.get_counter("evolu_pull_bytes_total") == before + wave
    got = metrics.registry.get_histogram("evolu_pull_wave_bytes")
    assert got is not None and got[3] >= 1
    assert metrics.get_counter("evolu_pull_seconds_total") > 0


# --- evidence dump carries the ledger ---


def test_write_evidence_includes_ledger_snapshot(tmp_path):
    from evolu_tpu.obs import trace

    ledger_mod.count(ledger_mod.INGRESS_SYNC, 4, owner="ev-owner")
    path = trace.write_evidence("ledger-evidence-test", seed=1)
    assert not path.startswith("<")
    payload = json.loads(open(path).read())
    assert payload["ledger"]["stations"][ledger_mod.INGRESS_SYNC] == 4
    assert "violations" in payload["ledger"]
