"""Continuous-batching sync scheduler (evolu_tpu/server/scheduler.py).

Semantic ground truth: anti-entropy responses depend only on store
state plus the one request (Merkle-CRDTs set reconciliation), so a
fused engine pass over DISTINCT-owner requests must be byte-identical
— wire responses, Merkle tree strings, SQLite end state — to serving
the same requests one-at-a-time. Same-owner requests are ordered: the
scheduler defers the later one to the next batch, and the pair must
come out exactly as a sequential server would produce it.

Robustness: queue-full answers 503 + Retry-After and the client's
bounded backoff recovers without data loss; a poisoned batch is
retried as singletons so one bad request can't fail its batchmates;
stop() drains in-flight work; and varying micro-batch sizes never
recompile the fused jit pipeline (bucket-stable shapes — pinned via
`engine.merkle_jit_cache_size()`, like the bench fence).
"""

import threading
import time
import urllib.error
import urllib.request

import pytest

from evolu_tpu.core.merkle import (
    apply_prefix_xors,
    create_initial_merkle_tree,
    merkle_tree_to_string,
    minute_deltas_host,
)
from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.obs import metrics
from evolu_tpu.server.relay import RelayServer, RelayStore, ShardedRelayStore
from evolu_tpu.server.scheduler import SchedulerQueueFull, SyncScheduler
from evolu_tpu.sync import protocol

BASE = 1_700_000_000_000
FRESH_NODE = "f" * 16  # no message carries it → own-msg exclusion no-op


def _msgs(node: str, start: int, n: int):
    return tuple(
        protocol.EncryptedCrdtMessage(
            timestamp_to_string(Timestamp(BASE + (start + i) * 1000, 0, node)),
            b"ct-%d" % (start + i),
        )
        for i in range(n)
    )


def _post_raw(url: str, req: protocol.SyncRequest) -> bytes:
    body = protocol.encode_sync_request(req)
    with urllib.request.urlopen(
        urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/octet-stream"}
        ),
        timeout=60,
    ) as r:
        return r.read()


def _run_threads(workers, timeout: float = 120.0):
    barrier = threading.Barrier(len(workers))
    errors = []

    def wrap(fn):
        try:
            barrier.wait(timeout=30)
            fn()
        except Exception as e:  # noqa: BLE001 - collected and re-raised
            errors.append(e)

    threads = [threading.Thread(target=wrap, args=(fn,)) for fn in workers]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout)
    assert not any(t.is_alive() for t in threads), "scheduler test thread hung"
    if errors:
        raise errors[0]


def _owner_state(store, user_id: str):
    """(message rows, stored merkle tree string) for one owner."""
    shard = store.shard_of(user_id) if hasattr(store, "shard_of") else store
    rows = shard.db.exec_sql_query(
        'SELECT "timestamp", "content" FROM "message" WHERE "userId" = ? '
        'ORDER BY "timestamp"',
        (user_id,),
    )
    return (
        [(r["timestamp"], r["content"]) for r in rows],
        store.get_merkle_tree_string(user_id),
    )


def test_32_concurrent_mixed_owners_batched_parity_and_fewer_passes():
    """The acceptance shape: 32 concurrent mixed-owner clients through
    the scheduler must produce byte-identical wire responses, Merkle
    tree strings, and SQLite end state as one-at-a-time serving — in
    ≥4× fewer engine passes than per-request dispatch."""
    clients, rounds, per_round = 32, 4, 12
    users = [f"user{i:02d}" for i in range(clients)]
    # Two "devices" per owner: pull legs see the other node's earlier
    # messages, so response byte-identity covers the message stream,
    # not just the tree field.
    nodes = [(f"{2 * i + 1:016x}", f"{2 * i + 2:016x}") for i in range(clients)]
    batches0 = metrics.get_counter("evolu_sched_batches_total")
    coalesced0 = metrics.get_counter("evolu_sched_coalesced_requests_total")

    store = ShardedRelayStore(shards=4)
    server = RelayServer(store, batching=True).start()
    results = {u: [None] * rounds for u in users}
    try:
        def client(u, pair):
            def run():
                for rnd in range(rounds):
                    node = pair[rnd % 2]
                    req = protocol.SyncRequest(
                        _msgs(node, rnd * per_round, per_round), u, node, "{}"
                    )
                    results[u][rnd] = _post_raw(server.url, req)
            return run

        _run_threads([client(u, p) for u, p in zip(users, nodes)])

        oracle = RelayStore()
        try:
            for u, pair in zip(users, nodes):
                for rnd in range(rounds):
                    node = pair[rnd % 2]
                    req = protocol.SyncRequest(
                        _msgs(node, rnd * per_round, per_round), u, node, "{}"
                    )
                    want = oracle.sync_wire(req)
                    if want is None:
                        want = protocol.encode_sync_response(oracle.sync(req))
                    assert results[u][rnd] == want, (u, rnd)
                rows, tree = _owner_state(store, u)
                orows, otree = _owner_state(oracle, u)
                assert rows == orows, u
                assert tree == otree, u
        finally:
            oracle.close()

        n_requests = clients * rounds
        passes = metrics.get_counter("evolu_sched_batches_total") - batches0
        coalesced = (
            metrics.get_counter("evolu_sched_coalesced_requests_total") - coalesced0
        )
        assert coalesced == n_requests, "every request must ride a fused pass"
        assert passes * 4 <= n_requests, (
            f"{n_requests} requests took {passes} engine passes — continuous "
            f"batching must beat per-request dispatch by ≥4×"
        )
    finally:
        server.stop()


def test_duplicate_owner_in_one_batch_keeps_sequential_semantics():
    """Two same-owner requests submitted into ONE coalescing window:
    the second must observe the first's inserts exactly as a
    sequential server would — the scheduler defers it to the next
    pass (2 batches), and both responses + end state are byte-equal
    to sequential serving."""
    store = ShardedRelayStore(shards=2)
    sched = SyncScheduler(store, max_batch=8, max_wait_s=0.3)
    batches0 = metrics.get_counter("evolu_sched_batches_total")
    user = "dup-owner"
    push = protocol.SyncRequest(_msgs("a" * 16, 0, 6), user, "a" * 16, "{}")
    # Cold-sync pull from a second device: sequential-after-push gives
    # it the push's messages; a same-batch merge would too, but a
    # swapped order (pull first) would return an empty stream — the
    # bytes distinguish every wrong interleaving.
    pull = protocol.SyncRequest((), user, FRESH_NODE, "{}")
    got = {}
    try:
        def submit(name, req):
            def run():
                got[name] = sched.submit(req)
            return run

        t1 = threading.Thread(target=submit("push", push))
        t1.start()
        time.sleep(0.05)  # push is queued first, window still open
        t2 = threading.Thread(target=submit("pull", pull))
        t2.start()
        t1.join(30), t2.join(30)
    finally:
        sched.stop()

    oracle = RelayStore()
    try:
        for name, req in (("push", push), ("pull", pull)):
            want = oracle.sync_wire(req)
            if want is None:
                want = protocol.encode_sync_response(oracle.sync(req))
            assert got[name] == want, name
        assert _owner_state(store, user) == _owner_state(oracle, user)
    finally:
        oracle.close()
        store.close()
    assert metrics.get_counter("evolu_sched_batches_total") - batches0 == 2, (
        "same-owner pair must split across exactly two engine passes"
    )
    resp = protocol.decode_sync_response(got["pull"])
    assert [m.timestamp for m in resp.messages] == [
        m.timestamp for m in push.messages
    ], "the deferred pull must see the push's rows"


def test_batch_window_counts_from_the_previous_pass_end():
    """PR 41: requests that piled up behind a pass do not close a batch
    the moment it ends: the batch stays open max_wait_s from the LATER
    of its oldest request's enqueue and the previous pass's end, so one
    that arrives inside that window rides the same pass. (While a pass
    was 20-30 ms it was the coalescing window itself; at 6 ms the
    dispatcher ran passes of three requests back to back.)"""
    from evolu_tpu.server.engine import BatchReconciler

    store = ShardedRelayStore(shards=2)
    eng = BatchReconciler(store)
    orig, sizes = eng.run_batch_wire, []

    def slow_run(reqs, *stage):
        sizes.append(len(reqs))
        time.sleep(0.6)  # longer than max_wait_s: the old rule closed at once
        return orig(reqs, *stage)

    def request(i):
        node = f"{i + 0x70:016x}"
        return protocol.SyncRequest(_msgs(node, 0, 2), f"win{i}", node, "{}")

    sched = SyncScheduler(store, engine=eng, max_batch=8, max_wait_s=0.5)
    threads = [threading.Thread(target=sched.submit, args=(request(i),)) for i in range(3)]
    try:
        sched.submit(request(9))      # compiles the bucket's kernel
        eng.run_batch_wire = slow_run
        time.sleep(0.55)              # the warm-up's own window is over
        threads[0].start()            # pass 1: alone, closes at 0.5 s, runs to ~1.1 s
        time.sleep(0.7)
        threads[1].start()            # queued behind pass 1
        time.sleep(0.65)              # 1.35 s: pass 1 over, the window open to ~1.6 s
        threads[2].start()            # the older rule's pass 2 left at 1.2 s without it
        for t in threads:
            t.join(30)
            assert not t.is_alive()
    finally:
        sched.stop()
        eng.close()
        store.close()
    assert sizes == [1, 2]


def test_queue_full_returns_503_with_retry_after():
    store = ShardedRelayStore(shards=2)
    sched = SyncScheduler(store, max_queue=0, retry_after_s=3)
    server = RelayServer(store, scheduler=sched).start()
    rejected0 = metrics.get_counter("evolu_sched_rejected_total")
    try:
        req = protocol.SyncRequest(_msgs("b" * 16, 0, 3), "bp-user", "b" * 16, "{}")
        with pytest.raises(urllib.error.HTTPError) as e:
            _post_raw(server.url, req)
        assert e.value.code == 503
        assert e.value.headers["Retry-After"] == "3"
        assert metrics.get_counter("evolu_sched_rejected_total") == rejected0 + 1
        # Backpressure is flow control: /ping still answers.
        with urllib.request.urlopen(server.url + "/ping", timeout=10) as r:
            assert r.read() == b"ok"
    finally:
        sched.stop()
        server.stop()


def test_backpressure_and_client_backoff_recover_without_data_loss():
    """A deliberately tiny queue in front of a slowed engine: most of 8
    simultaneous clients bounce with 503 + Retry-After, and the sync
    client's bounded backoff (`sync.client._http_post`) retries them
    all through — every message lands exactly once."""
    from evolu_tpu.server.engine import BatchReconciler
    from evolu_tpu.sync.client import _http_post

    store = ShardedRelayStore(shards=2)
    eng = BatchReconciler(store)
    orig = eng.run_batch_wire

    def slow_run(reqs, *stage):
        time.sleep(0.05)
        return orig(reqs, *stage)

    eng.run_batch_wire = slow_run
    sched = SyncScheduler(store, engine=eng, max_batch=8, max_queue=2,
                          retry_after_s=0.02)
    server = RelayServer(store, scheduler=sched).start()
    # Warm the engine's jit pipeline OUTSIDE the contention window: a
    # first-batch compile would stall the tiny queue for seconds and
    # exhaust the clients' bounded retries.
    sched.submit(
        protocol.SyncRequest(_msgs("c" * 16, 0, 4), "bo-warm", "c" * 16, "{}")
    )
    rejected0 = metrics.get_counter("evolu_sched_rejected_total")
    retries0 = metrics.get_counter(
        "evolu_sync_backoff_retries_total", reason="503"
    )
    users = [f"bo{i:02d}" for i in range(8)]
    nodes = [f"{i + 0x10:016x}" for i in range(8)]
    try:
        def client(u, node):
            def run():
                for rnd in range(2):
                    body = protocol.encode_sync_request(
                        protocol.SyncRequest(_msgs(node, rnd * 5, 5), u, node, "{}")
                    )
                    _http_post(server.url, body, retries=30)
            return run

        _run_threads([client(u, n) for u, n in zip(users, nodes)])

        assert metrics.get_counter("evolu_sched_rejected_total") > rejected0, (
            "the tiny queue must actually have bounced someone"
        )
        assert metrics.get_counter(
            "evolu_sync_backoff_retries_total", reason="503"
        ) > retries0, "recovery must have gone through the client backoff"
        for u, node in zip(users, nodes):
            rows, tree = _owner_state(store, u)
            assert [t for t, _c in rows] == [
                m.timestamp for m in _msgs(node, 0, 10)
            ], u
            deltas, _ = minute_deltas_host(t for t, _c in rows)
            assert tree == merkle_tree_to_string(
                apply_prefix_xors(create_initial_merkle_tree(), deltas)
            ), u
    finally:
        sched.stop()
        eng.close()
        server.stop()


def test_poisoned_batch_retried_as_singletons_spares_batchmates():
    from evolu_tpu.server.engine import BatchReconciler

    store = ShardedRelayStore(shards=2)
    eng = BatchReconciler(store)
    orig = eng.run_batch_wire
    state = {"boom": 1}

    def poisoned(reqs, *stage):
        if state["boom"]:
            state["boom"] -= 1
            raise RuntimeError("injected device failure")
        return orig(reqs, *stage)

    eng.run_batch_wire = poisoned
    sched = SyncScheduler(store, engine=eng, max_batch=8, max_wait_s=0.2)
    poisoned0 = metrics.get_counter("evolu_sched_poisoned_batches_total")
    fb0 = metrics.get_counter("evolu_sched_fallback_total", reason="poison_retry")
    users = [("pz-a", "1" * 16), ("pz-b", "2" * 16), ("pz-c", "3" * 16)]
    got = {}
    try:
        def submit(u, node):
            def run():
                got[u] = sched.submit(
                    protocol.SyncRequest(_msgs(node, 0, 4), u, node, "{}")
                )
            return run

        _run_threads([submit(u, n) for u, n in users])
        assert (
            metrics.get_counter("evolu_sched_poisoned_batches_total")
            == poisoned0 + 1
        )
        assert (
            metrics.get_counter("evolu_sched_fallback_total", reason="poison_retry")
            == fb0 + len(users)
        )
        # The singleton retry produced exactly the per-request bytes,
        # and a later batch rides the engine again (recovery).
        oracle = RelayStore()
        try:
            for u, node in users:
                req = protocol.SyncRequest(_msgs(node, 0, 4), u, node, "{}")
                want = oracle.sync_wire(req) or protocol.encode_sync_response(
                    oracle.sync(req)
                )
                assert got[u] == want, u
        finally:
            oracle.close()
        after = sched.submit(
            protocol.SyncRequest(_msgs("4" * 16, 0, 2), "pz-d", "4" * 16, "{}")
        )
        assert after, "post-poison batches must ride the engine again"
        assert metrics.get_counter(
            "evolu_sched_poisoned_batches_total"
        ) == poisoned0 + 1, "poison must not repeat once the engine recovers"
    finally:
        sched.stop()
        eng.close()
        store.close()


def test_non_canonical_width_prescreens_to_host_path_without_batch_damage():
    """A malformed-width timestamp must never enter a packed batch: it
    dispatches as a singleton on the per-request path (whose host
    oracle is the error surface) and fails ALONE — concurrent
    canonical requests coalesce and succeed."""
    store = ShardedRelayStore(shards=2)
    sched = SyncScheduler(store, max_batch=8, max_wait_s=0.2)
    fb0 = metrics.get_counter("evolu_sched_fallback_total", reason="non_canonical")
    bad = protocol.SyncRequest(
        (protocol.EncryptedCrdtMessage("not-a-timestamp", b"x"),),
        "nc-bad", "9" * 16, "{}",
    )
    ok_req = protocol.SyncRequest(_msgs("8" * 16, 0, 3), "nc-good", "8" * 16, "{}")
    results = {}

    def submit_bad():
        with pytest.raises(Exception):
            sched.submit(bad)
        results["bad"] = "raised"

    def submit_ok():
        results["ok"] = sched.submit(ok_req)

    try:
        _run_threads([submit_bad, submit_ok])
        assert results["bad"] == "raised"
        assert (
            metrics.get_counter("evolu_sched_fallback_total", reason="non_canonical")
            == fb0 + 1
        )
        oracle = RelayStore()
        try:
            want = oracle.sync_wire(ok_req) or protocol.encode_sync_response(
                oracle.sync(ok_req)
            )
            assert results["ok"] == want
        finally:
            oracle.close()
        rows, _t = _owner_state(store, "nc-bad")
        assert rows == [], "the malformed request must have no side effects"
    finally:
        sched.stop()
        store.close()


def test_varying_batch_sizes_never_recompile_the_fused_pipeline():
    """The bench fence, applied to the scheduler: micro-batches of
    different request/row counts inside one power-of-two row bucket
    must keep the engine's jit cache size flat (shapes are padded by
    `ops.bucket_size`; a recompile per batch would wreck serving
    latency)."""
    from evolu_tpu.server import engine as eng_mod

    store = ShardedRelayStore(shards=2)
    sched = SyncScheduler(store, max_batch=8, max_wait_s=0.0)
    try:
        # Warm-up: first pass compiles the bucket's kernel.
        sched.submit(
            protocol.SyncRequest(_msgs("5" * 16, 0, 3), "jit-w", "5" * 16, "{}")
        )
        size0 = eng_mod.merkle_jit_cache_size()
        assert size0 > 0, "warm-up must have compiled the Merkle kernel"
        for i, n in enumerate((1, 5, 17, 33)):  # all ≤ the 64-row bucket
            sched.submit(
                protocol.SyncRequest(
                    _msgs(f"{i + 0x60:016x}", 0, n), f"jit{i}", f"{i + 0x60:016x}", "{}"
                )
            )
        assert eng_mod.merkle_jit_cache_size() == size0, (
            "a varying micro-batch size recompiled the fused pipeline — "
            "shapes must stay bucket-stable"
        )
    finally:
        sched.stop()
        store.close()


def test_stop_drains_inflight_batches():
    """stop() must serve everything already queued (no request dropped
    mid-shutdown) and reject new submits with SchedulerQueueFull."""
    from evolu_tpu.server.engine import BatchReconciler

    store = ShardedRelayStore(shards=2)
    eng = BatchReconciler(store)
    orig = eng.run_batch_wire

    def slow_run(reqs, *stage):
        time.sleep(0.08)
        return orig(reqs, *stage)

    eng.run_batch_wire = slow_run
    sched = SyncScheduler(store, engine=eng, max_batch=2, max_wait_s=0.0)
    users = [(f"dr{i}", f"{i + 0x30:016x}") for i in range(6)]
    got, errs = {}, []
    try:
        def submit(u, node):
            def run():
                try:
                    got[u] = sched.submit(
                        protocol.SyncRequest(_msgs(node, 0, 3), u, node, "{}")
                    )
                except Exception as e:  # noqa: BLE001
                    errs.append((u, e))
            return run

        threads = [threading.Thread(target=submit(u, n)) for u, n in users]
        for t in threads:
            t.start()
        time.sleep(0.05)  # all enqueued; first slow batch in flight
        sched.stop()  # must drain, not drop
        assert not errs, errs
        for t in threads:
            t.join(30)
        assert all(not t.is_alive() for t in threads)
        for u, node in users:
            assert got[u], u
            rows, _t = _owner_state(store, u)
            assert [t for t, _c in rows] == [m.timestamp for m in _msgs(node, 0, 3)], u
        with pytest.raises(SchedulerQueueFull):
            sched.submit(
                protocol.SyncRequest(_msgs("7" * 16, 0, 1), "late", "7" * 16, "{}")
            )
    finally:
        eng.close()
        store.close()


def test_singleton_fallback_never_overlaps_an_open_engine_pass(monkeypatch):
    """Store writes serialize on the dispatcher thread: a non-batchable
    request arriving mid-pass must be served AFTER the pass, never
    concurrently — `NativeDatabase.transaction()` JOINS an open
    transaction on the shared connection, so a handler-thread fallback
    write acked mid-batch would be silently rolled back if the batch
    later poisoned (review finding)."""
    import evolu_tpu.server.scheduler as sched_mod
    from evolu_tpu.server.engine import BatchReconciler

    store = ShardedRelayStore(shards=2)
    eng = BatchReconciler(store)
    orig = eng.run_batch_wire
    in_pass = threading.Event()

    def slow(reqs, *stage):
        in_pass.set()
        try:
            time.sleep(0.15)
            return orig(reqs, *stage)
        finally:
            in_pass.clear()

    eng.run_batch_wire = slow
    orig_serve = sched_mod.serve_single_request
    overlap = []

    def spying_serve(store_, request):
        overlap.append(in_pass.is_set())
        return orig_serve(store_, request)

    monkeypatch.setattr(sched_mod, "serve_single_request", spying_serve)
    sched = SyncScheduler(store, engine=eng, max_batch=4, max_wait_s=0.0)
    bad = protocol.SyncRequest(
        (protocol.EncryptedCrdtMessage("short", b"x"),), "ser-bad", "6" * 16, "{}"
    )
    try:
        t1 = threading.Thread(target=lambda: sched.submit(
            protocol.SyncRequest(_msgs("5" * 16, 0, 2), "ser-ok", "5" * 16, "{}")
        ))
        t1.start()
        in_pass.wait(10)  # the engine pass is genuinely open now

        def submit_bad():
            with pytest.raises(Exception):
                sched.submit(bad)

        t2 = threading.Thread(target=submit_bad)
        t2.start()
        t1.join(30), t2.join(30)
        assert overlap == [False], (
            "the singleton fallback ran while an engine pass (and its "
            "store transactions) were open"
        )
    finally:
        sched.stop()
        eng.close()
        store.close()


# -- client backoff unit surface (sync.client._http_post) --


class _FakeResponse:
    def __init__(self, body: bytes):
        self._body = body

    def read(self):
        return self._body

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def _http_error(code: int, headers: dict):
    import email.message

    msg = email.message.Message()
    for k, v in headers.items():
        msg[k] = v
    return urllib.error.HTTPError("http://x/", code, "err", msg, None)


def test_http_post_backoff_honors_retry_after(monkeypatch):
    from evolu_tpu.sync import client as sync_client

    calls = {"n": 0}

    def fake_urlopen(req, timeout=None):
        calls["n"] += 1
        if calls["n"] == 1:
            raise _http_error(503, {"Retry-After": "2"})
        return _FakeResponse(b"pong")

    slept = []
    monkeypatch.setattr(urllib.request, "urlopen", fake_urlopen)
    out = sync_client._http_post(
        "http://x/", b"body", sleep=slept.append, rng=lambda: 1.0
    )
    assert out == b"pong"
    assert slept == [2.0], "Retry-After seconds must be honored verbatim"


def test_http_post_backoff_bounded_and_jittered(monkeypatch):
    from evolu_tpu.sync import client as sync_client

    def always_503(req, timeout=None):
        raise _http_error(503, {})

    slept = []
    monkeypatch.setattr(urllib.request, "urlopen", always_503)
    with pytest.raises(urllib.error.HTTPError):
        sync_client._http_post(
            "http://x/", b"body", retries=3, base_delay=0.1,
            sleep=slept.append, rng=lambda: 0.5,
        )
    # Exponential: 0.1, 0.2, 0.4 — halved by the injected jitter draw.
    assert slept == pytest.approx([0.05, 0.1, 0.2])


def test_http_post_retries_connection_errors_then_surfaces(monkeypatch):
    from evolu_tpu.sync import client as sync_client

    calls = {"n": 0}

    def flaky(req, timeout=None):
        calls["n"] += 1
        if calls["n"] <= 2:
            raise urllib.error.URLError(OSError("connection refused"))
        return _FakeResponse(b"ok")

    slept = []
    monkeypatch.setattr(urllib.request, "urlopen", flaky)
    assert sync_client._http_post(
        "http://x/", b"b", sleep=slept.append, rng=lambda: 1.0
    ) == b"ok"
    assert len(slept) == 2

    def dead(req, timeout=None):
        raise urllib.error.URLError(OSError("down"))

    monkeypatch.setattr(urllib.request, "urlopen", dead)
    with pytest.raises(urllib.error.URLError):
        sync_client._http_post(
            "http://x/", b"b", retries=2, sleep=lambda _s: None
        )


def test_http_post_does_not_retry_non_retryable_http(monkeypatch):
    from evolu_tpu.sync import client as sync_client

    calls = {"n": 0}

    def not_found(req, timeout=None):
        calls["n"] += 1
        raise _http_error(404, {})

    monkeypatch.setattr(urllib.request, "urlopen", not_found)
    with pytest.raises(urllib.error.HTTPError):
        sync_client._http_post("http://x/", b"b", sleep=lambda _s: None)
    assert calls["n"] == 1, "4xx other than 429 must surface immediately"
