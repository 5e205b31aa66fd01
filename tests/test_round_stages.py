"""The served round and the engine pass, tiled into named host stages
(ISSUE 26): `obs.anatomy.stage` at every seam of `scheduler._run_batch`
→ `engine.start_batch`/`finish_batch`, the handler-thread legs of
`relay.do_POST`, the scheduler's queue-wait and wake histograms, and the
dispatcher thread's idle/busy/cpu seconds.

What is pinned: the eight `pass_*` stages are recorded once per pass,
never overlap and cover the pass (their seconds sum to 0.90-1.00 of
`evolu_sched_batch_ms`); the dispatch and apply children stay inside
their parents; the per-request families count EVERY request, traced or
not; the dispatcher's idle + busy seconds are its wall time; with
annotations on, every stage is one `evolu/<name>` profiler annotation on
the thread that did the work; and none of it changes a byte of a
response or of the store.

ISSUE 37 adds each pass stage's wait (wall time less its thread's own
CPU time) beside it, and the threaded connection tier's legs before
`do_POST`: the acceptor's idle / busy / cpu seconds, `conn_spawn`
(accept() returned → the handler thread's first instruction),
`conn_head` (→ `do_POST`) and the handler thread's wall / cpu seconds.
Pinned: every 200 round has exactly one `conn_spawn`, one `conn_head`
and one `evolu_relay_round_ms`, and no other request any; the acceptor's
idle + busy seconds are its wall time; `evolu/accept` and
`evolu/conn_head` are one annotation a connection on their own threads;
the pass stages' CPU adds up to the dispatcher's; the round's legs, on
25 handler threads, read no CPU clock.
"""

import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

import evolu_tpu.utils.log as log_mod
from conftest import relay_store_dump
from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.obs import metrics, trace
from evolu_tpu.server import relay as relay_mod
from evolu_tpu.server.relay import RelayServer, ShardedRelayStore
from evolu_tpu.sync import protocol

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASE = 1_700_000_000_000
PASS_STAGES = ("pass_pack", "pass_parse", "pass_layout", "pass_device_call",
               "pass_insert", "pass_pull_wait", "pass_tree", "pass_respond")
DISPATCH_CHILDREN, APPLY_CHILDREN = PASS_STAGES[:4], PASS_STAGES[4:7]
DISPATCHER = "evolu_sched_dispatcher_seconds_total"
ACCEPTOR = "evolu_relay_acceptor_seconds_total"
HANDLER = "evolu_relay_handler_seconds_total"
CONN_LEGS = [("evolu_relay_stage_ms", {"stage": "conn_spawn"}),
             ("evolu_relay_stage_ms", {"stage": "conn_head"})]


def _body(owner: int, round_: int, n: int) -> bytes:
    node = f"{owner + 1:016x}"
    msgs = tuple(
        protocol.EncryptedCrdtMessage(
            timestamp_to_string(Timestamp(BASE + (round_ * n + i) * 1000, 0, node)),
            b"ct-%d" % i)
        for i in range(n))
    return protocol.encode_sync_request(
        protocol.SyncRequest(msgs, f"owner-{owner}", node, "{}"))


def _post(url: str, body: bytes) -> bytes:
    # No traceparent header: the families below must not depend on one.
    with urllib.request.urlopen(
            urllib.request.Request(url, data=body, method="POST"), timeout=120) as r:
        return r.read()


def _push_rounds(url: str, clients: int, rounds: range, n: int) -> None:
    """`clients` closed-loop threads, each its own owner, one push a round."""
    errors = []

    def client(owner):
        try:
            for k in rounds:
                _post(url, _body(owner, k, n))
        except Exception as e:  # noqa: BLE001 - collected and re-raised
            errors.append(e)

    threads = [threading.Thread(target=client, args=(o,)) for o in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads), "a client hung"
    assert not errors, errors


def _hist(family: str, **labels):
    h = metrics.registry.get_histogram(family, **labels)
    return (h[2], h[3]) if h else (0.0, 0)  # (sum, count)


def _stage_seconds(stage: str) -> float:
    return metrics.get_counter("evolu_stage_seconds_total", stage=stage)


def _wait_until(cond, what: str, timeout: float = 30.0) -> None:
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.001)


def _tails():
    """→ a wait for the tails a client's answer does not wait for: the
    dispatcher observes batch_ms and its own seconds AFTER it resolved
    the last future, and a handler closes `respond_write` and observes
    round_ms AFTER the socket write. Every round here answers 200, so
    both are settled when the counts since now agree."""
    def counts():
        return (metrics.get_counter("evolu_sched_batches_total"),
                _hist("evolu_sched_batch_ms")[1],
                metrics.get_counter("evolu_relay_requests_total", endpoint="/"),
                _hist("evolu_relay_round_ms")[1])

    b0, m0, q0, r0 = counts()

    def settled():
        def done():
            b, m, q, r = counts()
            return b - b0 == m - m0 and q - q0 == r - r0
        _wait_until(done, "the dispatcher's and the handlers' tails")
    return settled


@pytest.fixture
def server(monkeypatch):
    # every connection: the acceptor posts its seconds, the handler thread is timed
    monkeypatch.setattr(relay_mod, "_POST_EVERY", 1)
    srv = RelayServer(ShardedRelayStore(shards=4), batching=True).start()
    try:
        srv.settled = _tails()
        _push_rounds(srv.url, 4, range(0, 2), 200)  # compile outside every reading
        srv.settled()
        yield srv
    finally:
        srv.stop()


def test_pass_stages_tile_the_pass(server):
    def reading():
        return ({s: (_stage_seconds(s), _hist("evolu_stage_ms", stage=s)[1])
                 for s in PASS_STAGES + ("device_dispatch", "host_apply")},
                metrics.get_counter("evolu_sched_batches_total"),
                _hist("evolu_sched_batch_ms")[0])

    stages0, batches0, batch_ms0 = reading()
    _push_rounds(server.url, 8, range(2, 8), 200)
    server.settled()
    stages1, batches1, batch_ms1 = reading()
    passes = batches1 - batches0
    assert passes >= 6
    seconds = {s: stages1[s][0] - stages0[s][0] for s in stages1}
    for s in PASS_STAGES:  # once per pass, every one of them
        assert stages1[s][1] - stages0[s][1] == passes, s
    # They tile: never more than the pass (no overlap), and what is
    # outside every tile (queue-wait records, span open/close, the few
    # lines between the lumps) is a small share of it.
    share = sum(seconds[s] for s in PASS_STAGES) / ((batch_ms1 - batch_ms0) / 1e3)
    assert 0.90 <= share <= 1.00, share
    assert sum(seconds[s] for s in DISPATCH_CHILDREN) <= seconds["device_dispatch"]
    assert sum(seconds[s] for s in APPLY_CHILDREN) <= seconds["host_apply"]


@pytest.mark.parametrize("traced", [True, False], ids=["trace-on", "trace-off"])
def test_round_families_count_every_request(server, traced):
    families = [("evolu_sched_queue_wait_ms", {}), ("evolu_sched_wake_ms", {}),
                ("evolu_relay_round_ms", {}),
                ("evolu_relay_stage_ms", {"stage": "read_decode"}),
                ("evolu_relay_stage_ms", {"stage": "respond_write"})] + CONN_LEGS
    before = [_hist(f, **labels) for f, labels in families]
    trace.set_enabled(traced)
    try:
        _push_rounds(server.url, 5, range(2, 6), 20)
    finally:
        trace.set_enabled(True)
    sent = 5 * 4
    server.settled()
    after = [_hist(f, **labels) for f, labels in families]
    for (family, labels), b, a in zip(families, before, after):
        assert a[1] - b[1] == sent, (family, labels)
    (decode, queue, wake, respond, round_) = (
        after[i][0] - before[i][0] for i in (3, 0, 1, 4, 2))
    assert decode + queue + wake + respond <= round_  # legs of one round
    assert after[5][0] - before[5][0] >= 0 and after[6][0] - before[6][0] > 0  # spawn, head
    # the legs fire per request on many threads: wall time only (`cpu = False`)
    assert metrics.registry.get_histogram("evolu_relay_stage_wait_ms", stage="read_decode") is None


def test_dispatcher_idle_plus_busy_is_wall_time(server):
    def reading():
        # `cpu` is the last of the three the dispatcher posts for a pass.
        return {s: metrics.get_counter(DISPATCHER, state=s)
                for s in ("cpu", "idle", "busy")}, time.perf_counter()

    def one_round(k):
        cpu = metrics.get_counter(DISPATCHER, state="cpu")
        _post(server.url, _body(0, k, 50))
        _wait_until(lambda: metrics.get_counter(DISPATCHER, state="cpu") != cpu,
                    "the dispatcher's counters")

    one_round(2)
    r0, t0 = reading()
    time.sleep(0.5)  # idle
    for k in range(3, 8):
        one_round(k)
    r1, t1 = reading()
    idle, busy, cpu = (r1[s] - r0[s] for s in ("idle", "busy", "cpu"))
    assert busy > 0 and idle > 0.4
    assert idle + busy == pytest.approx(t1 - t0, rel=0.05)
    assert cpu <= busy


def test_acceptor_idle_plus_busy_is_wall_time(server):
    def reading():
        # one `inc_many` a connection: the three move together
        return {s: metrics.get_counter(ACCEPTOR, state=s)
                for s in ("cpu", "idle", "busy")}, time.perf_counter()

    def one_round(k):
        busy = metrics.get_counter(ACCEPTOR, state="busy")
        _post(server.url, _body(0, k, 50))
        _wait_until(lambda: metrics.get_counter(ACCEPTOR, state="busy") != busy,
                    "the acceptor's counters")

    one_round(2)
    r0, t0 = reading()
    time.sleep(0.5)  # idle, in select
    for k in range(3, 9):
        one_round(k)
    r1, t1 = reading()
    idle, busy, cpu = (r1[s] - r0[s] for s in ("idle", "busy", "cpu"))
    assert busy > 0 and idle > 0.4
    assert idle + busy == pytest.approx(t1 - t0, rel=0.05)
    assert 0 < cpu <= busy


def test_acceptor_posts_and_times_a_handler_once_in_post_every_connections(monkeypatch):
    """A registry acquisition a connection on the acceptor's or a
    handler's thread costs the served relay over 1 % each, and a read of
    the CPU clock is a system call: the acceptor sums its seconds and
    posts them once in `_POST_EVERY` connections, and that connection's
    handler thread is the one that is timed."""
    monkeypatch.setattr(relay_mod, "_POST_EVERY", 4)
    srv = RelayServer(ShardedRelayStore(shards=2), batching=True).start()
    try:
        def posted():
            return (metrics.get_counter(ACCEPTOR, state="busy"),
                    metrics.get_counter(HANDLER, state="wall"))

        before = posted()
        for k in range(3):
            _post(srv.url, _body(0, k, 5))
        time.sleep(0.05)
        assert posted() == before  # three connections: nothing yet
        t0 = time.perf_counter()
        _post(srv.url, _body(0, 3, 5))
        _wait_until(lambda: posted()[1] != before[1], "the fourth handler thread's seconds")
        busy, wall = (a - b for a, b in zip(posted(), before))
        assert 0 < busy and 0 < wall <= time.perf_counter() - t0  # four extents; ONE thread's life
        assert metrics.get_counter(ACCEPTOR, state="idle") > 0
        assert _hist("evolu_relay_stage_ms", stage="conn_spawn")[1] >= 4  # the legs: every round
    finally:
        srv.stop()


def test_connection_legs_count_the_200_rounds_and_nothing_else(server):
    """One `conn_spawn`, one `conn_head` (and its wait) for every round
    that posts `evolu_relay_round_ms`; a GET, a refused POST / and a
    POST elsewhere take a handler thread (its seconds count) and post
    none of the three."""
    def reading():
        return ([_hist(f, **labels)[1] for f, labels in CONN_LEGS],
                _hist("evolu_relay_round_ms")[1],
                {s: metrics.get_counter(HANDLER, state=s) for s in ("wall", "cpu")})

    def head_and_round_ms():
        return _hist(*CONN_LEGS[1][:1], **CONN_LEGS[1][1])[0] + _hist("evolu_relay_round_ms")[0]

    def handler_settled(wall):
        _wait_until(lambda: metrics.get_counter(HANDLER, state="wall") != wall,
                    "the handler thread's seconds")

    legs0, rounds0, handler0 = reading()
    inside0 = head_and_round_ms()
    _push_rounds(server.url, 3, range(2, 6), 20)
    server.settled()
    # `conn_head` and the round lie end to end inside their thread's life,
    # which the thread posts last (every thread is timed here: the
    # fixture's `_POST_EVERY` is 1)
    _wait_until(lambda: 0 < head_and_round_ms() - inside0 <= 1e3 * (
        metrics.get_counter(HANDLER, state="wall") - handler0["wall"]),
        "the handler threads' seconds")
    legs1, rounds1, handler1 = reading()
    assert rounds1 - rounds0 == 12
    assert [b - a for a, b in zip(legs0, legs1)] == [12, 12]
    wall = handler1["wall"] - handler0["wall"]
    assert 0 < handler1["cpu"] - handler0["cpu"] <= wall
    for refused in ("get", "garbage", "elsewhere"):
        wall = metrics.get_counter(HANDLER, state="wall")
        if refused == "get":
            with urllib.request.urlopen(server.url + "/ping", timeout=30) as r:
                assert r.status == 200
        else:
            path = "" if refused == "garbage" else "/replicate/summary"
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(server.url + path, b"\xff\xff\xff not a sync request")
            assert err.value.code in (404, 500)
        handler_settled(wall)
    legs2, rounds2, _ = reading()
    assert rounds2 == rounds1 and legs2 == legs1


def test_pass_stage_cpu_adds_up_to_the_dispatchers(server):
    """Σ over the eight pass stages of (evolu_stage_ms − its wait) is
    the dispatcher's own CPU seconds over the same passes: the stages
    tile the pass on both clocks, and what lies outside every tile is
    a small share."""
    def reading():
        return (sum(_hist("evolu_stage_ms", stage=s)[0]
                    - _hist("evolu_stage_wait_ms", stage=s)[0] for s in PASS_STAGES),
                metrics.get_counter(DISPATCHER, state="cpu") * 1e3,
                [_hist("evolu_stage_wait_ms", stage=s)[1] for s in PASS_STAGES],
                metrics.get_counter("evolu_sched_batches_total"))

    tiled0, cpu0, counts0, batches0 = reading()
    _push_rounds(server.url, 8, range(2, 8), 200)
    server.settled()
    # the dispatcher posts `cpu` after batch_ms: wait until the last pass's is in
    last = [None]

    def quiet():
        time.sleep(0.02)
        now = reading()
        last[0], was = now, last[0]
        return now == was
    _wait_until(quiet, "the dispatcher's last cpu seconds")
    tiled1, cpu1, counts1, batches1 = last[0]
    passes = batches1 - batches0
    assert [b - a for a, b in zip(counts0, counts1)] == [passes] * len(PASS_STAGES)
    assert 0.85 * (cpu1 - cpu0) <= tiled1 - tiled0 <= cpu1 - cpu0


def test_every_stage_is_one_annotation_on_its_thread(server):
    events, built = [], []

    class Recording:
        def __init__(self, name):
            built.append(name)
            self.name = name

        def __enter__(self):
            events.append(("open", self.name, threading.get_ident()))
            return self

        def __exit__(self, *exc):
            events.append(("close", self.name, threading.get_ident()))

    batches0 = metrics.get_counter("evolu_sched_batches_total")
    orig = log_mod._trace_annotation_cls
    log_mod._trace_annotation_cls = Recording
    try:
        _push_rounds(server.url, 4, range(2, 5), 50)
        server.settled()
    finally:
        log_mod._trace_annotation_cls = orig
    passes = int(metrics.get_counter("evolu_sched_batches_total") - batches0)
    assert passes >= 3
    dispatcher = server.scheduler._thread.ident
    by_name = {}
    for kind, name, tid in events:
        by_name.setdefault(name, {"open": [], "close": []})[kind].append(tid)
    for s in PASS_STAGES + ("device_dispatch", "host_apply"):
        rec = by_name["evolu/" + s]
        assert rec["open"] == rec["close"] == [dispatcher] * passes, s
    pulls = by_name["evolu/pull_wave"]
    assert len(pulls["open"]) == passes and pulls["open"] == pulls["close"]
    assert dispatcher not in pulls["open"]  # the pull thread's line
    for leg in ("read_decode", "respond_write"):  # handler threads, per request
        rec = by_name["evolu/" + leg]
        assert len(rec["open"]) == 4 * 3 and sorted(rec["open"]) == sorted(rec["close"])
        assert dispatcher not in rec["open"]
    # The connection tier: one `accept` a connection on the acceptor's
    # thread, one `conn_head` on the thread that then ran that
    # connection's round.
    acceptor = server._thread.ident
    assert by_name["evolu/accept"]["open"] == by_name["evolu/accept"]["close"] \
        == [acceptor] * (4 * 3)
    rec = by_name["evolu/conn_head"]
    assert sorted(rec["open"]) == sorted(rec["close"]) \
        == sorted(by_name["evolu/read_decode"]["open"])
    assert acceptor not in rec["open"] and dispatcher not in rec["open"]
    per_thread = {}
    for kind, name, tid in events:
        if tid not in (acceptor, dispatcher) and name != "evolu/pull_wave":
            per_thread.setdefault(tid, []).append((kind, name[len("evolu/"):]))
    one = [("open", "conn_head"), ("close", "conn_head"),
           ("open", "read_decode"), ("close", "read_decode"),
           ("open", "respond_write"), ("close", "respond_write")]
    # a thread a connection (a thread's ident may be used again)
    assert sum(len(order) for order in per_thread.values()) == len(one) * 4 * 3
    for order in per_thread.values():
        assert order == one * (len(order) // len(one))
    # No pass_* opens while another pass_* of that thread is open.
    open_pass = {}
    for kind, name, tid in events:
        if name.startswith("evolu/pass_"):
            if kind == "open":
                assert open_pass.get(tid) is None, (name, open_pass[tid])
                open_pass[tid] = name
            else:
                assert open_pass.pop(tid) == name
    assert not open_pass
    # Annotations off: the stand-in (or any class) is never constructed.
    count = len(built)
    _push_rounds(server.url, 2, range(5, 6), 50)
    server.settled()
    assert len(built) == count


def _serve_script(enabled: bool):
    """One deterministic script of pushes and pulls against a fresh
    batching relay → (every response's bytes, the store's dump): a
    sequential part, then (ISSUE 40) ONE mixed batch, four requests 50 ms
    apart inside a 0.4 s coalescing window: a push, a pull of the same
    owner from another device (kept for the next pass: it must see the
    push), a pull with messages and a push of other owners."""
    from evolu_tpu.server.scheduler import SyncScheduler

    metrics.set_enabled(enabled)
    store = ShardedRelayStore(shards=2)
    srv = RelayServer(store, scheduler=SyncScheduler(store, max_wait_s=0.4)).start()
    try:
        out = [_post(srv.url, _body(owner, k, 30))
               for k in range(3) for owner in range(3)]
        for owner in range(3):  # another device of each owner pulls it all
            out.append(_post(srv.url, protocol.encode_sync_request(
                protocol.SyncRequest((), f"owner-{owner}", "f" * 16, "{}"))))
        mixed = [_body(0, 3, 30),
                 protocol.encode_sync_request(
                     protocol.SyncRequest((), "owner-0", "e" * 16, "{}")),
                 protocol.encode_sync_request(
                     protocol.SyncRequest((), "owner-1", "e" * 16, "{}")),
                 _body(2, 3, 30)]
        answers = [None] * len(mixed)

        def send(i):
            answers[i] = _post(srv.url, mixed[i])

        threads = [threading.Thread(target=send, args=(i,)) for i in range(len(mixed))]
        for t in threads:
            t.start()
            time.sleep(0.05)
        for t in threads:
            t.join(timeout=120)
        return out + answers, relay_store_dump(srv.store)
    finally:
        srv.stop()
        metrics.set_enabled(True)


def test_responses_and_store_identical_with_metrics_disabled():
    deferred0 = metrics.get_counter("evolu_sched_deferred_total", reason="same_owner")
    on = _serve_script(True)
    assert metrics.get_counter(
        "evolu_sched_deferred_total", reason="same_owner") == deferred0 + 1
    off = _serve_script(False)
    assert on[0] == off[0] and len(on[0]) == 16 and all(on[0])
    assert on[1] == off[1]
    assert sum(len(messages) for messages, _trees in on[1]) == (3 * 3 + 2) * 30
    # the mixed batch: the same owner's pull saw the push queued before it
    pulled = [len(protocol.decode_sync_response(a).messages) for a in on[0][12:]]
    assert pulled == [0, 120, 90, 0]  # "{}" trees: everything but the node's own


def test_perf_selfcheck_reads_every_new_layer_file():
    """`perf/selfcheck.py` holds every `perf/layers/*.json` against
    `BENCHMARK.json`; the 15 stage metrics are data it must accept."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = subprocess.run([sys.executable, os.path.join(ROOT, "perf", "selfcheck.py")],
                          capture_output=True, text=True, timeout=300, env=env)
    assert done.returncode == 0, done.stderr[-2000:]
    listed = set(os.listdir(os.path.join(ROOT, "perf", "layers")))
    assert {f"{s}_ms.json" for s in PASS_STAGES} <= listed
    assert {"req_decode_ms.json", "sched_queue_wait_ms.json", "sched_wake_ms.json",
            "req_respond_ms.json", "relay_round_ms.json",
            "dispatcher_busy_share.json", "pass_cpu_share.json"} <= listed
    assert {f"{name}.json" for name in WAIT_AND_CONNECTION_METRICS} <= listed


# metric -> (cell, the histogram series or the counter family it reads)
WAIT_AND_CONNECTION_METRICS = {
    **{f"{s}_wait_ms": ("relay-reference.push", ("evolu_stage_wait_ms", s))
       for s in PASS_STAGES if s != "pass_pull_wait"},
    "conn_head_ms": ("relay-reference.push", ("evolu_relay_stage_ms", "conn_head")),
    "conn_spawn_ms": ("relay-reference.push", ("evolu_relay_stage_ms", "conn_spawn")),
    "acceptor_busy_share": ("relay-reference.push", ACCEPTOR),
    "acceptor_cpu_share": ("relay-reference.push", ACCEPTOR),
    "handler_cpu_share": ("relay-reference.push", HANDLER),
    "recv_handle_wait_ms": ("client-todo.restore", ("evolu_stage_wait_ms", "recv_handle")),
    "pass_insert_wait_ms.mesh4": ("relay-mesh4.backfill", ("evolu_stage_wait_ms", "pass_insert")),
}


@pytest.mark.parametrize("name", sorted(WAIT_AND_CONNECTION_METRICS))
def test_each_wait_and_connection_metric_is_data_over_a_family_the_program_emits(name, server):
    """The fourteen metrics of ISSUE 37 (the two waits of the round's
    legs were not added: no CPU clock is read there): a file and a
    manifest entry each, an existing kind of reading, one cell, and (for the relay's)
    a series the served relay of this module has really posted."""
    import json

    cell, reads = WAIT_AND_CONNECTION_METRICS[name]
    assert len(WAIT_AND_CONNECTION_METRICS) == 14
    with open(os.path.join(ROOT, "perf", "layers", f"{name}.json")) as f:
        spec = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [cell] and spec["cells"] == [cell.rsplit(".", 1)[0]]
    assert entry["moves"] == spec["moves"] == (
        "sync_p50" if cell == "relay-reference.push" else "ingest_rate")
    read = spec["read"]
    if isinstance(reads, tuple):
        assert entry["source"] == "program_span" and read["kind"] == "hist_mean"
        assert (read["family"], read["labels"]) == (reads[0], {"stage": reads[1]})
        if cell == "relay-reference.push":
            assert _hist(reads[0], stage=reads[1])[1] > 0
    else:
        assert entry["source"] == "program_counter" and read["kind"] == "counter_ratio"
        states = {labels["state"] for fam, labels in read["num"] + read["den"]}
        assert {fam for fam, _l in read["num"] + read["den"]} == {reads}
        assert all(metrics.get_counter(reads, state=s) > 0 for s in states)
