"""`relay-skewed` against its plain reference at a small size (ISSUE 40).

The benchmark's driver (`perf/drivers/relay_skewed.py`) serves a preloaded
native sharded store through `RelayServer` + `SyncScheduler`; the cell's
own device model (`perf/gen_mix.py`) sends it pulls and one-field updates
of skewed owners, several devices an owner; and
`perf/reference/relay_sync.py` runs upstream's `sync` on the same requests
over stdlib sqlite3. Every answer's rows and tree must equal the
reference's and every owner's final dump must be byte-identical. The
cell's `correct` rests on the same comparison and on guarantees (d)-(f),
whose checks are held here to their negatives too. Also here: the
scheduler's deferral counters and the respond leg's two parts that the
deployment made visible, and the cell's data files.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from evolu_tpu.obs import metrics
from evolu_tpu.server.relay import RelayServer, ShardedRelayStore
from evolu_tpu.server.scheduler import SyncScheduler
from evolu_tpu.storage import native
from evolu_tpu.sync import native_crypto, protocol
from perf import gen, gen_mix, load_module, readers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
reference = load_module("reference", "relay_sync")
driver = load_module("drivers", "relay_skewed")
closed_loop_mix = load_module("traffic", "closed_loop_mix")

needs_native = pytest.mark.skipif(
    not (native.native_available() and native_crypto.native_available()),
    reason="the packed ingest needs both native libraries")

CELL = "relay-skewed.ycsb-a"
with open(os.path.join(ROOT, "perf", "configs", "relay-skewed.json")) as f:
    _CONFIG = json.load(f)
with open(os.path.join(ROOT, "perf", "workloads", f"{CELL}.json")) as f:
    PARAMS = json.load(f)["params"]
CFG = {**_CONFIG, **_CONFIG["rehearsal"], "messages": 1600, "owners": 64,
       "ciphertext_pool": 16, "reference_hottest": 4, "reference_touched": 4,
       "cold_sync_sample": 2}
NEW_METRICS = ("sched_deferred_share", "sched_passes_waited", "pull_answer_share",
               "respond_msgs_request", "respond_kb_request", "respond_diff_ms",
               "respond_fetch_ms")
TWINS = {f"{base}.skewed": base for base in (
    "sched_batch_requests", "sched_batch_ms", "sched_queue_wait_ms", "relay_round_ms",
    "pass_respond_ms", "pass_insert_ms", "pass_tree_ms", "pass_device_call_ms")}
TWINS["window_compiles.skewed"] = "window_compiles.relay"


def _pairs(messages) -> list:
    return [(m.timestamp, m.content) for m in messages]


def _post(url: str, request: protocol.SyncRequest) -> protocol.SyncResponse:
    body = protocol.encode_sync_request(request)
    return protocol.decode_sync_response(gen.http_post(url, body, 120))


def _dump(store, owner: str) -> tuple:
    db = store.shard_of(owner).db
    return reference.owner_dump(
        lambda sql, args: [tuple(r.values()) for r in db.exec_sql_query(sql, args)], owner)


# (a) the served relay, one request at a time, answer by answer


@needs_native
@pytest.mark.parametrize("seed", [5, 77, 2**31 + 17])
def test_served_mixed_rounds_equal_the_plain_reference(seed, tmp_path):
    state = driver.setup(CFG, seed, scratch=str(tmp_path))
    twin = reference.ReferenceRelay()
    try:
        requests = state["requests"]
        for r in requests:
            twin.add_messages(r.user_id, _pairs(r.messages), {})
        draw = gen_mix.OwnerDraw(len(requests), seed, PARAMS["zipf_theta"])
        rng = gen_mix.thread_rng(seed, 0)
        devices, with_rows = {}, 0
        for _ in range(90):
            rank, update = gen_mix.draw_round(draw, rng, PARAMS["update_share"])
            index, process = draw.owner_of_rank[rank], rng.randrange(3)
            device = devices.get((index, process))
            if device is None:
                r = requests[index]
                device = devices[index, process] = gen_mix.Device(
                    r.user_id, gen_mix.device_node("d", process, index), r.merkle_tree,
                    [m.timestamp for m in r.messages], state["push_base_millis"],
                    PARAMS["msgs_per_update"], state["pool"])
            request = device.request(update)
            assert len(request.messages) == (PARAMS["msgs_per_update"] if update else 0)
            got = _post(state["url"], request)
            rows, tree = twin.sync(request.user_id, request.node_id,
                                   _pairs(request.messages), request.merkle_tree)
            assert _pairs(got.messages) == rows, (request.user_id, request.node_id)
            assert got.merkle_tree == tree
            assert device.merge(got), "guarantee (d)"
            with_rows += bool(rows)
        # several devices of the hot owners: some answers must carry rows
        assert with_rows >= 5 and len({i for i, _p in devices}) < len(devices)
        for r in requests:
            assert _dump(state["store"], r.user_id) == twin.owner_dump(r.user_id), r.user_id
    finally:
        driver.close(state)
        twin.close()


# (b) and (g): two and three requests of ONE owner in one queue


def _device(owner: str, process: int) -> gen_mix.Device:
    return gen_mix.Device(owner, gen_mix.device_node("d", process, 7), "{}", [],
                          gen.BASE_MILLIS, 2, [b"ct-0", b"ct-1", b"ct-2"])


def _deferred() -> dict:
    return {reason: metrics.get_counter("evolu_sched_deferred_total", reason=reason)
            for reason in ("same_owner", "owner_blocked", "capacity", "single")}


def _passes() -> tuple:
    return (metrics.get_counter("evolu_sched_batches_total"),
            metrics.get_counter("evolu_sched_coalesced_total"))


def _waited() -> tuple:
    h = metrics.registry.get_histogram("evolu_sched_passes_waited")
    return (h[2], h[3]) if h else (0.0, 0)  # (sum, count)


@pytest.fixture(scope="module")
def one_owner_queue():
    """A served relay whose dispatcher waits 0.6 s for a batch, fed
    requests of one owner 0.1 s apart: every one of a phase is in the
    queue when the dispatcher closes. → per phase the requests in
    arrival order, the answers, and the counters' movement; the registry
    read before and after."""
    if not (native.native_available() and native_crypto.native_available()):
        pytest.skip("the packed ingest needs both native libraries")
    store = ShardedRelayStore(":memory:", "native", shards=2)
    scheduler = SyncScheduler(store, max_batch=8, max_wait_s=0.6)
    server = RelayServer(store, scheduler=scheduler).start()
    phases = []
    try:
        _post(server.url, _device("someone-else", 0).request(True))  # compiles
        before = metrics.registry.snapshot()
        devices = [_device("one-owner", p) for p in range(3)]
        for plan in ([(0, True), (1, False)], [(0, True), (1, True), (2, False)]):
            deferred0, waited0, passes0 = _deferred(), _waited(), _passes()
            requests = [devices[p].request(update) for p, update in plan]
            answers = [None] * len(requests)

            def send(i):
                answers[i] = _post(server.url, requests[i])

            threads = [threading.Thread(target=send, args=(i,)) for i in range(len(requests))]
            for t in threads:
                t.start()
                time.sleep(0.1)
            for t in threads:
                t.join(60)
            for (p, _update), answer in zip(plan, answers):
                assert devices[p].merge(answer), "guarantee (d)"
            deferred1, waited1, passes1 = _deferred(), _waited(), _passes()
            phases.append({
                "requests": requests, "answers": answers,
                "deferred": {k: deferred1[k] - deferred0[k] for k in deferred1},
                "waited": (waited1[0] - waited0[0], waited1[1] - waited0[1]),
                "passes": passes1[0] - passes0[0], "coalesced": passes1[1] - passes0[1]})
        after = metrics.registry.snapshot()
    finally:
        server.stop()
    return {"phases": phases, "before": before, "after": after}


def test_queued_requests_of_one_owner_are_served_in_order_and_counted(one_owner_queue):
    twin = reference.ReferenceRelay()
    try:
        for phase in one_owner_queue["phases"]:
            for request, got in zip(phase["requests"], phase["answers"]):
                rows, tree = twin.sync(request.user_id, request.node_id,
                                       _pairs(request.messages), request.merkle_tree)
                assert _pairs(got.messages) == rows and got.merkle_tree == tree
    finally:
        twin.close()
    two, three = one_owner_queue["phases"]
    none = {"same_owner": 0, "owner_blocked": 0, "capacity": 0, "single": 0}
    # the second device's pull saw the first one's update of the same queue
    assert [m.timestamp for m in two["answers"][1].messages] == \
        [m.timestamp for m in two["requests"][0].messages]
    # since ISSUE 42 both ride ONE pass, the pull in sequence behind the update
    assert (two["passes"], two["coalesced"]) == (1, 1)
    assert two["deferred"] == none and two["waited"] == (0.0, 2)
    # a third request behind them: the same pass, answered after both
    assert (three["passes"], three["coalesced"]) == (1, 2)
    assert three["deferred"] == none and three["waited"] == (0.0, 3)
    assert len(three["answers"][2].messages) == 3 * 2  # every update but its own: none is


def test_capacity_and_single_deferrals_are_counted_without_a_dispatcher():
    """`_close_batch` alone, on a queue made by hand: what is kept is
    what was kept before the counters (FIFO, distinct owners, singles
    alone), and every kept request counts once under its reason."""
    from evolu_tpu.server import scheduler as sched_mod

    sched = SyncScheduler(object(), max_batch=2)
    sched.stop()
    wide = protocol.EncryptedCrdtMessage("not-46-characters", b"x")

    def pending(owner, single=False):
        messages = (wide,) if single else ()
        return sched_mod._Pending(
            protocol.SyncRequest(messages, owner, "f" * 16, "{}"), single=single)

    a1, b1, c1, s1, a2, s2 = (pending("a"), pending("b"), pending("c"),
                              pending("s", single=True), pending("a"), pending("s"))
    sched._queue = [a1, b1, c1, s1, a2, s2]
    assert sched._close_batch() == [a1, b1]
    assert sched._kept == {"capacity": 1, "single": 1, "same_owner": 1, "owner_blocked": 1}
    assert sched._queue == [c1, s1, a2, s2]
    assert sched._close_batch() == [c1, a2] and sched._kept == {"single": 1, "owner_blocked": 1}
    assert sched._close_batch() == [s1] and sched._kept == {"single": 1}
    assert sched._close_batch() == [s2] and sched._kept == {}
    assert [p.kept for p in (a1, b1, c1, s1, a2, s2)] == [0, 0, 1, 2, 1, 3]


# (c) the generator


def test_the_draw_is_seeded_skewed_and_half_updates():
    draw = gen_mix.OwnerDraw(100_000, 2**31 + 40, 0.99)
    assert sorted(draw.owner_of_rank) == list(range(100_000))  # a bijection
    assert draw.owner_of_rank == gen_mix.OwnerDraw(100_000, 2**31 + 40, 0.99).owner_of_rank
    assert draw.owner_of_rank != gen_mix.OwnerDraw(100_000, 41, 0.99).owner_of_rank
    assert draw.share(1) == pytest.approx(0.0783, abs=0.0005)
    assert draw.share(10) == pytest.approx(0.231, abs=0.001)
    assert draw.share(1000) == pytest.approx(0.605, abs=0.001)

    def rounds(seed, slot, n):
        rng = gen_mix.thread_rng(seed, slot)
        return [gen_mix.draw_round(draw, rng, 0.5) for _ in range(n)]

    assert rounds(9, 3, 50) == rounds(9, 3, 50)  # from (seed, slot) alone
    assert rounds(9, 3, 50) != rounds(9, 4, 50) and rounds(9, 3, 50) != rounds(10, 3, 50)
    drawn = rounds(2**31 + 40, 0, 200_000)
    ranks = [rank for rank, _update in drawn]
    assert 0 <= min(ranks) and max(ranks) < 100_000
    assert sum(1 for r in ranks if r == 0) / len(ranks) == pytest.approx(0.0783, abs=0.005)
    assert sum(1 for r in ranks if r < 10) / len(ranks) == pytest.approx(0.231, abs=0.01)
    assert sum(1 for _r, update in drawn if update) / len(drawn) == pytest.approx(0.5, abs=0.01)
    # the operation does not depend on the owner
    hot = [update for rank, update in drawn if rank < 10]
    assert sum(hot) / len(hot) == pytest.approx(0.5, abs=0.02)


def test_a_device_sends_what_its_node_and_count_say_and_the_owner_file_round_trips(tmp_path):
    requests = gen.build_requests(900, 12, 2**31 + 3, [b"p0", b"p1", b"p2"])
    path = str(tmp_path / "owners.bin")
    gen_mix.write_owner_file(path, requests)
    owners = gen_mix.OwnerFile(path)
    assert len(owners) == len(requests) == 12
    for i in (0, 5, 11):
        assert owners.read(i) == (requests[i].user_id, requests[i].merkle_tree,
                                  [m.timestamp for m in requests[i].messages])
    owners.close()
    base = gen.BASE_MILLIS + 900 // 16 + 60_000
    node = gen_mix.device_node("d", 4, 5)
    assert node == "d004000000000005" and len(node) == 16
    device = gen_mix.Device(requests[5].user_id, node, requests[5].merkle_tree,
                            [m.timestamp for m in requests[5].messages], base, 2, [b"a", b"b"])
    pull = device.request(False)
    assert pull == protocol.SyncRequest((), requests[5].user_id, node, requests[5].merkle_tree)
    first, second = device.request(True), device.request(True)
    assert first.messages == gen_mix.update_messages(node, 0, base, 2, [b"a", b"b"])
    assert second.messages == gen_mix.update_messages(node, 1, base, 2, [b"a", b"b"])
    stamps = [m.timestamp for m in first.messages + second.messages]
    assert len(set(stamps)) == 4 and all(t.endswith(node) and len(t) == 46 for t in stamps)
    assert min(stamps) > max(m.timestamp for r in requests for m in r.messages)  # a minute on
    assert device.updates == 2 and len(device.held) == len(requests[5].messages) + 4
    assert second.merkle_tree == device.tree_string != first.merkle_tree


# (d) the check's negatives


def test_an_answer_with_a_row_removed_fails_guarantee_d_at_the_device():
    twin = reference.ReferenceRelay()
    try:
        writer, reader, other = _device("o", 0), _device("o", 1), _device("o", 2)
        for _ in range(3):
            request = writer.request(True)
            twin.sync("o", writer.node, _pairs(request.messages), request.merkle_tree)
        pull = reader.request(False)
        rows, tree = twin.sync("o", reader.node, [], pull.merkle_tree)
        assert len(rows) == 6
        whole = protocol.SyncResponse(
            tuple(protocol.EncryptedCrdtMessage(t, c) for t, c in rows), tree)
        assert reader.merge(whole) and reader.held == writer.held
        assert reader.merge(whole)  # rows it holds are not folded twice
        assert not other.merge(protocol.SyncResponse(whole.messages[:-1], tree))
    finally:
        twin.close()


def _log(owner, node, update, t_send, t_done, held, count=None):
    return {"owner": owner, "node": node, "update": update, "count": count,
            "t_send": t_send, "t_done": t_done, "ok": True, "error": None,
            "held": held, "answer": 0}


def test_check_holdings_bounds_every_round_by_what_was_acknowledged_and_sent():
    preload = {3: 20, 4: 7}.__getitem__
    good = [
        _log(3, "A", True, 1.0, 2.0, 22, 0),    # its own update
        _log(3, "B", False, 1.5, 2.5, 22),      # overlaps A's: may hold it ...
        _log(3, "C", False, 1.6, 1.9, 20),      # ... or not yet
        _log(3, "B", True, 3.0, 4.0, 24, 0),    # A's was acknowledged before it left
        _log(3, "C", False, 4.5, 5.0, 24),
        _log(4, "D", False, 0.0, 9.0, 7),       # another owner: nothing of owner 3
    ]
    driver.check_holdings(good, preload, 2)
    # (e): C's last pull left after both updates were acknowledged and lacks one
    stale = good[:4] + [_log(3, "C", False, 4.5, 5.0, 22)] + good[5:]
    with pytest.raises(AssertionError, match=r"held 22 rows.*outside \[24, 24\]"):
        driver.check_holdings(stale, preload, 2)
    # a row nobody had sent: C's early pull holds an update that left later
    early = good[:2] + [_log(3, "C", False, 0.2, 0.9, 22)] + good[3:]
    with pytest.raises(AssertionError, match=r"outside \[20, 20\]"):
        driver.check_holdings(early, preload, 2)
    # an updater always holds its own update
    with pytest.raises(AssertionError, match="outside"):
        driver.check_holdings([_log(3, "A", True, 1.0, 2.0, 20, 0)], preload, 2)
    # a failed round never reaches the bounds
    with pytest.raises(AssertionError, match="failed round"):
        driver.check_holdings([{**good[0], "ok": False}], preload, 2)


class _Window:
    compiles_inside = 0

    def begin(self):
        pass

    def end(self):
        pass


@needs_native
def test_the_drivers_check_holds_the_store_to_the_log_and_the_reference(tmp_path):
    """Set-up, warm-up and a few rounds over HTTP, then the driver's own
    check; then what it must refuse: a deleted acknowledged row, a tree
    that is not the fold, a count that is off."""
    metrics.reset()  # the check reads the process's fallback counters whole
    seed = 2**31 + 40
    state = driver.setup(CFG, seed, scratch=str(tmp_path))
    try:
        driver.warm(state, PARAMS)
        assert state["warm_rows"] == 2 and state["timings"]["warm_buckets"] == [64]
        assert len(state["warm_log"]) == PARAMS["clients"] + 1 + 1
        assert state["warm_log"][-1]["answer"] == 2  # one answer with messages
        draw = gen_mix.OwnerDraw(len(state["requests"]), seed, PARAMS["zipf_theta"])
        pool_path, owners_path = str(tmp_path / "pool.bin"), str(tmp_path / "owners.bin")
        gen.write_pool(pool_path, state["pool"])
        gen_mix.write_owner_file(owners_path, state["requests"])
        from perf import loadgen_mix as loadgen

        rounds, t_start = [], time.monotonic()
        for process in range(2):
            spec = {"process": process, "base_millis": state["push_base_millis"],
                    "msgs_per_update": 2}
            devices = loadgen.Devices(spec, gen.read_pool(pool_path),
                                      gen_mix.OwnerFile(owners_path))
            rng = gen_mix.thread_rng(seed, process)
            for _ in range(25):
                rank, update = gen_mix.draw_round(draw, rng, 0.5)
                index = draw.owner_of_rank[rank]
                rounds.append(loadgen.one_round(devices.of(index), index, update,
                                                state["url"], 60))
            devices.owners.close()
        outcome = closed_loop_mix.account(rounds, 2, t_start, time.monotonic() - t_start)
        assert outcome["failed"] == 0 and outcome["attempted"] == 50
        outcome["window_compiles"] = 0
        assert driver.check(state, outcome) is True

        acked = driver.acknowledged(state["warm_log"] + rounds, state["push_base_millis"],
                                    2, state["pool"])
        index = next(r["owner"] for r in rounds if r["update"])
        owner = state["requests"][index].user_id
        lost = acked[index][0][0].timestamp
        db = state["store"].shard_of(owner).db
        db.run('DELETE FROM "message" WHERE "userId" = ? AND "timestamp" = ?', (owner, lost))
        with pytest.raises(AssertionError, match="stored rows"):
            driver.check(state, outcome)
        with pytest.raises(AssertionError, match="dump != the reference's"):
            driver.check_reference(state, [index], acked)
        with pytest.raises(AssertionError, match="1 failed rounds"):
            driver.check(state, {**outcome, "failed": 1})
        with pytest.raises(AssertionError, match="compiles inside the window"):
            driver.check(state, {**outcome, "window_compiles": 1})
    finally:
        driver.close(state)


# (e) the traffic module's accounting


def test_only_rounds_answered_inside_the_window_count_and_only_updates_are_messages():
    def r(update, t_send, t_done, ok=True, answer=0, owner=1):
        return {"owner": owner, "node": "n", "update": update, "count": 0 if update else None,
                "t_send": t_send, "t_done": t_done, "ok": ok,
                "error": None if ok else "HTTPError(503)", "held": 0, "answer": answer}

    rounds = [
        r(True, 8.0, 9.5),                 # the lead-in: acknowledged, not counted
        r(True, 9.9, 10.0),                # answered at t_start: inside
        r(False, 10.0, 10.04, answer=6),   # a pull with messages
        r(True, 11.0, 11.08),
        r(False, 12.0, 12.02, owner=2),
        r(False, 19.0, 19.99, ok=False),   # failed inside the window
        r(True, 19.9, 20.0),               # answered at t_end: outside
        r(True, 19.95, 20.3, owner=3),
    ]
    out = closed_loop_mix.account(rounds, 2, t_start=10.0, seconds=10.0)
    assert out["attempted"] == 5 and out["failed"] == 1 and out["rounds_ok"] == 4
    assert out["errors"] == ["HTTPError(503)"]
    assert (out["updates_ok"], out["pulls_ok"], out["answers_with_messages"]) == (2, 2, 1)
    assert out["acked_msgs"] == 4 and out["acked_msgs_total"] == 10 and out["window_s"] == 10.0
    assert out["latency_ms"] == pytest.approx([100.0, 40.0, 80.0, 20.0])
    assert out["owners_touched"] == 3 and out["rounds"] is rounds

    def metric(name):
        with open(os.path.join(ROOT, "perf", "metrics", f"{name}.json")) as f:
            return readers.read(json.load(f)["read"], None, None, out)

    assert metric("ingest_rate") == 0.4  # 4 messages of 2 updates in 10 s
    assert metric("sync_p50") == pytest.approx(60.0) and metric("sync_p95") > 90


# (f) the cell, rehearsed


@needs_native
def test_rehearsal_of_the_cell_ends_correct_with_every_new_metric():
    """`perf/run.py --rehearse --trace 1` (which runs `perf/selfcheck.py`
    first) in a process of its own: control flow, counts and `correct`,
    never a device number."""
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 40), "--seconds", "2", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    line = lines[-1]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(got) == set(NEW_METRICS) | set(TWINS) | {
        "device_transfers_pass", "relay_tree_text_share"}
    assert got["device_transfers_pass"] == 2.0  # PR 41: one buffer up, one back
    assert got["relay_tree_text_share"] == 100  # every owner's tree folded as text
    assert got["window_compiles.skewed"] == 0
    assert 0 < got["pull_answer_share"] <= 100 and got["respond_msgs_request"] > 0
    assert 0 <= got["sched_deferred_share"] <= 100 and got["sched_passes_waited"] >= 0
    assert got["respond_diff_ms"] + got["respond_fetch_ms"] <= got["pass_respond_ms.skewed"]
    e2e = next(l["metrics"] for l in lines if l.get("info") == "the other set")
    assert set(e2e) == {"sync_p50", "sync_p95", "ingest_rate", "setup_s"}
    outcome = next(l for l in lines if l.get("info") == "outcome")
    assert outcome["acked_msgs"] == 2 * outcome["updates_ok"]
    assert outcome["rounds_ok"] == outcome["updates_ok"] + outcome["pulls_ok"] == line["attempted"]
    assert e2e["ingest_rate"]["value"] == pytest.approx(outcome["acked_msgs"] / 2.0)


# (g) the data files


def _layer(name: str) -> tuple:
    with open(os.path.join(ROOT, "perf", "layers", f"{name}.json")) as f:
        spec = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    return spec, entry


@pytest.mark.parametrize("name", [*NEW_METRICS, *sorted(TWINS)])
def test_each_new_layer_metric_is_data_over_a_family_the_program_emits(name, one_owner_queue):
    spec, entry = _layer(name)
    assert entry["workloads"] == [CELL] and spec["cells"] == ["relay-skewed"]
    assert all(spec[k] == entry[k] for k in ("unit", "better", "layer", "moves"))
    assert spec["read"]["kind"] in readers.KINDS  # data: no reader of its own
    if name in TWINS:  # reads what the accepted file reads, under the same manifest line
        base_spec, base_entry = _layer(TWINS[name])
        assert spec["read"] == base_spec["read"]
        assert all(entry[k] == base_entry[k] for k in ("unit", "better", "source", "layer", "moves"))
    else:
        assert "older" in spec["what"]  # says what it reads on an older program
        assert entry["source"] == (
            "program_span" if spec["read"]["kind"] == "hist_mean"
            and spec["read"]["family"] == "evolu_stage_ms" else "program_counter")
    value = readers.read(spec["read"], one_owner_queue["before"], one_owner_queue["after"],
                         {"window_compiles": 0})
    assert value is not None and value >= 0
    # the owner's queued requests rode one pass a phase (ISSUE 42): nothing deferred
    expect = {"sched_deferred_share": 0.0, "sched_passes_waited": 0.0,
              "pull_answer_share": 100 * 3 / 5, "respond_msgs_request": (2 + 4 + 6) / 5}
    if name in expect:
        assert value == pytest.approx(expect[name])
    # and nothing on a program without the families: the parent's side
    empty = {"counters": {}, "gauges": {}, "histograms": {}}
    if name in NEW_METRICS:
        assert readers.read(spec["read"], empty, empty, {}) is None
        older = {**one_owner_queue["after"], "counters": {
            k: v for k, v in one_owner_queue["after"]["counters"].items()
            if not k.startswith(("evolu_engine_respond_", "evolu_sched_deferred_"))}}
        if spec["read"]["kind"] == "counter_ratio":
            assert readers.read(spec["read"], empty, older, {}) is None


def test_perf_selfcheck_accepts_the_cell_and_its_files():
    from perf import selfcheck

    selfcheck.check_files()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (config,) = [c for c in manifest["configs"] if c["name"] == "relay-skewed"]
    assert config["reduced"] == ["store", "history"] and len(config["source"]) <= 200
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("relay-skewed", "ycsb-a", 1)
    kept = [m["name"] for m in manifest["end_to_end"] if CELL in m.get("workloads", [])]
    assert "setup_s" not in kept and len(kept) >= 2
    assert _CONFIG["reference"] == "relay_sync" and len(_CONFIG["guarantees"]) == 6
    assert (PARAMS["clients"], PARAMS["processes"], PARAMS["zipf_theta"],
            PARAMS["update_share"], PARAMS["msgs_per_update"], PARAMS["lead_s"],
            PARAMS["timeout_s"]) == (25, 5, 0.99, 0.5, 2, 2.0, 30)


def test_the_reference_and_the_load_generator_import_nothing_they_must_not():
    """The plain reference names nothing of the program in its source
    (its hash is the benchmark's own numpy murmur3, `perf/gen.py`'s); the
    load generator's child never imports JAX (it asserts so itself when
    it ends; here: a fresh interpreter that imports it)."""
    for name in ("relay_sync", "client_todo"):
        with open(os.path.join(ROOT, "perf", "reference", f"{name}.py")) as f:
            code = [line for line in f if line.lstrip().startswith(("import ", "from "))]
        assert code and not any("evolu_tpu" in line for line in code), name
    code = ("import sys; sys.path.insert(0, %r); import perf.loadgen_mix, perf.gen_mix; "
            "assert 'jax' not in sys.modules" % ROOT)
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          timeout=120)
    assert done.returncode == 0, done.stderr[-1000:]
