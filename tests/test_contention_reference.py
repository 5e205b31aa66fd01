"""`contention-64` against its plain references at a small size (ISSUE 42).

Since ISSUE 42 the scheduler puts several requests of one owner in ONE
engine pass where the engine says it answers each as a sequential relay
would (`BatchReconciler.answers_in_order`), and the engine does so: request
i is answered from the owner's tree after the new rows of requests <= i
(`engine._tree_prefixes`) and without the rows a later request inserted
(`eh_get_messages_wire`'s withheld rows). Here: k requests of one owner in
one pass, answer by answer against `perf/reference/relay_sync.py`; the
close rule and the requests it still keeps; the cell's device model
(`perf/gen_contention.py`) against `core/timestamp.py`; the cell rehearsed,
guarantee (g) included; and its data files.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from evolu_tpu.core.merkle import apply_prefix_xors, merkle_tree_to_string, minute_deltas_host
from evolu_tpu.core.murmur import to_int32
from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.obs import metrics
from evolu_tpu.server import engine as engine_mod
from evolu_tpu.server.relay import ShardedRelayStore
from evolu_tpu.server.scheduler import SyncScheduler
from evolu_tpu.storage import native
from evolu_tpu.sync import native_crypto, protocol
from perf import gen, gen_contention, load_module, readers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
reference = load_module("reference", "relay_sync")
CELL = "contention-64.push"
OWNER = "team-owner"

needs_native = pytest.mark.skipif(
    not (native.native_available() and native_crypto.native_available()),
    reason="the packed ingest needs both native libraries")


def _stamp(millis: int, counter: int, node: str) -> str:
    return timestamp_to_string(Timestamp(gen.BASE_MILLIS + millis, counter, node))


def _msg(millis: int, counter: int, node: str) -> protocol.EncryptedCrdtMessage:
    return protocol.EncryptedCrdtMessage(_stamp(millis, counter, node),
                                         b"ct-%d-%d-%s" % (millis, counter, node.encode()))


def _pairs(messages) -> list:
    return [(m.timestamp, m.content) for m in messages]


def _node(i: int) -> str:
    return f"c{i:015x}"


def _requests(k: int) -> tuple:
    """The preload (one request of 6 rows) and k requests of one owner,
    several devices, in arrival order: most edit (two rows in a shared
    millisecond, their counters and nodes deciding the order), one pulls,
    one re-sends a row an EARLIER request of the pass carries (k > 2), one
    a row already stored. Client trees: "{}" for odd devices and the pull
    (the diff starts at the first minute), the preload's tree for the
    others."""
    stored = tuple(_msg(60_000 * (i // 3), i, _node(90)) for i in range(6))
    preload = protocol.SyncRequest(stored, OWNER, _node(90), "{}")
    preload_tree = merkle_tree_to_string(apply_prefix_xors(
        {}, minute_deltas_host(m.timestamp for m in stored)[0]))
    out = []
    for i in range(k):
        node = _node(i)
        messages = (_msg(120_000 + 60_000 * (i % 3), 2 * i, node),
                    _msg(120_000 + 60_000 * (i % 3), 2 * i + 1, node))
        if i == (k - 1) // 2:
            messages = ()  # a pull
        elif i == k - 1 and k > 2:
            messages += (out[0].messages[0],)  # a row an earlier request carries
        elif i == 1:
            messages += (stored[2],)  # a row already stored
        tree = "{}" if i % 2 or not messages else preload_tree
        out.append(protocol.SyncRequest(messages, OWNER, node, tree))
    return preload, out


@needs_native
@pytest.mark.parametrize("k", [2, 5, 25])
def test_k_requests_of_one_owner_in_one_pass_equal_the_sequential_reference(k, monkeypatch):
    preload, requests = _requests(k)
    twin = reference.ReferenceRelay()
    folded, prefixes = {}, []
    real_fold, real_prefixes = engine_mod._fold_trees, engine_mod._tree_prefixes

    def fold(deltas_by_owner, *args):
        folded.update({o: dict(d) for o, d in deltas_by_owner.items()})
        return real_fold(deltas_by_owner, *args)

    def tree_prefixes(*args):
        prefixes.append(real_prefixes(*args))
        return prefixes[-1]

    wire_store = ShardedRelayStore(":memory:", "native", shards=2)
    object_store = ShardedRelayStore(":memory:", "native", shards=2)
    try:
        wire_engine = engine_mod.BatchReconciler(wire_store)
        object_engine = engine_mod.BatchReconciler(object_store)
        for eng in (wire_engine, object_engine):
            eng.reconcile([preload])
        twin.sync(OWNER, preload.node_id, _pairs(preload.messages), "{}")
        monkeypatch.setattr(engine_mod, "_fold_trees", fold)
        monkeypatch.setattr(engine_mod, "_tree_prefixes", tree_prefixes)
        withheld0 = metrics.get_counter("evolu_engine_respond_withheld_total")
        wires = wire_engine.run_batch_wire(requests)
        objects = object_engine.reconcile(requests)
        assert len(prefixes) == 2 and prefixes[0].keys() == prefixes[1].keys()

        # Answer by answer against the sequential reference.
        seen = {m.timestamp for m in preload.messages}
        deltas, withheld = [], 0
        for request, wire, obj in zip(requests, wires, objects):
            rows, tree = twin.sync(OWNER, request.node_id, _pairs(request.messages),
                                   request.merkle_tree)
            got = protocol.decode_sync_response(wire)
            assert _pairs(got.messages) == rows and got.merkle_tree == tree, request.node_id
            assert protocol.encode_sync_response(obj) == wire  # the object path too
            new = [m.timestamp for m in request.messages if m.timestamp not in seen]
            seen.update(new)
            deltas.append(minute_deltas_host(new)[0])
        for request, wire in zip(requests, wires):
            got = protocol.decode_sync_response(wire)
            later = twin.get_messages(OWNER, request.node_id, json.loads(got.merkle_tree),
                                      json.loads(request.merkle_tree))
            withheld += len(later) - len(got.messages)
        assert withheld > 0
        assert metrics.get_counter("evolu_engine_respond_withheld_total") - withheld0 == withheld
        # The XOR of the per-request deltas is the pass's own owner delta.
        xor = {}
        for d in deltas:
            for key, h in d.items():
                xor[key] = to_int32(xor.get(key, 0) ^ h)
        assert xor == folded[OWNER]
        # The stored tree is the one after the last request, and the dump is the reference's.
        for store in (wire_store, object_store):
            db = store.shard_of(OWNER).db
            dump = reference.owner_dump(
                lambda sql, args: [tuple(r.values()) for r in db.exec_sql_query(sql, args)],
                OWNER)
            assert dump == twin.owner_dump(OWNER)
        wire_engine.close()
        object_engine.close()
    finally:
        twin.close()
        wire_store.close()
        object_store.close()


@needs_native
def test_a_pass_of_distinct_owners_takes_no_prefix(monkeypatch):
    """The path of one request an owner stays as it was: no per-request
    tree, no `tree_prefix` stage (`relay-reference.push`, the backfill)."""
    calls = []
    monkeypatch.setattr(engine_mod, "_tree_prefixes", lambda *a: calls.append(a) or {})
    store = ShardedRelayStore(":memory:", "native", shards=2)
    try:
        eng = engine_mod.BatchReconciler(store)
        requests = [protocol.SyncRequest((_msg(0, 0, _node(i)),), f"owner-{i}", _node(i), "{}")
                    for i in range(4)]
        assert engine_mod._owner_runs(requests) == {}
        eng.run_batch_wire(requests)
        eng.close()
    finally:
        store.close()
    assert calls == []


def _pending(owner: str, scoped: bool = False):
    from evolu_tpu.server import scheduler as sched_mod

    scope = protocol.ScopeClause(0, ("lane",), ()) if scoped else None
    return sched_mod._Pending(protocol.SyncRequest((), owner, "f" * 16, "{}", scope=scope))


@needs_native
@pytest.mark.parametrize("store", ["packed", "generic", "write_behind", "scoped"])
def test_the_close_takes_an_owners_requests_only_where_the_engine_answers_them_in_order(store):
    """`_close_batch` on a queue made by hand, from the first close on:
    an owner's queued requests ride one batch, in FIFO order, exactly
    where the scheduler was built for an engine that answers them in
    order (a packed store, no write-behind queue); otherwise the later
    ones are kept as before ISSUE 42, and so is any scoped one."""
    packed = ShardedRelayStore(":memory:", "native", shards=2)
    try:
        sched = SyncScheduler(object() if store == "generic" else packed, max_batch=8,
                              write_behind=object() if store == "write_behind" else None)
        sched.stop()
        a1, b1, a2, a3 = (_pending("a"), _pending("b"),
                          _pending("a", scoped=store == "scoped"), _pending("a"))
        sched._queue = [a1, b1, a2, a3]
        batch = sched._close_batch()
    finally:
        packed.close()
    assert sched._engine is None  # decided before any engine exists
    if store == "packed":
        assert batch == [a1, b1, a2, a3] and sched._coalesced == 2 and sched._kept == {}
        assert sched._queue == []
    else:
        assert batch == [a1, b1] and sched._coalesced == 0
        assert sched._kept == {"same_owner": 1, "owner_blocked": 1}
        assert sched._queue == [a2, a3]


@needs_native
@pytest.mark.parametrize("store", ["packed", "generic", "write_behind"])
def test_the_schedulers_capability_is_the_engine_it_builds(store):
    """What the scheduler decides when it is built is what the engine it
    builds states (`BatchReconciler.answers_in_order`), and an engine
    handed to it is asked instead."""
    from evolu_tpu.server.relay import RelayStore

    db = (ShardedRelayStore(":memory:", "native", shards=2) if store != "generic"
          else RelayStore(":memory:", "python"))
    wb = object() if store == "write_behind" else None
    try:
        sched = SyncScheduler(db, write_behind=wb)
        sched.stop()
        eng = engine_mod.BatchReconciler(db, write_behind=wb)
        assert sched._in_order is eng.answers_in_order is (store == "packed")
        given = SyncScheduler(db, engine=eng)
        given.stop()
        assert given._in_order is eng.answers_in_order
        eng.close()
    finally:
        db.close()


@needs_native
def test_a_scoped_request_first_keeps_its_owners_later_ones():
    """A scoped request reads the store after the whole pass, so an owner
    whose first request in the batch is scoped takes no second one."""
    packed = ShardedRelayStore(":memory:", "native", shards=2)
    try:
        sched = SyncScheduler(packed, max_batch=8)
        sched.stop()
        s1, a2 = _pending("a", scoped=True), _pending("a")
        sched._queue = [s1, a2]
        assert sched._close_batch() == [s1] and sched._kept == {"same_owner": 1}
    finally:
        packed.close()


# the device model


def test_the_device_models_clock_is_core_timestamps_without_the_drift_bound():
    import random

    from evolu_tpu.core.timestamp import receive_timestamp, send_timestamp
    from evolu_tpu.core.types import TimestampCounterOverflowError, TimestampDuplicateNodeError

    rng = random.Random(42)
    huge = 10 ** 12
    for _ in range(3000):
        local = (gen.BASE_MILLIS + rng.randrange(4), rng.randrange(5), _node(rng.randrange(3)))
        remote = (gen.BASE_MILLIS + rng.randrange(4), rng.randrange(5), _node(rng.randrange(3)))
        now = gen.BASE_MILLIS + rng.randrange(-2, 5)
        t = send_timestamp(Timestamp(*local), now, huge)
        assert gen_contention.send(local, now) == (t.millis, t.counter, t.node)
        if local[2] == remote[2]:
            with pytest.raises(ValueError):
                gen_contention.receive(local, remote, now)
            with pytest.raises(TimestampDuplicateNodeError):
                receive_timestamp(Timestamp(*local), Timestamp(*remote), now, huge)
            continue
        t = receive_timestamp(Timestamp(*local), Timestamp(*remote), now, huge)
        assert gen_contention.receive(local, remote, now) == (t.millis, t.counter, t.node)
    top = (gen.BASE_MILLIS, gen_contention.MAX_COUNTER, _node(0))
    with pytest.raises(OverflowError):
        gen_contention.send(top, gen.BASE_MILLIS)
    with pytest.raises(TimestampCounterOverflowError):
        send_timestamp(Timestamp(*top), gen.BASE_MILLIS)
    assert gen_contention.parse(gen_contention.render(top)) == top


def test_the_history_is_seeded_and_every_device_ends_holding_it_at_one_millisecond_a_minute():
    rows = gen_contention.row_ids(7, 10)
    assert rows == gen_contention.row_ids(7, 10) and len(set(rows)) == 10
    assert all(len(r) == 21 for r in rows)
    book = tuple(b"ct-%d" % i for i in range(8 * 30 * 2))
    base = gen.BASE_MILLIS + 10 * 60_000
    history, clocks, end = gen_contention.build_history(7, 8, 30, base, 30, book)
    again = gen_contention.build_history(7, 8, 30, base, 30, book)
    assert (history, clocks, end) == again
    assert len(history) == 8 * 30 * 2 == len({m.timestamp for m in history})
    assert end == gen_contention.physical_millis(base, 1, 8 * 30, 8)
    # every device has merged all of it: its clock is past every other node's stamp
    newest = max(gen_contention.parse(m.timestamp) for m in history)
    for clock in clocks:
        assert clock[:2] >= newest[:2]
    # the clock rule: one synthetic minute per 8 rounds, every edit of it in one millisecond
    assert len({m.timestamp[:24] for m in history}) == 31
    rounds = [{"stamps": [m.timestamp for m in history[i:i + 2]]}
              for i in range(0, len(history), 2)]
    assert gen_contention.devices_per_millisecond(rounds) > 6  # of 8 a minute
    contents = [m.content for m in history]
    assert sorted(contents) == sorted(book)  # each device's 30 edits, once each


def test_a_device_sends_its_book_and_merges_with_its_clock():
    book = tuple(b"ct-%d" % i for i in range(2 * 3 * 2))
    node = gen_contention.device_node("c", 1)
    device = gen_contention.TeamDevice("o", 1, (gen.BASE_MILLIS, 3, node), "{}", [], book, 3)
    first = device.request(gen.BASE_MILLIS)
    assert [m.content for m in first.messages] == [b"ct-6", b"ct-7"]
    assert [gen_contention.parse(m.timestamp) for m in first.messages] == [
        (gen.BASE_MILLIS, 4, node), (gen.BASE_MILLIS, 5, node)]
    other = protocol.EncryptedCrdtMessage(_stamp(0, 9, _node(5)), b"x")
    answer = protocol.SyncResponse((other,), device.tree_string)
    assert not device.merge(answer, gen.BASE_MILLIS)  # the row is new: the trees differ
    assert device.clock == (gen.BASE_MILLIS, 10, node)
    device.request(gen.BASE_MILLIS), device.request(gen.BASE_MILLIS)
    with pytest.raises(IndexError):
        device.request(gen.BASE_MILLIS)  # out of its book


# the cell, rehearsed, and its files


@needs_native
def test_rehearsal_of_the_cell_ends_correct_with_the_restored_65th_device():
    """`perf/run.py --rehearse --trace 1` in a process of its own:
    guarantees (a)-(g) held by `correct`, the new metrics read, one owner's
    requests coalesced. Never a device number."""
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 42), "--seconds", "2", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.strip().splitlines()]
    line = lines[-1]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(got) == set(NEW_METRICS) | set(TWINS) | {"relay_tree_text_share"}
    assert got["relay_tree_text_share"] == 100  # every owner's tree folded as text
    assert got["window_compiles.contention"] == 0
    assert got["sched_batch_requests.contention"] > 1 and got["coalesced_share"] > 0
    assert got["respond_withheld_rows"] > 0 and got["tree_prefix_ms"] > 0
    assert got["respond_msgs_request.contention"] > 0
    e2e = next(l["metrics"] for l in lines if l.get("info") == "the other set")
    assert set(e2e) == {"sync_p50", "sync_p95", "ingest_rate", "setup_s"}
    outcome = next(l for l in lines if l.get("info") == "outcome")
    assert outcome["acked_msgs"] == 2 * outcome["updates_ok"] == 2 * line["attempted"]
    assert outcome["pulls_ok"] == 0 and outcome["owners_touched"] == 1
    assert outcome["devices_per_ms"] > 1 and outcome["answer_msgs_mean"] > 0


@needs_native
def test_the_team_dump_and_the_restored_device_are_held_to_their_references(tmp_path):
    """The check's last two guarantees on a relay made by the driver at its
    rehearsal size: the team's dump against `ReferenceRelay`, and (g), the
    65th device restored over HTTP against `ReferenceClient`; each fails
    where the relay or the client would be wrong."""
    driver = load_module("drivers", "relay_contention")
    with open(os.path.join(ROOT, "perf", "configs", "contention-64.json")) as f:
        config = json.load(f)
    state = driver.setup({**config, **config["rehearsal"]}, 2**31 + 9, str(tmp_path))
    try:
        rows = driver.team_rows(state, [])
        served = driver.check_team_dump(state, rows)
        assert sorted(served) == sorted(rows)
        driver.check_restore(state, served)
        with pytest.raises(AssertionError, match="dump"):
            driver.check_team_dump(state, rows[:-1])  # a row the relay holds, unacknowledged
        stamp, content = served[-1]
        table, row, column, _value = state["plain"][content]
        state["plain"] = {**state["plain"], content: (table, row, column, "never written")}
        with pytest.raises(AssertionError, match=r"\(g\)"):
            driver.check_restore(state, served)  # the client resolved a cell otherwise
    finally:
        driver.close(state)


def _serve_team_in_passes(driver, state, passes) -> list:
    """Rounds of the team's devices served straight by the engine, the
    devices of each pass in ONE `run_batch_wire` call, logged as the
    cell's children log them."""
    cfg = state["cfg"]
    devices = [gen_contention.TeamDevice(
        state["owner"], d, state["clocks"][d], state["tree"], state["history_stamps"],
        state["book"], state["cap"]) for d in range(cfg["devices"])]
    eng = engine_mod.BatchReconciler(state["store"])
    rounds = []
    for p, members in enumerate(passes):
        now = state["window_base_millis"] + 60_000 * (p + 1)
        requests = [devices[d].request(now) for d in members]
        t_send = time.monotonic()
        wires = eng.run_batch_wire(requests)
        t_done = time.monotonic()
        for d, wire in zip(members, wires):
            answer = protocol.decode_sync_response(wire)
            assert devices[d].merge(answer, now), "(d)"
            rounds.append(gen_contention.round_record(devices[d], t_send, t_done, answer))
    eng.close()
    return rounds


@needs_native
@pytest.mark.parametrize("plant", [False, True])
def test_each_round_is_held_to_its_sequential_answer(plant, tmp_path, monkeypatch):
    """`check_answers` at the rehearsal size, over passes of several
    devices of the team: the served order read back from the answers'
    roots, every round byte-equal (digest) to `ReferenceRelay.sync` taken
    in that order. A relay that answers every request of a pass from the
    pass's final tree and withholds nothing (the engine without
    `_tree_prefixes`) passes (d), `check_holdings` and the dump, and
    `check` fails on it: `correct` false."""
    driver = load_module("drivers", "relay_contention")
    with open(os.path.join(ROOT, "perf", "configs", "contention-64.json")) as f:
        config = json.load(f)
    state = driver.setup({**config, **config["rehearsal"]}, 2**31 + 77, str(tmp_path))
    try:
        if plant:
            monkeypatch.setattr(engine_mod, "_tree_prefixes", lambda *a: {})
        withheld0 = metrics.get_counter("evolu_engine_respond_withheld_total")
        rounds = _serve_team_in_passes(
            driver, state, [(0, 1, 2), (3, 4, 5, 6, 7), tuple(range(8)), (5, 2), (1,)])
        driver.check_holdings(rounds, lambda _owner: len(state["history"]), gen_contention.MSGS)
        driver.check_team_dump(state, driver.team_rows(state, rounds))
        withheld = metrics.get_counter("evolu_engine_respond_withheld_total") - withheld0
        if not plant:
            assert withheld > 0
            driver.check_answers(state, rounds)
            # Every round's root alike: what the reference would answer
            # each candidate decides the order alone, as on a collision.
            from evolu_tpu.core import merkle

            real = merkle.minute_deltas_host
            monkeypatch.setattr(merkle, "minute_deltas_host", lambda s: (real(s)[0], 0))
            root = gen_contention.tree_root(merkle.merkle_tree_from_string(state["tree"]))
            driver.check_answers(state, [{**r, "root": root} for r in rounds])
            monkeypatch.setattr(merkle, "minute_deltas_host", real)
            with pytest.raises(AssertionError, match=r"\(f\)"):
                driver.check_answers(state, [{**r, "digest": r["digest"][::-1]}
                                             if i == 7 else r for i, r in enumerate(rounds)])
        else:
            assert withheld == 0
            outcome = {"failed": 0, "attempted": len(rounds), "window_compiles": 0,
                       "acked_msgs_total": 2 * len(rounds), "rounds": rounds, "errors": []}
            with pytest.raises(AssertionError, match=r"\(f\)"):
                driver.check(state, outcome)
    finally:
        driver.close(state)


NEW_METRICS = ("coalesced_share", "respond_withheld_rows", "tree_prefix_ms")
TWINS = {f"{base}.contention": base for base in (
    "sched_batch_requests", "sched_batch_ms", "relay_round_ms", "pass_respond_ms",
    "pass_tree_ms", "respond_fetch_ms", "respond_msgs_request", "sched_deferred_share",
    # the round's legs and the pass's stages the cell measured (REVIEW, PR 42)
    "sched_queue_wait_ms", "conn_head_ms", "acceptor_busy_share", "pass_insert_ms",
    "pass_device_call_ms", "pass_layout_ms")}
TWINS["window_compiles.contention"] = "window_compiles.relay"


def _layer(name: str) -> tuple:
    with open(os.path.join(ROOT, "perf", "layers", f"{name}.json")) as f:
        spec = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    return spec, entry


@pytest.mark.parametrize("name", [*NEW_METRICS, *sorted(TWINS)])
def test_each_new_layer_metric_is_data_the_cell_reports(name):
    spec, entry = _layer(name)
    assert entry["workloads"] == [CELL] and spec["cells"] == ["contention-64"]
    assert all(spec[k] == entry[k] for k in ("unit", "better", "layer", "moves"))
    assert spec["read"]["kind"] in readers.KINDS  # data: no reader of its own
    if name in TWINS:  # reads what the accepted file reads
        base_spec, base_entry = _layer(TWINS[name])
        assert spec["read"] == base_spec["read"]
        assert all(entry[k] == base_entry[k] for k in ("unit", "better", "source", "layer"))
    else:
        assert "older program" in spec["what"]
    empty = {"counters": {}, "gauges": {}, "histograms": {}}
    if spec["read"]["kind"] == "hist_mean":  # a span the parent does not record
        assert readers.read(spec["read"], empty, empty, {}) is None


def test_perf_selfcheck_accepts_the_cell_and_its_files():
    from perf import selfcheck

    selfcheck.check_files()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (config,) = [c for c in manifest["configs"] if c["name"] == "contention-64"]
    assert config["reduced"] == ["store"] and len(config["source"]) <= 200
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("contention-64", "push", 1)
    kept = [m["name"] for m in manifest["end_to_end"] if CELL in m.get("workloads", [])]
    assert "setup_s" not in kept and len(kept) >= 2
    with open(os.path.join(ROOT, "perf", "configs", "contention-64.json")) as f:
        cfg = json.load(f)
    assert (cfg["devices"], cfg["rows"], cfg["history_edits"]) == (64, 100, 60)
    assert len(cfg["guarantees"]) == 7 and cfg["guarantees"][6].startswith("(g)")
    assert (cfg["rehearsal"]["devices"], cfg["rehearsal"]["rows"],
            cfg["rehearsal"]["owners"]) == (8, 10, 2)


def test_the_load_generator_imports_no_jax():
    code = ("import sys; sys.path.insert(0, %r); import perf.loadgen_team, perf.gen_contention; "
            "assert 'jax' not in sys.modules" % ROOT)
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          timeout=120)
    assert done.returncode == 0, done.stderr[-1000:]
