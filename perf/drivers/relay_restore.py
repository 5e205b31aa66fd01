"""Driver of a cold sync: `relay-reference`'s served relay (a native sharded
store preloaded through the batch engine with 1,000 owners' history,
`RelayServer` with its batching `SyncScheduler` on a local port) holding
besides one owner's history of a year (`perf/gen_history.py`), and in the
same process one restored device after another pulling that owner whole
through the product's path: a fresh native `:memory:` database under
`DbWorker(Config(backend=..., sync_url=<the relay>))`, a `SyncTransport`
wired to it as `sync/client.py::connect` wires an `Evolu`, then
`RestoreOwner(mnemonic)`, `UpdateDbSchema` and the worker's own `Sync`.
The relay answers the empty tree with every row of the owner in one body,
the transport decrypts it and hands it to the worker as one `Receive`.

Set-up builds the relay as `perf/drivers/relay.py` does (its cut into
passes and row count are that file's, not copies) and adds the owner's
history in one engine pass, so the owner's stored tree is the engine's. A
restore is `state["restore"]()`, which `perf/traffic/restore_loop.py`
calls back to back; every device is kept until the check, which holds the
first and the last counted one to `perf/reference/cold_sync.py` and the
relay's rows of the owner to what they were before the first pull.
"""

import itertools
import random
import threading
import time

from perf import gen, gen_history, load_module, observe

_relay = load_module("drivers", "relay")
_client = load_module("drivers", "client")
_chunks, _stored_rows, dump = _relay._chunks, _relay._stored_rows, _client.dump

PRELOAD_NODE = "f" * 16  # the node the history's one pass is sent from
COMPARED = ("todo", "todoCategory", "todoNote", "__message", "__clock")


def device_node(seed: int, index: int) -> str:
    """The node id of the index-th restored device: the product draws it
    at random (`core/ids.py::create_node_id`); here it follows from the
    seed, so that a run repeats."""
    return f"{random.Random(f'{seed}-device-{index}').getrandbits(64):016x}"


def _owner_dump(state: dict) -> tuple:
    reference = load_module("reference", "relay_sync")
    db = state["store"].shard_of(state["owner"]).db
    return reference.owner_dump(
        lambda sql, args: [tuple(r.values()) for r in db.exec_sql_query(sql, args)],
        state["owner"])


def setup(cfg: dict, seed: int, scratch: str) -> dict:
    from evolu_tpu.core.ids import mnemonic_to_owner_id
    from evolu_tpu.core.merkle import (apply_prefix_xors, merkle_tree_to_string,
                                       minute_deltas_host)
    from evolu_tpu.parallel.mesh import create_mesh
    from evolu_tpu.server.engine import BatchReconciler
    from evolu_tpu.server.relay import RelayServer, ShardedRelayStore
    from evolu_tpu.server.scheduler import SyncScheduler
    from evolu_tpu.sync import protocol
    from evolu_tpu.sync.client import encrypt_messages

    observe.assert_native()
    t0 = time.monotonic()
    pool = gen.ciphertext_pool(cfg["ciphertext_pool"])
    requests = gen.build_requests(cfg["messages"], cfg["owners"], seed, pool)
    t1 = time.monotonic()
    history = gen_history.build_messages(
        cfg["history"], seed, cfg["rows_per_table"], cfg["nodes"],
        cfg["days"], cfg["sessions_per_day"], cfg["session_minutes"])
    encrypted = encrypt_messages(history, gen_history.MNEMONIC)
    plain = {e.content: (m.table, m.row, m.column, m.value)
             for m, e in zip(history, encrypted)}
    assert len(plain) == len(history), "two messages share a ciphertext"
    owner = mnemonic_to_owner_id(gen_history.MNEMONIC)
    deltas, _ = minute_deltas_host(m.timestamp for m in history)
    tree = merkle_tree_to_string(apply_prefix_xors({}, deltas))
    t2 = time.monotonic()

    store = ShardedRelayStore(cfg["store"], "native", shards=cfg["storage_shards"])
    engine = BatchReconciler(store, mesh=create_mesh(1))
    chunks = _chunks(requests, cfg["preload_batches"])
    responses = engine.reconcile(chunks[0])
    if len(chunks) > 1:
        responses += engine.reconcile(chunks[1])
    responses += [r for batch in engine.reconcile_stream(chunks[2:]) for r in batch]
    assert len(responses) == len(requests)
    for req, resp in zip(requests, responses):
        assert resp.messages == (), f"preload answered rows for {req.user_id}"
        assert resp.merkle_tree == req.merkle_tree, f"tree != host fold: {req.user_id}"
    (resp,) = engine.reconcile([protocol.SyncRequest(
        tuple(encrypted), owner, PRELOAD_NODE, tree)])
    assert resp.messages == () and resp.merkle_tree == tree, \
        "the restoring owner's history: tree != host fold"
    engine.close()
    preload = sum(len(r.messages) for r in requests) + len(history)
    assert _stored_rows(store) == preload
    t3 = time.monotonic()

    scheduler = SyncScheduler(store, **cfg["scheduler"])
    server = RelayServer(store, scheduler=scheduler).start()
    state = {
        "cfg": cfg, "seed": seed, "mnemonic": gen_history.MNEMONIC,
        "store": store, "server": server, "url": server.url, "owner": owner,
        "rows": [(e.timestamp, e.content) for e in encrypted], "plain": plain,
        "tree": tree, "preload_rows": preload, "restores": [], "warm_restores": 0,
        "timings": {"background_s": round(t1 - t0, 3), "history_s": round(t2 - t1, 3),
                    "preload_s": round(t3 - t2, 3), "tree_bytes": len(tree),
                    "minutes": len({m.timestamp[:16] for m in history})},
    }
    state["owner_dump"] = _owner_dump(state)
    state["restore"] = lambda: restore(state)
    return state


def restore(state: dict) -> dict:
    """One device restored from its mnemonic through the served relay →
    its record. The driver posts nothing to the relay itself: the
    worker's `Sync` makes the round, the transport posts it, decrypts the
    answer and hands it back as a `Receive`. The restore is done when the
    worker has emitted `OnReceive` (or an error) and handled the command,
    so that a second sync request, had its tree differed from the
    relay's, would have been posted by then. The record's error is read
    from what the worker and the transport reported; the database stays
    open for the check."""
    from evolu_tpu.core.timestamp import create_initial_timestamp, timestamp_to_string
    from evolu_tpu.core.types import TableDefinition
    from evolu_tpu.runtime import messages as rmsg
    from evolu_tpu.runtime.worker import DbWorker
    from evolu_tpu.storage.native import open_database
    from evolu_tpu.sync.client import SyncTransport
    from evolu_tpu.utils.config import Config

    cfg = state["cfg"]
    node = device_node(state["seed"], len(state["restores"]))
    outputs, posts, received = [], [], []
    answered = threading.Event()

    def on_output(output):
        outputs.append(output)
        if isinstance(output, (rmsg.OnReceive, rmsg.OnError)):
            answered.set()

    config = Config(backend=cfg["backend"], sync_url=state["url"])
    db = open_database(cfg["store"], backend="native")
    worker = DbWorker(db, config, on_output=on_output,
                      post_sync=lambda r: (posts.append(r), transport.request_sync(r)),
                      now=itertools.repeat(cfg["now_millis"]).__next__)

    def on_receive(messages, merkle_tree, previous_diff):
        received.append(len(messages))
        worker.post(rmsg.Receive(messages, merkle_tree, previous_diff))

    transport = SyncTransport(config, on_receive=on_receive, sync_lock=worker.sync_lock,
                              on_error=lambda e: on_output(rmsg.OnError(e)))
    record = {"db": db, "worker": worker, "transport": transport, "node": node,
              "received": received, "posts": posts, "messages": 0, "error": None}
    state["restores"].append(record)
    worker.start()
    worker.post(rmsg.RestoreOwner(state["mnemonic"]))
    worker.post(rmsg.UpdateDbSchema(tuple(
        TableDefinition.of(t, cols) for t, cols in gen_history.TABLES)))
    worker.flush()
    db.run('UPDATE "__clock" SET "timestamp" = ?',
           (timestamp_to_string(create_initial_timestamp(node)),))
    worker.post(rmsg.Sync())
    if not answered.wait(cfg["timeout_s"]):
        record["error"] = f"no answer within {cfg['timeout_s']} s"
        return record
    worker.flush()
    record["messages"] = sum(received)
    errors = [o.error for o in outputs if isinstance(o, rmsg.OnError)]
    if errors:
        record["error"] = f"OnError: {errors[0]!r}"
    elif len(posts) != 1:
        record["error"] = f"{len(posts)} sync requests: the tree differs from the relay's"
    elif len(received) != 1:
        record["error"] = f"{len(received)} Receives, not one"
    elif sum(isinstance(o, rmsg.OnReceive) for o in outputs) != 1:
        record["error"] = "the Receive was not acknowledged with one OnReceive"
    return record


def warm(state: dict, params: dict) -> None:
    """Whole restores, untimed: the relay's respond leg and the client's
    plan kernels at the history's bucket are compiled or read from the
    cache here."""
    t0 = time.monotonic()
    for _ in range(params["warm_restores"]):
        record = restore(state)
        assert record["error"] is None, f"warm-up restore failed: {record['error']}"
    state["warm_restores"] = len(state["restores"])
    state["timings"]["warm_s"] = round(time.monotonic() - t0, 3)


def check(state: dict, outcome: dict) -> bool:
    cfg = state["cfg"]
    assert outcome["attempted"] > 0, "no restore ran inside the window"
    assert outcome["failed"] == 0, f"{outcome['failed']} failed restores: {outcome['errors']}"
    assert outcome["window_compiles"] == 0, \
        f"{outcome['window_compiles']} compiles inside the window"
    counted = state["restores"][state["warm_restores"]:]
    assert len(counted) == outcome["attempted"], "restores kept != restores attempted"

    # The plain reference: the same rows, one answer, one receive.
    reference = load_module("reference", cfg["reference"])
    t0 = time.monotonic()
    want = reference.restore(state["owner"], state["rows"], state["plain"], gen_history.TABLES,
                             state["mnemonic"], counted[0]["node"], cfg["now_millis"])
    state["timings"]["reference_s"] = round(time.monotonic() - t0, 3)
    assert len(want["answered"]) == len(state["rows"]), "the reference's answer is not whole"
    assert want["tree"] == state["tree"], "the reference relay's tree != the engine's"
    assert want["dump"]["__clock"][0][1] == want["tree"], \
        "the reference device's tree != its relay's"

    # (a)-(d): one Receive of the whole history a restore, no second
    # round; the first and the last counted device byte for byte.
    full = {0, len(counted) - 1}
    for i, record in enumerate(state["restores"]):
        assert record["received"] == [cfg["history"]] and len(record["posts"]) == 1, \
            f"device {i}: Receives {record['received']}, sync requests {len(record['posts'])}"
        db = record["db"]
        clock = db.exec_sql_query('SELECT "timestamp", "merkleTree" FROM "__clock"')
        assert [(r["timestamp"][:29], r["merkleTree"]) for r in clock] == \
            want["dump"]["__clock"], f"device {i}: clock or tree != the reference's"
        if i - state["warm_restores"] in full:
            got = dump(db)
            for key in COMPARED:
                assert got[key] == want["dump"][key], f"device {i}: {key} != the reference's"
            continue
        for table in ("__message", *(t for t, _cols in gen_history.TABLES)):
            rows = db.exec_sql_query(f'SELECT COUNT(*) AS n FROM "{table}"')[0]["n"]
            assert rows == len(want["dump"][table]), f"device {i}: {rows} rows in {table}"
    packed = observe.counters("evolu_apply_batches_total")
    assert packed == {"route=packed": len(state["restores"])}, \
        f"not every Receive took the packed route: {packed}"

    # (e): a pull stores nothing; the owner's rows and tree are upstream's.
    assert _stored_rows(state["store"]) == state["preload_rows"], "a pull stored rows"
    after = _owner_dump(state)
    assert after == state["owner_dump"], "the owner's relay rows changed under the pulls"
    assert after == want["relay"], "the owner's relay rows != the reference relay's"
    observe.assert_no_fallback()
    observe.assert_pallas_route()
    return True


def close(state: dict) -> None:
    for record in state["restores"]:
        record["transport"].stop()
        record["worker"].stop()
        record["db"].close()
    state["server"].stop()  # closes the store
