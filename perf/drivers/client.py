"""Driver of a restoring client: one process that holds the chip, and in
it one device after another, each a fresh native `:memory:` database
under `DbWorker(Config(backend=...))` that is handed the relay's answers
as wire bytes and merges them: `decrypt_response_columns` →
`Receive(PackedReceive)` → `flush`, response by response.

Set-up builds the owner's history and the relay's responses once; a
restore is `state["restore"]()`, which the traffic module calls back to
back. Every restore's database and worker are kept until the check,
which compares them with `perf/reference/client_todo.py` outside the
window; `close` stops and closes them. The driver sets nothing of the
process (no allocator option, no environment, no thread of its own): the
cell reads what `DbWorker` delivers to any caller in a plain process.
"""

import itertools
import time

from perf import gen_client, load_module, observe

_ROUTE_COUNTERS = {
    "hits": "evolu_winner_cache_hits_total",
    "seeded": "evolu_winner_cache_seeded_cells_total",
    "streamed": "evolu_winner_cache_streamed_cells_total",
    "bounces": "evolu_apply_packed_bounces_total",
}


def _route_counts() -> dict:
    from evolu_tpu.obs import metrics

    return {k: metrics.get_counter(name) for k, name in _ROUTE_COUNTERS.items()}


def dump(db) -> dict:
    """The reference's dump of a worker's database."""
    reference = load_module("reference", "client_todo")
    return reference.dump(
        lambda sql: [tuple(r.values()) for r in db.exec_sql_query(sql)], gen_client.TABLES)


def setup(cfg: dict, seed: int, scratch: str) -> dict:
    observe.assert_native()
    t0 = time.monotonic()
    messages = gen_client.build_messages(
        cfg["messages"], seed, cfg["rows_per_table"], cfg["nodes"])
    t1 = time.monotonic()
    wires = gen_client.build_responses(messages, cfg["responses"], gen_client.MNEMONIC)
    t2 = time.monotonic()
    state = {
        "cfg": cfg, "seed": seed, "mnemonic": gen_client.MNEMONIC,
        "messages": messages, "wires": wires, "restores": [], "warm_restores": 0,
        "timings": {"history_s": round(t1 - t0, 3), "responses_s": round(t2 - t1, 3),
                    "wire_bytes": sum(len(w) for w in wires)},
    }
    state["restore"] = lambda: restore(state)
    return state


def restore(state: dict) -> dict:
    """One device restored from its mnemonic → its record. Only what a
    restore is runs here; the record's error is read from what the
    worker itself reported (an `OnError`, a sync request because its
    tree differs from the relay's, a packed batch bounced to the object
    path), and the database stays open for the check."""
    from evolu_tpu.core.types import TableDefinition
    from evolu_tpu.runtime import messages as rmsg
    from evolu_tpu.runtime.worker import DbWorker
    from evolu_tpu.storage.native import open_database
    from evolu_tpu.sync import native_crypto
    from evolu_tpu.utils.config import Config

    cfg, mnemonic = state["cfg"], state["mnemonic"]
    outputs, pushes, routes, error = [], [], [], None
    db = open_database(cfg["store"], backend="native")
    worker = DbWorker(
        db, Config(backend=cfg["backend"]), on_output=outputs.append,
        post_sync=pushes.append,
        now=itertools.count(cfg["now_millis"], cfg["now_step_millis"]).__next__)
    record = {"db": db, "worker": worker, "routes": routes,
              "messages": 0, "error": None}
    state["restores"].append(record)
    worker.start(mnemonic)
    worker.post(rmsg.UpdateDbSchema(tuple(
        TableDefinition.of(t, cols) for t, cols in gen_client.TABLES)))
    before = _route_counts()
    for wire in state["wires"]:
        decoded = native_crypto.decrypt_response_columns(wire, mnemonic)
        if decoded is None:
            error = "decrypt_response_columns gave no PackedReceive"
            break
        packed, tree = decoded
        worker.post(rmsg.Receive(packed, tree, None))
        worker.flush()
        after = _route_counts()
        routes.append("stream" if after["streamed"] > before["streamed"] else
                      "cached" if after["hits"] + after["seeded"] >
                      before["hits"] + before["seeded"] else "none")
        if after["bounces"] > before["bounces"]:
            error = error or "a Receive bounced to the object path"
        before = after
        record["messages"] += len(packed)
    errors = [o.error for o in outputs if isinstance(o, rmsg.OnError)]
    if errors:
        error = f"OnError: {errors[0]!r}"
    elif pushes:
        error = error or "the client's tree differs from the relay's: it asked to sync again"
    elif sum(isinstance(o, rmsg.OnReceive) for o in outputs) != len(routes):
        error = error or "a Receive was not acknowledged with OnReceive"
    record["error"] = error
    return record


def warm(state: dict, params: dict) -> None:
    """Whole restores, untimed: the streamed plan, the seed and the
    cached plan are compiled or read from the cache here."""
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
    counted = state["restores"][state["warm_restores"]:]
    assert len(counted) == outcome["attempted"], "restores kept != restores attempted"

    # The plain reference on the same messages, response by response.
    reference = load_module("reference", cfg["reference"])
    t0 = time.monotonic()
    twin = reference.ReferenceClient(gen_client.TABLES, state["mnemonic"])
    try:
        messages = state["messages"]
        batches = gen_client.split_responses(messages, cfg["responses"])
        for k, batch in enumerate(batches):
            twin.receive(
                [(m.timestamp, m.table, m.row, m.column, m.value) for m in batch],
                cfg["now_millis"] + k * cfg["now_step_millis"])
        want = twin.dump()
    finally:
        twin.close()
    state["timings"]["reference_s"] = round(time.monotonic() - t0, 3)
    assert len(want["__message"]) == len(messages), "the history holds a timestamp twice"

    routes = ["stream"] * 2 + ["cached"] * (cfg["responses"] - 2)
    full = {0, len(counted) - 1}  # the first and the last counted restore
    for i, record in enumerate(counted):
        assert record["routes"] == routes, f"restore {i}: routes {record['routes']}"
        db = record["db"]
        if i in full:
            got = dump(db)
            for key in want:
                assert got[key] == want[key], f"restore {i}: {key} != the reference's"
            continue
        clock = db.exec_sql_query('SELECT "timestamp", "merkleTree" FROM "__clock"')
        assert [(r["timestamp"][:29], r["merkleTree"]) for r in clock] == want["__clock"], \
            f"restore {i}: clock or tree != the reference's"
        for table in ("__message", *(t for t, _cols in gen_client.TABLES)):
            rows = db.exec_sql_query(f'SELECT COUNT(*) AS n FROM "{table}"')[0]["n"]
            assert rows == len(want[table]), f"restore {i}: {rows} rows in {table}"
    # Slots in HBM == SQLite's newest timestamp, for every cell the
    # cached Receives touched, in every worker, the warm-up's too.
    slots = len({(m.table, m.row, m.column) for batch in batches[2:] for m in batch})
    for i, record in enumerate(state["restores"]):
        audited = record["worker"].verify_winner_cache()
        assert audited == slots, f"worker {i}: {audited} winner slots audited, not {slots}"
    packed = observe.counters("evolu_apply_batches_total")
    assert packed == {"route=packed": len(state["restores"]) * cfg["responses"]}, \
        f"not every Receive took the packed route: {packed}"
    observe.assert_no_fallback()
    observe.assert_pallas_route()
    return True


def close(state: dict) -> None:
    for record in state["restores"]:
        record["worker"].stop()
        record["db"].close()
