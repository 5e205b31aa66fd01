"""Driver of a relay host brought up from a backlog on a device mesh:
one process that holds the host's chips, one `MeshContext` over them
for the whole process, and in it one backfill after another, each a
fresh native sharded store fed the whole log through
`BatchReconciler(store, mesh_ctx=ctx).reconcile_stream`, pass by pass.

Set-up builds the log once (`perf/gen.py::build_requests`: one
steady-state `SyncRequest` an owner) and cuts it into the
configuration's passes; a backfill is `state["restore"]()`, which the
traffic module calls back to back. Every counted backfill's store and
responses are kept until the check, which compares them with the
requests, with `perf/reference/relay_sync.py` and over HTTP outside
the window; `close` closes them. That is the program's normal offline
entry point, the one `relay-reference`'s preload uses; the driver sets
nothing of the process (no thread, option, environment variable or
allocator setting of its own).
"""

import random
import time

from perf import gen, load_module, observe


# The cut into passes and the row count are the served relay's driver's
# own functions, not copies.
_relay = load_module("drivers", "relay")
_chunks, _stored_rows = _relay._chunks, _relay._stored_rows


def _mesh_reading() -> dict:
    """What the program counted of its sharded dispatches so far."""
    from evolu_tpu.obs import metrics

    rows = metrics.registry.get_histogram("evolu_mesh_shard_rows")
    _edges, cumulative, _sum, count = rows or ((), [0], 0.0, 0)
    return {
        "dispatches": metrics.get_counter("evolu_mesh_dispatches_total"),
        "device_readings": count,
        # the first bucket holds the readings of 0 or 1 row: a device
        # the dispatch left empty
        "empty_devices": cumulative[0],
        "upload": observe.counters("evolu_engine_compact_upload_bytes_total"),
        "store_passes": observe.counters("evolu_engine_store_passes_total"),
    }


def _since(before, after):
    """`after - before` of one reading: a number, or the labels of a
    counter family that moved."""
    if isinstance(after, dict):
        moved = {k: v - before.get(k, 0) for k, v in after.items()}
        return {k: v for k, v in moved.items() if v}
    return after - before


def setup(cfg: dict, seed: int, scratch: str) -> dict:
    from evolu_tpu.parallel.mesh import get_mesh_context

    observe.assert_native()
    t0 = time.monotonic()
    pool = gen.ciphertext_pool(cfg["ciphertext_pool"])
    t1 = time.monotonic()
    requests = gen.build_requests(cfg["messages"], cfg["owners"], seed, pool)
    t2 = time.monotonic()
    # One context for the whole process, taken as the relay's own wiring
    # takes it, so every backfill runs the same compiled programs.
    ctx = get_mesh_context(cfg["mesh_devices"])
    assert ctx.n_shards == cfg["mesh_devices"], \
        f"the process's mesh has {ctx.n_shards} devices, not {cfg['mesh_devices']}"
    # Roles by seed: the owners the reference replays, the owners a
    # cold sync pulls whole.
    order = random.Random(seed).sample(
        range(len(requests)), cfg["reference_owners"] + cfg["cold_sync_sample"])
    chunks = _chunks(requests, cfg["passes"])
    state = {
        "cfg": cfg, "seed": seed, "ctx": ctx, "requests": requests, "chunks": chunks,
        "reference_requests": [requests[i] for i in order[:cfg["reference_owners"]]],
        "cold_sync_requests": [requests[i] for i in order[cfg["reference_owners"]:]],
        "backfills": [], "server": None, "mesh_before": _mesh_reading(),
        "timings": {"pool_s": round(t1 - t0, 3), "requests_s": round(t2 - t1, 3),
                    "pass_rows": [sum(len(r.messages) for r in c) for c in chunks]},
    }
    state["restore"] = lambda: backfill(state)
    return state


def backfill(state: dict) -> dict:
    """One relay brought up from the log → its record. Only what a
    backfill is runs here: an empty store, the engine on the process's
    mesh, the passes as one pipelined stream, the engine's close. The
    record counts the messages of the passes that were answered; the
    store and the responses stay for the check."""
    from evolu_tpu.server.engine import BatchReconciler
    from evolu_tpu.server.store import ShardedRelayStore

    cfg, chunks = state["cfg"], state["chunks"]
    store = ShardedRelayStore(cfg["store"], "native", shards=cfg["storage_shards"])
    record = {"store": store, "responses": [], "messages": 0, "error": None}
    state["backfills"].append(record)
    engine = BatchReconciler(store, mesh_ctx=state["ctx"])
    try:
        record["responses"] = engine.reconcile_stream(chunks)
    except Exception as e:  # noqa: BLE001 - the record carries it to `failed`
        record["error"] = f"{type(e).__name__}: {e}"
    finally:
        engine.close()
    record["messages"] = sum(
        len(r.messages) for chunk, _answers in zip(chunks, record["responses"]) for r in chunk)
    if record["error"] is None and len(record["responses"]) != len(chunks):
        record["error"] = f"{len(record['responses'])} of {len(chunks)} passes were answered"
    return record


def _check_counts(state: dict, record: dict, what: str) -> None:
    """Every backfill: nothing lost, nothing twice, every answer empty
    with the request's own tree, every stored tree the request's."""
    requests = state["requests"]
    assert record["error"] is None, f"{what}: {record['error']}"
    responses = [r for answers in record["responses"] for r in answers]
    assert len(responses) == len(requests), f"{what}: {len(responses)} answers"
    for req, resp in zip(requests, responses):
        assert resp.messages == (), f"{what}: rows answered for {req.user_id}"
        assert resp.merkle_tree == req.merkle_tree, f"{what}: tree != host fold: {req.user_id}"
    stored, want = _stored_rows(record["store"]), sum(len(r.messages) for r in requests)
    assert stored == want, f"{what}: {stored} rows stored, not {want}"
    assert dict(record["store"].owner_trees()) == \
        {r.user_id: r.merkle_tree for r in requests}, f"{what}: a stored tree != the request's"


def _check_table(state: dict, store, what: str) -> None:
    """The full `message` table against the requests' messages, row for
    row, shard by shard (an owner's rows live in one shard)."""
    if "want_rows" not in state:  # a million tuples, built once a check
        state["want_rows"] = {
            r.user_id: [(m.timestamp, r.user_id, m.content) for m in r.messages]
            for r in state["requests"]}
    want = state["want_rows"]
    seen = set()
    for shard in store.shards:
        rows = shard.db.exec_sql_query(
            'SELECT "timestamp", "userId", "content" FROM "message" '
            'ORDER BY "userId", "timestamp"')
        by_owner = {}
        for row in rows:
            by_owner.setdefault(row["userId"], []).append(tuple(row.values()))
        for owner, got in by_owner.items():
            assert owner not in seen, f"{what}: {owner} is stored in two shards"
            assert got == want.get(owner), f"{what}: {owner}'s rows != its request's"
        seen.update(by_owner)
    assert seen == set(want), f"{what}: {len(seen)} owners stored, not {len(want)}"


def _check_reference(state: dict, store, what: str) -> None:
    """A sample of owners replayed through the plain reference: their
    `message` rows and `merkleTree` row byte-identical."""
    reference = load_module("reference", state["cfg"]["reference"])
    twin = reference.ReferenceRelay()
    try:
        for req in state["reference_requests"]:
            answer, tree = twin.sync(
                req.user_id, req.node_id, [(m.timestamp, m.content) for m in req.messages],
                req.merkle_tree)
            assert answer == [] and tree == req.merkle_tree, \
                f"the reference's own answer differs for {req.user_id}"
            db = store.shard_of(req.user_id).db
            got = reference.owner_dump(
                lambda sql, args: [tuple(r.values()) for r in db.exec_sql_query(sql, args)],
                req.user_id)
            assert got == twin.owner_dump(req.user_id), \
                f"{what}: dump != the reference's: {req.user_id}"
    finally:
        twin.close()


def _check_cold_sync(state: dict, store) -> None:
    """A restored device (another node, empty tree) pulls an owner's
    whole history over HTTP from a relay on that store, whose scheduler
    holds the process's mesh."""
    from evolu_tpu.server.relay import RelayServer
    from evolu_tpu.sync import protocol

    state["server"] = server = RelayServer(store, mesh_ctx=state["ctx"]).start()
    for req in state["cold_sync_requests"]:
        body = protocol.encode_sync_request(
            protocol.SyncRequest((), req.user_id, "e" * 16, "{}"))
        answer = protocol.decode_sync_response(gen.http_post(server.url, body, 120))
        assert answer.messages == req.messages and answer.merkle_tree == req.merkle_tree, \
            f"cold sync != the owner's history: {req.user_id}"


def warm(state: dict, params: dict) -> None:
    """Whole backfills, untimed and checked like the counted ones: the
    mesh's program at the pass's bucket is compiled or read from the
    cache here. Their stores are closed at once."""
    t0 = time.monotonic()
    for i in range(params["warm_backfills"]):
        record = backfill(state)
        _check_counts(state, record, f"warm-up backfill {i}")
        record["store"].close()
        record["store"] = None
    state["warm_backfills"] = len(state["backfills"])
    state["timings"]["warm_s"] = round(time.monotonic() - t0, 3)


def check(state: dict, outcome: dict) -> bool:
    cfg = state["cfg"]
    assert outcome["attempted"] > 0, "no backfill ran inside the window"
    assert outcome["failed"] == 0, f"{outcome['failed']} failed backfills: {outcome['errors']}"
    counted = state["backfills"][state["warm_backfills"]:]
    assert len(counted) == outcome["attempted"], "backfills kept != backfills attempted"
    assert outcome["window_compiles"] == 0, \
        f"{outcome['window_compiles']} compiles inside the window"

    # From the program's counters, before the check's own relay runs a
    # pass: every dispatch since set-up was a backfill's pass, put rows
    # on every device, and landed through the packed ingest.
    mesh = {k: _since(state["mesh_before"][k], v) for k, v in _mesh_reading().items()}
    passes = len(state["backfills"]) * len(state["chunks"])
    assert mesh["dispatches"] == passes, f"{mesh['dispatches']} dispatches, not {passes}"
    assert mesh["device_readings"] == passes * cfg["mesh_devices"], \
        f"{mesh['device_readings']} device readings in {passes} dispatches"
    assert mesh["empty_devices"] == 0, \
        f"{mesh['empty_devices']} times a dispatch left a device without rows"
    assert mesh["store_passes"] == {"path=stream": passes}, \
        f"not every pass took the packed ingest: {mesh['store_passes']}"
    outcome["upload_variant"] = " ".join(sorted(mesh["upload"]))
    outcome["upload_bytes_pass"] = sum(mesh["upload"].values()) / passes

    t0 = time.monotonic()
    full = {0, len(counted) - 1}  # the first and the last counted backfill
    for i, record in enumerate(counted):
        _check_counts(state, record, f"backfill {i}")
        if i in full:
            _check_table(state, record["store"], f"backfill {i}")
            _check_reference(state, record["store"], f"backfill {i}")
    _check_cold_sync(state, counted[-1]["store"])
    outcome["check_s"] = round(time.monotonic() - t0, 3)
    observe.assert_no_fallback()
    observe.assert_pallas_route()
    return True


def close(state: dict) -> None:
    server = state["server"]
    if server is not None:
        server.stop()  # closes its store
    for record in state["backfills"]:
        if record["store"] is not None and (server is None or record["store"] is not server.store):
            record["store"].close()
