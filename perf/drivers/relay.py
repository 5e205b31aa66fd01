"""Driver of a served relay: one process that holds the chip, a native
sharded store preloaded through the batch engine, and
`RelayServer` with its batching `SyncScheduler` on a local port.

Set-up, warm-up and the check are copied from `chip_smoke.relay_phase`;
the timed window sits between them and belongs to the traffic module.
"""

import random
import time

from perf import gen, observe


def _chunks(requests, batches: int):
    per = -(-len(requests) // batches)
    return [requests[i:i + per] for i in range(0, len(requests), per)]


def _owner_dump(store, owner: str):
    """(message rows, merkleTree row) of one owner, comparable across
    storage backends."""
    return (
        store.db.exec_sql_query(
            'SELECT "timestamp", "userId", "content" FROM "message" '
            'WHERE "userId" = ? ORDER BY "timestamp"', (owner,)),
        store.db.exec_sql_query(
            'SELECT "userId", "merkleTree" FROM "merkleTree" WHERE "userId" = ?',
            (owner,)),
    )


def _stored_rows(store) -> int:
    return sum(s.stats()[0]["messages"] for s in store.shards)


def setup(cfg: dict, seed: int, scratch: str) -> dict:
    from evolu_tpu.parallel.mesh import create_mesh
    from evolu_tpu.server.engine import BatchReconciler
    from evolu_tpu.server.relay import RelayServer, ShardedRelayStore
    from evolu_tpu.server.scheduler import SyncScheduler

    observe.assert_native()
    t0 = time.monotonic()
    pool = gen.ciphertext_pool(cfg["ciphertext_pool"])
    t1 = time.monotonic()
    requests = gen.build_requests(cfg["messages"], cfg["owners"], seed, pool)
    t2 = time.monotonic()
    preload = sum(len(r.messages) for r in requests)
    chunks = _chunks(requests, cfg["preload_batches"])

    store = ShardedRelayStore(":memory:", "native", shards=cfg["storage_shards"])
    engine = BatchReconciler(store, mesh=create_mesh(1))
    responses = engine.reconcile(chunks[0])
    if len(chunks) > 1:
        responses += engine.reconcile(chunks[1])
    responses += [r for batch in engine.reconcile_stream(chunks[2:]) for r in batch]
    engine.close()
    assert len(responses) == len(requests)
    for req, resp in zip(requests, responses):
        assert resp.messages == (), f"preload answered rows for {req.user_id}"
        assert resp.merkle_tree == req.merkle_tree, f"tree != host fold: {req.user_id}"
    assert _stored_rows(store) == preload
    t3 = time.monotonic()

    # Roles by seed: the owners that push, the owners the warm-up pushes
    # to (never among the first), and quiet owners the check replays.
    limit, quiet = cfg["connection_limit"], cfg["quiet_sample"]
    order = random.Random(seed).sample(range(len(requests)), 2 * limit + quiet)
    # The scheduler's settings are the configuration's (the program's
    # defaults today), so that the file says what was run.
    scheduler = SyncScheduler(store, **cfg["scheduler"])
    server = RelayServer(store, scheduler=scheduler).start()
    return {
        "cfg": cfg, "seed": seed, "scratch": scratch, "pool": pool,
        "store": store, "server": server, "url": server.url,
        "preload_rows": preload, "warm_rows": 0,
        "timings": {"pool_s": round(t1 - t0, 3), "requests_s": round(t2 - t1, 3),
                    "preload_s": round(t3 - t2, 3)},
        # past every preloaded timestamp, as a client's clock would be
        "push_base_millis": gen.BASE_MILLIS + cfg["messages"] // 16 + 60_000,
        "client_requests": [requests[i] for i in order[:limit]],
        "warm_requests": [requests[i] for i in order[limit:2 * limit]],
        "quiet_requests": [requests[i] for i in order[2 * limit:]],
    }


def warm(state: dict, params: dict) -> None:
    """Every row bucket the window can produce goes once through the
    engine pass the scheduler runs (`run_batch_wire` on the same mesh, so
    the same compiled programs): k coalesced requests carry k x msgs
    rows, padded to a power-of-two bucket."""
    from evolu_tpu.ops import bucket_size
    from evolu_tpu.server.engine import BatchReconciler
    from evolu_tpu.sync import protocol

    t0 = time.monotonic()
    msgs, clients = params["msgs_per_round"], params["clients"]
    warmers = [
        gen.PushClient(i, r.user_id, r.merkle_tree, state["push_base_millis"],
                       msgs, state["pool"], kind="a")
        for i, r in enumerate(state["warm_requests"][:clients])]
    first_k = {}
    for k in range(1, clients + 1):
        first_k.setdefault(bucket_size(max(1, k * msgs)), k)
    engine = BatchReconciler(state["store"])
    for _bucket, k in sorted(first_k.items()):
        batch = [w.next_request() for w in warmers[:k]]
        answers = engine.run_batch_wire(batch)
        for w, wire in zip(warmers[:k], answers):
            resp = protocol.decode_sync_response(wire)
            assert resp.messages == () and resp.merkle_tree == w.tree_string, \
                f"warm-up answer != host fold: {w.owner}"
        state["warm_rows"] += k * msgs
    engine.close()
    state["timings"].update(warm_buckets=sorted(first_k),
                            warm_s=round(time.monotonic() - t0, 3))


def _cold_sync(url: str, owner: str) -> list:
    """A restored device (another node, empty tree) pulls the owner's
    whole history over HTTP → its timestamps."""
    from evolu_tpu.sync import protocol

    body = protocol.encode_sync_request(
        protocol.SyncRequest((), owner, "e" * 16, "{}"))
    answer = protocol.decode_sync_response(gen.http_post(url, body, 120))
    return [m.timestamp for m in answer.messages]


def check(state: dict, outcome: dict) -> bool:
    from evolu_tpu.server.relay import RelayStore

    cfg, store = state["cfg"], state["store"]
    assert outcome["failed"] == 0, f"{outcome['failed']} failed rounds: {outcome['errors']}"
    assert outcome["attempted"] > 0, "no round was answered inside the window"
    # Every acknowledged message is stored: nothing lost, nothing twice.
    stored = _stored_rows(store)
    want = state["preload_rows"] + state["warm_rows"] + outcome["acked_msgs_total"]
    assert stored == want, f"stored rows {stored} != preload + warm-up + acknowledged {want}"
    # ... and is read back by a cold sync, for a sample of the pushers:
    # the preloaded history plus every acknowledged timestamp, which
    # follow from the seed and the client's round count.
    by_owner = {r.user_id: r for r in state["client_requests"]}
    for c in outcome["clients"][:cfg["cold_sync_sample"]]:
        client = gen.PushClient(c["slot"], c["owner"], "{}", state["push_base_millis"],
                                outcome["msgs_per_round"], state["pool"])
        pushed = [t for r in range(c["acked_rounds"]) for t in client.timestamps(r)]
        history = [m.timestamp for m in by_owner[c["owner"]].messages]
        got = _cold_sync(state["url"], c["owner"])
        assert sorted(got) == sorted(history + pushed), \
            f"cold sync != preload + acknowledged: {c['owner']}"
    # Owners that did not push: full dump against a fresh single
    # RelayStore (stdlib SQLite, pure-Python hashing) replaying their
    # preload request through RelayStore.sync.
    twin = RelayStore(":memory:", "python")
    try:
        for req in state["quiet_requests"]:
            twin.sync(req)
            assert _owner_dump(store.shard_of(req.user_id), req.user_id) == \
                _owner_dump(twin, req.user_id), f"dump != RelayStore.sync twin: {req.user_id}"
    finally:
        twin.close()
    observe.assert_no_fallback()
    observe.assert_pallas_route()
    return True


def close(state: dict) -> None:
    state["server"].stop()  # closes the store
