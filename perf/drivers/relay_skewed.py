"""Driver of a shared relay under a skewed mix: `relay-reference`'s
process (a native sharded store preloaded through the batch engine,
`RelayServer` with its batching `SyncScheduler` on a local port) holding
many owners, each with a short history, whose devices pull and send
one-field updates (`perf/gen_mix.py`).

Set-up is the served relay's driver's (`perf/drivers/relay.py`), with
every preload request kept: any owner may be drawn. The warm-up runs the
row buckets the mix can produce and one answer with messages; the check
holds every round of the log to guarantees (d)-(f) of the configuration
and a sample of owners to the plain reference
(`perf/reference/relay_sync.py`).
"""

import bisect
import random
import time

from perf import gen, gen_mix, load_module, observe

# The cut into passes, the row count and the cold sync are the served
# relay's driver's own functions, not copies.
_relay = load_module("drivers", "relay")
_chunks, _stored_rows, _cold_sync = _relay._chunks, _relay._stored_rows, _relay._cold_sync


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
    assert len(requests) == cfg["owners"], \
        f"{cfg['owners'] - len(requests)} owners drew no preloaded message"
    t2 = time.monotonic()
    preload = sum(len(r.messages) for r in requests)
    chunks = _chunks(requests, cfg["preload_batches"])

    store = ShardedRelayStore(cfg["store"], "native", shards=cfg["storage_shards"])
    stages_before = _stage_seconds()
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
    stages = {k: round(v - stages_before.get(k, 0.0), 3)
              for k, v in _stage_seconds().items() if v - stages_before.get(k, 0.0) > 0}

    scheduler = SyncScheduler(store, **cfg["scheduler"])
    server = RelayServer(store, scheduler=scheduler).start()
    return {
        "cfg": cfg, "seed": seed, "scratch": scratch, "pool": pool, "requests": requests,
        "store": store, "server": server, "url": server.url,
        "preload_rows": preload, "warm_rows": 0, "warm_log": [],
        "timings": {"pool_s": round(t1 - t0, 3), "requests_s": round(t2 - t1, 3),
                    "preload_s": round(t3 - t2, 3), "preload_stage_s": stages},
        # past every preloaded timestamp, as a device's clock would be
        "push_base_millis": gen.BASE_MILLIS + cfg["messages"] // 16 + 60_000,
    }


def _stage_seconds() -> dict:
    """Seconds the program has recorded in each `pass_*` stage so far:
    the preload's passes, stage by stage, go on the set-up line."""
    return {k.split("=", 1)[1]: v
            for k, v in observe.counters("evolu_stage_seconds_total").items()
            if k.startswith("stage=pass_")}


def warm(state: dict, params: dict) -> None:
    """Every row bucket the window can produce goes once through the
    engine pass the scheduler runs (`run_batch_wire`, so the same
    compiled programs): a pass of pulls only (no row, no device call),
    then u updates for the first u of every power-of-two bucket of
    u x msgs_per_update rows; then one answer with messages: a second
    device of an updated owner pulls what the first one wrote. The
    warm-up's rounds go to the log the check reads."""
    from evolu_tpu.ops import bucket_size
    from evolu_tpu.server.engine import BatchReconciler
    from evolu_tpu.sync import protocol

    t0 = time.monotonic()
    state["params"] = params  # the check's: the skew names the hottest owners
    msgs, clients = params["msgs_per_update"], params["clients"]
    requests, log = state["requests"], state["warm_log"]
    picked = random.Random(state["seed"]).sample(range(len(requests)), clients)

    def device(kind: str, index: int) -> gen_mix.Device:
        r = requests[index]
        return gen_mix.Device(
            r.user_id, gen_mix.device_node(kind, 0, index), r.merkle_tree,
            [m.timestamp for m in r.messages], state["push_base_millis"], msgs,
            state["pool"])

    warmers = [device("a", i) for i in picked]
    first_u = {}
    for u in range(1, clients + 1):
        first_u.setdefault(bucket_size(max(1, u * msgs)), u)
    engine = BatchReconciler(state["store"])

    def run_pass(devices, indexes, update: bool) -> list:
        t_send = time.monotonic()
        wires = engine.run_batch_wire([d.request(update) for d in devices])
        answers = [protocol.decode_sync_response(w) for w in wires]
        for d, i, answer in zip(devices, indexes, answers):
            assert d.merge(answer), f"warm-up: tree after the merge != the answer's: {d.owner}"
            log.append(gen_mix.round_record(d, i, update, t_send, time.monotonic(), answer))
        return answers

    for answer in run_pass(warmers, picked, update=False):
        assert answer.messages == (), "warm-up: a pull of a quiet owner answered rows"
    for _bucket, u in sorted(first_u.items()):
        for answer in run_pass(warmers[:u], picked[:u], update=True):
            assert answer.messages == (), "warm-up: an update of a lone device answered rows"
        state["warm_rows"] += u * msgs
    other = device("b", picked[0])
    (answer,) = run_pass([other], picked[:1], update=False)
    wrote = sorted(t for t in warmers[0].held if t.endswith(warmers[0].node))
    assert [m.timestamp for m in answer.messages] == wrote and wrote, \
        "warm-up: the second device did not pull what the first one wrote"
    engine.close()
    state["timings"].update(warm_buckets=sorted(first_u),
                            warm_s=round(time.monotonic() - t0, 3))


def check_holdings(rounds: list, preload_of, msgs: int) -> None:
    """Guarantees (d)-(f) from the log, for every owner in it. A round R
    of owner o is ok only if its device's tree after its merge was the
    answer's (d), so what the device then held IS what the relay held of
    o when it served R, and must lie between

        preload(o) + msgs x (updates of o acknowledged before R was sent,
                             and R's own)
    and preload(o) + msgs x (updates of o sent before R was answered):

    an update acknowledged before R left is in R's answer
    (read-your-owner's-writes, (e)), and nothing is there that no device
    had sent. Two rounds of one owner that overlap in time were in the
    relay's queue together: the bounds hold for both only if each was
    served whole, one after the other (f)."""
    by_owner = {}
    for r in rounds:
        assert r["ok"], f"a failed round in the log: {r}"
        by_owner.setdefault(r["owner"], []).append(r)
    for owner, mine in by_owner.items():
        updates = [r for r in mine if r["update"]]
        acked = sorted(r["t_done"] for r in updates)
        sent = sorted(r["t_send"] for r in updates)
        base = preload_of(owner)
        for r in mine:
            low = base + msgs * (bisect.bisect_left(acked, r["t_send"]) + bool(r["update"]))
            high = base + msgs * bisect.bisect_left(sent, r["t_done"])
            assert low <= r["held"] <= high, (
                f"owner {owner}: a round of {r['node']} held {r['held']} rows after its "
                f"merge, outside [{low}, {high}] (an acknowledged update is missing "
                f"from a later round, or a row nobody sent is there)")


def acknowledged(rounds: list, base_millis: int, msgs: int, pool) -> dict:
    """owner index → the messages of each of its acknowledged updates,
    regenerated from (device, update count)."""
    out = {}
    for r in rounds:
        if r["update"] and r["ok"]:
            out.setdefault(r["owner"], []).append(
                gen_mix.update_messages(r["node"], r["count"], base_millis, msgs, pool))
    return out


def check_reference(state: dict, indexes, acked: dict) -> None:
    """The owners' full dumps (`message` rows and `merkleTree`) against
    the plain reference after it has replayed the preload request and
    every acknowledged update (upstream's `addMessages`, a request at a
    time)."""
    reference = load_module("reference", state["cfg"]["reference"])
    twin = reference.ReferenceRelay()
    try:
        for index in indexes:
            req = state["requests"][index]
            for messages in (req.messages, *acked.get(index, [])):
                twin.add_messages(req.user_id, [(m.timestamp, m.content) for m in messages],
                                  twin.get_merkle_tree(req.user_id))
            db = state["store"].shard_of(req.user_id).db
            got = reference.owner_dump(
                lambda sql, args: [tuple(r.values()) for r in db.exec_sql_query(sql, args)],
                req.user_id)
            assert got == twin.owner_dump(req.user_id), \
                f"dump != the reference's: {req.user_id}"
    finally:
        twin.close()


def check(state: dict, outcome: dict) -> bool:
    cfg, store, seed = state["cfg"], state["store"], state["seed"]
    msgs = outcome["msgs_per_update"]
    assert outcome["failed"] == 0, f"{outcome['failed']} failed rounds: {outcome['errors']}"
    assert outcome["attempted"] > 0, "no round was answered inside the window"
    assert outcome["window_compiles"] == 0, \
        f"{outcome['window_compiles']} compiles inside the window"
    # (a)-(c): every acknowledged message is stored, nothing lost, nothing twice.
    stored = _stored_rows(store)
    want = state["preload_rows"] + state["warm_rows"] + outcome["acked_msgs_total"]
    assert stored == want, f"stored rows {stored} != preload + warm-up + acknowledged {want}"
    t0 = time.monotonic()
    # (d)-(f): every round of every owner touched, the warm-up's too.
    rounds = state["warm_log"] + outcome["rounds"]
    requests = state["requests"]
    check_holdings(rounds, lambda index: len(requests[index].messages), msgs)
    # The reference: the hottest ranks and touched owners by the seed.
    draw = gen_mix.OwnerDraw(len(requests), seed, state["params"]["zipf_theta"])
    hottest = draw.owner_of_rank[:cfg["reference_hottest"]]
    touched = sorted({r["owner"] for r in rounds} - set(hottest))
    sample = random.Random(seed).sample(touched, min(cfg["reference_touched"], len(touched)))
    acked = acknowledged(rounds, state["push_base_millis"], msgs, state["pool"])
    check_reference(state, [*hottest, *sample], acked)
    # ... and read back over HTTP by a device that holds nothing.
    for index in [*hottest[:cfg["cold_sync_sample"] // 2],
                  *sample[:cfg["cold_sync_sample"] - cfg["cold_sync_sample"] // 2]]:
        req = requests[index]
        history = [m.timestamp for m in req.messages]
        pushed = [m.timestamp for update in acked.get(index, []) for m in update]
        assert sorted(_cold_sync(state["url"], req.user_id)) == sorted(history + pushed), \
            f"cold sync != preload + acknowledged: {req.user_id}"
    outcome["check_s"] = round(time.monotonic() - t0, 3)
    observe.assert_no_fallback()
    observe.assert_pallas_route()
    return True


def close(state: dict) -> None:
    state["server"].stop()  # closes the store
