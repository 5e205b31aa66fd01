"""Randomized end-to-end model check: mixed-backend replicas, random
interleavings of mutations and sync rounds, an offline stretch, and a
late-joining replica restored from the mnemonic — everything through
the REAL client/relay/HTTP stack. The reference never tests any
multi-node story (SURVEY.md §4); this is the strongest integration
property: total byte-level convergence from arbitrary schedules.
"""

import random
import time
from contextlib import contextmanager

import pytest

from evolu_tpu.core.merkle import merkle_tree_to_string
from evolu_tpu.runtime.client import create_evolu
from evolu_tpu.server.relay import RelayServer, RelayStore, ShardedRelayStore
from evolu_tpu.storage.clock import read_clock
from evolu_tpu.sync.client import connect
from evolu_tpu.utils.config import Config

SCHEMA = {"todo": ("title", "isCompleted", "categoryId"), "todoCategory": ("name",)}


@contextmanager
def _evidence(label, seed):
    """Seed-replay evidence (ROADMAP #5): on assertion failure the
    episode dumps seed + flight-recorder ring + span export + metrics
    snapshot + conservation-ledger snapshot to a tmp artifact whose
    path rides the failure message — a failed seed arrives with its
    causal history, not just a stack.

    ISSUE 15: every episode is ALSO a conservation proof. The ledger
    resets at entry and, after the episode body finished (teardown
    included — quiescence), `ledger.audit()` must return ZERO violated
    equations: every message that entered any ingress reached exactly
    one terminal, on every route the episode exercised. Oracle-twin
    phases (reference replays, not traffic) run under
    `ledger.quarantine()`."""
    from evolu_tpu.obs import ledger

    ledger.reset()
    try:
        yield
    except AssertionError as e:
        from evolu_tpu.obs import trace

        path = trace.write_evidence(label, seed=seed)
        raise AssertionError(
            f"{e}\nseed={seed}; replay evidence artifact: {path}"
        ) from e
    violations = ledger.audit(at_barrier=True)
    if violations:
        from evolu_tpu.obs import trace

        path = trace.write_evidence(label + "-ledger", seed=seed)
        raise AssertionError(
            f"conservation ledger violated at episode end: {violations}\n"
            f"seed={seed}; replay evidence artifact: {path}"
        )


def _dump(evolu):
    return (
        evolu.db.exec('SELECT * FROM "__message" ORDER BY "timestamp"'),
        evolu.db.exec('SELECT * FROM "todo" ORDER BY "id"'),
        evolu.db.exec('SELECT * FROM "todoCategory" ORDER BY "id"'),
    )


def _converge(replicas, deadline_s=40.0):
    """Sync rounds until every replica's history is byte-identical."""
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        for r in replicas:
            r.sync()
            r.worker.flush()
        dumps = [r.db.exec('SELECT * FROM "__message" ORDER BY "timestamp"')
                 for r in replicas]
        if all(d == dumps[0] for d in dumps):
            return
        time.sleep(0.05)
    raise AssertionError("replicas did not converge in time")


@pytest.mark.parametrize("seed", [1234, 99, 7, 4242, 31337])
def test_randomized_mixed_backend_schedules_converge(seed):
    with _evidence("model-check", seed):
        _run_randomized_episode(seed)


def _run_randomized_episode(seed):
    rng = random.Random(seed)
    server = RelayServer(ShardedRelayStore(shards=4)).start()
    cfg = lambda **kw: Config(sync_url=server.url, **kw)  # noqa: E731
    a = create_evolu(SCHEMA, config=cfg(backend="tpu"))  # HBM winner cache
    b = create_evolu(SCHEMA, config=cfg(backend="cpu"), mnemonic=a.owner.mnemonic)
    c = create_evolu(SCHEMA, config=cfg(backend="auto", receive_chunk_size=40),
                     mnemonic=a.owner.mnemonic)
    # d routes receive batches >= 8 messages through the hot-owner
    # cell-range sharding over the 8-device virtual mesh (VERDICT r2
    # #5: a multi-device replica in the mix).
    d = create_evolu(SCHEMA, config=cfg(backend="auto", hot_owner_min_batch=8,
                                        min_device_batch=8),
                     mnemonic=a.owner.mnemonic)
    replicas = [a, b, c, d]
    late = None
    # Pin that the HBM-cache route actually planned batches (the cache
    # may legitimately be EMPTY at the end: a livelock SyncError resets
    # it — the phantom-winner defense this test exists to exercise).
    cache = a.worker._planner.cache
    cache_calls = []
    orig_plan = cache.plan_batch
    cache.plan_batch = lambda *args, **kw: (cache_calls.append(1), orig_plan(*args, **kw))[1]
    # Pin that the hot-owner route actually ran for d.
    from evolu_tpu.parallel import hot_owner as hot_mod

    hot_calls = []
    orig_hot = hot_mod.reconcile_hot_owner
    hot_mod.reconcile_hot_owner = (
        lambda *args, **kw: (hot_calls.append(1), orig_hot(*args, **kw))[1]
    )
    try:
        for r in replicas:
            connect(r)
        row_ids: list = []
        offline = {id(b): False}
        b_transport = b._transport

        for step in range(60):
            r = rng.choice(replicas)
            op = rng.random()
            if op < 0.45 or not row_ids:
                row_ids.append(r.create("todo", {
                    "title": f"t{step}", "isCompleted": False,
                }))
            elif op < 0.7:
                r.update("todo", rng.choice(row_ids), {
                    "title": f"edit{step}", "isCompleted": bool(rng.getrandbits(1)),
                })
            elif op < 0.8:
                r.update("todo", rng.choice(row_ids), {"isDeleted": True})
            else:
                r.create("todoCategory", {"name": f"cat{step}"})
            r.worker.flush()
            if step == 20:
                # b drops FULLY off the network: detaching the
                # transport makes every push a no-op (the reference's
                # offline-swallow model), not just the explicit syncs.
                offline[id(b)] = True
                b._transport = None
            if step == 40:
                offline[id(b)] = False  # and returns with local edits
                b.attach_transport(b_transport)
            if rng.random() < 0.4:
                s = rng.choice(replicas)
                if not offline.get(id(s), False):
                    s.sync()
                    s.worker.flush()

        # Deterministically engage d's hot-owner route before the
        # convergence phase: ONE batched mutation (a single Send, a
        # single relay push) lands >= 18 messages atomically, so d's
        # next pull receives them as one batch above
        # hot_owner_min_batch. Unbatched creates push per-Send and a
        # racing pull can see them in dribbles — found by a 20-seed
        # sweep.
        with a.batching():
            for j in range(6):
                a.create("todo", {"title": f"hot{j}"})
        a.worker.flush()

        _converge(replicas)

        # A brand-new device restores from the mnemonic and must pull
        # the ENTIRE history (SURVEY.md §3.5).
        late = create_evolu(SCHEMA, config=cfg(backend="cpu"),
                            mnemonic=a.owner.mnemonic)
        connect(late)
        replicas.append(late)
        _converge(replicas)

        dumps = [_dump(r) for r in replicas]
        assert all(d == dumps[0] for d in dumps), "state diverged"
        # NB: cross-replica MERKLE TREE equality is deliberately NOT
        # asserted. The reference XORs a re-received non-winning
        # duplicate into the tree again (applyMessages.ts:104-122 — the
        # quirk merge.py reproduces), so under anti-entropy redelivery
        # the tree depends on each replica's delivery history, not just
        # the converged message set; the reference surfaces the
        # consequence as the SyncError livelock guard, which this
        # schedule can legitimately trip. Data convergence above is the
        # CRDT guarantee.
        assert cache_calls, "tpu replica's cache never engaged"
        assert hot_calls, "hot-owner multi-device planner never engaged"
    finally:
        hot_mod.reconcile_hot_owner = orig_hot
        for r in replicas:
            r.dispose()
        server.stop()


def test_adversarial_clocks_through_two_relay_fleet_converge():
    """ROADMAP #5's named gap, small dose: regressing/stuttering HLC
    `now` schedules have only ever run against the pure timestamp unit
    tests — here one seeded schedule drives them through an end-to-end
    2-relay FLEET episode (placement ring, 307 redirects, learned
    client routes — server/fleet.py), asserting byte-identical
    convergence AND the winner-cache == MAX(timestamp) invariant on
    the device-backend replica."""
    with _evidence("model-check-adversarial-clocks", 20240731):
        _run_adversarial_clock_episode()


def _run_adversarial_clock_episode():
    import numpy as np

    from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
    from evolu_tpu.obs import metrics
    from evolu_tpu.utils.config import FleetConfig

    seed = 20240731
    rng = random.Random(seed)
    base = int(time.time() * 1000)

    def adversarial_now(sub_seed):
        """Deterministic hostile wall clock: 40% frozen (stuttering —
        the HLC counter must absorb it), 20% regressing (bounded well
        under max_drift so the schedule stays in the legal envelope:
        total advance <= 60*500ms + regression floor 20s < 60s drift),
        else small advances."""
        r = random.Random(sub_seed)
        state = {"t": base}

        def now():
            roll = r.random()
            if roll < 0.4:
                pass  # stutter: frozen clock
            elif roll < 0.6:
                state["t"] = max(base - 20_000,
                                 state["t"] - r.randrange(0, 10_000))
            else:
                state["t"] += r.randrange(1, 500)
            return state["t"]

        return now

    a = RelayServer(RelayStore(), peers=[], replication_interval_s=30).start()
    b = RelayServer(RelayStore(), peers=[], replication_interval_s=30).start()
    # R=1: the shared owner has ONE authoritative relay; clients
    # pointed at the other must learn the route through a live 307.
    fleet_cfg = FleetConfig(relays=(a.url, b.url), replication_factor=1,
                            version=1)
    a.enable_fleet(fleet_cfg)
    b.enable_fleet(fleet_cfg)
    replicas = []
    try:
        # One device-backend replica (HBM winner cache engaged) homed
        # at relay a, one cpu replica at relay b: exactly one of them
        # starts on the wrong side of the ring.
        r1 = create_evolu(SCHEMA, config=Config(sync_url=a.url, backend="tpu"))
        r2 = create_evolu(SCHEMA, config=Config(sync_url=b.url, backend="cpu"),
                          mnemonic=r1.owner.mnemonic)
        replicas = [r1, r2]
        for i, r in enumerate(replicas):
            r.worker.now = adversarial_now(seed + i)
            connect(r)
        redirects_before = metrics.get_counter("evolu_sync_redirects_total")
        row_ids = []
        for step in range(60):
            r = rng.choice(replicas)
            if rng.random() < 0.5 or not row_ids:
                row_ids.append(r.create("todo", {
                    "title": f"adv{step}", "isCompleted": False,
                }))
            else:
                r.update("todo", rng.choice(row_ids), {
                    "title": f"advedit{step}",
                    "isCompleted": bool(rng.getrandbits(1)),
                })
            r.worker.flush()
            if rng.random() < 0.5:
                s = rng.choice(replicas)
                s.sync()
                s.worker.flush()
        _converge(replicas)
        # Quiesce BOTH loops before reading HBM cache arrays: a sync
        # round still in flight on the transport thread would plan a
        # batch concurrently, DONATING the very buffers this test is
        # about to read (donated jax arrays read as deleted).
        for r in replicas:
            r._transport.flush()
            r.worker.flush()
        dumps = [_dump(r) for r in replicas]
        assert dumps[0] == dumps[1], "state diverged under adversarial clocks"
        # The fleet was actually exercised: the replica homed at the
        # non-primary relay followed at least one 307 and cached the
        # route to the primary.
        assert metrics.get_counter(
            "evolu_sync_redirects_total") > redirects_before
        primary = a if a.fleet.ring.primary(r1.owner.id) == a.url else b
        assert primary.store.user_ids() == [r1.owner.id]
        other = b if primary is a else a
        assert other.store.user_ids() == []  # R=1: partitioned, not mirrored
        # Winner-cache == MAX(timestamp) per cell on the device
        # replica (CLAUDE.md invariant), read straight out of the HBM
        # slot arrays.
        cache = r1.worker._planner.cache
        w1 = np.asarray(cache._w1)
        w2 = np.asarray(cache._w2)
        checked = 0
        for (table, row, col), slot in cache._slots.items():
            got = r1.db.exec_sql_query(
                'SELECT MAX("timestamp") AS m FROM "__message" '
                'WHERE "table" = ? AND "row" = ? AND "column" = ?',
                (table, row, col),
            )[0]["m"]
            k1, k2 = int(w1[slot]), int(w2[slot])
            if k1 == 0 and k2 == 0:
                assert got is None, (table, row, col)
                continue
            cached_ts = timestamp_to_string(
                Timestamp(k1 >> 16, k1 & 0xFFFF, f"{k2:016x}")
            )
            assert cached_ts == got, (table, row, col)
            checked += 1
        # A livelock SyncError reset can legitimately empty the cache;
        # but the schedule above must at least have ENGAGED it.
        assert cache._slots or checked == 0
    finally:
        for r in replicas:
            r.dispose()
        a.stop()
        b.stop()


@pytest.mark.parametrize("seed,crash_at", [(5, 1), (11, 2), (47, 3)])
def test_crash_mid_chunked_receive_restart_converges(tmp_path, seed, crash_at):
    """Crash injection (VERDICT r2 #5): a replica pulling a large
    history in chunks dies at the Nth per-chunk clock persist — the
    crashing chunk's transaction rolls back, earlier chunks stay
    committed (rows + clock atomic per chunk). A RESTARTED process
    over the same database file must resume from the persisted clock
    and converge to byte-identical state."""
    with _evidence("model-check-crash-restart", seed):
        _run_crash_restart_episode(tmp_path, seed, crash_at)


def _run_crash_restart_episode(tmp_path, seed, crash_at):
    from evolu_tpu.runtime.client import Evolu
    import evolu_tpu.runtime.worker as worker_mod

    rng = random.Random(seed)
    server = RelayServer(ShardedRelayStore(shards=2)).start()
    src = vic = vic2 = None
    real_update = worker_mod.update_clock
    try:
        cfg = Config(sync_url=server.url)
        src = create_evolu(SCHEMA, config=cfg)
        connect(src)
        for i in range(rng.randrange(100, 140)):
            src.create("todo", {"title": f"t{i}", "isCompleted": bool(i % 2)})
        src.worker.flush()
        src.sync()
        src.worker.flush()
        src._transport.flush()

        # Victim: chunked receive (several 50-message chunks), crash
        # injected at the crash_at-th per-chunk clock persist.
        vic_path = str(tmp_path / "victim.db")
        vcfg = Config(sync_url=server.url, receive_chunk_size=50)
        vic = Evolu(db_path=vic_path, config=vcfg, mnemonic=src.owner.mnemonic)
        vic.update_db_schema(SCHEMA)
        calls = {"n": 0}

        def crashing_update(db, clock, *slot):
            calls["n"] += 1
            if calls["n"] == crash_at:
                raise RuntimeError("injected crash: died before clock persist")
            return real_update(db, clock, *slot)

        worker_mod.update_clock = crashing_update
        errors = []
        vic.subscribe_error(errors.append)
        connect(vic)
        deadline = time.time() + 20
        while time.time() < deadline and not errors:
            vic.sync()
            vic.worker.flush()
            vic._transport.flush()
            vic.worker.flush()
            time.sleep(0.02)
        assert errors, "injected crash never fired"
        worker_mod.update_clock = real_update

        partial = vic.db.exec('SELECT COUNT(*) FROM "__message"')[0][0]
        total = src.db.exec('SELECT COUNT(*) FROM "__message"')[0][0]
        if crash_at == 1:
            # Dying at the FIRST per-chunk clock persist rolls that
            # whole chunk back: the crash leaves a clean zero state,
            # and restart re-syncs from scratch.
            assert partial == 0, (partial, total)
        else:
            assert 0 < partial < total, (partial, total)
        # The committed prefix must be digest-coherent: the persisted
        # tree covers exactly the stored rows (resume invariant).
        from evolu_tpu.core.merkle import (
            create_initial_merkle_tree, insert_into_merkle_tree,
        )
        from evolu_tpu.core.timestamp import timestamp_from_string

        clock = read_clock(vic.db)
        expect = create_initial_merkle_tree()
        for (ts,) in vic.db.exec('SELECT "timestamp" FROM "__message" ORDER BY "timestamp"'):
            expect = insert_into_merkle_tree(timestamp_from_string(ts), expect)
        assert merkle_tree_to_string(clock.merkle_tree) == merkle_tree_to_string(expect)
        vic.dispose()  # the "process" is gone

        # Restart over the same file: resume from the persisted clock.
        vic2 = Evolu(db_path=vic_path, config=vcfg, mnemonic=src.owner.mnemonic)
        vic2.update_db_schema(SCHEMA)
        connect(vic2)
        _converge([src, vic2])
        assert (
            vic2.db.exec('SELECT * FROM "todo" ORDER BY "id"')
            == src.db.exec('SELECT * FROM "todo" ORDER BY "id"')
        )
    finally:
        worker_mod.update_clock = real_update
        for r in (src, vic, vic2):
            if r is not None:
                try:
                    r.dispose()
                except Exception:  # noqa: BLE001,S110 - vic may already be disposed
                    pass
        server.stop()


def test_mixed_crdt_workload_adversarial_clocks_two_relay_fleet():
    """ISSUE 7 satellite (ROADMAP #5 small dose): LWW + PN-counter +
    AW-set columns under regressing/stuttering HLC clocks through a
    2-relay FLEET episode. Asserts byte-identical convergence of app
    tables AND __crdt_* merge state, counter EXACTNESS (the materialized
    value equals the sum of every acked increment), the AW-set add-wins
    outcome for a concurrent add/remove pair, and the per-type
    winner-cache contract on the device-backend replica."""
    with _evidence("model-check-mixed-crdt", 20250804):
        _run_mixed_crdt_episode()


def _run_mixed_crdt_episode():
    import numpy as np

    from evolu_tpu.core import crdt_types as ct
    from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
    from evolu_tpu.utils.config import FleetConfig

    seed = 20250804
    rng = random.Random(seed)
    base = int(time.time() * 1000)

    def adversarial_now(sub_seed):
        r = random.Random(sub_seed)
        state = {"t": base}

        def now():
            roll = r.random()
            if roll < 0.4:
                pass  # stutter: frozen clock
            elif roll < 0.6:
                state["t"] = max(base - 20_000,
                                 state["t"] - r.randrange(0, 10_000))
            else:
                state["t"] += r.randrange(1, 400)
            return state["t"]

        return now

    schema = {"todo": ("title", "isCompleted"),
              "metrics": ("name", "clicks:counter", "tags:awset")}
    a = RelayServer(RelayStore(), peers=[], replication_interval_s=30).start()
    b = RelayServer(RelayStore(), peers=[], replication_interval_s=30).start()
    fleet_cfg = FleetConfig(relays=(a.url, b.url), replication_factor=1,
                            version=1)
    a.enable_fleet(fleet_cfg)
    b.enable_fleet(fleet_cfg)
    replicas = []
    errors = []
    try:
        r1 = create_evolu(schema, config=Config(sync_url=a.url, backend="tpu"))
        r2 = create_evolu(schema, config=Config(sync_url=b.url, backend="cpu"),
                          mnemonic=r1.owner.mnemonic)
        replicas = [r1, r2]
        for i, r in enumerate(replicas):
            r.worker.now = adversarial_now(seed + i)
            r.subscribe_error(errors.append)
            connect(r)
        counter_rows = []
        expected_sum = {}
        for r in replicas:
            rid = r.create("metrics", {"name": f"m-{id(r)}"})
            r.worker.flush()
            counter_rows.append(rid)
            expected_sum[rid] = 0
        lww_rows = []
        for step in range(70):
            r = rng.choice(replicas)
            roll = rng.random()
            if roll < 0.25 or not lww_rows:
                lww_rows.append(r.create("todo", {
                    "title": f"t{step}", "isCompleted": False}))
            elif roll < 0.40:
                r.update("todo", rng.choice(lww_rows), {
                    "title": f"e{step}",
                    "isCompleted": bool(rng.getrandbits(1))})
            elif roll < 0.70:
                rid = rng.choice(counter_rows)
                d = rng.randrange(-50, 51)
                r.increment("metrics", rid, "clicks", d)
                expected_sum[rid] += d
            elif roll < 0.85:
                r.set_add("metrics", rng.choice(counter_rows), "tags",
                          rng.choice("abcd"))
            else:
                rid = rng.choice(counter_rows)
                elem = rng.choice("abcd")
                r.set_remove("metrics", rid, "tags", elem)
            r.worker.flush()
            if rng.random() < 0.5:
                s = rng.choice(replicas)
                s.sync()
                s.worker.flush()
        _converge(replicas)

        # Concurrent add/remove → ADD WINS: both replicas know tag T1;
        # r2 removes (observing only T1) while r1 concurrently re-adds.
        aw_row = counter_rows[0]
        r1.set_add("metrics", aw_row, "tags", "awinner")
        r1.worker.flush()
        _converge(replicas)
        r2.set_remove("metrics", aw_row, "tags", "awinner")  # observes T1 only
        r1.set_add("metrics", aw_row, "tags", "awinner")     # concurrent T2
        r1.worker.flush()
        r2.worker.flush()
        _converge(replicas)
        for r in replicas:
            r._transport.flush()
            r.worker.flush()

        # The only tolerated errors are the livelock SyncError guard
        # (redelivery quirk, reference semantics) — a drift/overflow
        # error would mean an increment was NOT acked.
        from evolu_tpu.core.types import SyncError
        real = [e for e in errors if not isinstance(e, SyncError)]
        assert not real, real

        dumps = []
        for r in replicas:
            dumps.append((
                r.db.exec('SELECT * FROM "__message" ORDER BY "timestamp"'),
                r.db.exec('SELECT * FROM "todo" ORDER BY "id"'),
                r.db.exec('SELECT * FROM "metrics" ORDER BY "id"'),
                r.db.exec('SELECT * FROM "__crdt_counter" ORDER BY "row","column"'),
                r.db.exec('SELECT * FROM "__crdt_set" ORDER BY "tag"'),
                r.db.exec('SELECT * FROM "__crdt_kill" ORDER BY "tag"'),
            ))
        assert dumps[0] == dumps[1], "typed state diverged under adversarial clocks"

        # Counter EXACTNESS: materialized value == sum of acked increments.
        for rid, total in expected_sum.items():
            got = r1.db.exec_sql_query(
                'SELECT "clicks" FROM "metrics" WHERE "id" = ?', (rid,)
            )[0]["clicks"]
            assert got == total, (rid, got, total)

        # Add-wins outcome: the concurrently re-added element survives.
        tags = r1.db.exec_sql_query(
            'SELECT "tags" FROM "metrics" WHERE "id" = ?', (aw_row,))[0]["tags"]
        assert '"awinner"' in tags, tags

        # Fold integrity: rebuilding state from the full log is a no-op.
        schema_r1 = ct.load_schema(r1.db)
        before = r1.db.exec('SELECT * FROM "__crdt_set" ORDER BY "tag"')
        ct.rebuild_state(r1.db, schema_r1)
        assert r1.db.exec('SELECT * FROM "__crdt_set" ORDER BY "tag"') == before

        # Winner-cache contract per type on the device replica: slot ==
        # MAX(timestamp) for LWW and typed cells alike (the xor gate),
        # while typed app values are the fold (asserted above).
        cache = r1.worker._planner.cache
        w1 = np.asarray(cache._w1)
        w2 = np.asarray(cache._w2)
        typed_checked = 0
        for (table, row, col), slot in cache._slots.items():
            got = r1.db.exec_sql_query(
                'SELECT MAX("timestamp") AS m FROM "__message" '
                'WHERE "table" = ? AND "row" = ? AND "column" = ?',
                (table, row, col))[0]["m"]
            k1, k2 = int(w1[slot]), int(w2[slot])
            if k1 == 0 and k2 == 0:
                assert got is None, (table, row, col)
                continue
            cached_ts = timestamp_to_string(
                Timestamp(k1 >> 16, k1 & 0xFFFF, f"{k2:016x}"))
            assert cached_ts == got, (table, row, col)
            if schema_r1.is_typed(table, col):
                typed_checked += 1
        # A livelock reset can legitimately empty the cache; the
        # schedule must merely have engaged it (same tolerance as the
        # adversarial-clock fleet test above).
        assert cache._slots or typed_checked == 0
    finally:
        for r in replicas:
            r.dispose()
        a.stop()
        b.stop()


def test_list_crdt_partition_heal_adversarial_clocks_episode():
    """ISSUE 14 satellite (ROADMAP #5 dose): the RGA list type through
    a 2-relay FLEET under regressing/stuttering HLC clocks, a PARTITION
    stretch (both replicas mutate offline, with concurrent interleaved
    inserts at the SAME anchor and a delete racing an insert anchored
    on the deleted element), a NON-CANONICAL batch bouncing to the host
    oracle mid-partition, then heal. Asserts byte-identical convergence
    of app + __crdt_list state, winner-cache == MAX(timestamp) on the
    device replica, and list materialization == the pure host-oracle
    replay of the merged op log."""
    with _evidence("model-check-list-crdt", 20260805):
        _run_list_crdt_episode()


def _run_list_crdt_episode():
    import numpy as np

    from evolu_tpu.core import crdt_list as cl
    from evolu_tpu.core.merkle import create_initial_merkle_tree
    from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
    from evolu_tpu.core.types import CrdtMessage
    from evolu_tpu.obs import metrics
    from evolu_tpu.utils.config import FleetConfig

    seed = 20260805
    rng = random.Random(seed)
    base = int(time.time() * 1000)

    def adversarial_now(sub_seed):
        r = random.Random(sub_seed)
        state = {"t": base}

        def now():
            roll = r.random()
            if roll < 0.4:
                pass  # stutter: frozen clock
            elif roll < 0.6:
                state["t"] = max(base - 20_000,
                                 state["t"] - r.randrange(0, 10_000))
            else:
                state["t"] += r.randrange(1, 400)
            return state["t"]

        return now

    schema = {"doc": ("title", "body:list")}
    a = RelayServer(RelayStore(), peers=[], replication_interval_s=30).start()
    b = RelayServer(RelayStore(), peers=[], replication_interval_s=30).start()
    fleet_cfg = FleetConfig(relays=(a.url, b.url), replication_factor=1,
                            version=1)
    a.enable_fleet(fleet_cfg)
    b.enable_fleet(fleet_cfg)
    replicas = []
    errors = []
    try:
        r1 = create_evolu(schema, config=Config(sync_url=a.url, backend="tpu"))
        r2 = create_evolu(schema, config=Config(sync_url=b.url, backend="cpu"),
                          mnemonic=r1.owner.mnemonic)
        replicas = [r1, r2]
        for i, r in enumerate(replicas):
            r.worker.now = adversarial_now(seed + i)
            r.subscribe_error(errors.append)
            connect(r)

        # Phase 1 (online): seed a shared document so both sides know
        # the same anchors, and keep syncing.
        row = r1.create("doc", {"title": "shared"})
        for v in ("a", "b", "c", "d"):
            r1.list_append("doc", row, "body", v)
        r1.worker.flush()
        _converge(replicas)
        elems = r1.list_elements("doc", row, "body")
        assert [v for _t, v in elems] == ["a", "b", "c", "d"]
        anchor = elems[1][0]        # "b" — the contested anchor
        victim = elems[2][0]        # "c" — deleted on one side, anchored on the other

        # Phase 2 (PARTITION): no sync rounds. Both replicas interleave
        # inserts at the SAME anchor; r2 deletes the element r1 keeps
        # anchoring on (tombstone-position semantics under fire).
        r2.list_delete("doc", row, "body", victim)
        for step in range(24):
            r = replicas[step % 2]
            roll = rng.random()
            if roll < 0.55:
                r.list_insert("doc", row, "body",
                              f"p{(step % 2) + 1}-{step}", after=anchor)
            elif roll < 0.75:
                r.list_insert("doc", row, "body",
                              f"v{(step % 2) + 1}-{step}", after=victim)
            else:
                r.list_append("doc", row, "body", f"t{(step % 2) + 1}-{step}")
            r.worker.flush()

        # Mid-partition hostile case: a NON-CANONICAL (uppercase node
        # hex) remote batch — LWW cells bounce the device planner to
        # the host oracle (winner-cache invalidation included on the
        # tpu replica) and a list op proves the fold is case-blind
        # (dedup is by raw string). Injected into BOTH replicas so the
        # merged histories stay identical.
        bounces_before = metrics.get_counter("evolu_merge_host_fallbacks_total")
        empty_tree = merkle_tree_to_string(create_initial_merkle_tree())

        def nc_ts(i):
            s = timestamp_to_string(
                Timestamp(base + 5000 + i, i, "00000000000000ab"))
            return s[:30] + s[30:].upper()

        hostile = tuple(
            [CrdtMessage(nc_ts(j), "doc", "remrow", "title", f"h{j}")
             for j in range(3)]
            + [CrdtMessage(nc_ts(7), "doc", "remrow", "body",
                           cl.list_insert_value("ghostwrite"))])
        for r in replicas:
            r.receive(hostile, empty_tree)
            r.worker.flush()
        assert metrics.get_counter(
            "evolu_merge_host_fallbacks_total") > bounces_before

        # Phase 3 (HEAL): sync rounds resume; fleet routing (R=1, one
        # authoritative relay) carries both sides to one history.
        _converge(replicas)
        for r in replicas:
            r._transport.flush()
            r.worker.flush()

        from evolu_tpu.core.types import SyncError
        real = [e for e in errors if not isinstance(e, SyncError)]
        assert not real, real

        dumps = []
        for r in replicas:
            dumps.append((
                r.db.exec('SELECT * FROM "__message" ORDER BY "timestamp"'),
                r.db.exec('SELECT * FROM "doc" ORDER BY "id"'),
                r.db.exec('SELECT * FROM "__crdt_list" ORDER BY "tag"'),
                r.db.exec('SELECT * FROM "__crdt_list_kill" ORDER BY "tag"'),
            ))
        assert dumps[0] == dumps[1], "list state diverged after partition/heal"

        # List materialization == the pure host-oracle replay of the
        # merged log (the fold is a function of the op SET alone).
        body_rows = r1.db.exec_sql_query(
            'SELECT "timestamp", "table", "row", "column", "value" '
            'FROM "__message" WHERE "table" = ? AND "column" = ?',
            ("doc", "body"))
        replayed = cl.replay_log([
            CrdtMessage(r["timestamp"], r["table"], r["row"], r["column"],
                        r["value"]) for r in body_rows])
        assert replayed, "episode produced no list traffic"
        for (_t, rid, _c), val in replayed.items():
            got = r1.db.exec_sql_query(
                'SELECT "body" FROM "doc" WHERE "id" = ?', (rid,))[0]["body"]
            assert got == val, (rid, got, val)

        # Both partition sides' same-anchor inserts survived, and the
        # deleted anchor's tombstone still anchored its children.
        final = [v for _t, v in r1.list_elements("doc", row, "body")]
        assert any(v.startswith("p1-") for v in final)
        assert any(v.startswith("p2-") for v in final)
        assert any(v.startswith("v") for v in final)
        assert "c" not in final  # the victim stayed deleted
        # The non-canonical list op folded into its own row's cell.
        assert r1.db.exec_sql_query(
            'SELECT "body" FROM "doc" WHERE "id" = ?',
            ("remrow",))[0]["body"] == '["ghostwrite"]'

        # Winner-cache == MAX(timestamp) on the device replica.
        cache = r1.worker._planner.cache
        w1 = np.asarray(cache._w1)
        w2 = np.asarray(cache._w2)
        for (table, rr, col), slot in cache._slots.items():
            got = r1.db.exec_sql_query(
                'SELECT MAX("timestamp") AS m FROM "__message" '
                'WHERE "table" = ? AND "row" = ? AND "column" = ?',
                (table, rr, col))[0]["m"]
            k1, k2 = int(w1[slot]), int(w2[slot])
            if k1 == 0 and k2 == 0:
                assert got is None, (table, rr, col)
                continue
            cached_ts = timestamp_to_string(
                Timestamp(k1 >> 16, k1 & 0xFFFF, f"{k2:016x}"))
            assert cached_ts == got, (table, rr, col)
    finally:
        for r in replicas:
            r.dispose()
        a.stop()
        b.stop()


def test_no_stale_query_results_adversarial_clocks_host_bounce():
    """ISSUE 9 satellite (ROADMAP #5 small dose): one seeded adversarial
    episode through the changed-set-gated query invalidation layer —
    regressing/stuttering HLC `now`, a NON-CANONICAL remote batch
    bouncing to the host oracle mid-stream (winner-cache invalidation
    included: backend="tpu"), a rolled-back Send, and eviction churn —
    driving TWIN workers (gated vs the re-run-everything oracle) over
    the identical command schedule. NO stale query result may ever be
    delivered: the gated worker's output stream must be byte-identical
    to the oracle's at every step, and at the end every cached
    subscription must equal a fresh SQL read of the live database."""
    with _evidence("model-check-stale-query", 20260804):
        _run_stale_query_episode()


def _run_stale_query_episode():
    from dataclasses import replace as dc_replace

    from evolu_tpu.core.merkle import create_initial_merkle_tree, merkle_tree_to_string
    from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
    from evolu_tpu.core.types import (CrdtClock, CrdtMessage, NewCrdtMessage,
                                      TableDefinition)
    from evolu_tpu.obs import metrics
    from evolu_tpu.runtime import messages as msg
    from evolu_tpu.runtime.worker import DbWorker
    from evolu_tpu.storage.clock import read_clock, update_clock
    from evolu_tpu.storage.native import open_database

    seed = 20260804
    base = 1_700_000_000_000
    empty_tree = merkle_tree_to_string(create_initial_merkle_tree())
    mnemonic = ("abandon abandon abandon abandon abandon abandon "
                "abandon abandon abandon abandon abandon about")
    tds = (TableDefinition.of("todo", ("title", "done")),
           TableDefinition.of("other", ("name",)))

    def adversarial_now(sub_seed):
        """Deterministic hostile wall clock (same envelope as the fleet
        episode above): 40% frozen, 20% bounded regression, else small
        advances. Gating never changes how often the worker samples
        `now`, so twin workers with the same sub_seed stamp identical
        timestamps — any divergence would itself be a bug."""
        r = random.Random(sub_seed)
        state = {"t": base}

        def now():
            roll = r.random()
            if roll < 0.4:
                pass  # stutter: frozen clock
            elif roll < 0.6:
                state["t"] = max(base - 20_000,
                                 state["t"] - r.randrange(0, 5_000))
            else:
                state["t"] += r.randrange(1, 400)
            return state["t"]

        return now

    def make_worker(gated):
        db = open_database(":memory:")
        outputs, pushes = [], []
        w = DbWorker(db, config=Config(backend="tpu", query_invalidation=gated),
                     on_output=outputs.append, post_sync=pushes.append,
                     now=adversarial_now(seed))
        w.start(mnemonic)
        w.stop()  # drive handle() synchronously: deterministic twin runs
        clock = read_clock(db)
        with db.transaction():  # pin the HLC node id across the twins
            update_clock(db, CrdtClock(
                dc_replace(clock.timestamp, node="00c0ffee00c0ffee"),
                clock.merkle_tree))
        w.handle(msg.UpdateDbSchema(tds))
        outputs.clear()
        return w, outputs, pushes

    def remote_ts(i, counter=0, upper=False):
        s = timestamp_to_string(
            Timestamp(base + i, counter, "00000000000000ab"))
        return s[:30] + s[30:].upper() if upper else s

    qs = tuple(
        [msg.serialize_query('SELECT "id", "title", "done" FROM "todo" '
                             'WHERE "id" = ?', (f"row{i}",)) for i in range(8)]
        + [msg.serialize_query('SELECT "id", "title" FROM "todo" '
                               'WHERE "done" = ? ORDER BY "title"', (i,))
           for i in range(4)]
        + [msg.serialize_query('SELECT "id", "name" FROM "other" ORDER BY "id"')])

    rng = random.Random(seed)
    schedule = [msg.Query(qs)]
    for step in range(48):
        roll = rng.random()
        if roll < 0.40:
            table, row = ("todo", f"row{rng.randrange(12)}") if roll < 0.30 \
                else ("other", f"o{rng.randrange(3)}")
            col = "title" if table == "todo" else "name"
            schedule.append(msg.Send(
                (NewCrdtMessage(table, row, col, f"v{step}"),), (), qs))
        elif roll < 0.55:
            schedule.append(msg.Send(
                (NewCrdtMessage("todo", f"row{rng.randrange(12)}", "done",
                                rng.randrange(2)),), (f"cb{step}",), qs))
        elif roll < 0.70:
            schedule.append(msg.Query(qs))
        elif roll < 0.80:
            batch = tuple(
                CrdtMessage(remote_ts(1000 + step * 10 + j, counter=j),
                            "todo", f"rem{j % 2}", "title", f"m{step}.{j}")
                for j in range(3))
            schedule.append(msg.Receive(batch, empty_tree))
            schedule.append(msg.Query(qs))
        elif roll < 0.90:
            schedule.append(msg.EvictQueries((rng.choice(qs),)))
            schedule.append(msg.Query(qs))
        else:
            # un-encodable value: the Send rolls back before any write
            schedule.append(msg.Send(
                (NewCrdtMessage("todo", "row0", "title", b"\x00"),), (), qs))
            schedule.append(msg.Query(qs))
    # The named mid-stream hostile case: NON-CANONICAL hex timestamps
    # bounce the batch to the host oracle and invalidate winner-cache
    # cells; more gated sweeps follow it.
    schedule[len(schedule) // 2:len(schedule) // 2] = [
        msg.Receive(tuple(
            CrdtMessage(remote_ts(9000 + j, counter=j, upper=True),
                        "todo", "row1", "done", j) for j in range(3)),
            empty_tree),
        msg.Query(qs),
    ]

    skips_before = sum(metrics.get_counter(k) for k in (
        "evolu_query_skipped_by_table_total",
        "evolu_query_skipped_by_rows_total",
        "evolu_query_skipped_clean_total"))
    bounces_before = metrics.get_counter("evolu_merge_host_fallbacks_total")
    w_gated, out_g, push_g = make_worker(True)
    w_naive, out_n, push_n = make_worker(False)
    try:
        for cmd in schedule:
            w_gated.handle(cmd)
            w_naive.handle(cmd)
        # Byte-identical delivery: same outputs (OnError compared by
        # type — exception objects don't compare equal), same pushes.
        assert [type(o).__name__ for o in out_g] \
            == [type(o).__name__ for o in out_n]
        stream_g = [o for o in out_g if not isinstance(o, msg.OnError)]
        stream_n = [o for o in out_n if not isinstance(o, msg.OnError)]
        assert stream_g == stream_n, \
            "gated patch stream diverged from the re-exec oracle"
        assert push_g == push_n
        for sql in ('SELECT * FROM "__message" ORDER BY "timestamp"',
                    'SELECT * FROM "todo" ORDER BY "id"',
                    'SELECT * FROM "other" ORDER BY "id"'):
            assert w_gated.db.exec(sql) == w_naive.db.exec(sql)
        # Direct no-staleness oracle: every cached subscription equals
        # a fresh read of the live database RIGHT NOW.
        for q in qs:
            if q not in w_gated.queries_rows_cache:
                continue  # evicted by churn; next sweep root-replaces
            sql, params = msg.deserialize_query(q)
            assert w_gated.queries_rows_cache[q] \
                == w_gated.db.exec_sql_query(sql, params), q
        # The episode actually exercised the gate (skips happened) AND
        # the named hostile route (host-oracle bounce mid-stream).
        assert sum(metrics.get_counter(k) for k in (
            "evolu_query_skipped_by_table_total",
            "evolu_query_skipped_by_rows_total",
            "evolu_query_skipped_clean_total")) > skips_before
        assert metrics.get_counter(
            "evolu_merge_host_fallbacks_total") > bounces_before
    finally:
        w_gated.db.close()
        w_naive.db.close()


# -- PR-11 torture: the write-behind queue's durability license --


@pytest.mark.slow
@pytest.mark.parametrize("seed", [3, 17, 71])
def test_write_behind_sigkill_torture(tmp_path, seed):
    """SIGKILL a write-behind relay worker at an arbitrary point
    (mid-queue, mid-drain, mid-checkpoint — the drain is slowed and
    checkpoints run behind the barrier every 4 batches), restart it,
    and demand the drained SQLite end state be byte-identical (state
    crc) to a synchronous-apply oracle twin of the ACKed prefix. The
    ACK point is the record-log fsync: a kill can land between the
    fsync and the ACK print, so prefix+1 is also an accepted oracle.
    This is the license for promoting device state to truth
    (ROADMAP #1): an ACKed write is never lost, and replay's
    always-exact tree fold converges to the oracle regardless of
    where the kill landed."""
    with _evidence("write-behind-sigkill", seed):
        _run_write_behind_torture(tmp_path, seed)


@pytest.mark.slow
@pytest.mark.parametrize("seed", [7, 29, 101])
def test_write_behind_sharded_sigkill_torture(tmp_path, seed):
    """PR-19: the same SIGKILL episode against a 3-shard store with
    one drain worker per shard. The kill can now land with shard k's
    transaction committed and shard j's still pending (workers drain
    concurrently) — replay must heal the partial commit exactly:
    committed rows re-classify as duplicates, the end state is still
    byte-identical to a synchronous oracle of the ACKed prefix (or
    prefix+1 — fsync-before-ACK-print), and the finish process's
    episode audit stays clean."""
    with _evidence("write-behind-sharded-sigkill", seed):
        _run_write_behind_torture(tmp_path, seed, shards=3, workers=3)


def _run_write_behind_torture(tmp_path, seed, shards=1, workers=0):
    import os
    import signal
    import subprocess
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from _write_behind_worker import seeded_batches, state_crc

    from evolu_tpu.server.engine import BatchReconciler

    rng = random.Random(seed)
    n_batches = 12
    db_path = str(tmp_path / "victim.db")
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "_write_behind_worker.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.Popen(
        [sys.executable, worker, "ingest", db_path, str(seed),
         str(n_batches), "0.15", str(shards), str(workers)],
        stdout=subprocess.PIPE, text=True, env=env,
    )
    kill_after = rng.randrange(1, n_batches - 1)
    acked = -1
    try:
        for line in proc.stdout:
            if line.startswith("ACK "):
                acked = int(line.split()[1])
                if acked >= kill_after:
                    # Land the kill anywhere in the next batches'
                    # serve/drain/checkpoint window.
                    time.sleep(rng.random() * 0.3)
                    proc.kill()  # SIGKILL — no teardown, no flush
                    break
            elif line.startswith("DONE"):
                break
        # The worker may have ACKed more batches into the pipe before
        # dying than the loop above consumed — the TRUE acked count is
        # the last ACK line anywhere in its output.
        for line in proc.stdout:
            if line.startswith("ACK "):
                acked = int(line.split()[1])
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    assert acked >= 0, "worker never ACKed a batch"

    # Restart: constructor replay + flush, then the state crc.
    out = subprocess.run(
        [sys.executable, worker, "finish", db_path, str(shards),
         str(workers)],
        capture_output=True, text=True, timeout=300, env=env, check=True,
    )
    done = [ln for ln in out.stdout.splitlines() if ln.startswith("DONE crc=")]
    assert done, out.stdout
    got_crc = done[-1].split("crc=")[1]

    # Oracle twins: synchronous apply of the ACKed prefix — and of
    # prefix+1 (a kill between the log fsync and the ACK print means
    # one more batch is legitimately durable). The kill may also land
    # mid-append of batch acked+1: each record is crc-framed, so a
    # torn frame is discarded at replay — on a single-shard store the
    # batch is ONE record (fully durable or absent, exactly the two
    # twins above). A sharded store appends one record PER LIVE SHARD
    # (ascending shard order) under one fsync, and a kill mid-append
    # can leave a complete frame PREFIX of that batch on disk (the
    # kernel's page cache survives process death), so every
    # record-prefix of batch acked+1 is also an accepted twin. The
    # restriction is well-defined: in-batch dedup never crosses
    # shards (its key includes the owner, and an owner's rows all
    # land in one shard).
    batches = seeded_batches(seed, n_batches)
    accepted = set()
    from evolu_tpu.obs import ledger as ledger_mod

    def _twin(prefix_batches, partial_reqs=None):
        oracle = RelayStore()
        eng = BatchReconciler(oracle)
        for reqs in prefix_batches:
            eng.run_batch_wire(reqs)
        if partial_reqs:
            eng.run_batch_wire(partial_reqs)
        crc = f"{state_crc(oracle):08x}"
        eng.close()
        oracle.close()
        return crc

    with ledger_mod.quarantine():  # reference computation, not traffic
        for extra in (0, 1):
            accepted.add(_twin(batches[: acked + 1 + extra]))
        if shards > 1 and acked + 1 < len(batches):
            import zlib as _zlib

            def shard_of(u):
                return _zlib.crc32(u.encode("utf-8")) % shards

            nxt = batches[acked + 1]
            live = sorted({shard_of(r.user_id) for r in nxt if r.messages})
            for r in range(1, len(live)):
                allow = set(live[:r])
                sub = [q for q in nxt if shard_of(q.user_id) in allow]
                accepted.add(_twin(batches[: acked + 1], sub))
    assert got_crc in accepted, (got_crc, accepted, acked)


def test_mixed_traffic_ledger_conservation_episode(tmp_path):
    """ISSUE 15's dedicated conservation episode: one relay process
    sees EVERY hostile flow at once — a write-behind log inherited from
    a SIGKILLed predecessor (restart replay), canonical pushes with
    exact redeliveries, a non-canonical-width reject, a poisoned engine
    pass retried as singletons, and a 503 backpressure shed — and the
    ledger must still prove conservation: replayed records reconcile
    (classify as duplicates where a pre-kill drain already committed
    them) rather than double-count, every terminal fires exactly once
    per delivery attempt, wb.queued == wb.drained at the barrier, and
    `ledger.audit()` returns zero violated equations."""
    with _evidence("ledger-mixed-traffic", 20260805):
        _run_mixed_ledger_episode(tmp_path, 20260805)


def _run_mixed_ledger_episode(tmp_path, seed):
    import os
    import subprocess
    import sys
    import urllib.error
    import urllib.request

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from evolu_tpu.obs import ledger as ledger_mod
    from evolu_tpu.server.engine import BatchReconciler
    from evolu_tpu.sync import protocol

    # --- phase 1: a write-behind relay worker dies by SIGKILL with
    # ACKed-but-undrained records in its durable log. ---
    db_path = str(tmp_path / "mixed.db")
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "_write_behind_worker.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.Popen(
        [sys.executable, worker, "ingest", db_path, str(seed), "6", "0.2"],
        stdout=subprocess.PIPE, text=True, env=env,
    )
    acked = -1
    try:
        for line in proc.stdout:
            if line.startswith("ACK "):
                acked = int(line.split()[1])
                if acked >= 2:
                    time.sleep(0.15)  # land mid-drain
                    proc.kill()
                    break
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    assert acked >= 0, "worker never ACKed a batch"
    log_bytes = os.path.getsize(db_path + ".wblog")
    assert log_bytes > 16, "SIGKILL left no undrained log to replay"

    ledger_mod.reset()  # the proof window starts at the restart

    # --- phase 2: restart over the same store + log. The constructor
    # replays the predecessor's records (ingress.replay), classifying
    # rows a pre-kill drain already committed as store.duplicate —
    # reconciled, never double-counted. ---
    from evolu_tpu.server.relay import RelayServer, RelayStore

    orig_rbw = BatchReconciler.run_batch_wire
    poison = {"armed": False, "fired": 0}

    def flaky(self, requests, *stage):
        if poison["armed"] and not poison["fired"]:
            poison["fired"] += 1
            raise RuntimeError("injected poisoned batch")
        return orig_rbw(self, requests, *stage)

    BatchReconciler.run_batch_wire = flaky
    server = RelayServer(RelayStore(db_path), write_behind=True).start()
    try:
        t = ledger_mod.totals()
        replayed = t.get(ledger_mod.INGRESS_REPLAY, 0)
        assert replayed > 0, "restart replayed nothing"
        assert (t.get(ledger_mod.STORE_INSERTED, 0)
                + t.get(ledger_mod.STORE_DUPLICATE, 0)) == replayed

        def post(req, expect_error=None):
            body = protocol.encode_sync_request(req)
            try:
                with urllib.request.urlopen(
                    urllib.request.Request(server.url, data=body),
                    timeout=30,
                ) as r:
                    return r.read()
            except urllib.error.HTTPError as e:
                assert expect_error == e.code, e
                return None

        def req(user, node, ts_list):
            return protocol.SyncRequest(
                tuple(protocol.EncryptedCrdtMessage(ts, b"ct") for ts in ts_list),
                user, node, "{}",
            )

        ts = [timestamp_to_string_at(i) for i in range(4)]
        # Canonical pushes + one exact redelivery (duplicates).
        post(req("mixed-alice", "a" * 16, ts[:3]))
        post(req("mixed-alice", "a" * 16, ts[:3]))
        # Non-canonical width → singleton host-oracle reject (500).
        post(req("mixed-nc", "b" * 16,
                 ["1970-01-01T00:00:00.001Z-001-deadbeefdeadbeef"]),
             expect_error=500)
        # Poisoned engine pass → singleton retry serves it exactly once.
        poison["armed"] = True
        post(req("mixed-bob", "c" * 16, [ts[3]]))
        poison["armed"] = False
        assert poison["fired"] == 1, "poison injection never fired"
        # 503 backpressure shed.
        real_max = server.scheduler.max_queue
        server.scheduler.max_queue = 0
        post(req("mixed-shed", "d" * 16, ts[:2]), expect_error=503)
        server.scheduler.max_queue = real_max

        server.write_behind.flush()
        t = ledger_mod.totals()
        assert t[ledger_mod.WB_QUEUED] == t[ledger_mod.WB_DRAINED]
        assert t[ledger_mod.SHED_BACKPRESSURE] == 2
        assert t[ledger_mod.REJECT_INVALID] == 1
        assert t[ledger_mod.BOUNCE_NON_CANONICAL] >= 1
        # mixed-bob's row: exactly once despite the poisoned pass.
        bob = ledger_mod.ledger.owner_totals("mixed-bob")
        assert bob[ledger_mod.STORE_INSERTED] == 1
        assert bob.get(ledger_mod.STORE_DUPLICATE, 0) == 0
        violations = ledger_mod.audit(at_barrier=True)
        assert violations == [], violations
    finally:
        BatchReconciler.run_batch_wire = orig_rbw
        server.stop()


def timestamp_to_string_at(i):
    from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string

    return timestamp_to_string(
        Timestamp(1700000000000 + i * 1000, 0, "1234567890abcdef")
    )


@pytest.mark.slow
def test_write_behind_torture_winner_state_matches_sqlite(tmp_path):
    """The client-side half of the PR-11 invariant bar: after an
    update-heavy apply schedule (repeated cells — the shape the
    adaptive gate keeps on the cached route; a create-heavy churn
    workload legitimately streams with zero slots), the HBM winner
    slots equal SQLite's MAX(timestamp) per cell — read back from the
    device arrays via the worker's audit surface. A restart re-seeds
    the (volatile) cache lazily; the invariant must hold again after
    post-restart traffic."""
    from evolu_tpu.runtime.client import Evolu

    db_path = str(tmp_path / "client.db")
    cfg = Config(backend="tpu", min_device_batch=1)  # every apply on the cache route
    ev = Evolu(db_path=db_path, config=cfg)
    ev.update_db_schema(SCHEMA)
    try:
        ids = [ev.create("todo", {"title": f"t{i}"}) for i in range(4)]
        ev.worker.flush()
        # Update-heavy on ONE hot row, one batch per mutation (flush
        # each): repeated cells are the shape the adaptive gate keeps
        # cached (tiny batches over alternating rows read as 100%
        # churn and legitimately stream — the gate is tuned for the
        # 1M-row receive shape, not 3-cell mutations).
        hot = ids[0]
        for i in range(20):
            ev.update("todo", hot, {"title": f"edit{i}",
                                    "isCompleted": bool(i % 2)})
            ev.worker.flush()
        checked = ev.worker.verify_winner_cache()
        assert checked > 0, "the winner cache never engaged"
        ev.dispose()

        # Restart: HBM is volatile — the cache re-seeds from SQLite
        # lazily; the audit must hold on the re-seeded slots too.
        ev = Evolu(db_path=db_path, config=cfg)
        ev.update_db_schema(SCHEMA)
        for i in range(15):
            ev.update("todo", hot, {"title": f"post{i}"})
            ev.worker.flush()
        assert ev.worker.verify_winner_cache() > 0
    finally:
        ev.dispose()


def test_mesh_sharded_multi_relay_scheduler_episode(seed=90210):
    """ISSUE 12: multi-relay traffic coalescing through ONE shared
    scheduler onto the mesh-sharded engine (stable owner→device
    placement over the 8-device virtual mesh), with the PR-11
    write-behind queue on the serving path, a non-canonical hex-case
    batch (host-fold quarantine), and a non-canonical width request
    (rejected before any side effect). End state must be byte-identical
    to a SINGLE-DEVICE oracle twin replaying the same requests, and the
    clients' mesh-sharded winner caches must equal SQLite's
    MAX(timestamp) per cell, audited through the per-shard slot
    arrays."""
    import threading
    import urllib.error
    import urllib.request

    from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
    from evolu_tpu.obs import metrics
    from evolu_tpu.ops.winner_cache import MeshShardedWinnerCache
    from evolu_tpu.parallel.mesh import MeshContext
    from evolu_tpu.server.engine import BatchReconciler
    from evolu_tpu.server.scheduler import SyncScheduler
    from evolu_tpu.storage.write_behind import WriteBehindQueue
    from evolu_tpu.sync import protocol
    from evolu_tpu.parallel.mesh import create_mesh

    with _evidence("mesh-model-check", seed):
        rng = random.Random(seed)
        store = ShardedRelayStore(shards=4)
        wb = WriteBehindQueue(store)
        ctx = MeshContext()
        sched = SyncScheduler(store, write_behind=wb, mesh_ctx=ctx,
                              max_batch=8, max_wait_s=0.002)
        # Capture every request the shared scheduler serves, in
        # arrival order, for the oracle replay.
        req_log: list = []
        log_lock = threading.Lock()
        orig_submit = sched.submit

        def logged_submit(request):
            with log_lock:
                req_log.append(request)
            return orig_submit(request)

        sched.submit = logged_submit
        # TWO relays handing traffic to the ONE scheduler/device pool.
        r1 = RelayServer(store, scheduler=sched).start()
        r2 = RelayServer(store, scheduler=sched).start()
        dispatches0 = metrics.get_counter("evolu_mesh_dispatches_total")

        cfg = lambda url: Config(sync_url=url, backend="tpu",  # noqa: E731
                                 mesh_engine=True)
        a = create_evolu(SCHEMA, config=cfg(r1.url))
        b = create_evolu(SCHEMA, config=cfg(r2.url), mnemonic=a.owner.mnemonic)
        replicas = [a, b]
        try:
            transports = [connect(r) for r in replicas]
            assert type(a.worker._planner.cache) is MeshShardedWinnerCache
            row_ids: list = []
            for step in range(24):
                r = rng.choice(replicas)
                op = rng.random()
                if op < 0.5 or not row_ids:
                    row_ids.append(r.create("todo", {
                        "title": f"m{step}", "isCompleted": False,
                    }))
                elif op < 0.85:
                    r.update("todo", rng.choice(row_ids), {
                        "title": f"edit{step}",
                        "isCompleted": bool(rng.getrandbits(1)),
                    })
                else:
                    for x in replicas:
                        x.sync(); x.worker.flush()
            # Concurrent distinct-owner burst straight at both relays
            # (coalesces into fused sharded passes).
            BASE = 1_700_000_000_000

            def push(url, owner, node, start, n):
                msgs = tuple(
                    protocol.EncryptedCrdtMessage(
                        timestamp_to_string(
                            Timestamp(BASE + (start + i) * 1000, 0, node)),
                        b"mesh-%d" % (start + i))
                    for i in range(n))
                body = protocol.encode_sync_request(
                    protocol.SyncRequest(msgs, owner, node, "{}"))
                with urllib.request.urlopen(urllib.request.Request(
                        url, data=body,
                        headers={"Content-Type": "application/octet-stream"}),
                        timeout=60) as resp:
                    resp.read()

            threads = [
                threading.Thread(target=push, args=(
                    (r1 if i % 2 else r2).url, f"mesh-x{i}",
                    f"{i + 0x41:016x}", rng.randrange(3), 5 + i))
                for i in range(6)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            # Non-canonical hex CASE (width 46 — batchable): the engine
            # must quarantine this owner to the host fold, still store.
            node_uc = "ABCDEF0123456789"
            push(r1.url, "mesh-nc", node_uc, 0, 4)
            # Non-canonical WIDTH: singleton path, rejected with NO
            # side effect (the oracle twin never sees it either — the
            # log records it, the replay skips it identically).
            bad_ts = timestamp_to_string(Timestamp(BASE, 0, "9" * 16)) + "Z"
            body = protocol.encode_sync_request(protocol.SyncRequest(
                (protocol.EncryptedCrdtMessage(bad_ts, b"x"),),
                "mesh-bad", "9" * 16, "{}"))
            try:
                urllib.request.urlopen(urllib.request.Request(
                    r2.url, data=body,
                    headers={"Content-Type": "application/octet-stream"}),
                    timeout=60).read()
                raise AssertionError("non-canonical width must be rejected")
            except urllib.error.HTTPError as e:
                assert e.code == 500
            _converge(replicas)
            # Quiesce the clients BEFORE the quarantined oracle replay:
            # `ledger.quarantine()` switches the process-global ledger
            # off, and `_converge` returns on equal logs with
            # anti-entropy rounds possibly still in flight — a round
            # counted at ingress before the quarantine whose terminal
            # fell inside it read as a lost delivery (the flake under
            # the driver's six loaded workers). `stop()` joins each
            # transport's in-flight round; the workers stay up for the
            # winner-cache audit below.
            for t, r in zip(transports, replicas):
                t.stop()
                r.worker.flush()
            # Write-behind drain barrier, then the authoritative dump
            # (ONE shared parity-dump helper — tests/conftest.py).
            wb.flush()
            from tests.conftest import relay_store_dump as dump

            # Oracle twin: a SINGLE-DEVICE engine (1-device mesh, no
            # write-behind, per-batch LPT) replays the captured request
            # log one request per pass.
            from evolu_tpu.obs import ledger as ledger_mod

            oracle = ShardedRelayStore(shards=4)
            oeng = BatchReconciler(oracle, mesh=create_mesh(1))
            try:
                with log_lock:
                    replay = list(req_log)
                assert len(replay) > 10, "episode produced no traffic"
                with ledger_mod.quarantine():  # reference replay, not traffic
                    for req in replay:
                        try:
                            oeng.run_batch_wire([req])
                        except Exception:
                            pass  # the width-reject raises here too
                assert dump(store) == dump(oracle), (
                    "sharded multi-relay end state diverged from the "
                    "single-device oracle twin"
                )
            finally:
                oeng.close()
                oracle.close()
            # The host-fold owner really landed (quarantine stored it).
            assert store.get_merkle_tree_string("mesh-nc") != "{}"
            # Sharded passes actually ran, and the winner caches hold
            # slot == MAX(timestamp), audited via the per-shard arrays.
            assert metrics.get_counter(
                "evolu_mesh_dispatches_total") > dispatches0
            for r in replicas:
                checked = r.worker.verify_winner_cache()
                cache = r.worker._planner.cache
                assert sum(cache.shard_slot_counts()) == len(cache._slots)
                assert checked == len(cache._slots)
        finally:
            for r in replicas:
                r.dispose()
            r1.stop()
            r2.stop()
            wb.close()
            store.close()


def test_push_subscription_partition_heal_episode():
    """ISSUE 13 / ROADMAP #5 small dose: a seeded schedule drives push
    subscriptions through a network partition and heal, on the
    EVENT-LOOP connection tier. A subscriber is parked at relay B;
    writes land at relay A and reach B only via Merkle anti-entropy.
    Invariants: (1) while partitioned, B's subscriber never wakes for
    A-side writes (nothing became visible at B); (2) after heal, the
    replication-ingest wakeup fires — no wakeup missed across the
    fault; (3) wakes stay bounded by qualifying batches; (4) the
    relays converge byte-identically — push changed no state anywhere.
    """
    import json
    import threading
    import urllib.request

    from evolu_tpu.obs import metrics
    from evolu_tpu.server.replicate import ReplicationManager
    from evolu_tpu.sync import protocol
    from tests.test_replication import (
        _FaultyTransport,
        _state,
        _write,
    )
    from tests.test_push import SUB, _msgs, _sync_body  # noqa: F401

    seed = 20260813
    with _evidence("model-check-push-partition", seed):
        rng = random.Random(seed)
        n1 = "1" * 16
        stores = [RelayStore(), RelayStore()]
        faults = [_FaultyTransport(), _FaultyTransport()]
        mgrs = [
            ReplicationManager(
                s, [], replica_id=f"push-{i}", interval_s=0.1,
                debounce_s=0.02, backoff_base_s=0.05, backoff_max_s=0.3,
                http_post=f.post,
            )
            for i, (s, f) in enumerate(zip(stores, faults))
        ]
        servers = [
            RelayServer(s, replication=m,
                        connection_tier="eventloop").start()
            for s, m in zip(stores, mgrs)
        ]
        a, b = servers
        try:
            mgrs[0].add_peer(b.url)
            mgrs[1].add_peer(a.url)
            wakes = []
            stop = threading.Event()

            def subscriber():
                cursor = 0
                while not stop.is_set():
                    url = (f"{b.url}/push/poll?owner=ow&node={SUB}"
                           f"&cursor={cursor}&timeout=0.5")
                    try:
                        with urllib.request.urlopen(url, timeout=10) as r:
                            body = json.loads(r.read())
                    except Exception:  # noqa: BLE001 - teardown
                        return
                    cursor = body["cursor"]
                    if body["wake"]:
                        wakes.append(time.monotonic())

            th = threading.Thread(target=subscriber)
            th.start()
            time.sleep(0.2)

            # Phase 1 — connected: a foreign write at A must wake the
            # subscriber at B through replication ingest.
            repl_wakes0 = metrics.get_counter(
                "evolu_push_wakeups_total", reason="replication")
            _write(a.url, "ow", n1, _msgs(n1, 0, 3))
            deadline = time.time() + 15
            while not wakes:
                assert time.time() < deadline, \
                    "pre-partition replication wake never fired at B"
                time.sleep(0.02)
            assert metrics.get_counter(
                "evolu_push_wakeups_total",
                reason="replication") > repl_wakes0

            # Phase 2 — partition both directions, keep writing at A
            # (mixed authors, seeded). B's subscriber must stay silent:
            # nothing became visible AT B.
            faults[0].block(b.url)
            faults[1].block(a.url)
            time.sleep(0.2)
            n_wakes_at_partition = len(wakes)
            qualifying = 0
            base = 100
            for _step in range(rng.randint(3, 6)):
                author = rng.choice([n1, SUB])
                n = rng.randint(1, 3)
                _write(a.url, "ow", author, _msgs(author, base, n))
                base += n
                qualifying += 1 if author != SUB else 0
            time.sleep(0.6)  # several gossip intervals
            assert len(wakes) == n_wakes_at_partition, \
                "subscriber at B woke during the partition"

            # Phase 3 — heal: the pulled rows must wake B's subscriber
            # (they can never arrive as a local POST there), and both
            # relays converge byte-identically.
            faults[0].heal()
            faults[1].heal()
            mgrs[0].hint()
            mgrs[1].hint()
            deadline = time.time() + 20
            while len(wakes) == n_wakes_at_partition:
                assert time.time() < deadline, \
                    "post-heal replication wake never fired (wakeup missed)"
                time.sleep(0.02)
            deadline = time.time() + 20
            while _state(stores[0]) != _state(stores[1]):
                assert time.time() < deadline, "relays did not converge"
                time.sleep(0.05)
            sa = _state(stores[0])
            assert sa == _state(stores[1])
            assert sum(len(rows) for _t, rows in sa.values()) == base - 100 + 3
            # Spurious bound: the subscriber woke at most once per
            # qualifying foreign batch (+1 for the heal's coalesced
            # pull — replication may deliver the backlog as one batch).
            assert len(wakes) <= 1 + qualifying + 1
        finally:
            stop.set()
            for s in servers:
                s.stop()
            th.join(timeout=5)


def test_scoped_partial_replication_episode():
    """ISSUE 18 satellite: one seeded adversarial episode through the
    partial-replication plane — a FULL and a SCOPED device of one
    owner, homed at DIFFERENT relays that gossip via anti-entropy
    replication, under regressing/stuttering HLC clocks, a relay-level
    partition and heal, a NON-CANONICAL batch bouncing to the host
    oracle mid-stream, and a mid-stream scope escalation. Invariants:
    the two devices' __message logs converge byte-identically (the
    scoped device defers MATERIALIZATION, never history); the scoped
    device's in-scope table is byte-identical to the full device's;
    the out-of-scope table stays empty with a COUNTER-EXACT deferred
    frontier; after widening, the scoped device is byte-identical
    everywhere, including rows written after the escalation; and the
    conservation ledger balances at episode end (_evidence audits).

    The reference's livelock guard (repeated identical merkle diff) CAN
    fire transiently here — frozen adversarial clocks cluster rows into
    one minute while relay gossip keeps landing foreign rows into that
    same minute between a device's rounds — so transient SyncError is
    tolerated (each next sync starts a fresh chain), matching the other
    replicating-relay episodes above; any OTHER surfaced error fails
    the episode."""
    with _evidence("model-check-scope", 20260807):
        _run_scoped_partial_replication_episode()


def _run_scoped_partial_replication_episode():
    from evolu_tpu.core.merkle import apply_prefix_xors, minute_deltas_host
    from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
    from evolu_tpu.core.types import CrdtMessage, SyncError
    from evolu_tpu.obs import metrics
    from evolu_tpu.runtime import messages as wmsg
    from evolu_tpu.server.replicate import ReplicationManager
    from evolu_tpu.sync.scope import ScopeDeferred, SyncScope  # noqa: F401
    from tests.test_replication import _FaultyTransport, _state

    seed = 20260807
    rng = random.Random(seed)
    base = int(time.time() * 1000)

    def adversarial_now(sub_seed):
        """Same hostile envelope as the fleet episode above: 40%
        frozen, 20% bounded regression, else small advances."""
        r = random.Random(sub_seed)
        state = {"t": base}

        def now():
            roll = r.random()
            if roll < 0.4:
                pass  # stutter
            elif roll < 0.6:
                state["t"] = max(base - 20_000,
                                 state["t"] - r.randrange(0, 10_000))
            else:
                state["t"] += r.randrange(1, 400)
            return state["t"]

        return now

    stores = [RelayStore(), RelayStore()]
    faults = [_FaultyTransport(), _FaultyTransport()]
    mgrs = [
        ReplicationManager(
            s, [], replica_id=f"scope-{i}", interval_s=0.1,
            debounce_s=0.02, backoff_base_s=0.05, backoff_max_s=0.3,
            http_post=f.post,
        )
        for i, (s, f) in enumerate(zip(stores, faults))
    ]
    servers = [RelayServer(s, replication=m).start()
               for s, m in zip(stores, mgrs)]
    a, b = servers
    replicas = []
    try:
        mgrs[0].add_peer(b.url)
        mgrs[1].add_peer(a.url)
        full = create_evolu(SCHEMA, config=Config(sync_url=a.url,
                                                  backend="tpu"))
        thin = create_evolu(
            SCHEMA, mnemonic=full.owner.mnemonic,
            config=Config(sync_url=b.url, backend="cpu",
                          sync_scope=SyncScope(tables=("todo",))))
        replicas = [full, thin]
        errors = []
        for i, r in enumerate(replicas):
            r.worker.now = adversarial_now(seed + i)
            connect(r)
            r.subscribe_error(errors.append)

        def step(r, allow_category):
            tables = ["todo", "todo", "todoCategory"] if allow_category \
                else ["todo"]
            t = rng.choice(tables)
            if t == "todo":
                r.create("todo", {"title": f"t{rng.randrange(10**6)}",
                                  "isCompleted": False})
            else:
                r.create("todoCategory",
                         {"name": f"c{rng.randrange(10**6)}"})
            r.worker.flush()
            if rng.random() < 0.4:
                r.sync()
                r.worker.flush()

        # Phase 1 — connected: mixed writes. The full device writes
        # both tables; the scoped device writes only its slice.
        for _ in range(14):
            step(full, True)
            step(thin, False)

        # Mid-stream NON-CANONICAL batch (uppercase node hex) injected
        # at the full device for the IN-SCOPE table: the apply must
        # route to the host oracle (r5 contract) on every replica it
        # reaches via anti-entropy.
        bounces0 = metrics.get_counter("evolu_merge_host_fallbacks_total")
        full._transport.flush()
        full.worker.flush()
        nc = tuple(
            CrdtMessage(
                (lambda s: s[:30] + s[30:].upper())(timestamp_to_string(
                    Timestamp(base + 1000 + i, 0, "00000000000000ab"))),
                "todo", f"ncrow{i}", "title", f"nc{i}")
            for i in range(3)
        )
        from evolu_tpu.storage.clock import read_clock
        local = read_clock(full.db).merkle_tree
        deltas, _ = minute_deltas_host(m.timestamp for m in nc)
        full.receive(nc, merkle_tree_to_string(
            apply_prefix_xors(dict(local), deltas)))
        full.worker.flush()
        assert metrics.get_counter(
            "evolu_merge_host_fallbacks_total") > bounces0

        # Phase 2 — partition the relay gossip both directions; the
        # devices keep writing against their OWN relay.
        faults[0].block(b.url)
        faults[1].block(a.url)
        for _ in range(8):
            step(full, True)
            step(thin, False)

        # Phase 3 — heal, then converge: relay gossip AND both
        # devices' sync rounds, until the two LOGS are byte-identical.
        faults[0].heal()
        faults[1].heal()
        mgrs[0].hint()
        mgrs[1].hint()

        def log(r):
            return r.db.exec(
                'SELECT * FROM "__message" ORDER BY "timestamp"')

        deadline = time.time() + 60
        while True:
            for r in replicas:
                r.sync()
                r.worker.flush()
            if log(full) == log(thin) and \
                    _state(stores[0]) == _state(stores[1]):
                break
            assert time.time() < deadline, \
                "logs/relays did not converge across the scope boundary"
            time.sleep(0.05)
        for r in replicas:
            r._transport.flush()
            r.worker.flush()
        assert not [e for e in errors if not isinstance(e, SyncError)], \
            "non-livelock error surfaced"

        # Within-slice byte-identity: the scoped device's in-scope
        # table equals the full device's, non-canonical rows included.
        todo_full = full.db.exec('SELECT * FROM "todo" ORDER BY "id"')
        todo_thin = thin.db.exec('SELECT * FROM "todo" ORDER BY "id"')
        assert todo_full == todo_thin
        assert any(r[0].startswith("ncrow")
                   for r in thin.db.exec('SELECT "id" FROM "todo"'))
        # Out-of-scope: zero materialized rows, counter-EXACT frontier
        # (the thin device authored no todoCategory rows, so every one
        # in its log was deferred — and redeliveries must not inflate).
        assert thin.db.exec('SELECT * FROM "todoCategory"') == []
        n_cat = thin.db.exec_sql_query(
            'SELECT COUNT(*) AS n FROM "__message" WHERE "table" = ?',
            ("todoCategory",))[0]["n"]
        assert n_cat > 0, "episode never exercised the deferred leg"
        frontier = thin.db.exec_sql_query(
            'SELECT "rows" FROM "__scope_deferred" WHERE "table" = ?',
            ("todoCategory",))
        assert frontier and frontier[0]["rows"] == n_cat

        # Mid-stream escalation: widen to full, then keep writing.
        thin.worker.post(wmsg.WidenSyncScope(full=True))
        thin.worker.flush()
        assert thin.db.exec_sql_query(
            'SELECT * FROM "__scope_deferred"') == []
        for _ in range(4):
            step(full, True)
        deadline = time.time() + 60
        while True:
            for r in replicas:
                r.sync()
                r.worker.flush()
            if log(full) == log(thin):
                break
            assert time.time() < deadline, \
                "post-escalation convergence failed"
            time.sleep(0.05)
        for r in replicas:
            r._transport.flush()
            r.worker.flush()
        # Byte-identical EVERYWHERE now — the re-materialized table
        # equals the always-materialized one, new writes included.
        assert full.db.exec('SELECT * FROM "todoCategory" ORDER BY "id"') \
            == thin.db.exec('SELECT * FROM "todoCategory" ORDER BY "id"')
        assert full.db.exec('SELECT * FROM "todo" ORDER BY "id"') \
            == thin.db.exec('SELECT * FROM "todo" ORDER BY "id"')
        assert not [e for e in errors if not isinstance(e, SyncError)], \
            "non-livelock error surfaced"
    finally:
        for r in replicas:
            r.dispose()
        for s in servers:
            s.stop()


def test_tensor_crdt_partition_heal_adversarial_clocks_episode():
    """ISSUE 20 satellite (ROADMAP #5 dose): tensor-valued columns
    (sum / mean-by-count / max monoids with overwrite∘delta semidirect
    composition) under regressing/stuttering HLC clocks through a
    2-relay fleet with a partition/heal cycle and a mid-stream
    non-canonical host-bounce. Asserts ELEMENT-EXACT tensor
    convergence against the pure-numpy replay oracle, counter
    exactness for the LWW/counter traffic riding along, winner-cache
    == MAX(timestamp) on the device replica, and (via _evidence)
    `ledger.audit()` returning zero violated equations."""
    with _evidence("model-check-tensor-crdt", 20260807):
        _run_tensor_crdt_episode()


def _run_tensor_crdt_episode():
    import numpy as np

    from evolu_tpu.core import crdt_tensor as tz
    from evolu_tpu.core import crdt_types as ct
    from evolu_tpu.core.merkle import create_initial_merkle_tree
    from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
    from evolu_tpu.core.types import CrdtMessage
    from evolu_tpu.obs import metrics
    from evolu_tpu.utils.config import FleetConfig

    seed = 20260807
    rng = random.Random(seed)
    base = int(time.time() * 1000)

    def adversarial_now(sub_seed):
        r = random.Random(sub_seed)
        state = {"t": base}

        def now():
            roll = r.random()
            if roll < 0.4:
                pass  # stutter: frozen clock
            elif roll < 0.6:
                state["t"] = max(base - 20_000,
                                 state["t"] - r.randrange(0, 10_000))
            else:
                state["t"] += r.randrange(1, 400)
            return state["t"]

        return now

    tensor_cols = {"weights": "tensor:sum:f32:4",
                   "avg": "tensor:mean:f32:2",
                   "peak": "tensor:max:f32:3"}
    schema = {"models": ("name", "clicks:counter", "tags:awset",
                         "steps:list") + tuple(
                             f"{c}:{t}" for c, t in tensor_cols.items())}
    cfgs = {c: tz.parse_tensor_type(t) for c, t in tensor_cols.items()}
    a = RelayServer(RelayStore(), peers=[], replication_interval_s=30).start()
    b = RelayServer(RelayStore(), peers=[], replication_interval_s=30).start()
    fleet_cfg = FleetConfig(relays=(a.url, b.url), replication_factor=1,
                            version=1)
    a.enable_fleet(fleet_cfg)
    b.enable_fleet(fleet_cfg)
    replicas = []
    errors = []
    try:
        r1 = create_evolu(schema, config=Config(sync_url=a.url, backend="tpu"))
        r2 = create_evolu(schema, config=Config(sync_url=b.url, backend="cpu"),
                          mnemonic=r1.owner.mnemonic)
        replicas = [r1, r2]
        for i, r in enumerate(replicas):
            r.worker.now = adversarial_now(seed + i)
            r.subscribe_error(errors.append)
            connect(r)

        # Phase 1 (online): shared rows + overwrite bases, kept in sync.
        rows = []
        expected_sum = {}
        for r in replicas:
            rid = r.create("models", {"name": f"m-{id(r)}"})
            r.worker.flush()
            rows.append(rid)
            expected_sum[rid] = 0
        r1.tensor_set("models", rows[0], "weights", [10.0, 20.0, -5.0, 0.5])
        r1.tensor_set("models", rows[0], "avg", [100.0, 200.0], count=2)
        r1.worker.flush()
        _converge(replicas)
        assert metrics.get_gauge(
            "evolu_crdt_tensor_capability_negotiated") == 1

        def random_step(r, step, online):
            roll = rng.random()
            rid = rng.choice(rows)
            if roll < 0.30:
                col = rng.choice(("weights", "avg", "peak"))
                cfg = cfgs[col]
                vals = [rng.uniform(-25, 25) for _ in range(cfg.size)]
                cnt = rng.randrange(1, 6) if cfg.monoid == "mean" else 1
                r.tensor_delta("models", rid, col, vals, count=cnt)
            elif roll < 0.38:
                # A mid-stream overwrite: resets the fold base, later
                # deltas reapply (the semidirect composition under fire).
                col = rng.choice(("weights", "peak"))
                cfg = cfgs[col]
                r.tensor_set("models", rid, col,
                             [rng.uniform(-25, 25) for _ in range(cfg.size)])
            elif roll < 0.58:
                d = rng.randrange(-50, 51)
                r.increment("models", rid, "clicks", d)
                expected_sum[rid] += d
            elif roll < 0.72:
                r.set_add("models", rid, "tags", rng.choice("abcd"))
            elif roll < 0.80:
                r.set_remove("models", rid, "tags", rng.choice("abcd"))
            elif roll < 0.90:
                r.list_append("models", rid, "steps", f"s{step}")
            else:
                r.update("models", rid, {"name": f"n{step}"})
            r.worker.flush()
            if online and rng.random() < 0.5:
                s = rng.choice(replicas)
                s.sync()
                s.worker.flush()

        for step in range(40):  # online phase
            random_step(rng.choice(replicas), step, online=True)
        _converge(replicas)

        # Phase 2 (PARTITION): no sync rounds; both sides mutate the
        # SAME tensor cells concurrently, including competing overwrites.
        for step in range(40, 72):
            random_step(replicas[step % 2], step, online=False)

        # Mid-partition hostile case: a NON-CANONICAL (uppercase node
        # hex) remote batch — the LWW cell bounces the device planner
        # to the host oracle, and a tensor op in the SAME batch proves
        # the tensor leg is canonicalization-blind (host raw-string
        # ordering; the device never sees a timestamp). Injected into
        # BOTH replicas so the merged histories stay identical.
        bounces_before = metrics.get_counter("evolu_merge_host_fallbacks_total")
        empty_tree = merkle_tree_to_string(create_initial_merkle_tree())

        def nc_ts(i):
            s = timestamp_to_string(
                Timestamp(base + 5000 + i, i, "00000000000000ab"))
            return s[:30] + s[30:].upper()

        hostile = tuple(
            [CrdtMessage(nc_ts(j), "models", "remrow", "name", f"h{j}")
             for j in range(3)]
            + [CrdtMessage(nc_ts(7), "models", "remrow", "weights",
                           tz.tensor_delta_value(
                               cfgs["weights"], [1.0, 2.0, 3.0, 4.0]))])
        for r in replicas:
            r.receive(hostile, empty_tree)
            r.worker.flush()
        assert metrics.get_counter(
            "evolu_merge_host_fallbacks_total") > bounces_before

        # Phase 3 (HEAL): sync rounds resume.
        _converge(replicas)
        for r in replicas:
            r._transport.flush()
            r.worker.flush()

        from evolu_tpu.core.types import SyncError
        real = [e for e in errors if not isinstance(e, SyncError)]
        assert not real, real

        dumps = []
        for r in replicas:
            dumps.append((
                r.db.exec('SELECT * FROM "__message" ORDER BY "timestamp"'),
                r.db.exec('SELECT * FROM "models" ORDER BY "id"'),
                r.db.exec('SELECT * FROM "__crdt_tensor" ORDER BY "tag","column"'),
                r.db.exec('SELECT * FROM "__crdt_counter" ORDER BY "row","column"'),
                r.db.exec('SELECT * FROM "__crdt_set" ORDER BY "tag"'),
                r.db.exec('SELECT * FROM "__crdt_list" ORDER BY "tag"'),
            ))
        assert dumps[0] == dumps[1], "state diverged after partition/heal"

        # ELEMENT-EXACT tensor convergence: every materialized tensor
        # cell equals the pure-numpy replay of the merged log, bit for
        # bit (the any-permutation acceptance bar, end to end).
        log_rows = r1.db.exec_sql_query(
            'SELECT "timestamp", "table", "row", "column", "value" '
            'FROM "__message" WHERE "table" = ?', ("models",))
        types = {("models", c): t for c, t in tensor_cols.items()}
        oracle = tz.replay_log(types, [
            CrdtMessage(r["timestamp"], r["table"], r["row"], r["column"],
                        r["value"]) for r in log_rows])
        assert oracle, "episode produced no tensor traffic"
        folded_cells = 0
        for (table, rid, col), expected in oracle.items():
            for r in replicas:
                got = tz.tensor_state(r.db, table, rid, col)
                assert got is not None and got.tobytes() == expected, \
                    (rid, col)
            folded_cells += 1
        assert folded_cells >= 4  # the schedule exercised several cells
        # The non-canonical tensor delta folded into its own cell.
        assert np.array_equal(
            tz.tensor_state(r1.db, "models", "remrow", "weights"),
            np.asarray([1.0, 2.0, 3.0, 4.0], np.float32))

        # Counter EXACTNESS rides along undisturbed.
        for rid, total in expected_sum.items():
            got = r1.db.exec_sql_query(
                'SELECT "clicks" FROM "models" WHERE "id" = ?', (rid,)
            )[0]["clicks"]
            assert got == total, (rid, got, total)

        # Fold integrity: rebuilding from the full log is a no-op.
        schema_r1 = ct.load_schema(r1.db)
        before = r1.db.exec('SELECT * FROM "__crdt_tensor" ORDER BY "tag"')
        ct.rebuild_state(r1.db, schema_r1)
        assert r1.db.exec(
            'SELECT * FROM "__crdt_tensor" ORDER BY "tag"') == before

        # Winner-cache == MAX(timestamp) on the device replica.
        cache = r1.worker._planner.cache
        w1 = np.asarray(cache._w1)
        w2 = np.asarray(cache._w2)
        for (table, rr, col), slot in cache._slots.items():
            got = r1.db.exec_sql_query(
                'SELECT MAX("timestamp") AS m FROM "__message" '
                'WHERE "table" = ? AND "row" = ? AND "column" = ?',
                (table, rr, col))[0]["m"]
            k1, k2 = int(w1[slot]), int(w2[slot])
            if k1 == 0 and k2 == 0:
                assert got is None, (table, rr, col)
                continue
            cached_ts = timestamp_to_string(
                Timestamp(k1 >> 16, k1 & 0xFFFF, f"{k2:016x}"))
            assert cached_ts == got, (table, rr, col)
    finally:
        for r in replicas:
            r.dispose()
        a.stop()
        b.stop()
