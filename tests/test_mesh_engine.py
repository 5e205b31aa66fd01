"""Mesh-sharded engine (ISSUE 12, parallel/mesh.py::MeshContext):
one shard_map pass reconciles every owner across the device mesh with
STABLE owner→device placement. Gates: sharded `run_batch_wire`
responses + SQLite end state byte-identical to the SINGLE-DEVICE
engine; jit caches flat across varying batch sizes within a bucket
(the fused-seed recompile trap); the mesh-sharded winner cache plans
identically to the single-device cache and holds slot == SQLite
MAX(timestamp) per shard; the `evolu_mesh_*` obs family and the relay
`/stats` mesh section are live; the sharded path is config-selectable
and DEFAULT-OFF."""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from evolu_tpu.core.merkle import merkle_tree_to_string
from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.core.types import CrdtMessage
from evolu_tpu.obs import metrics
from evolu_tpu.parallel.mesh import MeshContext, create_mesh, owner_shard
from evolu_tpu.server.relay import RelayServer, ShardedRelayStore
from evolu_tpu.sync import protocol

BASE = 1_700_000_000_000


def _msgs(node: str, start: int, n: int, step_ms: int = 1000):
    return tuple(
        protocol.EncryptedCrdtMessage(
            timestamp_to_string(Timestamp(BASE + (start + i) * step_ms, 0, node)),
            b"ct-%d" % (start + i),
        )
        for i in range(n)
    )


from tests.conftest import relay_store_dump as _store_dump  # noqa: E402


def _request_rounds(owners: int, rounds: int):
    """Deterministic multi-round traffic: per round, every owner pushes
    a partially-overlapping window (duplicates exercise the was-new
    correction) and pulls against an empty client tree (a non-trivial
    diff response that streams stored messages)."""
    out = []
    for rnd in range(rounds):
        reqs = []
        for i in range(owners):
            node = f"{i + 1:016x}"
            reqs.append(protocol.SyncRequest(
                _msgs(node, rnd * 4, 6 + (i % 5)), f"mesh-u{i:03d}", node, "{}"
            ))
        out.append(tuple(reqs))
    return out


def test_sharded_run_batch_wire_byte_identical_to_single_device_engine():
    """THE parity gate: the 8-device sharded pass must serve the exact
    bytes — and commit the exact SQLite end state — of a single-device
    engine, round after round (overlapping pushes included)."""
    from evolu_tpu.server.engine import BatchReconciler

    sharded_store = ShardedRelayStore(shards=4)
    single_store = ShardedRelayStore(shards=4)
    eng = BatchReconciler(sharded_store, mesh_ctx=MeshContext())
    oracle = BatchReconciler(single_store, mesh=create_mesh(1))
    assert eng.mesh.devices.size >= 8, "conftest must supply the 8-device mesh"
    try:
        for reqs in _request_rounds(owners=13, rounds=3):
            assert eng.run_batch_wire(reqs) == oracle.run_batch_wire(reqs)
        assert _store_dump(sharded_store) == _store_dump(single_store)
    finally:
        eng.close()
        oracle.close()
        sharded_store.close()
        single_store.close()


def test_stable_placement_is_stable_and_owner_sharded():
    """Placement is a pure function (same owner → same device across
    contexts and batches) and hot-owner chunks spill round-robin from
    the owner's home shard."""
    ctx = MeshContext()
    assert ctx.n_shards >= 8
    for o in ("alice", "bob", "user-123"):
        assert ctx.place(o) == owner_shard(o, ctx.n_shards) == MeshContext().place(o)
    shards = ctx.assign_stable({("hot", 0): 10, ("hot", 1): 10, ("cold", 0): 1})
    home = ctx.place("hot")
    assert ("hot", 0) in shards[home]
    assert ("hot", 1) in shards[(home + 1) % ctx.n_shards]
    assert ("cold", 0) in shards[ctx.place("cold")]


def test_sharded_engine_jit_cache_flat_within_bucket():
    """The recompile fence for the sharded pipeline (satellite 2):
    varying batch sizes inside one power-of-two row bucket must not
    add jit-cache entries (the fused-seed negative-result trap —
    docs/BENCHMARKS.md)."""
    from evolu_tpu.server import engine as eng_mod
    from evolu_tpu.server.engine import BatchReconciler

    store = ShardedRelayStore(shards=2)
    eng = BatchReconciler(store, mesh_ctx=MeshContext())
    try:
        # Warm-up compiles the sharded kernels for the smallest bucket.
        eng.run_batch_wire([protocol.SyncRequest(
            _msgs("a" * 16, 0, 3), "jit-warm", "a" * 16, "{}")])
        size0 = eng_mod.merkle_jit_cache_size()
        assert size0 > 0, "warm-up must have compiled the Merkle kernel"
        for i, n in enumerate((1, 2, 4, 6)):  # all inside the 64-row bucket
            eng.run_batch_wire([protocol.SyncRequest(
                _msgs(f"{i + 0x70:016x}", 0, n), f"jit-m{i}",
                f"{i + 0x70:016x}", "{}")])
        assert eng_mod.merkle_jit_cache_size() == size0, (
            "a varying micro-batch size recompiled the sharded pipeline"
        )
    finally:
        eng.close()
        store.close()


def test_reconcile_owner_batches_stable_placement_parity():
    """The client/pod multi-owner reconcile under stable placement must
    produce the same per-owner plans, deltas, and digest as the LPT
    layout (the decoders are layout-agnostic — pinned here)."""
    from evolu_tpu.core.types import CrdtMessage
    from evolu_tpu.parallel.reconcile import reconcile_owner_batches

    mesh = create_mesh()
    batches = {}
    for o in range(10):
        node = f"{o + 1:016x}"
        batches[f"own{o}"] = [
            CrdtMessage(
                timestamp_to_string(Timestamp(BASE + i * 1000, 0, node)),
                "todo", f"r{i % 3}", "title", f"v{o}-{i}",
            )
            for i in range(5 + o)
        ]
    lpt, digest_lpt = reconcile_owner_batches(mesh, batches, {})
    stable, digest_stable = reconcile_owner_batches(
        mesh, batches, {}, mesh_ctx=MeshContext(mesh)
    )
    assert digest_lpt == digest_stable
    assert lpt.keys() == stable.keys()
    for o in lpt:
        assert lpt[o][0] == stable[o][0]  # xor masks
        assert lpt[o][1] == stable[o][1]  # upserts
        assert lpt[o][2] == stable[o][2]  # minute deltas


# -- the mesh-sharded winner cache --


def _client_db():
    from evolu_tpu.storage.native import open_database
    from evolu_tpu.storage.schema import init_db_model

    db = open_database(":memory:", "auto")
    init_db_model(db, mnemonic=None)
    db.exec('CREATE TABLE "todo" ("id" TEXT PRIMARY KEY, "title" BLOB, "done" BLOB)')
    return db


def _mk(i, node="a1b2c3d4e5f60718", row=None, col="title", value=None):
    return CrdtMessage(
        timestamp_to_string(Timestamp(BASE + i * 977, i % 4, node)),
        "todo", row or f"r{i % 23}", col, value if value is not None else f"v{i}",
    )


def test_mesh_sharded_winner_cache_parity_growth_and_shard_audit():
    """The sharded slot arrays must plan bit-identically to the
    single-device cache across overlapping batches (growth forced by a
    tiny initial capacity), keep cells spread over devices, and hold
    slot == SQLite MAX(timestamp) PER SHARD (the audit runs through the
    sharded gather; a per-shard sweep re-audits each placement group)."""
    from evolu_tpu.ops.winner_cache import DeviceWinnerCache, MeshShardedWinnerCache
    from evolu_tpu.storage.apply import apply_messages

    rng = np.random.default_rng(12)
    db_a, db_b = _client_db(), _client_db()
    ctx = MeshContext()
    cache_a = DeviceWinnerCache(db_a, capacity=64)
    cache_b = MeshShardedWinnerCache(db_b, mesh_ctx=ctx, capacity=16)
    tree_a, tree_b = {}, {}

    def _dump(db):
        return (db.exec('SELECT * FROM "__message" ORDER BY "timestamp"'),
                db.exec('SELECT * FROM "todo" ORDER BY "id"'))

    try:
        for batch_no in range(4):
            order = rng.permutation(130)
            batch = tuple(_mk(int(i) + batch_no * 40) for i in order)
            tree_a = apply_messages(db_a, tree_a, batch, planner=cache_a.plan_batch)
            tree_b = apply_messages(db_b, tree_b, batch, planner=cache_b.plan_batch)
            assert _dump(db_a) == _dump(db_b), f"batch {batch_no}"
            assert merkle_tree_to_string(tree_a) == merkle_tree_to_string(tree_b)
        counts = cache_b.shard_slot_counts()
        assert sum(counts) == len(cache_b._slots)
        assert sum(1 for c in counts if c) >= 4, (
            f"cells did not spread over the mesh: {counts}"
        )
        # Whole-cache audit through the sharded gather, then per shard.
        assert cache_b.verify_against_db() == len(cache_b._slots)
        by_shard = {}
        for cell, slot in cache_b._slots.items():
            by_shard.setdefault(slot % cache_b.n_shards, []).append(cell)
        for si, cells in by_shard.items():
            for c in cells:
                assert cache_b._cell_shard(c) == si
        # Invalidation releases slots back to the owning shard only.
        victims = list(cache_b._slots)[:4]
        victim_shards = [cache_b._slots[c] % cache_b.n_shards for c in victims]
        cache_b.invalidate(victims)
        for si in victim_shards:
            assert cache_b._free_by_shard[si], "freed slot not returned per shard"
        batch = tuple(_mk(int(i)) for i in range(50))
        tree_a = apply_messages(db_a, tree_a, batch, planner=cache_a.plan_batch)
        tree_b = apply_messages(db_b, tree_b, batch, planner=cache_b.plan_batch)
        assert _dump(db_a) == _dump(db_b)
        assert cache_b.verify_against_db() == len(cache_b._slots)
        # The foreign-write reset gate must see per-shard FREED slots
        # even when nothing is live (review finding: the base gate read
        # `_free`, which the sharded subclass never populates).
        cache_b.invalidate(list(cache_b._slots))
        assert not cache_b._slots and any(cache_b._free_by_shard)
        assert cache_b._has_slot_state() is True
    finally:
        db_a.close()
        db_b.close()


def test_mesh_sharded_cache_jit_flat_within_bucket():
    """Satellite 2, cache half: `mesh_jit_cache_size` must stay flat
    across varying batch sizes within one bucket."""
    from evolu_tpu.ops.winner_cache import MeshShardedWinnerCache, mesh_jit_cache_size
    from evolu_tpu.storage.apply import apply_messages

    db = _client_db()
    # adaptive=False pins the cached path: the adaptive gate streams
    # first-contact batches (rate 1.0 > seed_hi), which would leave the
    # sharded kernels uncompiled and the fence vacuous.
    cache = MeshShardedWinnerCache(db, mesh_ctx=MeshContext(), capacity=256,
                                   adaptive=False)
    tree = {}
    try:
        tree = apply_messages(db, tree, tuple(_mk(i) for i in range(40)),
                              planner=cache.plan_batch)
        size0 = mesh_jit_cache_size()
        assert size0 > 0, "warm-up must have compiled the sharded cache kernels"
        for n in (3, 11, 23, 40):  # same per-shard bucket as the warm-up
            tree = apply_messages(db, tree, tuple(_mk(i) for i in range(n)),
                                  planner=cache.plan_batch)
        assert mesh_jit_cache_size() == size0, (
            "a varying batch size recompiled the sharded winner-cache kernels"
        )
    finally:
        db.close()


def test_worker_selects_sharded_cache_only_when_configured():
    """Config selection: default OFF (DeviceWinnerCache), mesh_engine
    → MeshShardedWinnerCache on a multi-device host."""
    from evolu_tpu.ops.winner_cache import DeviceWinnerCache, MeshShardedWinnerCache
    from evolu_tpu.runtime.worker import select_planner
    from evolu_tpu.utils.config import Config

    db = _client_db()
    try:
        default = select_planner(Config(backend="tpu"), db)
        assert type(default.cache) is DeviceWinnerCache
        sharded = select_planner(Config(backend="tpu", mesh_engine=True), db)
        assert type(sharded.cache) is MeshShardedWinnerCache
    finally:
        db.close()


# -- relay wiring + observability --


def test_relay_mesh_engine_default_off_and_env_override(monkeypatch):
    server = RelayServer(ShardedRelayStore(shards=1))
    try:
        assert server.mesh_engine is False
        assert server.scheduler is None  # default path untouched
    finally:
        server.store.close()
    monkeypatch.setenv("EVOLU_MESH_ENGINE", "1")
    server = RelayServer(ShardedRelayStore(shards=1))
    try:
        assert server.mesh_engine is True
        assert server.scheduler is not None  # implies batching
    finally:
        server.scheduler.stop()
        server.store.close()
    monkeypatch.setenv("EVOLU_MESH_ENGINE", "0")
    server = RelayServer(ShardedRelayStore(shards=1))
    try:
        assert server.mesh_engine is False
    finally:
        server.store.close()


def test_mesh_obs_family_and_stats_section():
    """Driving a sync through a mesh_engine relay must populate the
    `evolu_mesh_*` family and surface the /stats `mesh` section
    (devices gauge, dispatch counter, occupancy/padding histograms,
    cross-device reduce counters — docs/OBSERVABILITY.md)."""
    store = ShardedRelayStore(shards=2)
    server = RelayServer(store, mesh_ctx=MeshContext()).start()
    try:
        body = protocol.encode_sync_request(
            protocol.SyncRequest(_msgs("d" * 16, 0, 9), "obs-u", "d" * 16, "{}")
        )
        with urllib.request.urlopen(
            urllib.request.Request(
                server.url, data=body,
                headers={"Content-Type": "application/octet-stream"},
            ),
            timeout=60,
        ) as r:
            r.read()
        assert metrics.get_gauge("evolu_mesh_devices") >= 8
        assert metrics.get_counter("evolu_mesh_dispatches_total") > 0
        assert metrics.get_counter(
            "evolu_mesh_xdev_reduce_total", kind="digest") > 0
        with urllib.request.urlopen(server.url + "/stats", timeout=30) as r:
            stats = json.loads(r.read())
        mesh = stats["mesh"]
        assert mesh["devices"] >= 8
        assert mesh["dispatches_total"] > 0
        assert mesh["shard_rows"]["count"] > 0
        assert mesh["padding_waste_rows"]["count"] > 0
        assert mesh["xdev_reduce_total"]["digest"] > 0
    finally:
        server.stop()
        store.close()


def test_occupancy_counters_move_by_rows_and_slots_under_one_lock(monkeypatch):
    """A sharded dispatch's occupancy as a ratio of two counter deltas
    (ISSUE 35): `evolu_mesh_rows_total` moves by the rows laid out,
    `evolu_mesh_slot_rows_total` by devices x bucket, beside one
    observation a device in each histogram and one dispatch, and
    `record_occupancy` takes the registry's lock once for all of it."""
    import threading

    ctx = MeshContext(n_devices=4)

    def reading():
        shard_rows = metrics.registry.get_histogram("evolu_mesh_shard_rows")
        waste = metrics.registry.get_histogram("evolu_mesh_padding_waste_rows")
        return {
            "rows": metrics.get_counter("evolu_mesh_rows_total"),
            "slots": metrics.get_counter("evolu_mesh_slot_rows_total"),
            "dispatches": metrics.get_counter("evolu_mesh_dispatches_total"),
            "shard_rows": (shard_rows[2], shard_rows[3]) if shard_rows else (0.0, 0),
            "waste": (waste[2], waste[3]) if waste else (0.0, 0),
        }

    class CountingLock:
        def __init__(self):
            self.lock, self.taken = threading.Lock(), 0

        def __enter__(self):
            self.taken += 1
            return self.lock.__enter__()

        def __exit__(self, *exc):
            return self.lock.__exit__(*exc)

    before = reading()
    counting = CountingLock()
    monkeypatch.setattr(metrics.registry, "_lock", counting)
    ctx.record_occupancy([61_700, 63_300, 62_500, 0], 65_536)
    assert counting.taken == 1
    monkeypatch.undo()
    after = reading()
    assert after["rows"] - before["rows"] == 187_500
    assert after["slots"] - before["slots"] == 4 * 65_536
    assert after["dispatches"] - before["dispatches"] == 1
    assert after["shard_rows"][1] - before["shard_rows"][1] == 4
    assert after["shard_rows"][0] - before["shard_rows"][0] == 187_500
    assert after["waste"][1] - before["waste"][1] == 4
    assert after["waste"][0] - before["waste"][0] == 4 * 65_536 - 187_500
    # row counts, not milliseconds: the family keeps the count buckets
    # whichever call observed it first
    assert metrics.registry.get_histogram("evolu_mesh_shard_rows")[0] == metrics.COUNT_BUCKETS

    # An engine pass on the mesh posts exactly that once a dispatch.
    store = ShardedRelayStore(shards=2)
    from evolu_tpu.server.engine import BatchReconciler

    engine = BatchReconciler(store, mesh_ctx=ctx)
    try:
        requests = [protocol.SyncRequest(_msgs(f"{i + 1:016x}", 0, 5 + i), f"occ-u{i:02d}",
                                         f"{i + 1:016x}", "{}") for i in range(12)]
        before = reading()
        engine.reconcile(requests)
        after = reading()
    finally:
        engine.close()
        store.close()
    rows = sum(len(r.messages) for r in requests)
    assert after["dispatches"] - before["dispatches"] == 1
    assert after["rows"] - before["rows"] == rows
    slots = after["slots"] - before["slots"]
    assert slots % 4 == 0 and slots >= rows and (slots // 4) & (slots // 4 - 1) == 0


def test_non_canonical_batch_bounces_before_side_effect_on_sharded_path():
    """The r5 contract, kept on the sharded path: a non-canonical
    timestamp width never enters a packed sharded batch — it dispatches
    as a singleton through the host-oracle route (and the response
    still serves)."""
    store = ShardedRelayStore(shards=2)
    server = RelayServer(store, mesh_ctx=MeshContext()).start()
    try:
        good = timestamp_to_string(Timestamp(BASE, 0, "e" * 16))
        bad_req = protocol.SyncRequest(
            (protocol.EncryptedCrdtMessage(good + "Z", b"x"),),
            "nc-u", "e" * 16, "{}",
        )
        body = protocol.encode_sync_request(bad_req)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                urllib.request.Request(
                    server.url, data=body,
                    headers={"Content-Type": "application/octet-stream"},
                ),
                timeout=60,
            )
        # Same answer the per-request relay gives (the storage-layer
        # timestamp parse, not the wire decoder, is what rejects the
        # width) — the sharded path must not change the error surface.
        assert ei.value.code == 500
        assert all(
            s.db.exec_sql_query('SELECT COUNT(*) AS n FROM "message"', ())[0]["n"] == 0
            for s in store.shards
        )
    finally:
        server.stop()
        store.close()
