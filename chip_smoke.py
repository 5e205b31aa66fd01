"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the
sizes BASELINE.json names, on the attached TPU, and checks every phase
against a plain host reference outside its timed region:

- relay   (BASELINE config 3): 1M real ciphertexts across 1k owners, loaded
  through `BatchReconciler.reconcile` / `reconcile_stream` into a native
  `ShardedRelayStore`, then served by `RelayServer(batching=True)` to a real
  `SyncTransport` over HTTP (push, steady-state round, cold sync);
- client  (BASELINE config 2): 100k messages of the 3-table schema received
  by a `DbWorker(Config(backend="tpu"))` as `PackedReceive` batches — the HBM
  winner-cache route and the packed SQLite apply — against a
  `Config(backend="cpu")` worker on stdlib SQLite;
- kernel  (the bench shape): `reconcile_columns_sharded` at 1M rows / 1k
  owners with stored winners, masks and digest against a Python recompute.

`--chips 4` runs ONLY what exists across chips, with what it is compared
with: the mesh-sharded engine against its one-device twin, the mesh-sharded
winner cache against the host oracle, and the sharded kernel with its XOR
all-reduce against the Python recompute.

One process, one `import jax`; no child that needs the chip. No phase has a
fallback: the smoke fails unless JAX is on a TPU, the native libraries were
rebuilt from source and loaded, the Pallas scan route was the one compiled at
N >= 2^15, and no host-fallback counter moved. Data is made from `--seed`.
Each phase prints one JSON line (rows, seconds, compiles, routes, cache
counters); seconds are information, not results. The LAST line of stdout is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}` and is
printed only when every phase passed.

The phase functions take their sizes as arguments and never ask which
backend they run on, so tests/test_chip_smoke.py rehearses them tiny on the
CPU; the device assertions live in `main()`.
"""

import argparse
import itertools
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# The workers' wall clock starts past every generated timestamp and steps a
# second per command: a Receive samples it once, and the reference's HLC merge
# bumps the counter for every message older than "now" — more than 65,535 such
# messages inside one clock reading is a counter overflow by design.
_NOW_MILLIS = 1_700_010_000_000
_CLIENT_TABLES = (
    ("todo", ("title", "isCompleted", "categoryId")),
    ("todoCategory", ("name",)),
    ("todoNote", ("text",)),
)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


# --------------------------------------------------------------------
# What the process counts: compiles, routes, caches, device memory
# --------------------------------------------------------------------


class CompileLog:
    """Counts XLA compiles and persistent-cache traffic through
    `jax.monitoring` — the fact a cold chip run turns on: a program that
    hits the cache "compiles" in the time it takes to read it back."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        self.programs = []  # (fun_name, seconds) of every compile >= 1 s
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += seconds
            if seconds >= 1.0:
                self.programs.append((str(kw.get("fun_name")), round(seconds, 1)))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def mark(self):
        return (self.compiles, self.compile_s, self.cache_hits,
                self.cache_writes, len(self.programs))

    def since(self, mark) -> dict:
        c, s, h, w, p = mark
        return {
            "compiles": self.compiles - c,
            "compile_s": round(self.compile_s - s, 2),
            "persistent_cache_hits": self.cache_hits - h,
            "persistent_cache_writes": self.cache_writes - w,
            "programs_over_1s": self.programs[p:],
        }


def counters(name: str) -> dict:
    """{label-string: value} of one counter family (the registry reads
    by exact label set; the smoke wants the whole family)."""
    from evolu_tpu.obs import metrics

    fam = metrics.snapshot()["counters"].get(name, [])
    return {
        ",".join(f"{k}={v}" for k, v in sorted(e["labels"].items())): e["value"]
        for e in fam
    }


def total(name: str) -> float:
    return sum(counters(name).values())


def observed() -> dict:
    """Routes, fallbacks, cache counters and jit-cache sizes as they
    stand now (cumulative over the process; phases print them as they
    go, `main` asserts on the final state)."""
    from evolu_tpu.ops import merge, winner_cache
    from evolu_tpu.parallel import reconcile
    from evolu_tpu.server import engine

    return {
        "scan_route": counters("evolu_merge_scan_total"),
        "plan_path": counters("evolu_merge_plan_total"),
        "reconcile_kernel": counters("evolu_reconcile_kernel_total"),
        "apply_route": counters("evolu_apply_batches_total"),
        "host_fallbacks": {
            "merge": total("evolu_merge_host_fallbacks_total"),
            "winner_cache": total("evolu_winner_cache_host_fallbacks_total"),
            "reconcile_owners": total("evolu_reconcile_host_owner_fallbacks_total"),
            "packed_bounces": total("evolu_apply_packed_bounces_total"),
            "native_load_failures": total("evolu_native_load_failures_total"),
        },
        "winner_cache": {
            "hits": total("evolu_winner_cache_hits_total"),
            "seeded": total("evolu_winner_cache_seeded_cells_total"),
            "streamed": total("evolu_winner_cache_streamed_cells_total"),
            "grows": total("evolu_winner_cache_grows_total"),
            "mode_switches": counters("evolu_winner_cache_mode_switches_total"),
        },
        "jit_cache": {
            **engine.observe_jit_caches(),
            "plan_full": merge._plan_full_kernel._cache_size(),
            "cached_plan": winner_cache._cached_plan_kernel._cache_size(),
            "seed": winner_cache._seed_kernel._cache_size(),
            "shard_kernels": reconcile._compiled_kernel.cache_info().currsize,
        },
    }


def assert_no_fallback(obs: dict) -> None:
    """The data is canonical and the native libraries are built from
    source: any fallback counter that moved is a hidden device or
    host-path failure."""
    moved = {k: v for k, v in obs["host_fallbacks"].items() if v}
    if moved:
        raise AssertionError(f"fallback counters moved: {moved}")


def peak_device_bytes():
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            return "not reported"
        peaks.append(int(stats["peak_bytes_in_use"]))
    return peaks


def live_children() -> list:
    """PIDs of this process's live children (Linux /proc). The smoke
    runs the relay in-process with one worker: a child here would be a
    second process reaching for the chip."""
    pids = []
    task_dir = f"/proc/{os.getpid()}/task"
    for tid in os.listdir(task_dir):
        try:
            with open(f"{task_dir}/{tid}/children") as f:
                pids.extend(int(p) for p in f.read().split())
        except OSError:
            continue
    return pids


def rebuild_native() -> None:
    """`make -B`: both .so files are git-ignored and rebuilt from the
    tracked sources, so nothing stale from a working tree is loaded."""
    subprocess.run(
        ["make", "-B", "-s", "-C", os.path.join(REPO, "native")],
        check=True, timeout=600,
    )


def cache_dir_entries() -> tuple:
    import jax

    path = jax.config.jax_compilation_cache_dir
    n = len(os.listdir(path)) if path and os.path.isdir(path) else 0
    return path, n


# --------------------------------------------------------------------
# Phase: relay (BASELINE config 3)
# --------------------------------------------------------------------


def _owner_dump(store, owner: str):
    """(message rows, merkleTree row) of one owner, cross-backend
    comparable (dict rows, bytes content)."""
    return (
        store.db.exec_sql_query(
            'SELECT "timestamp", "userId", "content" FROM "message" '
            'WHERE "userId" = ? ORDER BY "timestamp"', (owner,)),
        store.db.exec_sql_query(
            'SELECT "userId", "merkleTree" FROM "merkleTree" WHERE "userId" = ?',
            (owner,)),
    )


def _chunks(requests, batches: int):
    per = -(-len(requests) // batches)
    return [requests[i:i + per] for i in range(0, len(requests), per)]


def relay_phase(seed: int, compiles: CompileLog, n_messages: int = 1_000_000,
                owners: int = 1_000, batches: int = 4, push: int = 64,
                sample: int = 8) -> dict:
    from benchmarks.config3_server_reconcile import MNEMONIC, build_requests
    from evolu_tpu.core.merkle import (
        apply_prefix_xors, merkle_tree_from_string, merkle_tree_to_string,
        minute_deltas_host)
    from evolu_tpu.core.packed import PackedReceive
    from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
    from evolu_tpu.core.types import CrdtMessage, Owner
    from evolu_tpu.parallel.mesh import create_mesh
    from evolu_tpu.runtime.messages import SyncRequestInput
    from evolu_tpu.server.engine import BatchReconciler
    from evolu_tpu.server.relay import RelayServer, RelayStore, ShardedRelayStore
    from evolu_tpu.sync import protocol
    from evolu_tpu.sync.client import SyncTransport, _http_post
    from evolu_tpu.utils.config import Config

    mark = compiles.mark()
    # Set-up: the requests carry each owner's host-fold tree (the
    # client's own post-apply tree), which is also the reference every
    # stored tree is held to below.
    requests = build_requests(n=n_messages, owners=owners, seed=seed)
    n_msgs = sum(len(r.messages) for r in requests)
    chunks = _chunks(requests, batches)

    store = ShardedRelayStore(":memory:", "native", shards=8)
    engine = BatchReconciler(store, mesh=create_mesh(1))
    t0 = time.perf_counter()
    first = engine.reconcile(chunks[0])
    t1 = time.perf_counter()
    warm = engine.reconcile(chunks[1]) if len(chunks) > 1 else []
    t2 = time.perf_counter()
    streamed = engine.reconcile_stream(chunks[2:])
    t3 = time.perf_counter()
    engine.close()
    responses = first + warm + [r for batch in streamed for r in batch]
    assert len(responses) == len(requests)
    for req, resp in zip(requests, responses):
        assert resp.messages == (), f"steady-state load answered rows for {req.user_id}"
        assert resp.merkle_tree == req.merkle_tree, f"tree != host fold: {req.user_id}"

    # A real client against the live relay, same process, one worker.
    http_owner = requests[0]
    history = [m.timestamp for m in http_owner.messages]
    node = "00000000000c11e7"
    base = 1_700_000_000_000 + n_messages // 16 + 60_000
    fresh = tuple(
        CrdtMessage(timestamp_to_string(Timestamp(base + j, 0, node)),
                    "todo", f"row{j:04d}", "title", f"pushed {j}")
        for j in range(push))
    deltas, _ = minute_deltas_host(m.timestamp for m in fresh)
    pushed_tree = merkle_tree_to_string(apply_prefix_xors(
        merkle_tree_from_string(http_owner.merkle_tree), deltas))

    bodies, received, errors = [], [], []

    def post(url, body, headers=None):
        bodies.append(body)
        return _http_post(url, body, headers=headers)

    server = RelayServer(store, batching=True).start()
    transport = SyncTransport(
        Config(sync_url=server.url),
        on_receive=lambda messages, tree, prev: received.append((messages, tree)),
        on_error=errors.append, http_post=post)
    owner = Owner(id=http_owner.user_id, mnemonic=MNEMONIC)
    t4 = time.perf_counter()
    try:
        rounds = (
            # push with new messages: the client's tree already holds them
            SyncRequestInput(fresh, fresh[-1].timestamp, pushed_tree, owner),
            # steady state: nothing to send, nothing to fetch
            SyncRequestInput((), fresh[-1].timestamp, pushed_tree, owner),
            # cold sync: a restored device (other node, empty tree)
            SyncRequestInput((), timestamp_to_string(
                Timestamp(base, 0, "e" * 16)), "{}", owner),
        )
        for r in rounds:
            transport.request_sync(r)
            transport.flush()
    finally:
        transport.stop()
    t5 = time.perf_counter()
    assert not errors, f"sync transport errors: {errors!r}"
    assert len(received) == 3, f"expected 3 answered rounds, got {len(received)}"
    for messages, tree in received[:2]:
        assert len(messages) == 0 and tree == pushed_tree
    cold, cold_tree = received[2]
    assert isinstance(cold, PackedReceive), "cold sync left the packed receive leg"
    assert cold_tree == pushed_tree
    assert sorted(cold.timestamp_strings()) == sorted(
        history + [m.timestamp for m in fresh]), "cold sync != the owner's history"
    children = live_children()
    assert not children, f"the relay phase left child processes: {children}"

    # The plain reference, outside every timed region: all stored trees
    # against the host fold, the row count, and sampled owners' full
    # dumps against a fresh single RelayStore (stdlib SQLite, pure-Python
    # hashing) replaying the same requests through RelayStore.sync.
    stored_trees = dict(store.owner_trees())
    assert len(stored_trees) == len(requests)
    for req in requests[1:]:
        assert stored_trees[req.user_id] == req.merkle_tree
    assert stored_trees[http_owner.user_id] == pushed_tree
    stored = sum(s.stats()[0]["messages"] for s in store.shards)
    assert stored == n_msgs + push, (stored, n_msgs + push)
    twin = RelayStore(":memory:", "python")
    sampled = requests[:sample]
    for req in sampled:
        twin.sync(req)
    for body in bodies:
        twin.sync(protocol.decode_sync_request(body))
    for req in sampled:
        assert _owner_dump(store.shard_of(req.user_id), req.user_id) == \
            _owner_dump(twin, req.user_id), f"dump != RelayStore.sync twin: {req.user_id}"
    twin.close()
    backend = type(store.shards[0].db).__name__
    server.stop()  # closes the store

    obs = observed()
    assert_no_fallback(obs)
    return {
        "phase": "relay", "messages": n_msgs, "owners": len(requests),
        "storage_backend": backend, "storage_shards": 8,
        "load_batches": len(chunks),
        "seconds": {
            "load_first_batch": round(t1 - t0, 3),
            "load_warm_batch": round(t2 - t1, 3),
            "compile_first_minus_warm": round((t1 - t0) - (t2 - t1), 3),
            "load_streamed_rest": round(t3 - t2, 3),
            "http_rounds": round(t5 - t4, 3),
        },
        "http": {"pushed": push, "cold_sync_messages": len(cold),
                 "relay_processes": 1, "child_processes": len(children)},
        "checked": {"owner_trees_vs_host_fold": len(requests),
                    "stored_rows": stored,
                    "owner_dumps_vs_relaystore_sync_twin": len(sampled)},
        "compile": compiles.since(mark),
        "peak_device_bytes": peak_device_bytes(),
        **obs,
    }


# --------------------------------------------------------------------
# Phase: client (BASELINE config 2)
# --------------------------------------------------------------------


def _client_state(db) -> dict:
    """Everything the apply leaves behind. The clock's node id is drawn
    at random per database, so the clock compares as (millis+counter,
    tree); everything else compares byte for byte."""
    state = {
        "__message": db.exec_sql_query(
            'SELECT * FROM "__message" ORDER BY "timestamp", "table", "row", "column"', ()),
        "__clock": [
            (r["timestamp"][:29], r["merkleTree"])
            for r in db.exec_sql_query('SELECT "timestamp", "merkleTree" FROM "__clock"', ())
        ],
        "__owner": db.exec_sql_query('SELECT * FROM "__owner"', ()),
    }
    for table, _cols in _CLIENT_TABLES:
        state[table] = db.exec_sql_query(f'SELECT * FROM "{table}" ORDER BY "id"', ())
    return state


def _run_worker(db, config, batches, trees, mnemonic, after_batch=None):
    """One DbWorker receiving `batches` in order → (state, seconds per
    batch). `trees[k]` is the relay's tree after batch k (the host fold
    of every timestamp so far), so each Receive ends in sync."""
    from evolu_tpu.core.types import TableDefinition
    from evolu_tpu.runtime import messages as rmsg
    from evolu_tpu.runtime.worker import DbWorker

    outputs = []
    clock = itertools.count(_NOW_MILLIS, 1000)
    worker = DbWorker(db, config, on_output=outputs.append, now=clock.__next__)
    worker.start(mnemonic=mnemonic)
    seconds = []
    try:
        worker.post(rmsg.UpdateDbSchema(tuple(
            TableDefinition.of(t, cols) for t, cols in _CLIENT_TABLES)))
        worker.flush()
        for k, batch in enumerate(batches):
            t0 = time.perf_counter()
            worker.post(rmsg.Receive(batch, trees[k], None))
            worker.flush()
            seconds.append(round(time.perf_counter() - t0, 3))
            if after_batch is not None:
                after_batch(k)
        errors = [o.error for o in outputs if isinstance(o, rmsg.OnError)]
        assert not errors, f"worker errors: {errors!r}"
        audited = worker.verify_winner_cache()
        planner = worker._planner
        return _client_state(db), seconds, audited, planner
    finally:
        worker.stop()


def client_phase(seed: int, compiles: CompileLog, n_messages: int = 100_000,
                 rows: int = 1_000, batches: int = 4,
                 mesh_engine: bool = False) -> dict:
    from benchmarks.config2_single_chip import MN, build_messages
    from evolu_tpu.core.merkle import (
        apply_prefix_xors, merkle_tree_to_string, minute_deltas_host)
    from evolu_tpu.core.packed import PackedReceive
    from evolu_tpu.storage.native import open_database
    from evolu_tpu.sync import native_crypto, protocol
    from evolu_tpu.sync.client import encrypt_messages
    from evolu_tpu.utils.config import Config

    mark = compiles.mark()
    # `rows` bounds the schema at <= 5*rows cells: each batch then touches
    # nearly every cell, so the winner cache's adaptive gate (new-cell EWMA,
    # ops/winner_cache.py) leaves streaming after two batches — batch 3 seeds
    # the HBM slots from SQLite and batch 4 plans from stored winners in HBM.
    # One batch bucket for the whole phase; no cache growth.
    messages = build_messages(n=n_messages, seed=seed, rows=rows)
    per = -(-n_messages // batches)
    object_batches = [tuple(messages[i:i + per]) for i in range(0, n_messages, per)]
    trees, tree = [], {}
    for batch in object_batches:
        deltas, _ = minute_deltas_host(m.timestamp for m in batch)
        tree = apply_prefix_xors(tree, deltas)
        trees.append(merkle_tree_to_string(tree))
    # What a client receives: response wire bytes, decrypted and
    # columnarized in one native call.
    packed_batches = []
    for batch in object_batches:
        wire = protocol.encode_sync_response(
            protocol.SyncResponse(tuple(encrypt_messages(batch, MN)), "{}"))
        out = native_crypto.decrypt_response_columns(wire, MN)
        assert out is not None, "native crypto did not produce a PackedReceive"
        assert isinstance(out[0], PackedReceive) and len(out[0]) == len(batch)
        packed_batches.append(out[0])

    per_batch = []
    last = {"hits": 0.0, "seeded": 0.0, "streamed": 0.0}

    def after_batch(_k):
        now = {"hits": total("evolu_winner_cache_hits_total"),
               "seeded": total("evolu_winner_cache_seeded_cells_total"),
               "streamed": total("evolu_winner_cache_streamed_cells_total")}
        per_batch.append({k: int(now[k] - last[k]) for k in now})
        last.update(now)

    db = open_database(backend="native")
    config = Config(backend="tpu", mesh_engine=mesh_engine)
    state, seconds, audited, planner = _run_worker(
        db, config, packed_batches, trees, MN, after_batch)
    backend = type(db).__name__
    cache_class = type(planner.cache).__name__
    db.close()
    for entry, s, batch in zip(per_batch, seconds, object_batches):
        entry.update(rows=len(batch), seconds=s,
                     mode="stream" if entry["streamed"] else "cached")

    # The sequential host oracle: Config(backend="cpu") (the host planner)
    # on stdlib SQLite, the same messages as plain objects.
    oracle_db = open_database(backend="python")
    want, _s, _a, _p = _run_worker(
        oracle_db, Config(backend="cpu"), object_batches, trees, MN)
    oracle_db.close()
    for key in want:
        assert state[key] == want[key], f"client end state != host oracle: {key}"
    assert len(state["__message"]) == n_messages
    assert state["__clock"][0][1] == trees[-1], "clock tree != host fold"

    obs = observed()
    assert_no_fallback(obs)
    packed_applies = obs["apply_route"].get("route=packed", 0)
    assert packed_applies >= batches, f"packed apply route not taken: {obs['apply_route']}"
    import jax

    hot_min = config.hot_owner_min_batch
    hot_owner = hot_min is not None and per >= hot_min and len(jax.devices()) >= 2
    return {
        "phase": "client", "messages": n_messages, "tables": len(_CLIENT_TABLES),
        "rows_per_table": rows, "cells_touched": len({
            (m.table, m.row, m.column) for m in messages}),
        "storage_backend": backend, "winner_cache_class": cache_class,
        "route": {"mesh_engine": mesh_engine,
                  "hot_owner": hot_owner},
        "batches": per_batch,
        "seconds": {
            "all_batches": round(sum(seconds), 3),
            "compile_first_minus_warm": {
                "streamed": round(seconds[0] - seconds[1], 3),
                "cached": round(seconds[2] - seconds[3], 3),
            } if len(seconds) >= 4 else "needs 4 batches",
        },
        "checked": {"sqlite_tables_vs_host_oracle": sorted(want),
                    "winner_cache_slots_vs_sqlite_max": audited},
        "compile": compiles.since(mark),
        "peak_device_bytes": peak_device_bytes(),
        **obs,
    }


# --------------------------------------------------------------------
# Phase: kernel at the bench shape
# --------------------------------------------------------------------


def _host_plan(cols: dict, shard_size: int):
    """The LWW plan by the reference's sequential rule, one row at a
    time in shard order: xor unless the running winner IS this
    timestamp; the final winner upserts iff it beats the stored one.
    → (xor_mask, upsert_mask) numpy bools over the laid-out columns."""
    import numpy as np

    pad = 0x7FFFFFFF
    cell = cols["cell_id"].tolist()
    k1, k2 = cols["k1"].tolist(), cols["k2"].tolist()
    e1, e2 = cols["ex_k1"].tolist(), cols["ex_k2"].tolist()
    n = len(cell)
    xor = np.zeros(n, bool)
    upsert = np.zeros(n, bool)
    for start in range(0, n, shard_size):
        running, final = {}, {}
        for i in range(start, start + shard_size):
            c = cell[i]
            if c == pad:
                continue
            key = (k1[i], k2[i])
            stored = (e1[i], e2[i])  # (0, 0) = no stored winner
            w = running.get(c, stored)
            xor[i] = w != key
            if key > w:
                running[c] = key
                final[c] = (i, stored)
            else:
                running[c] = w
        for c, (i, stored) in final.items():
            upsert[i] = running[c] > stored
    return xor, upsert


def kernel_phase(seed: int, compiles: CompileLog, rows: int = 1_000_000,
                 owners: int = 1_000, n_devices: int = 1) -> dict:
    import jax
    import numpy as np

    import bench
    from evolu_tpu.core.timestamp import Timestamp, timestamp_to_hash
    from evolu_tpu.ops import to_host_many
    from evolu_tpu.ops.merge import unpermute_masks
    from evolu_tpu.parallel.mesh import create_mesh
    from evolu_tpu.parallel.reconcile import reconcile_columns_sharded

    mark = compiles.mark()
    cols, total_rows = bench.shard_layout(
        bench.build_columns(n=rows, owners=owners, seed=seed, stored_winners=True),
        n_devices)
    kernel_cols = {k: cols[k] for k in
                   ("cell_id", "k1", "k2", "ex_k1", "ex_k2", "owner_ix")}
    mesh = create_mesh(n_devices)
    t0 = time.perf_counter()
    outs = jax.block_until_ready(reconcile_columns_sharded(mesh, kernel_cols))
    t1 = time.perf_counter()
    outs = jax.block_until_ready(reconcile_columns_sharded(mesh, kernel_cols))
    t2 = time.perf_counter()
    devices = sorted({s.device.id for s in outs[0].addressable_shards})
    assert len(devices) == n_devices, f"rows landed on devices {devices}"
    xor_s, upsert_s, i_s, *_rest, digest = to_host_many(*outs)
    shard_size = total_rows // n_devices
    xor_mask, upsert_mask = unpermute_masks(xor_s, upsert_s, i_s, block_size=shard_size)

    want_xor, want_upsert = _host_plan(kernel_cols, shard_size)
    assert np.array_equal(xor_mask, want_xor), "xor mask != host recompute"
    assert np.array_equal(upsert_mask, want_upsert), "upsert mask != host recompute"
    want_digest = 0
    millis, counter, node = (cols[k].tolist() for k in ("millis", "counter", "node"))
    for i in np.nonzero(want_xor)[0].tolist():
        want_digest ^= timestamp_to_hash(
            Timestamp(millis[i], counter[i], f"{node[i]:016x}")) & 0xFFFFFFFF
    assert int(digest) == want_digest, (hex(int(digest)), hex(want_digest))

    obs = observed()
    assert_no_fallback(obs)
    return {
        "phase": "kernel", "rows": rows, "owners": owners,
        "devices": n_devices, "padded_rows": total_rows,
        "seconds": {"first_call": round(t1 - t0, 3), "warm_call": round(t2 - t1, 3),
                    "compile_first_minus_warm": round((t1 - t0) - (t2 - t1), 3)},
        "checked": {"xor_rows": int(want_xor.sum()),
                    "upsert_rows": int(want_upsert.sum()),
                    "digest": f"0x{want_digest:08x}"},
        "compile": compiles.since(mark),
        "peak_device_bytes": peak_device_bytes(),
        **obs,
    }


# --------------------------------------------------------------------
# Phase: the mesh-sharded engine (--chips 4)
# --------------------------------------------------------------------


def store_dump(store):
    """Byte-identity parity dump of a relay store (message + merkleTree
    rows per storage shard) — the ONE copy behind every end-state
    parity gate: this smoke, `__graft_entry__.dryrun_multichip`, and
    the tests (`tests/conftest.py::relay_store_dump` is this function;
    a chip process cannot import that module, which pins the platform
    to the CPU)."""
    return [
        (s.db.exec('SELECT * FROM "message" ORDER BY "timestamp", "userId"'),
         s.db.exec('SELECT * FROM "merkleTree" ORDER BY "userId"'))
        for s in store.shards
    ]


def mesh_phase(seed: int, compiles: CompileLog, n_messages: int = 1_000_000,
               owners: int = 1_000, n_devices: int = 4) -> dict:
    import numpy as np

    from evolu_tpu.core.merkle import (
        apply_prefix_xors, merkle_tree_from_string, merkle_tree_to_string)
    from evolu_tpu.ops.host_parse import parse_timestamp_strings
    from evolu_tpu.parallel.mesh import MeshContext, create_mesh
    from evolu_tpu.server import engine as eng
    from evolu_tpu.server.store import ShardedRelayStore
    from perf import gen

    mark = compiles.mark()
    # The benchmark's own log (the cell relay-mesh4.backfill feeds the
    # same requests to the same engine, pass by pass).
    requests = gen.build_requests(
        n_messages, owners, seed, gen.ciphertext_pool(min(8192, n_messages)))
    n_msgs = sum(len(r.messages) for r in requests)
    ctx = MeshContext(create_mesh(n_devices))
    assert ctx.n_shards == n_devices, f"mesh has {ctx.n_shards} devices"

    # The owner-sharded engine (stable owner→device placement) and its
    # one-device twin, same requests, one engine pass each.
    seconds = {}
    dumps = {}
    for label, kw in (("sharded", {"mesh_ctx": ctx}),
                      ("one_device_twin", {"mesh": create_mesh(1)})):
        store = ShardedRelayStore(":memory:", "native", shards=8)
        engine = eng.BatchReconciler(store, **kw)
        t0 = time.perf_counter()
        responses = engine.reconcile(requests)
        seconds[label] = round(time.perf_counter() - t0, 3)
        engine.close()
        for req, resp in zip(requests, responses):
            assert resp.messages == () and resp.merkle_tree == req.merkle_tree, \
                f"{label}: tree != host fold for {req.user_id}"
        dumps[label] = store_dump(store)
        store.close()
    assert dumps["sharded"] == dumps["one_device_twin"], \
        "sharded end state != one-device twin"
    stored = sum(len(msgs) for msgs, _trees in dumps["sharded"])
    assert stored == n_msgs
    del dumps

    # One sharded dispatch over every row, held open between dispatch and
    # finish: where the output shards live, and the digest the devices
    # XOR-all-reduced, against the host fold (a Merkle root is the XOR of
    # every hash under it).
    flat = [m.timestamp for r in requests for m in r.messages]
    all_m, all_c, all_n, case_ok = parse_timestamp_strings(flat, with_case=True)
    owner_index, pos = {}, 0
    for r in requests:
        owner_index[r.user_id] = np.arange(pos, pos + len(r.messages))
        pos += len(r.messages)
    state = eng.deltas_dispatch(
        ctx.mesh, owner_index, all_m, all_c, all_n, case_ok, flat, ctx=ctx)
    packed = state[3][0]
    placement = sorted(
        (s.device.id, int(s.data.shape[0])) for s in packed.addressable_shards)
    assert len({d for d, _n in placement}) == n_devices, \
        f"rows landed on {placement}, wanted {n_devices} devices"
    deltas, digest = eng.deltas_finish(state)
    want_digest = 0
    for r in requests:
        host_tree = merkle_tree_from_string(r.merkle_tree)
        want_digest ^= host_tree.get("hash", 0) & 0xFFFFFFFF
        assert merkle_tree_to_string(apply_prefix_xors({}, deltas[r.user_id])) == \
            r.merkle_tree, f"sharded deltas != host fold: {r.user_id}"
    assert digest & 0xFFFFFFFF == want_digest, (hex(digest), hex(want_digest))

    obs = observed()
    assert_no_fallback(obs)
    return {
        "phase": "mesh_relay", "messages": n_msgs, "owners": len(requests),
        "devices": n_devices, "seconds": seconds,
        "output_shards": [{"device": d, "rows": n} for d, n in placement],
        "checked": {"end_state_vs_one_device_twin": stored,
                    "owner_trees_vs_host_fold": len(requests),
                    "digest_allreduce_vs_host_fold": f"0x{want_digest:08x}"},
        "mesh": {"dispatches": total("evolu_mesh_dispatches_total"),
                 "xdev_reduce": counters("evolu_mesh_xdev_reduce_total")},
        "compile": compiles.since(mark),
        "peak_device_bytes": peak_device_bytes(),
        **obs,
    }


# --------------------------------------------------------------------
# main: the only place that asks what the process runs on
# --------------------------------------------------------------------


def assert_pallas_route(obs: dict) -> None:
    """Every scan traced at N >= 2^15 must have taken the Pallas kernel
    (`evolu_merge_scan_total` counts only where there is a choice)."""
    route = obs["scan_route"]
    if route.get("path=xla", 0) or not route.get("path=pallas", 0):
        raise AssertionError(f"Pallas scan route not taken at N >= 2^15: {route}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the multi-chip path and its one-device twin")
    args = ap.parse_args(argv)
    if not __debug__:
        print("chip_smoke.py checks with assert; run it without -O", file=sys.stderr)
        return 2

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX came up on "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke.py --chips {args.chips} found {len(devices)} device(s)",
              file=sys.stderr)
        return 2
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}

    rebuild_native()
    import evolu_tpu.ops  # noqa: F401 - places the compile cache
    from evolu_tpu.storage import native
    from evolu_tpu.sync import native_crypto

    assert native.native_available(), "libevolu_host.so did not load"
    assert native_crypto.native_available(), "libevolu_crypto.so did not load"
    compiles = CompileLog()
    start = compiles.mark()
    cache_path, entries_before = cache_dir_entries()
    emit({"phase": "start", "device": device, "seed": args.seed, "chips": args.chips,
          "compile_cache_dir": cache_path,
          "compile_cache_dir_from_env": "JAX_COMPILATION_CACHE_DIR" in os.environ,
          "compile_cache_entries": entries_before,
          "jax": jax.__version__})

    t0 = time.perf_counter()
    if args.chips == 4:
        emit(mesh_phase(args.seed, compiles, n_devices=4))
        emit(client_phase(args.seed, compiles, mesh_engine=True))
        emit(kernel_phase(args.seed, compiles, n_devices=4))
    else:
        emit(relay_phase(args.seed, compiles))
        emit(client_phase(args.seed, compiles))
        emit(kernel_phase(args.seed, compiles))
    obs = observed()
    assert_no_fallback(obs)
    assert_pallas_route(obs)
    assert not live_children(), f"child processes left: {live_children()}"
    emit({"phase": "end", "seconds": round(time.perf_counter() - t0, 1),
          "compile": compiles.since(start),
          "compile_cache_dir": cache_path,
          "compile_cache_entries": {"before": entries_before,
                                    "after": cache_dir_entries()[1]}})
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
