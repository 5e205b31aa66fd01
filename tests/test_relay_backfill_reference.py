"""`relay-mesh4` against its plain reference at a small size (ISSUE 35).

The benchmark's driver (`perf/drivers/relay_mesh.py`) backfills a fresh
native sharded store through `BatchReconciler(store, mesh_ctx=ctx)
.reconcile_stream` on a 4-device `MeshContext` (of the suite's 8 forced
host devices), and `perf/reference/relay_sync.py` runs upstream's `sync`
on the same requests one message at a time over stdlib sqlite3. The full
dump of `message` and `merkleTree` must be byte-identical, and so must
every answer. The cell's `correct` rests on the same comparison at full
size on four chips.
"""

import json
import os
import subprocess
import sys

import pytest

from evolu_tpu.obs import metrics
from evolu_tpu.parallel import mesh as mesh_module
from evolu_tpu.storage import native
from evolu_tpu.sync import native_crypto, protocol
from perf import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
reference = load_module("reference", "relay_sync")
driver = load_module("drivers", "relay_mesh")
restore_loop = load_module("traffic", "restore_loop")

needs_native = pytest.mark.skipif(
    not (native.native_available() and native_crypto.native_available()),
    reason="the packed ingest needs both native libraries")

with open(os.path.join(ROOT, "perf", "configs", "relay-mesh4.json")) as f:
    _CONFIG = json.load(f)
CFG = {**_CONFIG, **_CONFIG["rehearsal"], "messages": 2400, "owners": 24,
       "ciphertext_pool": 16, "reference_owners": 4, "cold_sync_sample": 2}


@pytest.fixture
def mesh4(monkeypatch):
    """The process's mesh context as the driver will find it: four of
    the suite's eight host devices, whatever an earlier test left."""
    ctx = mesh_module.MeshContext(n_devices=4)
    monkeypatch.setattr(mesh_module, "_process_ctx", ctx)
    return ctx


def _store_dump(store) -> tuple:
    """Both tables whole, in an order of their own."""
    rows, trees = [], []
    for shard in store.shards:
        rows += [tuple(r.values()) for r in shard.db.exec_sql_query(
            'SELECT "timestamp", "userId", "content" FROM "message"')]
        trees += [tuple(r.values()) for r in shard.db.exec_sql_query(
            'SELECT "userId", "merkleTree" FROM "merkleTree"')]
    return sorted(rows, key=lambda r: (r[1], r[0])), sorted(trees)


def _reference_dump(twin) -> tuple:
    rows = twin.db.execute('SELECT "timestamp", "userId", "content" FROM "message" '
                           'ORDER BY "userId", "timestamp"').fetchall()
    trees = twin.db.execute('SELECT "userId", "merkleTree" FROM "merkleTree" '
                            'ORDER BY "userId"').fetchall()
    return rows, trees


def _variant_chunks(variant: str, chunks: list) -> list:
    if variant == "steady-state":
        return chunks
    if variant == "resend-a-third":
        # A further pass re-sends every third message of every owner with
        # the owner's own tree: nothing is stored, no tree is XORed twice.
        again = [protocol.SyncRequest(r.messages[::3], r.user_id, r.node_id, r.merkle_tree)
                 for chunk in chunks for r in chunk]
        return [*chunks, again]
    assert variant == "empty-client-tree"
    # A device that holds nothing and writes as one of the owner's own
    # nodes: the answer is the owner's stored rows but that node's.
    return [[protocol.SyncRequest(r.messages, r.user_id, r.messages[0].timestamp[30:], "{}")
             for r in chunk] for chunk in chunks]


@needs_native
@pytest.mark.parametrize("variant", ["steady-state", "resend-a-third", "empty-client-tree"])
@pytest.mark.parametrize("seed", [5, 2**31 + 17])
def test_backfill_equals_the_plain_reference(mesh4, seed, variant):
    state = driver.setup(CFG, seed, scratch="")
    assert state["ctx"] is mesh4 and len(state["chunks"]) == CFG["passes"]
    state["chunks"] = _variant_chunks(variant, state["chunks"])
    dispatches0 = metrics.get_counter("evolu_mesh_dispatches_total")
    twin = reference.ReferenceRelay()
    try:
        record = driver.backfill(state)
        assert record["error"] is None
        assert record["messages"] == sum(len(r.messages) for c in state["chunks"] for r in c)
        for chunk, answers in zip(state["chunks"], record["responses"]):
            assert len(answers) == len(chunk)
            for req, got in zip(chunk, answers):
                rows, tree = twin.sync(req.user_id, req.node_id,
                                       [(m.timestamp, m.content) for m in req.messages],
                                       req.merkle_tree)
                assert [(m.timestamp, m.content) for m in got.messages] == rows, req.user_id
                assert got.merkle_tree == tree, req.user_id
                if variant == "empty-client-tree":
                    own = [m for m in req.messages if m.timestamp.endswith(req.node_id)]
                    assert rows and own and len(rows) == len(req.messages) - len(own)
                else:
                    assert rows == [] and tree == req.merkle_tree
        got, want = _store_dump(record["store"]), _reference_dump(twin)
    finally:
        driver.close(state)
        twin.close()
    assert got[0] == want[0] and got[1] == want[1]
    assert len(got[0]) == CFG["messages"]  # stored once, whatever was sent twice
    # every pass was one dispatch on the process's four-device mesh
    assert metrics.get_counter("evolu_mesh_dispatches_total") - dispatches0 == \
        len(state["chunks"])


# The reference folds the compared tree with `perf/reference/client_todo.py`'s
# functions, which tests/test_client_restore_reference.py holds to upstream's
# snapshots; its own diff and its `sync` are held to them here
# (packages/evolu/test/__snapshots__/merkleTree.test.ts.snap, timestamp.test.ts),
# not to `evolu_tpu.core`: a fault there must not pass both sides.
NODE1 = "0000000000000001"
STAMP = "2022-07-03T18:42:18.591Z-0000-0000000000000001"  # millis 1656873738591


def _tree_of(*millis: int) -> dict:
    tree = {}
    stamps = [(m, 0, NODE1) for m in millis]
    for (m, _c, _n), h in zip(stamps, reference.timestamp_hashes(stamps)):
        reference.tree_insert(tree, m, h)
    return tree


@pytest.mark.parametrize("tree1, tree2, want", [
    ((), (), None),
    ((), (1656873738591,), 1656873720000),          # snapshot `diffMerkleTrees 2`
    ((1656873738591,), (), 1656873720000),
    ((1656873738591,), (1656873738591,), None),
    ((0, 1656873738591), (1656873738591,), 0),      # the first minute that differs
    ((0, 1656873738591, 1656873858591), (0, 1656873738591), 1656873840000),
], ids=["both-empty", "snapshot-2", "symmetric", "equal", "first-minute", "later-minute"])
def test_reference_diff_matches_upstream_snapshots(tree1, tree2, want):
    assert reference.tree_diff(_tree_of(*tree1), _tree_of(*tree2)) == want


def test_reference_hash_tree_string_and_sync_timestamp():
    assert reference.timestamp_hashes([(0, 0, "0" * 16)]) == [4179357717]
    assert reference.tree_to_string(_tree_of(0)) == \
        '{"0":{"hash":-1416139081},"hash":-1416139081}'
    assert _tree_of(1656873738591, 0)["hash"] == 1335454297
    assert reference.key_to_millis("") == 0
    assert reference.key_to_millis("1220221222001120") == 1656873720000
    assert reference.render_timestamp(1656873720000, 0, reference.SYNC_NODE) == \
        "2022-07-03T18:42:00.000Z-0000-0000000000000000"


def test_reference_sync_gates_the_tree_on_a_changed_row():
    """index.ts:153-158: only `changes == 1` folds the hash; the answer
    leaves out the caller's own node (index.ts:100)."""
    twin = reference.ReferenceRelay()
    try:
        want = reference.tree_to_string(_tree_of(1656873738591))
        assert '"hash":-468843282' in want
        rows, tree = twin.sync("u", NODE1, [(STAMP, b"c")], want)
        assert (rows, tree) == ([], want)
        rows, tree = twin.sync("u", NODE1, [(STAMP, b"other")], want)  # sent twice
        assert (rows, tree) == ([], want)
        assert twin.owner_dump("u") == ([(STAMP, "u", b"c")], [("u", want)])
        assert twin.sync("u", "f" * 16, [], "{}") == ([(STAMP, b"c")], want)
        assert twin.sync("u", NODE1, [], "{}") == ([], want)  # its own node's rows
        assert twin.sync("nobody", NODE1, [], "{}") == ([], "{}")
    finally:
        twin.close()


class _Window:
    def begin(self):
        pass

    def end(self):
        pass


@needs_native
def test_restore_loop_counts_whole_backfills_and_the_check_holds_them(mesh4, monkeypatch):
    """`restore_loop`'s accounting with the driver's unit, then the
    driver's own check on what the loop left: counts, the full table,
    the reference's owners, a cold sync over HTTP, the mesh counters."""
    metrics.reset()  # the check reads the process's fallback counters whole
    params = {"messages": CFG["messages"], "concurrency": 1, "warm_backfills": 1}
    state = driver.setup(CFG, 2**31 + 9, scratch="")
    try:
        driver.warm(state, params)
        assert state["warm_backfills"] == 1 and state["backfills"][0]["store"] is None
        import time

        outcome = restore_loop.run(state, params, 2**31 + 9, time.monotonic(), 0.4, _Window())
        counted = state["backfills"][1:]
        assert outcome["attempted"] == outcome["restores_ok"] == len(counted) >= 1
        assert outcome["failed"] == 0 and outcome["errors"] == []
        assert outcome["acked_msgs"] == CFG["messages"] * len(counted)
        assert len(outcome["restore_seconds"]) == len(counted)
        assert outcome["window_s"] == pytest.approx(sum(outcome["restore_seconds"]), rel=0.05)
        outcome["window_compiles"] = 0
        assert driver.check(state, outcome) is True
        assert outcome["upload_variant"] == "variant=delta"  # 16 B a slot
        # PR 41: one buffer a pass, `base` (8 B) in each of the four devices' tails
        assert (outcome["upload_bytes_pass"] - 4 * 8) % (4 * 16) == 0

        # A check that would pass anything decides nothing: a tree that
        # is not the request's, a lost row, a dispatch that was not counted.
        store = counted[0]["store"]
        owner = state["requests"][0].user_id
        store.shard_of(owner).db.run(
            'UPDATE "merkleTree" SET "merkleTree" = ? WHERE "userId" = ?', ("{}", owner))
        with pytest.raises(AssertionError, match="stored tree"):
            driver._check_counts(state, counted[0], "tampered")
        store.shard_of(owner).db.run(
            'DELETE FROM "message" WHERE "userId" = ? AND "timestamp" = ?',
            (owner, state["requests"][0].messages[0].timestamp))
        with pytest.raises(AssertionError, match="rows stored"):
            driver._check_counts(state, counted[0], "tampered")
        with pytest.raises(AssertionError, match="rows != its request's"):
            driver._check_table(state, store, "tampered")
    finally:
        driver.close(state)

    # A backfill whose engine raises counts in `failed`, its messages for nothing.
    from evolu_tpu.server.engine import BatchReconciler

    state = driver.setup(CFG, 7, scratch="")
    real, calls = BatchReconciler.reconcile_stream, []

    def sometimes(self, batches):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("the second backfill's engine failed")
        return real(self, batches)

    monkeypatch.setattr(BatchReconciler, "reconcile_stream", sometimes)
    ticks = iter(range(100))
    state["clock"], state["sleep"] = (lambda: next(ticks)), (lambda s: None)
    try:
        outcome = restore_loop.run(state, params, 7, 1.0, 6.0, _Window())
    finally:
        driver.close(state)
    assert (outcome["attempted"], outcome["failed"], outcome["restores_ok"]) == (3, 1, 2)
    assert outcome["acked_msgs"] == 2 * CFG["messages"]
    assert outcome["errors"] == ["RuntimeError: the second backfill's engine failed"]


@needs_native
def test_rehearsal_of_the_cell_ends_correct_with_the_mesh_metrics():
    """`perf/run.py --rehearse --trace 1` (which runs `perf/selfcheck.py`
    first) in a process of its own with four forced host devices, which
    the harness wants for a cell of four chips: control flow, counts
    and `correct`, never a device number."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"), "--workload",
         "relay-mesh4.backfill", "--seed", str(2**31 + 35), "--seconds", "1",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 4
    got = {k: v["value"] for k, v in line["metrics"].items()}
    stages = {f"pass_{s}_ms.mesh4" for s in
              ("pack", "parse", "layout", "device_call", "insert", "pull_wait", "tree")}
    assert stages | {"window_compiles.mesh4", "backfill_p50_s", "mesh_occupancy_share",
                     "mesh_rows_device_pass", "mesh_xdev_reduce_pass",
                     "mesh_upload_kb_pass", "mesh_pull_kb_pass",
                     "pack_native_share", "pass_insert_wait_ms.mesh4",
                     "stream_overlap_share", "pass_stage_join_ms.mesh4",
                     "device_transfers_pass.mesh4", "relay_tree_text_share"} == set(got)
    assert got["device_transfers_pass.mesh4"] == 2.0  # PR 41: one buffer up, one back
    assert got["relay_tree_text_share"] == 100  # every owner's tree folded as text
    # PR 39: the rehearsal's two passes a backfill, the second staged on the helper
    assert got["stream_overlap_share"] == 50 and got["pass_stage_join_ms.mesh4"] >= 0
    # the control of PR 37: one thread works alone, so the insert's wall
    # time is nearly all its own CPU time (signed: jitter may read below 0)
    assert got["pass_insert_wait_ms.mesh4"] <= got["pass_insert_ms.mesh4"]
    assert got["window_compiles.mesh4"] == 0
    assert got["pack_native_share"] == 100  # every pass packed by the native walk (PR 36)
    assert got["mesh_xdev_reduce_pass"] == 1  # the digest's all-reduce; no owner is split
    assert got["mesh_rows_device_pass"] == 6000 / 2 / 4  # 2 passes over 4 devices
    # 16 B a slot of 4 devices x the fullest device's bucket, and 8 B of
    # `base` a device
    slots = (got["mesh_upload_kb_pass"] * 1024 - 4 * 8) / 16
    assert slots % 4 == 0 and got["mesh_occupancy_share"] == pytest.approx(3000 / slots * 100)
    other = [json.loads(l) for l in done.stdout.strip().splitlines()[:-1]]
    e2e = next(l["metrics"] for l in other if l.get("info") == "the other set")
    assert set(e2e) == {"ingest_rate", "setup_s"}
