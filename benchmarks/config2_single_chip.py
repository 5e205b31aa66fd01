"""BASELINE config 2: 3-table schema, 100k messages — full-system
single-chip client throughput (planner + SQLite apply + tree update),
not just the kernel.

r5 rewrite (VERDICT r4 next #5: the old row predated the winner cache,
the packed reader, and the fused receive). Measures the CURRENT client
paths, fresh store per trial, median of TRIALS:

- `objects`: the production planner (`select_planner` — HBM winner
  cache above `min_device_batch`) applying a CrdtMessage batch: the
  local-mutation (`_send`) shape.
- `packed`: the fused receive leg — response wire bytes →
  `decrypt_response_columns` → PackedReceive → packed plan →
  `eh_apply_planned_cells` (decrypt INCLUDED in the timed region; the
  wire bytes are what a client actually receives).
- `packed_v2`: the SAME timed region over an `aead-batch-v1` response
  (sync/aead.py — session-keyed GCM records instead of per-message
  OpenPGP): what a NEGOTIATED client receives. The delta vs `packed`
  is the full-system share of the ISSUE-8 crypto-ceiling lift.
- `legacy_streamed`: the pre-r3 shape (plan_batch_device_full with
  SQLite-streamed winners) kept for cross-round continuity.

Prints one JSON line.
"""

import json
import os
import random
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from evolu_tpu.core.merkle import diff_merkle_trees
from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.core.types import CrdtMessage
from evolu_tpu.runtime.worker import select_planner
from evolu_tpu.storage.apply import apply_messages
from evolu_tpu.storage.native import open_database
from evolu_tpu.storage.schema import init_db_model
from evolu_tpu.utils.config import Config

N = int(os.environ.get("CONFIG2_N", 100_000))
TRIALS = int(os.environ.get("CONFIG2_TRIALS", 3))
MN = "legal winner thank year wave sausage worth useful legal winner thank yellow"


def build_messages(n=N, seed=2, rows=5000):
    rng = random.Random(seed)
    tables = [("todo", ("title", "isCompleted", "categoryId")),
              ("todoCategory", ("name",)),
              ("todoNote", ("text",))]
    nodes = [f"{rng.getrandbits(64):016x}" for _ in range(8)]
    base = 1_700_000_000_000
    out = []
    for i in range(n):
        table, cols = rng.choice(tables)
        out.append(CrdtMessage(
            timestamp_to_string(Timestamp(base + i // 4, i % 4, rng.choice(nodes))),
            table, f"row{rng.randrange(rows)}", rng.choice(cols), f"v{i}",
        ))
    return out


def mkdb():
    db = open_database(backend="auto")
    init_db_model(db, mnemonic=None)
    for t in ("todo", "todoCategory", "todoNote"):
        db.exec(
            f'CREATE TABLE "{t}" ("id" TEXT PRIMARY KEY, "title" BLOB, '
            '"isCompleted" BLOB, "categoryId" BLOB, "name" BLOB, "text" BLOB)'
        )
    return db


def main():
    from evolu_tpu.ops.merge import plan_batch_device_full
    from evolu_tpu.sync import native_crypto, protocol
    from evolu_tpu.sync.client import encrypt_messages, encrypt_messages_v2

    messages = build_messages()
    resp_bytes = protocol.encode_sync_response(
        protocol.SyncResponse(tuple(encrypt_messages(messages, MN)), "{}")
    )
    resp_bytes_v2 = protocol.encode_sync_response(
        protocol.SyncResponse(tuple(encrypt_messages_v2(messages, MN)), "{}")
    )
    probe = mkdb()
    backend = type(probe).__name__  # Cpp vs Py sqlite matters for the record
    probe.close()

    def trial_objects():
        db = mkdb()
        planner = select_planner(Config(), db)
        t0 = time.perf_counter()
        tree = apply_messages(db, {}, messages, planner=planner)
        dt = time.perf_counter() - t0
        return db, tree, dt

    def _trial_wire(wire_bytes):
        db = mkdb()
        planner = select_planner(Config(), db)
        t0 = time.perf_counter()
        out = native_crypto.decrypt_response_columns(wire_bytes, MN)
        if out is None:  # no native crypto: the client's object fallback
            batch, _tree_str = native_crypto.decrypt_response(wire_bytes, MN) or (
                None, None,
            )
            if batch is None:
                from evolu_tpu.sync.client import decrypt_messages

                resp = protocol.decode_sync_response(wire_bytes)
                batch = decrypt_messages(resp.messages, MN)
        else:
            batch, _tree_str = out
        tree = apply_messages(db, {}, batch, planner=planner)
        dt = time.perf_counter() - t0
        return db, tree, dt

    def trial_packed():
        return _trial_wire(resp_bytes)

    def trial_packed_v2():
        return _trial_wire(resp_bytes_v2)

    def trial_legacy():
        db = mkdb()
        t0 = time.perf_counter()
        tree = apply_messages(db, {}, messages, planner=plan_batch_device_full)
        dt = time.perf_counter() - t0
        return db, tree, dt

    results = {}
    diff_ms = None
    trees = {}
    for label, fn in (("objects", trial_objects), ("packed", trial_packed),
                      ("packed_v2", trial_packed_v2),
                      ("legacy_streamed", trial_legacy)):
        db, tree, _ = fn()  # warm the jit bucket (compile once per bucket)
        stored = db.exec_sql_query('SELECT COUNT(*) FROM "__message"', ())
        assert next(iter(stored[0].values())) == N
        trees[label] = tree
        if diff_ms is None:
            t0 = time.perf_counter()
            assert diff_merkle_trees(tree, {}) is not None
            diff_ms = (time.perf_counter() - t0) * 1e3
        db.close()
        rates = []
        for _ in range(TRIALS):
            db, _tree, dt = fn()
            rates.append(N / dt)
            db.close()
        results[label] = round(statistics.median(rates))

    # The v2 wire must land the exact state the v1 wire lands (the
    # store and Merkle algebra are version-blind — ISSUE 8 contract).
    assert trees["packed_v2"] == trees["packed"] == trees["objects"]

    import jax

    print(json.dumps({
        "metric": "config2_full_system_msgs_per_sec",
        "value": results["packed"],
        "unit": "msgs/sec",
        "detail": {
            "messages": N, "trials": TRIALS,
            "paths": results,
            "merkle_diff_ms": round(diff_ms, 3),
            "backend": backend,
            "platform": jax.devices()[0].platform,
        },
    }))


if __name__ == "__main__":
    main()
