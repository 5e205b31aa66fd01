"""Compact-transfer delta encoding: bytes/row before vs after, with a
byte-equal end-state check on the config-3 full-system shape
(ISSUE 4 satellite, VERDICT #9).

Runs the BatchReconciler ingest twice over identical request sets —
EVOLU_COMPACT_DELTA=0 (the r3 20 B/row packed-HLC-key upload) vs =1
(u32 millis-delta + u32 owner|counter + u64 node = 16 B/row) — on
fresh sharded stores, asserts the dumped end state (every row + every
tree) is byte-equal via crc32, and reports the per-variant upload
bytes/row from the `evolu_engine_compact_upload_bytes_total` metric
(the padded-total bytes the device leg actually ships). What those
bytes cost on the attached chip: not measured; on this CPU mesh the
wall-time delta is noise and is reported as such.

Prints one JSON line.
"""

import json
import os
import statistics
import sys
import time
import zlib

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

N = int(os.environ.get("CTD_N", 200_000))
OWNERS = int(os.environ.get("CTD_OWNERS", 500))
SHARDS = 8
TRIALS = int(os.environ.get("CTD_TRIALS", 3))


def main():
    from benchmarks.config3_server_reconcile import _ciphertext_pool, build_requests
    from evolu_tpu.obs import metrics
    from evolu_tpu.server.engine import BatchReconciler
    from evolu_tpu.server.relay import ShardedRelayStore

    pool = _ciphertext_pool(2048)
    requests = build_requests(n=N, owners=OWNERS, pool=pool)
    n_msgs = sum(len(r.messages) for r in requests)

    warm = BatchReconciler(ShardedRelayStore(shards=SHARDS))
    warm.reconcile(requests)

    def dump_crc(store):
        crc = 0
        for sh in store.shards:
            for row in sh.db.exec(
                'SELECT "timestamp","userId","content" FROM "message" '
                'ORDER BY "userId","timestamp"'
            ):
                for v in row:
                    crc = zlib.crc32(v if isinstance(v, bytes) else str(v).encode(), crc)
            for row in sh.db.exec(
                'SELECT "userId","merkleTree" FROM "merkleTree" ORDER BY "userId"'
            ):
                for v in row:
                    crc = zlib.crc32(str(v).encode(), crc)
        return crc

    results, crcs = {}, {}
    for flag, label in (("0", "full_key_20B"), ("1", "delta_16B")):
        os.environ["EVOLU_COMPACT_DELTA"] = flag
        walls = []
        store = engine = None
        for _ in range(TRIALS):
            if store is not None:
                engine.close(); store.close()
            store = ShardedRelayStore(shards=SHARDS)
            engine = BatchReconciler(store, warm.mesh)
            metrics.reset()
            t0 = time.perf_counter()
            engine.reconcile(requests)
            walls.append(time.perf_counter() - t0)
        variant = "delta" if flag == "1" else "full"
        upload = metrics.get_counter(
            "evolu_engine_compact_upload_bytes_total", variant=variant
        )
        results[label] = {
            "wall_s_median": round(statistics.median(walls), 3),
            "msgs_per_sec": round(n_msgs / statistics.median(walls)),
            "upload_bytes": int(upload),
            "upload_bytes_per_row": round(upload / n_msgs, 2),
        }
        crcs[label] = dump_crc(store)
        engine.close(); store.close()
    os.environ.pop("EVOLU_COMPACT_DELTA", None)

    assert crcs["full_key_20B"] == crcs["delta_16B"], crcs
    print(json.dumps({
        "metric": "compact_transfer_delta_encoding",
        "n": n_msgs,
        "owners": OWNERS,
        "end_state_crc32": f"{crcs['delta_16B']:08x}",
        "end_state_byte_equal": True,
        "variants": results,
        "key_column_bytes_per_row": {"before": 8, "after": 4},
    }))


if __name__ == "__main__":
    main()
