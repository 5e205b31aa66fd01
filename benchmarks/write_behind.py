"""Write-behind storage inversion (PR-11) vs the synchronous engine.

Drives the SAME seeded request stream through two `BatchReconciler`s:
the synchronous path (insert + tree upsert inside the serving pass —
the PR-8..10 shape) and the write-behind path (serve from in-memory
trees, ACK into the durable record log, SQLite materialized by the
background drain). Three figures:

- `serve` slope: Δmessages/Δwall of the SERVING path alone between two
  batch counts (CLAUDE.md timing discipline — setup, jit warmup, and
  store open cancel out). This is the number the 503/Retry-After
  admission bound protects: what a client observes while the btree
  lags behind.
- `end_to_end` slope: the same but including the final drain — the
  sustained-throughput bound (the btree still has to swallow every
  row; write-behind moves it off the latency path, it does not make
  it free).
- `sync` slope: the synchronous engine on the identical stream.

Gates (hard-fail, run in --smoke too):
- byte-identity: after the drain, both stores' rows + trees are
  identical (the oracle-twin contract the SIGKILL torture extends).
- checksum-carry liveness: the state crc is printed and must MOVE when
  the payload is perturbed — a serving leg that drops rows cannot go
  unnoticed (the r2/r3 DCE lesson applied to the host path).

Runs on the 8-device virtual CPU mesh by default (the env is forced
below, so it never takes the chip); EVOLU_WB_BENCH_TPU=1 inherits the
ambient platform. Prints ONE JSON line; numbers live in docs/BENCHMARKS.md.
"""

import json
import os
import sys
import time
import zlib

if not os.environ.get("EVOLU_WB_BENCH_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.server.engine import BatchReconciler
from evolu_tpu.server.relay import RelayStore, ShardedRelayStore
from evolu_tpu.storage.write_behind import WriteBehindQueue
from evolu_tpu.sync import protocol

BASE = 1_700_000_000_000
OWNERS = 16
SHARDS = 4


def _stream(n_batches: int, rows_per_owner: int, payload: bytes):
    """Seeded batches of distinct-owner in-sync pushes (the steady-
    state hot shape: response diff empty, no serve-side flush). Client
    trees come from a deterministic tree oracle."""
    from evolu_tpu.core.merkle import merkle_tree_to_string

    oracle = RelayStore()
    batches = []
    for b in range(n_batches):
        reqs = []
        for o in range(OWNERS):
            owner = f"owner{o:02d}"
            node = f"{o + 1:016x}"
            msgs = tuple(
                protocol.EncryptedCrdtMessage(
                    timestamp_to_string(Timestamp(
                        BASE + (b * rows_per_owner + i) * 1000, 0, node
                    )),
                    payload,
                )
                for i in range(rows_per_owner)
            )
            tree = oracle.add_messages(owner, msgs)
            reqs.append(protocol.SyncRequest(
                msgs, owner, node, merkle_tree_to_string(tree)
            ))
        batches.append(reqs)
    oracle.close()
    return batches


def _state_crc(store) -> int:
    crc = 0
    shards = getattr(store, "shards", None) or [store]
    for s in shards:
        for u in sorted(s.user_ids()):
            crc = zlib.crc32(s.get_merkle_tree_string(u).encode(), crc)
            for m in s.replica_messages(u, ""):
                crc = zlib.crc32(m.timestamp.encode(), crc)
                crc = zlib.crc32(m.content, crc)
    return crc


def _dump(store):
    rows, trees = [], []
    for s in (getattr(store, "shards", None) or [store]):
        rows += [(r["userId"], r["timestamp"], r["content"])
                 for r in s.db.exec_sql_query(
                     'SELECT "timestamp", "userId", "content" FROM "message"')]
        trees += [(r["userId"], r["merkleTree"])
                  for r in s.db.exec_sql_query(
                      'SELECT "userId", "merkleTree" FROM "merkleTree"')]
    return sorted(rows), sorted(trees)


def _drive(batches, write_behind: bool, hold_drain: bool = False):
    """Serve `batches`; → (serve_wall, drain_wall, store, crc).

    `hold_drain` parks the drain behind `db_lock` for the SERVE
    measurement (after one warmup batch seeds the tree cache, the
    steady-state serve path takes no locks): on the 1-core container
    thread interleaving is serial, so this is the only way to measure
    the serving path and the btree drain as separate walls — the
    roadmap's recorded limit for core-count claims. drain_wall is then
    the timed flush of the full backlog (the btree's bulk cost)."""
    store = ShardedRelayStore(shards=SHARDS)
    wb = WriteBehindQueue(store) if write_behind else None
    eng = BatchReconciler(store, write_behind=wb)
    crc = 0
    if hold_drain and wb is not None:
        for out in eng.run_batch_wire(batches[0]):  # warmup: seed caches
            crc = zlib.crc32(out, crc)
        wb.flush()
        wb.db_lock.acquire()
        batches = batches[1:]
    t0 = time.perf_counter()
    for reqs in batches:
        for out in eng.run_batch_wire(reqs):
            crc = zlib.crc32(out, crc)
    t_serve = time.perf_counter() - t0
    t1 = time.perf_counter()
    if wb is not None:
        if hold_drain:
            wb.db_lock.release()
        wb.flush()
    t_drain = time.perf_counter() - t1
    if wb is not None:
        wb.close()
    eng.close()
    return t_serve, t_drain, store, crc


def _slope(lo_batches, hi_batches, rows_per_batch, write_behind,
           hold_drain: bool = False):
    s_lo, d_lo, st_lo, _ = _drive(lo_batches, write_behind, hold_drain)
    s_hi, d_hi, st_hi, crc = _drive(hi_batches, write_behind, hold_drain)
    n = (len(hi_batches) - len(lo_batches)) * rows_per_batch
    serve = n / max(s_hi - s_lo, 1e-9)
    drain = n / max(d_hi - d_lo, 1e-9)
    st_lo.close()
    return serve, drain, st_hi, crc


def main() -> None:
    smoke = "--smoke" in sys.argv
    rows_per_owner = 32 if smoke else 256
    lo, hi = (2, 5) if smoke else (4, 16)
    payload = b"x" * 64
    rows_per_batch = OWNERS * rows_per_owner

    batches = _stream(hi, rows_per_owner, payload)

    # -- byte-identity + liveness gates (always) --
    _s, _e, store_wb, crc_wb = _drive(batches[:lo], True)
    _s, _e, store_sync, crc_sync = _drive(batches[:lo], False)
    assert _dump(store_wb) == _dump(store_sync), (
        "write-behind drained state != synchronous oracle"
    )
    state_crc = _state_crc(store_wb)
    store_wb.close()
    store_sync.close()
    # Liveness: perturb the payload — the state crc MUST move.
    perturbed = _stream(lo, rows_per_owner, b"y" * 64)
    _s, _e, store_p, _c = _drive(perturbed, True)
    assert _state_crc(store_p) != state_crc, (
        "checksum did not move under payload perturbation — dead serving leg"
    )
    store_p.close()

    # -- slopes --
    # Serving path with the drain held: the latency-path number (what
    # a client sees while the btree lags). Drain slope: the btree's
    # bulk cost, timed separately (1-core limit — see _drive).
    wb_serve, wb_drain, st1, _ = _slope(
        batches[:lo], batches, rows_per_batch, True, hold_drain=True
    )
    # Interleaved (drain competing for the core): the sustained bound.
    wb_inter, _d, st3, _ = _slope(batches[:lo], batches, rows_per_batch, True)
    sync_serve, _d2, st2, _ = _slope(batches[:lo], batches, rows_per_batch, False)
    st1.close()
    st2.close()
    st3.close()

    print(json.dumps({
        "bench": "write_behind",
        "smoke": smoke,
        "platform": os.environ.get("JAX_PLATFORMS", "ambient"),
        "owners": OWNERS,
        "shards": SHARDS,
        "rows_per_batch": rows_per_batch,
        "serve_msgs_per_s_drain_held": round(wb_serve),
        "drain_msgs_per_s_bulk": round(wb_drain),
        "serve_msgs_per_s_interleaved": round(wb_inter),
        "serve_msgs_per_s_sync": round(sync_serve),
        "serve_path_speedup": round(wb_serve / max(sync_serve, 1e-9), 2),
        "interleaved_vs_sync": round(wb_inter / max(sync_serve, 1e-9), 2),
        "byte_identity": "ok",
        "liveness": "ok",
        "state_crc": f"{state_crc:08x}",
    }))


if __name__ == "__main__":
    main()
