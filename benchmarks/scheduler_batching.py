"""Continuous-batching scheduler vs per-request relay dispatch.

32 concurrent clients (one owner each, 20 encrypted messages per
round) hammer the HTTP relay twice: once with the per-request
`sync_wire` path (the reference relay's shape) and once through the
`SyncScheduler` → one fused `BatchReconciler` pass per micro-batch.

Throughput uses the SLOPE method (CLAUDE.md timing discipline): each
config is driven at TWO round counts after a warmup leg, and the
msgs/s figure is Δmessages/Δwall between them — server start, jit
warmup, and connection setup cancel out instead of burying the result.
Every response byte feeds a crc32 checksum that is printed, so no
serving leg can be skipped unnoticed.

Runs on the 8-device virtual CPU mesh by default (the env is forced
below, so this never takes the chip); set EVOLU_SCHED_BENCH_TPU=1 to
inherit the ambient platform instead.

Prints ONE JSON line; numbers live in docs/BENCHMARKS.md.
"""

import json
import os
import statistics
import sys
import threading
import time
import urllib.request
import zlib

if not os.environ.get("EVOLU_SCHED_BENCH_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.obs import metrics
from evolu_tpu.server.relay import RelayServer, ShardedRelayStore
from evolu_tpu.sync import protocol

CLIENTS = 32
MSGS_PER_ROUND = 20
ROUNDS_LO, ROUNDS_HI = 2, 8
BASE = 1_700_000_000_000


def _msgs(node: str, start: int, n: int):
    return tuple(
        protocol.EncryptedCrdtMessage(
            timestamp_to_string(Timestamp(BASE + (start + i) * 1000, 0, node)),
            b"x" * 64,
        )
        for i in range(n)
    )


def _drive(url: str, namespace: str, rounds: int):
    """32 concurrent clients × `rounds` push rounds against `url`.
    Returns (wall_s, sorted per-request latencies, response checksum).
    The checksum folds EVERY response's bytes — the liveness guard."""
    latencies: list = []
    checksums = [0] * CLIENTS
    lock = threading.Lock()
    barrier = threading.Barrier(CLIENTS)
    errors: list = []

    def client(i: int):
        user = f"{namespace}-u{i:03d}"
        node = f"{i + 1:016x}"
        mine = []
        crc = 0
        try:
            barrier.wait(timeout=60)
            for rnd in range(rounds):
                req = protocol.SyncRequest(
                    _msgs(node, rnd * MSGS_PER_ROUND, MSGS_PER_ROUND),
                    user, node, "{}",
                )
                body = protocol.encode_sync_request(req)
                t0 = time.perf_counter()
                with urllib.request.urlopen(
                    urllib.request.Request(
                        url, data=body,
                        headers={"Content-Type": "application/octet-stream"},
                    ),
                    timeout=120,
                ) as r:
                    crc = zlib.crc32(r.read(), crc)
                mine.append(time.perf_counter() - t0)
        except Exception as e:  # noqa: BLE001
            errors.append(e)
        checksums[i] = crc
        with lock:
            latencies.extend(mine)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(CLIENTS)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    latencies.sort()
    checksum = 0
    for c in checksums:
        checksum = zlib.crc32(c.to_bytes(4, "little"), checksum)
    return wall, latencies, checksum


def measure(batching: bool) -> dict:
    store = ShardedRelayStore(shards=4)
    server = RelayServer(store, batching=batching).start()
    passes0 = metrics.get_counter("evolu_sched_batches_total")
    try:
        _drive(server.url, "warm", 1)  # jit + btree warmup, uncounted
        wall_lo, _lats, crc_lo = _drive(server.url, "lo", ROUNDS_LO)
        wall_hi, lats, crc_hi = _drive(server.url, "hi", ROUNDS_HI)
        passes = metrics.get_counter("evolu_sched_batches_total") - passes0
    finally:
        server.stop()
    d_msgs = CLIENTS * MSGS_PER_ROUND * (ROUNDS_HI - ROUNDS_LO)
    d_reqs = CLIENTS * (ROUNDS_HI - ROUNDS_LO)
    n_reqs_counted = CLIENTS * (1 + ROUNDS_LO + ROUNDS_HI)
    return {
        "msgs_per_sec_slope": round(d_msgs / (wall_hi - wall_lo)),
        "reqs_per_sec_slope": round(d_reqs / (wall_hi - wall_lo), 1),
        "p50_ms": round(statistics.median(lats) * 1e3, 2),
        "p99_ms": round(lats[int(len(lats) * 0.99) - 1] * 1e3, 2),
        "wall_lo_s": round(wall_lo, 3),
        "wall_hi_s": round(wall_hi, 3),
        "engine_passes": int(passes) if batching else n_reqs_counted,
        "requests": n_reqs_counted,
        "checksum": f"{crc_lo:08x}/{crc_hi:08x}",
    }


def main() -> None:
    baseline = measure(batching=False)
    batched = measure(batching=True)
    speedup = (
        batched["msgs_per_sec_slope"] / baseline["msgs_per_sec_slope"]
        if baseline["msgs_per_sec_slope"]
        else float("nan")
    )
    print(
        json.dumps(
            {
                "metric": "scheduler_batching_throughput_ratio",
                "value": round(speedup, 2),
                "unit": "x vs per-request dispatch @ 32 clients (slope)",
                "detail": {
                    "clients": CLIENTS,
                    "msgs_per_round": MSGS_PER_ROUND,
                    "rounds": [ROUNDS_LO, ROUNDS_HI],
                    "per_request": baseline,
                    "scheduler": batched,
                    "pass_reduction": round(
                        batched["requests"] / max(1, batched["engine_passes"]), 1
                    ),
                    "cpus": os.cpu_count(),
                },
            }
        )
    )


if __name__ == "__main__":
    main()
