"""Relay replication anti-entropy: transfer scales with DIVERGENCE.

The claim behind Merkle anti-entropy (server/replicate.py): syncing a
peer costs bandwidth proportional to what DIVERGED, not to database
size. Measured here directly: a source relay holds OWNERS×MINUTES×
PER_MIN messages; destination relays that are (a) fresh (full pull),
(b) 1 minute behind, (c) 8 minutes behind each run one gossip sweep,
and the messages-transferred counter (the same counter the
partition-heal acceptance test asserts on) plus wall time are
recorded.

Throughput uses the SLOPE method (CLAUDE.md timing discipline): the
msgs/s figure is Δtransferred/Δwall between the 1-minute and 8-minute
divergence legs (per-leg medians of 3 runs), so summary/diff overhead
that both legs share cancels out instead of polluting the number.
Liveness: every destination's full end state (tree strings + every
row) folds into a printed crc32 per leg — a sweep that skipped data
changes the checksum, and the per-leg checksums must MATCH the
source's own state checksum (asserted).

Runs host-side only (HTTP + SQLite + Merkle walks — no device leg);
the env is pinned to CPU so importing anything jax-adjacent can never
claim the real chip. Prints ONE JSON line; numbers live in
docs/BENCHMARKS.md.
"""

import json
import os
import statistics
import sys
import time
import zlib

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.obs import metrics
from evolu_tpu.server.relay import RelayServer, RelayStore
from evolu_tpu.server.replicate import ReplicationManager
from evolu_tpu.sync import protocol
from evolu_tpu.sync.client import _http_post

OWNERS = 8
MINUTES = 60
PER_MIN = 50
BASE = 1_700_000_000_000
TRIALS = 3
DIV_LO, DIV_HI = 1, 8  # minutes of divergence for the slope legs


def _owner_messages(node: str, minutes: int):
    return tuple(
        protocol.EncryptedCrdtMessage(
            timestamp_to_string(
                Timestamp(BASE + m * 60_000 + i * 500, 0, node)
            ),
            b"ct-%d-%d" % (m, i),
        )
        for m in range(minutes)
        for i in range(PER_MIN)
    )


def _owners():
    return [(f"owner{i:02d}", f"{i + 1:016x}") for i in range(OWNERS)]


def _state_crc(store) -> int:
    crc = 0
    for u in sorted(store.user_ids()):
        crc = zlib.crc32(store.get_merkle_tree_string(u).encode(), crc)
        for m in store.replica_messages(u, ""):
            crc = zlib.crc32(m.timestamp.encode(), crc)
            crc = zlib.crc32(m.content, crc)
    return crc


def _sweep(src_url: str, behind_minutes: int, tag: str):
    """One gossip sweep by a destination that is `behind_minutes`
    behind the source (MINUTES = fresh peer). Returns
    (wall_s, messages_pulled, end_state_crc)."""
    dest = RelayStore()
    try:
        if behind_minutes < MINUTES:
            for u, node in _owners():
                dest.add_messages(u, _owner_messages(node, MINUTES - behind_minutes))
        mgr = ReplicationManager(
            dest, [src_url], replica_id=tag,
            http_post=lambda u, d: _http_post(u, d, retries=0),
        )
        t0 = time.perf_counter()
        mgr.run_once()
        wall = time.perf_counter() - t0
        mgr.stop()
        pulled = metrics.get_counter(
            "evolu_repl_messages_pulled_total", replica=tag, peer=src_url.rstrip("/")
        )
        return wall, int(pulled), _state_crc(dest)
    finally:
        dest.close()


def main() -> None:
    src_store = RelayStore()
    for u, node in _owners():
        src_store.add_messages(u, _owner_messages(node, MINUTES))
    src = RelayServer(src_store, peers=[]).start()  # listener-only source
    try:
        src_crc = _state_crc(src_store)
        legs = {}
        for name, behind in (("full", MINUTES), ("lo", DIV_LO), ("hi", DIV_HI)):
            walls, pulls, crcs = [], set(), set()
            for t in range(TRIALS):
                wall, pulled, crc = _sweep(src.url, behind, f"bench-{name}-{t}")
                walls.append(wall)
                pulls.add(pulled)
                crcs.add(crc)
            (pulled,) = pulls  # transfer count must be deterministic
            (crc,) = crcs
            assert crc == src_crc, f"{name}: end state != source ({crc:08x})"
            legs[name] = {
                "behind_minutes": behind,
                "messages_pulled": pulled,
                "wall_median_s": round(statistics.median(walls), 4),
                "end_state_crc": f"{crc:08x}",
            }
    finally:
        src.stop()

    d_msgs = legs["hi"]["messages_pulled"] - legs["lo"]["messages_pulled"]
    d_wall = legs["hi"]["wall_median_s"] - legs["lo"]["wall_median_s"]
    total = OWNERS * MINUTES * PER_MIN
    print(
        json.dumps(
            {
                "metric": "replication_antientropy_transfer_ratio",
                "value": round(
                    legs["full"]["messages_pulled"]
                    / max(1, legs["lo"]["messages_pulled"]),
                    1,
                ),
                "unit": "x fresh-peer transfer vs 1-minute divergence",
                "detail": {
                    "db_messages": total,
                    "owners": OWNERS,
                    "minutes": MINUTES,
                    "legs": legs,
                    "pull_msgs_per_sec_slope": (
                        round(d_msgs / d_wall) if d_wall > 0 else None
                    ),
                    "cpus": os.cpu_count(),
                },
            }
        )
    )


if __name__ == "__main__":
    main()
