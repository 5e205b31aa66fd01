"""Fresh-peer cold start: snapshot bootstrap vs pure anti-entropy.

The claim behind server/snapshot.py: a fresh relay joining a fleet (or
restoring after disk loss) should cold-start in O(state) — one
manifest plus a handful of big crc-checked chunks — instead of
crawling the whole history through `serve_pull`'s capped, minute-
ranged rounds, each of which also re-ships BOTH sides' full per-owner
tree summaries. Measured here directly: one donor holding
OWNERS×MINUTES×PER_MIN messages; fresh destination relays converge by
(a) pure PR-3 anti-entropy under the donor's serve_pull caps —
swept honestly across the default caps AND production-latency-bounded
tight caps (the satellite made them constructor args) — and
(b) snapshot bootstrap. Per leg: HTTP round-trips (the same
`evolu_repl_round_trips_total` counter the acceptance test asserts
on), total wire bytes (request+response, counted at the transport),
wall, and the end-state crc32 (trees + every row), which must MATCH
the donor's own state crc (asserted — a leg that skipped data cannot
pass).

Round-trip accounting is the honest story here: at small histories
the default pull caps are generous enough that anti-entropy needs few
rounds too (reported as-is, including when the ratio is ~1); the
snapshot win scales with history ÷ caps, which the tight-caps leg and
the bytes column make visible without extrapolation.

Runs host-side only (HTTP + SQLite + Merkle walks — no device leg);
env pinned to CPU. Prints ONE JSON line; numbers live in
docs/BENCHMARKS.md. `--smoke` runs a tiny end-to-end pass for CI
(path exercise + crc identity, no ratio claims).
"""

import argparse
import json
import os
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

BASE = 1_700_000_000_000


class _CountingPost:
    """Transport wrapper: every call is one HTTP round-trip; bytes are
    request + response payloads (the honest wire cost, incl. the
    per-round summary overhead anti-entropy pays)."""

    def __init__(self):
        self.calls = 0
        self.bytes = 0

    def __call__(self, url, body):
        out = _http_post(url, body, retries=0)
        self.calls += 1
        self.bytes += len(body) + len(out)
        return out


def _seed(store, owners, minutes, per_min):
    for i in range(owners):
        node = f"{i + 1:016x}"
        msgs = tuple(
            protocol.EncryptedCrdtMessage(
                timestamp_to_string(
                    Timestamp(BASE + m * 60_000 + j * 500, 0, node)
                ),
                b"ct-%d-%d" % (m, j),
            )
            for m in range(minutes)
            for j in range(per_min)
        )
        store.add_messages(f"owner{i:03d}", msgs)


def _state_crc(store) -> int:
    crc = 0
    for u in sorted(store.user_ids()):
        crc = zlib.crc32(store.get_merkle_tree_string(u).encode(), crc)
        for m in store.replica_messages(u, ""):
            crc = zlib.crc32(m.timestamp.encode(), crc)
            crc = zlib.crc32(m.content, crc)
    return crc


def _converge_anti(donor_url, src_crc, tag, max_rounds=500):
    dest = RelayStore()
    post = _CountingPost()
    mgr = ReplicationManager(dest, [donor_url], replica_id=tag, http_post=post)
    try:
        t0 = time.perf_counter()
        rounds = 0
        while rounds < max_rounds:
            mgr.run_once()
            rounds += 1
            if _state_crc(dest) == src_crc:
                break
        wall = time.perf_counter() - t0
        crc = _state_crc(dest)
        pulled = metrics.get_counter(
            "evolu_repl_messages_pulled_total", replica=tag,
            peer=donor_url.rstrip("/"),
        )
        return {
            "round_trips": post.calls,
            "wire_bytes": post.bytes,
            "gossip_rounds": rounds,
            "messages_pulled": int(pulled),
            "wall_s": round(wall, 4),
            "end_state_crc": f"{crc:08x}",
            "converged": crc == src_crc,
        }
    finally:
        mgr.stop()
        dest.close()


def _converge_snapshot(donor_url, src_crc, tag, chunk_bytes):
    dest = RelayStore()
    post = _CountingPost()
    mgr = ReplicationManager(
        dest, [donor_url], replica_id=tag, http_post=post,
        bootstrap_lag_owners=1, snapshot_chunk_bytes=chunk_bytes,
    )
    try:
        t0 = time.perf_counter()
        mgr.run_once()  # bootstrap round
        mgr.run_once()  # watermark gossip round (confirms convergence)
        wall = time.perf_counter() - t0
        crc = _state_crc(dest)
        return {
            "round_trips": post.calls,
            "wire_bytes": post.bytes,
            "chunks": int(metrics.get_counter(
                "evolu_snap_chunks_fetched_total", replica=tag,
                peer=donor_url.rstrip("/"),
            )),
            "messages_pulled": int(metrics.get_counter(
                "evolu_repl_messages_pulled_total", replica=tag,
                peer=donor_url.rstrip("/"),
            )),
            "wall_s": round(wall, 4),
            "end_state_crc": f"{crc:08x}",
            "converged": crc == src_crc,
        }
    finally:
        mgr.stop()
        dest.close()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI pass: exercise the path, assert crc identity")
    args = ap.parse_args()

    if args.smoke:
        owners, minutes, per_min = 12, 2, 5
        cap_sweep = [("tight", 16, 64)]
        chunk_bytes = 64 << 10
    else:
        owners, minutes, per_min = 100, 10, 100  # 100k messages
        cap_sweep = [("default", None, None), ("tight", 1024, 8192)]
        chunk_bytes = 4 << 20

    donor_store = RelayStore()
    _seed(donor_store, owners, minutes, per_min)
    donor_mgr = ReplicationManager(donor_store, [], replica_id="bench-donor")
    donor = RelayServer(donor_store, replication=donor_mgr).start()
    try:
        src_crc = _state_crc(donor_store)
        legs = {}
        for cap_name, per_owner, per_resp in cap_sweep:
            donor_mgr.pull_messages_per_owner = per_owner
            donor_mgr.pull_messages_per_response = per_resp
            legs[f"anti_{cap_name}"] = {
                "pull_caps": [per_owner, per_resp],
                **_converge_anti(donor.url, src_crc, f"bench-anti-{cap_name}"),
            }
        donor_mgr.pull_messages_per_owner = None
        donor_mgr.pull_messages_per_response = None
        legs["snapshot"] = {
            "chunk_bytes": chunk_bytes,
            **_converge_snapshot(donor.url, src_crc, "bench-snap", chunk_bytes),
        }
    finally:
        donor.stop()

    for name, leg in legs.items():
        assert leg["converged"], f"{name}: end state != donor ({leg})"
        assert leg["end_state_crc"] == f"{src_crc:08x}"

    anti_key = "anti_tight" if "anti_tight" in legs else next(iter(legs))
    ratio = legs[anti_key]["round_trips"] / max(1, legs["snapshot"]["round_trips"])
    print(
        json.dumps(
            {
                "metric": "snapshot_bootstrap_round_trip_ratio",
                "value": round(ratio, 1),
                "unit": f"x fewer HTTP round-trips vs anti-entropy ({anti_key})",
                "detail": {
                    "db_messages": owners * minutes * per_min,
                    "owners": owners,
                    "smoke": bool(args.smoke),
                    "legs": legs,
                    "cpus": os.cpu_count(),
                },
            }
        )
    )


if __name__ == "__main__":
    main()
