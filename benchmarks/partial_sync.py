"""Partial replication: scoped-slice sync bytes vs full sync.

The claim behind sync/scope.py + server/scope.py (ISSUE 18): a thin
client that declares a slice — here 1 of 10 equal HMAC lanes — should
pay wire bytes proportional to the SLICE, not the owner's history.
Measured directly at the HTTP transport against a live relay: a fresh
scoped puller and a fresh full puller each converge from empty via the
real codec (`encode_sync_request` with the capability-gated scope
clause / plain v1 request), counting request+response bytes per leg.

Method: the SLOPE between two history sizes (CLAUDE.md: never divide
one wall/byte total by its count) — each sync round also ships both
sides' Merkle tree summaries, a per-round overhead that does not scale
with served rows; the byte slope between N1 and N2 cancels it. The
gate is on the slope ratio: a 10% slice must cost <= 15% of full-sync
bytes per row (the 5-point slack covers the scoped leg's extra clause
bytes and the shared summary overhead that the slope cannot fully
cancel when round counts differ).

Liveness fence (the r2/r3 lesson, transposed to the wire): every
served row feeds a crc32 carry (timestamp + ciphertext), and each
leg's carry must equal the donor-side crc of exactly the rows that leg
was OWED — full = the whole history, scoped = the lane's rows. A leg
that silently dropped or skipped rows cannot pass; the crcs are
deterministic (fixed BASE, seeded content) and double as exact-match
baseline gates for compare_baselines.py.

Host-side only (HTTP + SQLite + Merkle walks; the scoped minute-fold
routes host at these sizes — SCOPE_DEVICE_FOLD_MIN); env pinned to
CPU. Prints ONE JSON line; numbers live in docs/BENCHMARKS.md.
`--smoke` runs a tiny pass for CI: crc gates hard, the slope-ratio
gate enforced at a loosened bound (tiny histories leave the per-round
summary overhead a visible share of the slope).
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

from evolu_tpu.core.merkle import (
    apply_prefix_xors,
    merkle_tree_to_string,
    minute_deltas_host,
)
from evolu_tpu.core.timestamp import Timestamp, timestamp_to_string
from evolu_tpu.server import scope as server_scope
from evolu_tpu.server.relay import RelayServer, RelayStore
from evolu_tpu.sync import protocol
from evolu_tpu.sync.client import _http_post
from evolu_tpu.sync.scope import derive_scope_tag

BASE = 1_700_000_000_000
MINUTE = 60_000
OWNER = "bench-owner"
FEED_NODE = "feed00000000feed"
PULL_NODE = "9999aaaabbbbcccc"
MNEMONIC = "bench partial sync mnemonic"
TABLES = 10
MAX_ROUNDS = 200


def _seed(store, minutes, per_min):
    """`minutes` x `per_min` rows for each of TABLES lanes, all
    authored by the feed node, lane-tagged exactly as an author's
    capability-gated push would have (author-only rule included)."""
    tags = [derive_scope_tag(MNEMONIC, f"table{t}") for t in range(TABLES)]
    all_ts, all_tags = [], []
    msgs = []
    for m in range(minutes):
        for j in range(per_min):
            for t in range(TABLES):
                ts = timestamp_to_string(Timestamp(
                    BASE + m * MINUTE + (j * TABLES + t) * 40, 0, FEED_NODE))
                msgs.append(protocol.EncryptedCrdtMessage(
                    ts, b"ct%02d" % t + b"x" * 96 + b"%06d" % (m * per_min + j)))
                all_ts.append(ts)
                all_tags.append(tags[t])
    store.add_messages(OWNER, tuple(msgs))
    server_scope.record_push_lanes(store.db, OWNER, all_ts, all_tags,
                                   node_id=FEED_NODE)
    return msgs


def _crc_of(msgs):
    crc = 0
    for m in sorted(msgs, key=lambda m: m.timestamp):
        crc = zlib.crc32(m.timestamp.encode(), crc)
        crc = zlib.crc32(m.content, crc)
    return crc


def _pull(url, scope_clause):
    """Converge a fresh puller from empty; → (bytes, rows, rounds,
    crc_carry, wall_s). The carry consumes EVERY served row — the
    liveness fence."""
    tree = {}
    caps = (protocol.CAP_SYNC_SCOPE,) if scope_clause is not None else ()
    n_bytes = rows = rounds = crc = 0
    t0 = time.perf_counter()
    for _ in range(MAX_ROUNDS):
        body = protocol.encode_sync_request(protocol.SyncRequest(
            (), OWNER, PULL_NODE, merkle_tree_to_string(tree),
            caps, scope_clause))
        out = _http_post(url, body, retries=0)
        n_bytes += len(body) + len(out)
        rounds += 1
        resp = protocol.decode_sync_response(out)
        if not resp.messages:
            break
        for m in resp.messages:
            crc = zlib.crc32(m.timestamp.encode(), crc)
            crc = zlib.crc32(m.content, crc)
        rows += len(resp.messages)
        deltas, _ = minute_deltas_host(m.timestamp for m in resp.messages)
        tree = apply_prefix_xors(tree, deltas)
    else:
        raise AssertionError("puller did not converge in MAX_ROUNDS")
    return n_bytes, rows, rounds, crc, time.perf_counter() - t0


def _leg(minutes, per_min):
    store = RelayStore()
    server = RelayServer(store).start()
    try:
        msgs = _seed(store, minutes, per_min)
        slice_tag = derive_scope_tag(MNEMONIC, "table0")
        full_b, full_rows, full_rounds, full_crc, full_wall = _pull(
            server.url, None)
        sc_b, sc_rows, sc_rounds, sc_crc, sc_wall = _pull(
            server.url, protocol.ScopeClause(0, (slice_tag,), ()))
        owed_full = _crc_of(msgs)
        owed_scoped = _crc_of([m for m in msgs
                               if m.content.startswith(b"ct00")])
        assert full_rows == len(msgs)
        return {
            "rows_total": len(msgs),
            "full": {"wire_bytes": full_b, "rows": full_rows,
                     "rounds": full_rounds, "wall_s": round(full_wall, 4),
                     "served_crc": f"{full_crc:08x}",
                     "pass_crc": full_crc == owed_full},
            "scoped": {"wire_bytes": sc_b, "rows": sc_rows,
                       "rounds": sc_rounds, "wall_s": round(sc_wall, 4),
                       "served_crc": f"{sc_crc:08x}",
                       "pass_crc": sc_crc == owed_scoped},
        }
    finally:
        server.stop()
        store.close()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI pass: crc gates hard, ratio gate loosened")
    args = ap.parse_args()

    if args.smoke:
        sizes = [(2, 4), (6, 4)]  # (minutes, per_min): 80 / 240 rows
        gate = 0.30  # summary overhead is a real share at tiny sizes
    else:
        sizes = [(8, 25), (32, 25)]  # 2 000 / 8 000 rows
        gate = 0.15

    legs = [_leg(m, p) for m, p in sizes]
    n1, n2 = legs[0]["rows_total"], legs[1]["rows_total"]
    slope_full = (legs[1]["full"]["wire_bytes"]
                  - legs[0]["full"]["wire_bytes"]) / (n2 - n1)
    slope_scoped = (legs[1]["scoped"]["wire_bytes"]
                    - legs[0]["scoped"]["wire_bytes"]) / (n2 - n1)
    ratio = slope_scoped / slope_full
    rec = {
        "bench": "partial_sync",
        "platform": "cpu",
        "smoke": bool(args.smoke),
        "tables": TABLES,
        "slice_share": 1 / TABLES,
        "sizes_rows": [n1, n2],
        "legs": legs,
        "slope_bytes_per_row_full": round(slope_full, 2),
        "slope_bytes_per_row_scoped": round(slope_scoped, 2),
        "slope_ratio": round(ratio, 4),
        "byte_ratio_at_n2": round(
            legs[1]["scoped"]["wire_bytes"] / legs[1]["full"]["wire_bytes"],
            4),
        "gate": gate,
        "pass_slice_byte_gate": ratio <= gate,
        "method": ("byte slope between two history sizes (cancels "
                   "per-round tree-summary overhead); crc carry over "
                   "every served row == donor-side crc of the owed set"),
    }
    print(json.dumps(rec, separators=(",", ":")))
    assert rec["pass_slice_byte_gate"], \
        f"slice byte gate failed: slope ratio {ratio:.4f} > {gate}"
    for leg in legs:
        assert leg["full"]["pass_crc"], "full leg dropped served rows"
        assert leg["scoped"]["pass_crc"], "scoped leg crc != owed slice"


if __name__ == "__main__":
    main()
