"""CrdtClock persistence in the single-row __clock table.

Reference: packages/evolu/src/readClock.ts, updateClock.ts. The clock
row is the replica's resumable sync cursor: its timestamp is the HLC
high-water mark, its merkleTree the digest of all stored messages.
"""

from __future__ import annotations

from evolu_tpu.core.merkle import merkle_tree_from_string, merkle_tree_to_string
from evolu_tpu.core.timestamp import timestamp_from_string, timestamp_to_string
from evolu_tpu.core.types import CrdtClock
from evolu_tpu.obs import anatomy, metrics
from evolu_tpu.storage.sqlite import PySqliteDatabase
from evolu_tpu.utils.log import log


def read_clock(db: PySqliteDatabase) -> CrdtClock:
    """readClock.ts:15-27 (logged under clock:read, readClock.ts:26)."""
    row = db.exec_sql_query('SELECT "timestamp", "merkleTree" FROM "__clock" LIMIT 1')[0]
    text = row["merkleTree"]
    with anatomy.part("tree_load"):  # of a tiled Receive; a no-op elsewhere
        tree = merkle_tree_from_string(text)
    metrics.inc("evolu_merkle_tree_bytes_total", len(text), leg="load")
    clock = CrdtClock(timestamp=timestamp_from_string(row["timestamp"]), merkle_tree=tree)
    log("clock:read", timestamp=row["timestamp"])
    return clock


def update_clock(db: PySqliteDatabase, clock: CrdtClock) -> None:
    """updateClock.ts:8-26 (logged under clock:update, updateClock.ts:24)."""
    ts = timestamp_to_string(clock.timestamp)
    with anatomy.part("tree_store"):
        tree = merkle_tree_to_string(clock.merkle_tree)
        db.run('UPDATE "__clock" SET "timestamp" = ?, "merkleTree" = ?', (ts, tree))
    metrics.inc("evolu_merkle_tree_bytes_total", len(tree), leg="store")
    log("clock:update", timestamp=ts)
