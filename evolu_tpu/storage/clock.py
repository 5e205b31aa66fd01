"""CrdtClock persistence in the single-row __clock table.

Reference: packages/evolu/src/readClock.ts, updateClock.ts. The clock
row is the replica's resumable sync cursor: its timestamp is the HLC
high-water mark, its merkleTree the digest of all stored messages.

The tree is one JSON string there, and a year's is over a megabyte. It
is read as that text (`MerkleText`, parsed only if a reader asks for a
node) and a tree that is text is written as it is: the fold makes the
new text from the old (`core/merkle.py::fold_minute_deltas`).
"""

from __future__ import annotations

from evolu_tpu.core.merkle import MerkleText, merkle_tree_to_string
from evolu_tpu.core.timestamp import timestamp_from_string, timestamp_to_string
from evolu_tpu.core.types import CrdtClock
from evolu_tpu.obs import anatomy, metrics
from evolu_tpu.storage.sqlite import PySqliteDatabase
from evolu_tpu.utils.log import log


def read_clock(db: PySqliteDatabase) -> CrdtClock:
    """readClock.ts:15-27 (logged under clock:read, readClock.ts:26).
    The tree comes back as its text, unparsed."""
    row = db.exec_sql_query('SELECT "timestamp", "merkleTree" FROM "__clock" LIMIT 1')[0]
    text = row["merkleTree"]
    with anatomy.part("tree_load"):  # of a tiled Receive; a no-op elsewhere
        tree = MerkleText(text)
    metrics.inc("evolu_merkle_tree_bytes_total", len(text), leg="load")
    clock = CrdtClock(timestamp=timestamp_from_string(row["timestamp"]), merkle_tree=tree)
    log("clock:read", timestamp=row["timestamp"])
    return clock


def update_clock(db: PySqliteDatabase, clock: CrdtClock) -> str:
    """updateClock.ts:8-26 (logged under clock:update, updateClock.ts:24).
    → the tree's text as written."""
    ts = timestamp_to_string(clock.timestamp)
    with anatomy.part("tree_store"):
        text = merkle_tree_to_string(clock.merkle_tree)
        db.run('UPDATE "__clock" SET "timestamp" = ?, "merkleTree" = ?', (ts, text))
    metrics.inc("evolu_merkle_tree_bytes_total", len(text), leg="store")
    log("clock:update", timestamp=ts)
    return text
