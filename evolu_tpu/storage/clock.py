"""CrdtClock persistence in the single-row __clock table.

Reference: packages/evolu/src/readClock.ts, updateClock.ts. The clock
row is the replica's resumable sync cursor: its timestamp is the HLC
high-water mark, its merkleTree the digest of all stored messages.

The tree is one JSON string there, and a year's is over a megabyte. A
caller that reads and writes the clock again and again (the worker)
hands both functions one `TreeText` slot: the text last read or written
beside the tree it is the text of. Where `__clock` still holds that
text, the tree is not parsed again; where a tree is that tree, it is
not serialized again. Keyed by content: a rollback, a reset or restore
of the owner, or another writer of the file can only miss.
"""

from __future__ import annotations

from typing import Optional

from evolu_tpu.core.merkle import (
    OrderedTree,
    merkle_tree_to_string,
    ordered_tree_from_string,
)
from evolu_tpu.core.timestamp import timestamp_from_string, timestamp_to_string
from evolu_tpu.core.types import CrdtClock
from evolu_tpu.obs import anatomy, metrics
from evolu_tpu.storage.sqlite import PySqliteDatabase
from evolu_tpu.utils.log import log


class TreeText:
    """One slot: a tree and the text `merkle_tree_to_string` makes of
    it. Holds only an `OrderedTree` (whose dump is its text, key for
    key, so parsing the text gives an equal tree) and relies on trees
    never being mutated in place."""

    __slots__ = ("text", "tree")

    def __init__(self):
        self.text: Optional[str] = None
        self.tree: Optional[OrderedTree] = None

    def remember(self, text: str, tree: dict) -> None:
        if isinstance(tree, OrderedTree):
            self.text, self.tree = text, tree
        else:
            self.text = self.tree = None

    def text_of(self, tree: dict) -> Optional[str]:
        """The remembered text if `tree` IS the remembered tree."""
        return self.text if tree is self.tree and tree is not None else None


def tree_text(tree: dict, slot: Optional[TreeText] = None) -> str:
    """`merkle_tree_to_string(tree)`, from the slot where it has it."""
    text = slot.text_of(tree) if slot is not None else None
    return merkle_tree_to_string(tree) if text is None else text


def read_clock(db: PySqliteDatabase, slot: Optional[TreeText] = None) -> CrdtClock:
    """readClock.ts:15-27 (logged under clock:read, readClock.ts:26).
    The tree comes back in key order and marked so (`OrderedTree`)."""
    row = db.exec_sql_query('SELECT "timestamp", "merkleTree" FROM "__clock" LIMIT 1')[0]
    text = row["merkleTree"]
    with anatomy.part("tree_load"):  # of a tiled Receive; a no-op elsewhere
        hit = slot is not None and slot.text == text
        if hit:
            tree = slot.tree
        else:
            tree = ordered_tree_from_string(text)
            if slot is not None:
                slot.remember(text, tree)
    metrics.inc_many((
        ("evolu_merkle_tree_bytes_total", len(text), {"leg": "load"}),
        ("evolu_merkle_tree_text_checks_total", int(slot is not None), {"leg": "load"}),
        ("evolu_merkle_tree_text_hits_total", int(hit), {"leg": "load"}),
    ))
    clock = CrdtClock(timestamp=timestamp_from_string(row["timestamp"]), merkle_tree=tree)
    log("clock:read", timestamp=row["timestamp"])
    return clock


def update_clock(db: PySqliteDatabase, clock: CrdtClock,
                 slot: Optional[TreeText] = None) -> str:
    """updateClock.ts:8-26 (logged under clock:update, updateClock.ts:24).
    → the tree's text as written."""
    ts = timestamp_to_string(clock.timestamp)
    with anatomy.part("tree_store"):
        text = tree_text(clock.merkle_tree, slot)
        db.run('UPDATE "__clock" SET "timestamp" = ?, "merkleTree" = ?', (ts, text))
    if slot is not None:
        slot.remember(text, clock.merkle_tree)
    metrics.inc("evolu_merkle_tree_bytes_total", len(text), leg="store")
    log("clock:update", timestamp=ts)
    return text
